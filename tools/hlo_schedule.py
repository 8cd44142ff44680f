"""Where does the dp4 step's large all-reduce stand among its kernels?

Compiles ``convnet3000_dp4_bs5``'s own step (the 3000^2 ``s2dt`` ConvNet,
bf16, batch 5 a chip, SGD, built by ``DataParallel`` with the options the
engine itself hands ``jax.jit``) for a described four-chip v5e
(``v5e:2x2x1``: the 1x1x1 twin has no cross-chip collective to schedule)
and reads the scheduled module: instruction order in a module printed
with ``is_scheduled=true`` IS the schedule. It prints where the step's
largest collective, the fc gradient's all-reduce, stands among the
backward kernels: ``kernels_between_issue_and_consumer`` is 7 (``fc``'s
own input-gradient kernel and the six of the convolutions) where the
engine sums that leaf first and compiles with its options, and 0 where
one ``pmean`` trails the step, as before PR 34. The form that was measured
to run under its kernels (PERF.md section 6, PR 34) is this libtpu's
``async-collective-start`` / ``-done`` pair of fusions with the kernels
between them wrapped in ``async_collective_fusion``s; one synchronous
``all-reduce`` instruction is issued and waited for where it is printed.

Chipless and single-process like the other AOT tools: do not run two at
once. 25-45 s. Schedule structure, not measured step time; the benchmark
(benchmark/run.py) owns measured truth.

Usage (``--cell-step`` is accepted and means nothing: it is the spelling
the records quote, from when the tool had a second mode):
  python tools/hlo_schedule.py --cell-step           # the dp4 cell's order
  python tools/hlo_schedule.py --cell-step --image-size 2000 --dtype fp32
                                    # the same engine at another shape: does
                                    # it compile, and the VMEM its wrapped
                                    # kernels hold beside the collective
  python tools/hlo_schedule.py --hlo-file dump.txt   # re-read a dump
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)            # import aot_v5e / hlo_traffic as siblings
sys.path.insert(0, os.path.dirname(_HERE))  # import tpu_sandbox from the repo

from hlo_traffic import _INST, operand_tokens, shape_bytes  # noqa: E402

# aot_v5e (and with it libtpu topologies) stays lazy in the driver below:
# issue_order_report() must be importable on CPU-only boxes — the tier-1
# fixture tests run it against text.

_BWD = re.compile(r'op_name="[^"]*transpose\(')


def _entry_instructions(hlo_text: str) -> list[tuple[str, str, str, str]]:
    """(name, result shape, opcode, rest of the line) of the ENTRY
    computation's instructions, in the order printed: the schedule, in a
    module printed with ``is_scheduled=true``."""
    ops, inside = [], False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped:
            inside = stripped.startswith("ENTRY")
            continue
        m = _INST.match(line) if inside else None
        if m:
            ops.append(m.groups())
    return ops


_START, _DONE = "async-collective-start", "async-collective-done"
_USED_VMEM = re.compile(
    r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
    r'"size":"(\d+)"')


def issue_order_report(hlo_text: str) -> dict:
    """Where the step's largest all-reduce stands among its kernels.

    Pure text analysis of a scheduled module. The largest all-reduce of
    the ENTRY computation (by result bytes: the fc gradient's, in the
    ConvNet step) is either one ``all-reduce`` instruction, issued and
    waited for where it is printed, or what this libtpu makes of it under
    the engine's options: an ``async-collective-start`` fusion, the
    kernels it runs under each wrapped in an ``async_collective_fusion``
    of their own, and an ``async-collective-done``. ``order`` lists the
    gradient's producer, the collectives, every kernel (a ``custom-call``
    or such a wrapper; ``bwd`` where the line's ``op_name`` says
    transpose) and the first reader of the sum, as scheduled;
    ``kernels_between_issue_and_consumer`` counts the kernels between the
    issue and that reader, ``kernels_under_the_collective`` those between
    issue and wait. It is the pair that was measured to run under its
    kernels (PERF.md section 6, PR 34); for one instruction the second
    count is 0 wherever it stands. ``scoped_vmem_bytes_under_collective``
    is the most scoped VMEM one of those wrapped kernels holds, the
    collective's buffers included: what has to stay under the engine's
    ``xla_tpu_scoped_vmem_limit_kib`` for the step to compile at all (0
    where the text carries no ``backend_config``, as the trimmed fixtures
    do not)."""
    ops = _entry_instructions(hlo_text)

    def reads(i, name):
        return name in operand_tokens(ops[i][3])

    def first_reader(start, name):
        return next((i for i in range(start + 1, len(ops))
                     if reads(i, name)), None)

    def is_kernel(i):
        _, _, opcode, rest = ops[i]
        return opcode == "custom-call" or (
            opcode == "fusion" and "calls=%async_collective_fusion" in rest)

    at = {name: i for i, (name, *_) in enumerate(ops)}
    # (issue, wait, bytes): a start and the done of the same number, or an
    # all-reduce instruction that is both
    found = [(i, i, shape_bytes(op[1])) for i, op in enumerate(ops)
             if op[2] == "all-reduce"]
    for name, i in at.items():
        done = at.get(_DONE + name[len(_START):])
        if name.startswith(_START) and done is not None:
            found.append((i, done, shape_bytes(ops[done][1])))
    if not found:
        return {"collective": None, "form": None,
                "kernels_between_issue_and_consumer": 0, "order": []}
    issue, wait, nbytes = max(found, key=lambda f: f[2])
    consumer = first_reader(wait, ops[wait][0])
    producer = next((i for i in range(issue) if reads(issue, ops[i][0])),
                    None)
    end = len(ops) if consumer is None else consumer
    role = {producer: "gradient", issue: "collective", consumer: "consumer"}
    if wait != issue:
        role[wait] = "collective done"
    order = []
    for i, (name, _shape, opcode, rest) in enumerate(ops):
        if i in role:
            order.append({"op": name, "opcode": opcode, "role": role[i]})
        elif is_kernel(i):
            order.append({"op": name, "opcode": opcode, "role":
                          "bwd kernel" if _BWD.search(rest) else "kernel"})
        elif opcode == "all-reduce" or name.startswith((_START, _DONE)):
            order.append({"op": name, "opcode": opcode,
                          "role": "other collective"})
    return {
        "collective": ops[issue][0],
        "form": "async pair" if wait != issue else "one instruction",
        "collective_bytes": nbytes,
        "gradient": None if producer is None else ops[producer][0],
        "consumer": None if consumer is None else ops[consumer][0],
        "kernels_between_issue_and_consumer": sum(
            is_kernel(i) for i in range(issue + 1, end)),
        "kernels_under_the_collective": sum(
            is_kernel(i) for i in range(issue + 1, wait)),
        "scoped_vmem_bytes_under_collective": max(
            (int(m.group(1)) for i in range(issue + 1, wait) if is_kernel(i)
             for m in [_USED_VMEM.search(ops[i][3])] if m), default=0),
        "order": order,
    }


def lower_cell_step(devices, *, image_size: int = 3000,
                    batch_per_rank: int = 5, plan: str = "s2dt",
                    dtype: str = "bf16", opt: str = "sgd"):
    """Lower ``convnet3000_dp4_bs5``'s step on ``devices`` from shapes
    alone: ``pick_convnet(.., plan="s2dt", dtype=bf16)``, ``optax.sgd``,
    ``DataParallel`` as ``mnist_distributed.build`` makes it. Compiling the
    result applies the options the engine gave ``jax.jit``. The other
    arguments are ``mnist_distributed``'s ``--image-size``, ``--batch-size``,
    ``--plan``, ``--dtype`` and ``--opt``: the shapes its users can ask
    the same engine for."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.train import TrainState

    devices = np.array(devices)
    world = devices.size
    compute = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    model = pick_convnet(image_size, plan=plan, dtype=compute)
    tx = {"sgd": optax.sgd(1e-4), "momentum": optax.sgd(1e-4, momentum=0.9),
          "adamw": optax.adamw(1e-4)}[opt]
    state = jax.eval_shape(lambda: TrainState.create(
        model, jax.random.key(0),
        jnp.zeros((1, image_size, image_size, 1), compute), tx,
    ))
    state = state.replace(batch_stats=jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((world, *s.shape), s.dtype),
        state.batch_stats))
    dp = DataParallel(model, tx, Mesh(devices, ("data",)),
                      image_size=(image_size, image_size))
    return dp.lower_step(
        state,
        jax.ShapeDtypeStruct((world * batch_per_rank, 28, 28, 1),
                             jnp.float32),
        jax.ShapeDtypeStruct((world * batch_per_rank,), jnp.int32))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--topology", default="v5e:2x2x1",
                   help="compile-only TPU topology (needs >1 chip for "
                        "cross-chip collectives to exist)")
    p.add_argument("--chips-per-host", default="2,2,1")
    p.add_argument("--cell-step", action="store_true",
                   help="accepted and ignored: the tool's one job")
    p.add_argument("--batch-per-rank", type=int, default=5)
    p.add_argument("--image-size", type=int, default=3000,
                   help="the engine's step at another of "
                        "mnist_distributed's shapes (with --batch-per-rank, "
                        "--plan, --dtype, --opt)")
    p.add_argument("--plan", choices=["s2dt", "s2d", "plain"],
                   default="s2dt")
    p.add_argument("--dtype", choices=["bf16", "fp32"], default="bf16")
    p.add_argument("--opt", choices=["sgd", "momentum", "adamw"],
                   default="sgd")
    p.add_argument("--hlo-file", default=None,
                   help="re-analyze an existing optimized-HLO dump instead "
                        "of recompiling")
    p.add_argument("--dump-hlo", default=None,
                   help="also write the optimized HLO text here")
    args = p.parse_args()

    if args.hlo_file:
        text = open(args.hlo_file).read()
        source = f"hlo file {args.hlo_file}"
    else:
        from aot_v5e import make_topology

        topo = make_topology(
            args.topology,
            tuple(int(x) for x in args.chips_per_host.split(",")),
        )
        text = lower_cell_step(
            topo.devices, image_size=args.image_size,
            batch_per_rank=args.batch_per_rank, plan=args.plan,
            dtype=args.dtype, opt=args.opt).compile().as_text()
        source = (
            f"chipless {args.topology} AOT compile "
            "(schedule structure, not measured time)"
        )
        if args.dump_hlo:
            open(args.dump_hlo, "w").write(text)

    report = issue_order_report(text)
    for row in report.pop("order"):
        print(f"{row['role']:>16}  {row['opcode']:<16} {row['op']}")
    report["source"] = source
    print(json.dumps(report))


if __name__ == "__main__":
    main()

"""Did the gradient sync actually overlap? Read XLA's schedule and see.

The overlapped step pipeline (parallel/buckets.py) only earns its keep if
XLA really interleaves the per-bucket collectives with the remaining
backward compute. This tool parses an optimized-HLO dump — instruction
order in a scheduled module (``is_scheduled=true``) IS the schedule — and
reports, per collective:

- async ``-start``/``-done`` pairs (the GPU-style spelling): how many
  compute ops (dot / convolution / fusion / custom-call) sit strictly
  between start and done — >=1 means the latency hides under compute;
- synchronous collectives (how this libtpu prints a bucket's all-reduce:
  one instruction, no HLO async pair): whether the op is SCHEDULED before
  the last backward compute op (metadata ``op_name=".../transpose(..."``
  marks backprop). That is an issue point and no more: nothing measured
  says such an instruction runs under the operations behind it. What was
  measured to (PERF.md section 6, PR 34) is the other form this libtpu has,
  an ``async-collective-start`` / ``-done`` pair of fusions with the
  kernels between them wrapped in ``async_collective_fusion``s, which
  ``--cell-step`` reads;
- exposed vs overlapped communication bytes, and the receipt the bucketing
  exists to produce: ``all_reduce_issues_before_last_bwd_compute >= 1``.

Chipless: the driver builds a REAL multi-chip v5e topology
(``v5e:2x2x1``, 4 devices — the 1x1x1 twin has no cross-chip collectives
to schedule) via jax.experimental.topologies, AOT-compiles the bucketed
DataParallel step, and analyzes the result. Single-process like the other
AOT tools: do not run two at once. Estimates of schedule structure, not
measured step time; the benchmark (benchmark/run.py) owns measured truth.

``--cell-step`` compiles the four-chip cell's own step instead (the
3000^2 ``s2dt`` ConvNet, bf16, batch 5 a chip, SGD, built by
``DataParallel`` with the options the engine itself hands ``jax.jit``) and
prints where its largest collective, the fc gradient's all-reduce, stands
among the backward kernels: ``kernels_between_issue_and_consumer`` is 7
(``fc``'s own input-gradient kernel and the six of the convolutions)
where the engine sums that leaf first and compiles with its options, and
0 where one ``pmean`` trails the step, as before PR 34. 25-45 s.

Usage:
  python tools/hlo_schedule.py                       # compile + analyze
  python tools/hlo_schedule.py --no-overlap          # monolithic baseline
  python tools/hlo_schedule.py --hlo-file dump.txt   # re-analyze a dump
  python tools/hlo_schedule.py --cell-step           # the dp4 cell's order
  python tools/hlo_schedule.py --cell-step --image-size 2000 --dtype fp32
                                    # the same engine at another shape: does
                                    # it compile, and the VMEM its wrapped
                                    # kernels hold beside the collective
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

from hlo_traffic import (  # noqa: E402
    _COLLECTIVES,
    _INST,
    operand_bytes,
    operand_tokens,
    shape_bytes,
)

# aot_v5e (and with it libtpu topologies) stays lazy in the driver below:
# schedule_report() must be importable on CPU-only boxes — the tier-1
# fixture test runs it against text.

#: Opcodes that count as "compute a collective can hide under". Fusions
#: cover the elementwise/reduce bulk XLA packs around the dots; dots and
#: convolutions are the backward work itself; custom-call catches Mosaic.
_COMPUTE = ("dot", "convolution", "fusion", "custom-call")

_BWD = re.compile(r'op_name="[^"]*transpose\(')


def schedule_report(hlo_text: str) -> dict:
    """Schedule-structure report of an optimized (scheduled) HLO module.

    Pure text analysis — no jax import. Processes every computation
    independently (shard_map bodies compile to nested computations);
    instruction order within a computation is taken as the schedule, which
    holds for modules printed after scheduling (``is_scheduled=true``).
    """
    collectives = []    # per-collective detail rows, all computations
    issue_count = 0     # all-reduce issue points before last bwd compute
    last_bwd_op = None
    result_bytes: dict[str, int] = {}  # instruction name -> result bytes

    def flush(ops):
        """Process one computation's ordered instruction list."""
        nonlocal issue_count, last_bwd_op
        if not ops:
            return
        compute_idx = [
            i for i, (_, opcode, _, _line) in enumerate(ops)
            if opcode in _COMPUTE
        ]
        bwd_idx = [i for i in compute_idx if _BWD.search(ops[i][3])]
        last_bwd = bwd_idx[-1] if bwd_idx else None
        if last_bwd is not None:
            last_bwd_op = ops[last_bwd][0]
        starts = {}  # name -> (index, opcode base, bytes)
        for i, (name, opcode, rest, _line) in enumerate(ops):
            base = opcode
            for suf in ("-start", "-done"):
                if opcode.endswith(suf):
                    base = opcode[: -len(suf)]
            if base not in _COLLECTIVES:
                continue
            before_bwd = last_bwd is not None and i < last_bwd
            nbytes = operand_bytes(rest, result_bytes)
            if opcode.endswith("-start"):
                starts[name] = (i, base, nbytes)
                if base == "all-reduce" and before_bwd:
                    issue_count += 1
            elif opcode.endswith("-done"):
                for tok in operand_tokens(rest):
                    if tok in starts:
                        s_i, s_base, s_bytes = starts.pop(tok)
                        between = sum(1 for c in compute_idx if s_i < c < i)
                        collectives.append({
                            "op": tok, "opcode": s_base, "form": "async",
                            "bytes": s_bytes,
                            "compute_ops_between": between,
                            "overlapped": between >= 1,
                            "before_last_bwd_compute": (
                                last_bwd is not None and s_i < last_bwd
                            ),
                        })
                        break
            else:
                # sync-form collective: its schedule position is the issue
                # point; scheduled before the last backward compute op
                # means there is compute left for the TC to hide it under
                if base == "all-reduce" and before_bwd:
                    issue_count += 1
                collectives.append({
                    "op": name, "opcode": base, "form": "sync",
                    "bytes": nbytes,
                    "compute_ops_between": sum(
                        1 for c in compute_idx if c > i
                    ) if before_bwd else 0,
                    "overlapped": before_bwd,
                    "before_last_bwd_compute": before_bwd,
                })
        # a -start whose -done never showed up (shouldn't happen in valid
        # scheduled HLO): count it exposed rather than dropping bytes
        for name, (s_i, s_base, s_bytes) in starts.items():
            collectives.append({
                "op": name, "opcode": s_base, "form": "async",
                "bytes": s_bytes, "compute_ops_between": 0,
                "overlapped": False, "before_last_bwd_compute": False,
            })

    ops: list[tuple[str, str, str, str]] = []
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped:
            flush(ops)       # new computation header
            ops = []
            continue
        m = _INST.match(line)
        if m:
            name, shape, opcode, rest = m.groups()
            result_bytes[name] = shape_bytes(shape)
            ops.append((name, opcode, rest, line))
    flush(ops)

    overlapped_b = sum(c["bytes"] for c in collectives if c["overlapped"])
    exposed_b = sum(c["bytes"] for c in collectives if not c["overlapped"])
    total_b = overlapped_b + exposed_b
    n_async = sum(1 for c in collectives if c["form"] == "async")
    return {
        "collective_count": len(collectives),
        "async_pairs": n_async,
        "sync_collectives": len(collectives) - n_async,
        "overlapped_collectives": sum(
            1 for c in collectives if c["overlapped"]
        ),
        "comm_bytes_total": total_b,
        "comm_bytes_overlapped": overlapped_b,
        "comm_bytes_exposed": exposed_b,
        "exposed_comm_fraction": (
            round(exposed_b / total_b, 4) if total_b else None
        ),
        "all_reduce_issues_before_last_bwd_compute": issue_count,
        "last_bwd_compute_op": last_bwd_op,
        "collectives": collectives,
    }


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


def lower_overlapped_step(devices, *, batch_per_rank: int = 8,
                          bucket_mb: float = 0.02,
                          grad_compress: str = "none",
                          overlap: bool = True):
    """Lower the DataParallel MNIST step on ``devices`` (topology or real):
    the module as the program wrote it, one all-reduce a bucket, before any
    XLA pass. The tiny bucket_mb default is sized to the ~116 KB ConvNet
    gradient so the step splits into several buckets — the schedule
    structure under test, not a tuning suggestion (real models keep the
    25 MB default)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from tpu_sandbox.models import ConvNet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.train import TrainState

    devices = np.array(devices)
    mesh = Mesh(devices, ("data",))
    world = devices.size
    # BN-free so the bucketed grad sync is the ONLY collective in the step
    model = ConvNet(use_bn=False)
    tx = optax.sgd(1e-2, momentum=0.9)
    state = jax.eval_shape(lambda: TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx,
    ))
    dp = DataParallel(
        model, tx, mesh, grad_compress=grad_compress,
        overlap_grad_sync=overlap, bucket_mb=bucket_mb, donate=False,
    )
    if dp.compress.needs_residual:
        state = state.replace(grad_residual=jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((world, *p.shape), jnp.float32),
            state.params,
        ))
    imgs = jax.ShapeDtypeStruct(
        (world * batch_per_rank, 28, 28, 1), jnp.float32
    )
    labs = jax.ShapeDtypeStruct((world * batch_per_rank,), jnp.int32)
    return dp.lower_step(state, imgs, labs)


def build_overlapped_hlo(devices, *, compiler_options: dict | None = None,
                         **step) -> str:
    """AOT-compile ``lower_overlapped_step(devices, **step)`` and return
    the optimized HLO text."""
    from tpu_sandbox.parallel.data_parallel import (
        TPU_OVERLAP_COMPILER_OPTIONS)

    lowered = lower_overlapped_step(devices, **step)
    try:
        return lowered.compile(
            compiler_options=compiler_options or TPU_OVERLAP_COMPILER_OPTIONS
        ).as_text()
    except Exception:
        if compiler_options is not None:
            raise
        # non-TPU backends (the CPU fallback) reject TPU-only options
        return lowered.compile().as_text()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--topology", default="v5e:2x2x1",
                   help="compile-only TPU topology (needs >1 chip for "
                        "cross-chip collectives to exist)")
    p.add_argument("--chips-per-host", default="2,2,1")
    p.add_argument("--batch-per-rank", type=int, default=None,
                   help="default 8, and 5 with --cell-step")
    p.add_argument("--bucket-mb", type=float, default=0.02)
    p.add_argument("--grad-compress", choices=["none", "bf16", "int8"],
                   default="none")
    p.add_argument("--no-overlap", action="store_true",
                   help="monolithic single-all-reduce baseline")
    p.add_argument("--cell-step", action="store_true",
                   help="compile convnet3000_dp4_bs5's own step and print "
                        "where the fc gradient's all-reduce is scheduled")
    p.add_argument("--image-size", type=int, default=3000,
                   help="--cell-step: the engine's step at another of "
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
    p.add_argument("--detail", action="store_true",
                   help="include the per-collective detail list")
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
        if args.cell_step:
            text = lower_cell_step(
                topo.devices, image_size=args.image_size,
                batch_per_rank=args.batch_per_rank or 5, plan=args.plan,
                dtype=args.dtype, opt=args.opt).compile().as_text()
        else:
            text = build_overlapped_hlo(
                topo.devices, batch_per_rank=args.batch_per_rank or 8,
                bucket_mb=args.bucket_mb, grad_compress=args.grad_compress,
                overlap=not args.no_overlap,
            )
        source = (
            f"chipless {args.topology} AOT compile "
            "(schedule structure, not measured time)"
        )
        if args.dump_hlo:
            open(args.dump_hlo, "w").write(text)

    if args.cell_step:
        report = issue_order_report(text)
        for row in report.pop("order"):
            print(f"{row['role']:>16}  {row['opcode']:<16} {row['op']}")
        report["source"] = source
        print(json.dumps(report))
        return
    report = schedule_report(text)
    if not args.detail:
        report.pop("collectives")
    report["overlap"] = not args.no_overlap
    report["bucket_mb"] = args.bucket_mb
    report["grad_compress"] = args.grad_compress
    report["source"] = source
    print(json.dumps(report))


if __name__ == "__main__":
    main()

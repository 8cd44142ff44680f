"""Pass 2: step-function jaxpr + chipless AOT HLO lint.

Two layers, split so the cheap one is always available:

- **Pure functions** (``lint_jaxpr``, ``lint_hlo_text``,
  ``lint_int8_padding``) take already-built artifacts
  and emit findings. They import nothing heavy — the fixture tests drive
  them directly.
- **The driver** (:func:`run_hlo_pass`) builds the real engines chipless
  and feeds them through: it traces ``DataParallel`` (plain, ZeRO, and
  the int8-grad-compress flag variant),
  ``PjitEngine``, ``PipelineParallel``, ``SeqParallel``, and the serve
  decode + bucketed-prefill steps to jaxprs on CPU
  devices, then AOT-compiles the DP/ZeRO steps against a multi-chip v5e
  topology (``tools/aot_v5e.make_topology``) to verify input donation
  from XLA's own ``memory_analysis``.

The driver mutates process env (``make_topology`` forces compiled
Pallas kernels) — run it in a dedicated process (the ``graftlint`` CLI),
never inside a long-lived pytest process. AOT tools are single-process:
do not run two at once.

Donation is checked on the AOT TPU path only: the CPU backend does not
implement buffer donation (aliasing always reports 0 there), so a CPU
"check" would flag every engine. ``memory_analysis().alias_size_in_bytes``
vs ``output_size_in_bytes`` is the signal — parsing the
``input_output_alias={...}`` header breaks on nested braces.
"""

from __future__ import annotations

import os
import sys

from tpu_sandbox.analysis.findings import Finding, make_finding

#: convert_element_type upcasts smaller than this many elements are noise
#: (scalar losses, iteration counters); above it the fp32 copy of a bf16
#: tensor is a real HBM cost.
UPCAST_MIN_ELEMENTS = 4096

#: jaxpr primitives that round-trip through the host inside the step
HOST_TRANSFER_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "outside_call",
    "infeed", "outfeed", "host_callback_call",
})

#: int8 wire overhead (scales + alignment padding) above this fraction of
#: the all-in total means padding dominates the compression win.
INT8_OVERHEAD_THRESHOLD = 0.25

#: donated-aliasing below this fraction of output bytes counts as missing
#: (the non-aliasable remainder — the scalar loss — is well under 1%).
DONATION_MIN_FRACTION = 0.5


# --------------------------------------------------------------------------
# pure lints (no jax import; fixture tests call these directly)
# --------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Yield every eqn in a (Closed)Jaxpr, recursing through call/scan/
    cond/shard_map sub-jaxprs found in eqn params."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for val in eqn.params.values():
            stack = [val]
            while stack:
                v = stack.pop()
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    yield from _iter_eqns(v)
                elif isinstance(v, (list, tuple)):
                    stack.extend(v)


def lint_jaxpr(jaxpr, label: str) -> list[Finding]:
    """Lint one traced step jaxpr. ``label`` names the step (e.g. 'dp');
    findings carry ``file="<step:label>"`` and line 0."""
    file = f"<step:{label}>"
    findings: list[Finding] = []
    import numpy as np  # ubiquitous; fine even in the "pure" layer

    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "convert_element_type":
            new = eqn.params.get("new_dtype")
            if new is None or "float32" not in str(new):
                continue
            aval = eqn.invars[0].aval
            src = str(getattr(aval, "dtype", ""))
            n = int(np.prod(getattr(aval, "shape", ()) or (1,)))
            if src == "bfloat16" and n >= UPCAST_MIN_ELEMENTS:
                findings.append(make_finding(
                    "GL-H202", file, 0,
                    f"bf16->f32 convert of {n} elements "
                    f"(shape {tuple(aval.shape)}) inside the step",
                    snippet=f"convert_element_type {tuple(aval.shape)} "
                            f"bf16->f32",
                ))
        elif name in HOST_TRANSFER_PRIMITIVES:
            findings.append(make_finding(
                "GL-H203", file, 0,
                f"host-transfer primitive '{name}' inside the step",
                snippet=f"primitive {name}",
            ))
    return findings


def lint_hlo_text(hlo_text: str, label: str) -> list[Finding]:
    """Host-transfer + large-upcast scan over optimized HLO text (the
    post-fusion complement of the jaxpr walk)."""
    import re

    file = f"<step:{label}>"
    findings: list[Finding] = []
    host_marks = ("SendToHost", "RecvFromHost", "custom_call_target=\"tpu_"
                  "host", "infeed(", "outfeed(")
    upcast = re.compile(r"=\s*f32\[([\d,]*)\][^ ]*\s+convert\(\s*%?\S*bf16")
    for i, line in enumerate(hlo_text.splitlines(), start=1):
        if any(m in line for m in host_marks):
            findings.append(make_finding(
                "GL-H203", file, 0,
                f"host transfer op in optimized HLO (module line {i})",
                snippet=line.strip()[:120],
            ))
            continue
        m = upcast.search(line)
        if m:
            dims = [int(d) for d in m.group(1).split(",") if d]
            n = 1
            for d in dims:
                n *= d
            if n >= UPCAST_MIN_ELEMENTS:
                findings.append(make_finding(
                    "GL-H202", file, 0,
                    f"bf16->f32 convert of {n} elements survived into "
                    f"optimized HLO (module line {i})",
                    snippet=line.strip()[:120],
                ))
    return findings


def lint_donation(label: str, *, donate_requested: bool, alias_bytes: int,
                  output_bytes: int) -> tuple[list[Finding], dict]:
    """GL-H201 verdict from XLA's memory-analysis numbers. Returns
    ``(findings, report_entry)``; the driver feeds real compiles through
    here, the fixture tests feed synthetic numbers."""
    frac = alias_bytes / output_bytes if output_bytes else 0.0
    entry = {
        "donate_requested": donate_requested,
        "alias_bytes": int(alias_bytes),
        "output_bytes": int(output_bytes),
        "alias_fraction": round(frac, 4),
        "donation": "verified" if frac >= DONATION_MIN_FRACTION
        else "missing",
    }
    if frac < DONATION_MIN_FRACTION:
        return [make_finding(
            "GL-H201", f"<step:{label}>", 0,
            f"step compiled with donate={donate_requested} but XLA aliased "
            f"only {int(alias_bytes)}/{int(output_bytes)} output bytes — "
            "TrainState buffers are not donated",
            snippet=f"alias_fraction={frac:.4f}",
        )], entry
    return [], entry


def lint_int8_padding(leaf_sizes, size: int, *, block: int = 256,
                      label: str = "dp",
                      threshold: float = INT8_OVERHEAD_THRESHOLD,
                      compress=None) -> tuple[list[Finding], dict]:
    """GL-H205 from the analytic wire model: fraction of the int8 all-in
    wire bytes that is scales + block/axis alignment padding. Returns
    ``(findings, wire_report)``."""
    if compress is None:
        from tpu_sandbox.parallel.collectives import CompressedAllReduce
        compress = CompressedAllReduce(mode="int8", block=block)
    wire = compress.wire_bytes(list(leaf_sizes), size)
    frac = wire["overhead"] / wire["total"] if wire["total"] else 0.0
    wire = dict(wire, overhead_fraction=round(frac, 4), world=size,
                block=block)
    if frac > threshold:
        return [make_finding(
            "GL-H205", f"<step:{label}>", 0,
            f"int8 wire overhead (scales+padding) is {frac:.0%} of total "
            f"({wire['overhead']}/{wire['total']} bytes) at world={size}, "
            f"block={block}",
            snippet=f"int8 overhead_fraction={frac:.4f}",
        )], wire
    return [], wire


# --------------------------------------------------------------------------
# driver: build the real engines chipless and lint them
# --------------------------------------------------------------------------

def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _tools_on_path() -> None:
    tools = os.path.join(_repo_root(), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)


def _trace_targets(steps) -> tuple[list[Finding], dict]:
    """Jaxpr-lint the requested engines on CPU devices (needs 8; the CLI
    sets XLA_FLAGS=--xla_force_host_platform_device_count=8 pre-import)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from tpu_sandbox.models import ConvNet
    from tpu_sandbox.train import TrainState

    findings: list[Finding] = []
    report: dict = {}
    devices = np.array(jax.devices()[:8])
    if devices.size < 8:
        report["jaxpr"] = {"status": "skipped",
                           "reason": f"only {devices.size} devices"}
        return findings, report

    model = ConvNet(use_bn=False)
    tx = optax.sgd(1e-2, momentum=0.9)
    state = jax.eval_shape(lambda: TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx,
    ))
    imgs = jax.ShapeDtypeStruct((64, 28, 28, 1), jnp.float32)
    labs = jax.ShapeDtypeStruct((64,), jnp.int32)
    mesh = Mesh(devices, ("data",))

    def trace(label, fn, *args):
        try:
            jaxpr = fn.trace(*args).jaxpr
        except Exception as e:
            report[label] = {"status": "trace-failed", "error": str(e)[:200]}
            return
        fnd = lint_jaxpr(jaxpr, label)
        findings.extend(fnd)
        report[label] = {"status": "traced", "findings": len(fnd)}

    from tpu_sandbox.parallel import DataParallel, PjitEngine

    if "dp" in steps:
        dp = DataParallel(model, tx, mesh)
        trace("dp", dp._compile_for(state), state, imgs, labs)
    if "zero" in steps:
        dpz = DataParallel(model, tx, mesh, zero=True)
        trace("zero", dpz._compile_for(state), state, imgs, labs)
    if "pjit" in steps:
        eng = PjitEngine(model, tx, mesh)
        trace("pjit", eng._build(state), state, imgs, labs)
    if "pipeline" in steps:
        from tpu_sandbox.models.transformer import TransformerConfig
        from tpu_sandbox.parallel import PipelineParallel

        cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=4, d_ff=64, max_len=64)
        mesh_pp = Mesh(devices.reshape(2, 4), ("data", "pipe"))
        pp = PipelineParallel(cfg, tx, mesh_pp, microbatches=2)
        pstate = jax.eval_shape(
            pp.init_state, jax.random.key(0),
            jnp.zeros((4, 64), jnp.int32),
        )
        toks = jax.ShapeDtypeStruct((4, 64), jnp.int32)
        trace("pipeline", pp._compile_for(pstate), pstate, toks, toks)
    # engine-flag variant: the same DP step graph is a different graph
    # under grad compression, with its own regression history — lint it
    # as a first-class step
    if "dp-int8" in steps:
        dpc = DataParallel(model, tx, mesh, grad_compress="int8")
        trace("dp-int8", dpc._compile_for(state), state, imgs, labs)
    if "sp" in steps:
        from tpu_sandbox.models.transformer import TransformerConfig
        from tpu_sandbox.models.transformer import TransformerLM
        from tpu_sandbox.parallel import SeqParallel

        cfg_sp = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64, max_len=64)
        mesh_sp = Mesh(devices.reshape(2, 4), ("data", "sp"))
        sp = SeqParallel(
            lambda attn: TransformerLM(cfg_sp, attention_fn=attn),
            tx, mesh_sp)
        sstate = jax.eval_shape(
            sp.init_state, jax.random.key(0),
            jnp.zeros((2, 64), jnp.int32),
        )
        stoks = jax.ShapeDtypeStruct((2, 64), jnp.int32)
        trace("sp", sp._jitted, sstate, stoks, stoks, stoks)
    if "decode" in steps:
        from tpu_sandbox.models.transformer import TransformerConfig
        from tpu_sandbox.models.transformer import TransformerLM
        from tpu_sandbox.serve.cache import CacheConfig
        from tpu_sandbox.serve.decode import buffer_shapes, make_decode_fn

        cfg_d = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                  n_layers=2, d_ff=64, max_len=64)
        ccfg = CacheConfig(num_blocks=16, block_size=8,
                           max_blocks_per_seq=4)
        dparams = jax.eval_shape(
            lambda: TransformerLM(cfg_d).init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
        kd, vd = buffer_shapes(cfg_d, ccfg, 2, jnp.float32)
        trace("decode", make_decode_fn(cfg_d, ccfg),
              dparams, kd, vd,
              jax.ShapeDtypeStruct((2, 1), jnp.int32),
              jax.ShapeDtypeStruct((2,), jnp.int32),
              jax.ShapeDtypeStruct((2, ccfg.max_blocks_per_seq), jnp.int32))
    if "prefill" in steps:
        from tpu_sandbox.models.transformer import TransformerConfig
        from tpu_sandbox.models.transformer import TransformerLM
        from tpu_sandbox.serve.cache import CacheConfig
        from tpu_sandbox.serve.decode import buffer_shapes, make_prefill_fn

        cfg_p = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                  n_layers=2, d_ff=64, max_len=64)
        pcfg = CacheConfig(num_blocks=16, block_size=8,
                           max_blocks_per_seq=4)
        pparams = jax.eval_shape(
            lambda: TransformerLM(cfg_p).init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
        kp, vp = buffer_shapes(cfg_p, pcfg, 1, jnp.float32)
        # one trace per bucket length: each bucket is its own static-shape
        # program in the serve AOT set, and padding scatters through the
        # null block have their own upcast/host-transfer surface
        for bucket in (8, 16):
            trace("prefill" if bucket == 8 else f"prefill-b{bucket}",
                  make_prefill_fn(cfg_p),
                  pparams, kp, vp,
                  jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                  jax.ShapeDtypeStruct((bucket,), jnp.int32),
                  jax.ShapeDtypeStruct((), jnp.int32))
    # second-wave engines (VERDICT: the lint only covers what it traces):
    # FSDP-as-specs, the full Megatron TP ruleset, expert parallelism, and
    # the per-stage MPMD programs each have collective/donation surfaces
    # the first-wave steps never exercise
    if "fsdp" in steps:
        engf = PjitEngine(model, tx, mesh, fsdp_axis="data")
        trace("fsdp", engf._build(state), state, imgs, labs)
    if "tp" in steps:
        from tpu_sandbox.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )
        from tpu_sandbox.parallel.pjit_engine import megatron_rules

        # every megatron-ruled dim divisible by the 4-way model axis
        cfg_tp = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                   n_layers=2, d_ff=64, max_len=64)
        mesh_tp = Mesh(devices.reshape(2, 4), ("data", "model"))
        lm_tp = TransformerLM(cfg_tp)
        engt = PjitEngine(lm_tp, tx, mesh_tp, task="lm",
                          rules=megatron_rules("model"))
        tstate = jax.eval_shape(lambda: TrainState.create(
            lm_tp, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx))
        ttoks = jax.ShapeDtypeStruct((8, 16), jnp.int32)
        trace("tp", engt._build(tstate), tstate, ttoks, ttoks)
    if "ep" in steps:
        from jax.sharding import PartitionSpec as P

        from tpu_sandbox.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )

        cfg_ep = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                   n_layers=1, d_ff=64, max_len=64,
                                   n_experts=4, capacity_factor=2.0)
        mesh_ep = Mesh(devices.reshape(2, 4), ("data", "expert"))
        lm_ep = TransformerLM(cfg_ep)
        enge = PjitEngine(lm_ep, tx, mesh_ep, task="lm",
                          rules=[(r"w_(up|down)", P("expert", None, None))])
        estate = jax.eval_shape(lambda: TrainState.create(
            lm_ep, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx))
        etoks = jax.ShapeDtypeStruct((8, 16), jnp.int32)
        trace("ep", enge._build(estate), estate, etoks, etoks)
    if "mpmd" in steps:
        from tpu_sandbox.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )
        from tpu_sandbox.mpmd.program import StageProgram, stage_params

        cfg_m = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                  n_layers=4, d_ff=64, max_len=64)
        # stage_params slices concrete leaves; a tiny real init is cheap
        flat_m = jax.tree.map(np.asarray, TransformerLM(cfg_m).init(
            jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"])
        for s in (0, 1):
            prog = StageProgram(cfg_m, tx, s, 2, 2)
            absp = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                stage_params(flat_m, s, 2))
            if prog.is_first:
                x = jax.ShapeDtypeStruct((4, 16), jnp.int32)
            else:
                x = jax.ShapeDtypeStruct((4, 16, cfg_m.d_model), cfg_m.dtype)
            if prog.is_last:
                trace(f"mpmd-s{s}-loss_grad", prog.loss_grad, absp, x,
                      jax.ShapeDtypeStruct((4, 16), jnp.int32))
            else:
                trace(f"mpmd-s{s}-fwd", prog.fwd, absp, x)
                g = jax.eval_shape(prog.fwd, absp, x)
                trace(f"mpmd-s{s}-bwd", prog.bwd, absp, x, g)
    return findings, report


def _aot_targets(steps, *, topology: str, chips,
                 int8_check: bool) -> tuple[list[Finding], dict]:
    """Donation + padding lint against a chipless v5e topology."""
    _tools_on_path()
    from aot_v5e import make_topology

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from tpu_sandbox.models import ConvNet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.train import TrainState

    findings: list[Finding] = []
    report: dict = {}
    topo = make_topology(topology, tuple(chips))
    devices = np.array(topo.devices)
    world = devices.size
    mesh = Mesh(devices, ("data",))

    model = ConvNet(use_bn=False)
    tx = optax.sgd(1e-2, momentum=0.9)
    state = jax.eval_shape(lambda: TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx,
    ))
    imgs = jax.ShapeDtypeStruct((world * 8, 28, 28, 1), jnp.float32)
    labs = jax.ShapeDtypeStruct((world * 8,), jnp.int32)

    def check_donation(label: str, engine) -> None:
        compiled = engine.lower_step(state, imgs, labs).compile()
        ma = compiled.memory_analysis()
        alias = getattr(ma, "alias_size_in_bytes", None)
        out = getattr(ma, "output_size_in_bytes", 0)
        if alias is None:
            report[label] = {"donation": "unknown",
                             "reason": "no alias_size_in_bytes"}
            return
        fnd, report[label] = lint_donation(
            label, donate_requested=engine._donate,
            alias_bytes=int(alias), output_bytes=int(out),
        )
        findings.extend(fnd)
        findings.extend(lint_hlo_text(compiled.as_text(), label))

    if "dp" in steps:
        check_donation("dp", DataParallel(model, tx, mesh))
    if "zero" in steps:
        check_donation("zero", DataParallel(model, tx, mesh, zero=True))

    if int8_check:
        leaf_sizes = [
            int(np.prod(l.shape)) for l in jax.tree.leaves(state.params)
        ]
        fnd, wire = lint_int8_padding(leaf_sizes, world, label="dp")
        findings.extend(fnd)
        report["int8_wire"] = wire
    return findings, report


def run_hlo_pass(
    *,
    steps=("dp", "zero", "pjit", "pipeline", "dp-int8",
           "sp", "decode", "prefill", "fsdp", "tp", "ep", "mpmd"),
    aot: bool = True,
    topology: str = "v5e:2x2x1",
    chips=(2, 2, 1),
    int8_check: bool = True,
) -> tuple[list[Finding], dict]:
    """Full Pass 2. Returns ``(findings, report)``; ``report`` carries the
    per-step donation/trace status the acceptance gate prints. With
    ``aot=False`` only the CPU jaxpr layer runs (donation is then
    'skipped', never 'missing' — CPU can't witness aliasing)."""
    findings, report = _trace_targets(steps)
    if aot:
        try:
            aot_findings, aot_report = _aot_targets(
                steps, topology=topology, chips=chips,
                int8_check=int8_check,
            )
            findings.extend(aot_findings)
            report["aot"] = aot_report
        except Exception as e:
            report["aot"] = {"status": "skipped",
                             "reason": f"{type(e).__name__}: {e}"[:300]}
    else:
        report["aot"] = {"status": "skipped", "reason": "aot disabled"}
    return findings, report

"""Chipless v5e AOT analysis: compile the real train step for TPU without
a TPU and read XLA's own numbers.

The free rehearsal before spending chip time: it shows that Mosaic accepts
every kernel at production geometry, and what the compiler thinks the step
needs. jax.experimental.topologies + the local libtpu build a compile-only
v5e device (`chips_per_host_bounds=[1,1,1]` unlocks the 1x1x1 topology),
and `jit(...).lower().compile()` then yields:

- ``memory_analysis()``: argument/output/temp bytes — peak-HBM estimates
  (the chipless twin of the capacity experiment);
- ``cost_analysis()``: executed FLOPs and bytes accessed — the traffic
  model that predicts step time on the 819 GB/s HBM.

Usage:
  python tools/aot_v5e.py --plan s2d --batch 5            # one config
  python tools/aot_v5e.py --plan plain --batch 5
  python tools/aot_v5e.py --capacity --plan s2d           # bisect max batch

Numbers printed here are COMPILER estimates, labeled as such; measured
truth comes only from a run on the chip (chip_smoke.py, benchmark/run.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# runnable as `python tools/aot_v5e.py` from anywhere (sys.path[0] is
# tools/, which does not see the tpu_sandbox package at the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16 * 1024**3  # v5e: 16 GiB HBM per chip
HBM_BW = 819e9            # v5e HBM bandwidth, bytes/sec


def make_topology(topology_name: str = "v5e:1x1x1",
                  chips_per_host_bounds=(1, 1, 1)):
    """Compile-only TPU topology. The 1x1x1 default is the single-chip
    memory/FLOPs twin; multi-chip bounds (e.g. ``"v5e:2x2x1"``,
    ``(2, 2, 1)``) give tools that need real cross-chip collectives in the
    compiled HLO — the schedule receipt in tools/hlo_schedule.py — a mesh
    to compile against."""
    # env setup lives HERE, not at module import: importing this module
    # (e.g. tests importing hlo_traffic for its classifier) must not
    # flip the whole process into forced-compiled-kernel mode — that
    # poisoned a full pytest run once (interpret-mode CPU tests started
    # lowering real Mosaic kernels and died)
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    # lower the REAL Mosaic kernels, not the interpreter (see
    # pallas_common): this process only compiles, never executes
    os.environ.setdefault("TPU_SANDBOX_FORCE_COMPILED_KERNELS", "1")

    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology_name,
        chips_per_host_bounds=list(chips_per_host_bounds),
    )


def compile_step(topo, plan: str, batch: int, image_size: int = 3000,
                 dtype_name: str = "bf16", remat: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.train import TrainState, make_train_step

    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P())
    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    model = pick_convnet(image_size, plan=plan, dtype=dtype)
    tx = optax.sgd(1e-4)
    state = jax.eval_shape(lambda: TrainState.create(
        model, jax.random.key(0),
        jnp.zeros((1, image_size, image_size, 1), dtype), tx,
    ))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), state
    )
    imgs = jax.ShapeDtypeStruct((batch, 28, 28, 1), jnp.float32, sharding=sh)
    labs = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sh)
    step = make_train_step(model, tx, image_size=(image_size, image_size),
                           donate=True, remat=remat)
    return step.trace(state, imgs, labs).lower().compile()


def compile_head(topo, batch: int, image_size: int = 3000):
    """Only the s2dt plan's fc head: ``fc_t`` forward, its three gradients
    and the SGD add, on shapes alone — the 18,000,000 x 10 weight against
    the [N, H/4, 32, W/4] map. Ten seconds where the whole step takes 35,
    and the program tests/test_pallas_fc_t.py holds free of weight-sized
    loops."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_sandbox.ops.pallas_fc_t import fc_t

    sh = NamedSharding(Mesh(np.array(topo.devices), ("data",)), P())
    dtype = jnp.bfloat16  # the production compute dtype
    h = w = image_size // 4
    c, k = 32, 10

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def train_step(y, kernel, bias, labels):
        def loss(y, kernel, bias):
            logits = fc_t(y, kernel, bias, dtype).astype(jnp.float32)
            picked = jnp.take_along_axis(
                jax.nn.log_softmax(logits), labels[:, None], 1)
            return -jnp.mean(picked)

        dy, dkernel, dbias = jax.grad(loss, argnums=(0, 1, 2))(
            y, kernel, bias)
        return dy, kernel - 1e-4 * dkernel, bias - 1e-4 * dbias

    return jax.jit(train_step, donate_argnums=(1, 2)).lower(
        spec((batch, h, c, w), dtype), spec((h * c * w, k), jnp.float32),
        spec((k,), jnp.float32), spec((batch,), jnp.int32)).compile()


def analyze(compiled, plan: str, batch: int, remat: bool = False) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    # donated args alias outputs; live peak ~ args + temps
    peak = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    return {
        "plan": plan,
        "remat": remat,
        "batch": batch,
        "flops": ca["flops"],
        "bytes_accessed": ca.get("bytes accessed"),
        "argument_bytes": ma.argument_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "est_peak_bytes": peak,
        "est_peak_gb": round(peak / 1024**3, 2),
        "fits_16g_hbm": peak < HBM_BYTES * 0.98,
        "est_step_ms_bw_bound": (
            round(ca["bytes accessed"] / HBM_BW * 1e3, 1)
            if ca.get("bytes accessed") else None
        ),
        "source": "chipless v5e AOT compile (XLA estimates, not measurements)",
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--plan", choices=["s2dt", "s2d", "plain"],
                   default="s2dt")
    p.add_argument("--batch", type=int, default=5)
    p.add_argument("--image-size", type=int, default=3000)
    p.add_argument("--dtype", choices=["bf16", "fp32"], default="bf16")
    p.add_argument("--remat", action="store_true",
                   help="recompute-forward backward (jax.checkpoint over "
                        "the loss) — the capacity lever")
    p.add_argument("--capacity", action="store_true",
                   help="bisect the largest batch whose est peak fits HBM")
    args = p.parse_args()
    topo = make_topology()

    if not args.capacity:
        compiled = compile_step(topo, args.plan, args.batch, args.image_size,
                                args.dtype, remat=args.remat)
        print(json.dumps(analyze(compiled, args.plan, args.batch, args.remat)))
        return

    def fits(bs: int) -> bool:
        try:
            c = compile_step(topo, args.plan, bs, args.image_size, args.dtype,
                             remat=args.remat)
        except Exception as e:  # compiler OOM = does not fit
            if "exceed" in str(e).lower() or "memory" in str(e).lower():
                return False
            raise
        r = analyze(c, args.plan, bs, args.remat)
        print(json.dumps(r), flush=True)
        return r["fits_16g_hbm"]

    lo, hi, bs = 0, None, 1
    while bs <= 512:
        if fits(bs):
            lo = bs
            bs *= 2
        else:
            hi = bs
            break
    if hi is None:
        hi = 513
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    print(json.dumps({
        "metric": "aot_est_max_batch", "plan": args.plan,
        "remat": args.remat, "value": lo,
        "first_over": hi if hi <= 512 else None,
        "source": "chipless v5e AOT compile (XLA estimates)",
    }))


if __name__ == "__main__":
    main()

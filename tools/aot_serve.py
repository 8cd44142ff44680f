"""Chipless AOT receipt for the serve decode step: cache donation + cost.

Compiles ``serve/decode.py``'s single-token decode step for a v5e (no TPU
needed — jax.experimental.topologies) and reads XLA's own numbers:

- ``alias_size_in_bytes`` must cover both KV page buffers — the proof that
  the per-step cache update is in-place (donated), not a copy of the whole
  cache every token;
- argument/output/temp bytes and FLOPs — the decode step's HBM working
  set, which is what bounds tokens/sec on a real chip (decode is
  bandwidth-bound: the cache read dominates).

Usage:
  python tools/aot_serve.py                       # default geometry
  python tools/aot_serve.py --num-blocks 512 --block-size 16 --max-batch 8
  python tools/aot_serve.py --cache-dtype bf16    # half the cache traffic
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.aot_v5e import make_topology  # noqa: E402


def compile_decode(topo, *, num_blocks: int, block_size: int,
                   max_blocks_per_seq: int, max_batch: int,
                   cache_dtype_name: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.decode import make_decode_fn, page_shapes

    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P())

    model_cfg = TransformerConfig()
    cache_cfg = CacheConfig(num_blocks=num_blocks, block_size=block_size,
                            max_blocks_per_seq=max_blocks_per_seq)
    cache_dtype = jnp.bfloat16 if cache_dtype_name == "bf16" else jnp.float32

    def sharded(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    params = jax.eval_shape(
        lambda: TransformerLM(model_cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree.map(sharded, params)
    kd, vd = (sharded(s) for s in page_shapes(model_cfg, cache_cfg,
                                              cache_dtype))
    fn = make_decode_fn(model_cfg, cache_cfg, max_batch, cache_dtype)
    compiled = fn.lower(
        params, kd, vd,
        sharded(jax.ShapeDtypeStruct((max_batch, 1), jnp.int32)),
        sharded(jax.ShapeDtypeStruct((max_batch,), jnp.int32)),
        sharded(jax.ShapeDtypeStruct(
            (max_batch, cache_cfg.max_blocks_per_seq), jnp.int32)),
    ).compile()
    cache_bytes = 2 * kd.size * kd.dtype.itemsize
    return compiled, cache_bytes, model_cfg, cache_cfg


def analyze(compiled, cache_bytes: int, args) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    alias = ma.alias_size_in_bytes
    return {
        "metric": "serve_aot_donation",
        "geometry": {
            "num_blocks": args.num_blocks, "block_size": args.block_size,
            "max_blocks_per_seq": args.max_blocks_per_seq,
            "max_batch": args.max_batch, "cache_dtype": args.cache_dtype,
        },
        "kv_cache_bytes": cache_bytes,
        "alias_bytes": alias,
        # the decode step donates both page buffers: XLA must alias at
        # least the full cache input->output (anything less means a
        # fresh cache copy per generated token)
        "donation_verified": alias >= cache_bytes,
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "flops_per_step": ca.get("flops"),
        "bytes_accessed_per_step": ca.get("bytes accessed"),
        "source": "chipless v5e AOT compile (XLA estimates, not "
                  "measurements)",
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--num-blocks", type=int, default=64)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-blocks-per-seq", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--cache-dtype", choices=["fp32", "bf16"], default="fp32")
    args = p.parse_args()
    topo = make_topology()
    compiled, cache_bytes, _, _ = compile_decode(
        topo, num_blocks=args.num_blocks, block_size=args.block_size,
        max_blocks_per_seq=args.max_blocks_per_seq,
        max_batch=args.max_batch, cache_dtype_name=args.cache_dtype)
    print(json.dumps(analyze(compiled, cache_bytes, args)))


if __name__ == "__main__":
    main()

"""Chipless per-op attribution of the LM train step (the ConvNet trick,
applied to the second benchmark family — VERDICT r04 next-6 prep).

AOT-compiles bench_lm's EXACT headline step (12L d1024 ff4096 v32k
s2048 bf16, dots-remat, flash attention, fused Pallas CE, AdamW) for a
v5e via jax.experimental.topologies, then ranks the non-Pallas entry
ops by XLA's ``estimated_cycles`` and by padded operand/output bytes —
the same attribution that located the ConvNet's ~95 ms of layout glue
(memory: hlo-cycle-attribution). Pallas custom calls carry no estimate,
so this ranks exactly the "unattributed residue" between measured step
time and kernel time.

Usage: python tools/aot_lm_cycles.py [--batch 16] [--dump-hlo PATH]
One JSON doc to stdout. Estimates, not measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))

from aot_v5e import HBM_BW, make_topology  # noqa: E402


def compile_lm_step(topo, batch: int, seq: int = 2048):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_sandbox.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_attention import flash_attention_fn
    from tpu_sandbox.train import TrainState

    cfg = TransformerConfig(vocab_size=32768, d_model=1024, n_heads=8,
                            n_layers=12, d_ff=4096, max_len=seq,
                            dtype=jnp.bfloat16, remat=True,
                            remat_policy="dots", fp32_logits=False)
    model = TransformerLM(cfg, attention_fn=flash_attention_fn())
    tx = optax.adamw(3e-4)
    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P())
    state = jax.eval_shape(lambda: TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, seq), jnp.int32), tx))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state)

    def loss_fn(params, tokens, targets):
        logits = model.apply({"params": params}, tokens)
        return cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1))

    def step(state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, tokens, targets)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        return state.replace(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            opt_state=new_opt,
        ), loss

    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sh)
    return jax.jit(step, donate_argnums=(0,)).trace(
        state, toks, toks).lower().compile()


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--top", type=int, default=20)
    p.add_argument("--dump-hlo", default=None)
    args = p.parse_args()

    topo = make_topology()
    compiled = compile_lm_step(topo, args.batch, args.seq)
    txt = compiled.as_text()
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(txt)
    entry = txt[txt.index("ENTRY "):]

    rows = []
    for m in re.finditer(
            r'^\s+%?([\w.\-]+) = .*?estimated_cycles":"(\d+)"', entry, re.M):
        op = re.search(r'op_name="([^"]*)"', m.group(0))
        rows.append((int(m.group(2)) / 940e3, m.group(1),
                     (op.group(1) if op else "")))
    rows.sort(reverse=True)

    ca = compiled.cost_analysis()
    doc = {
        "what": ("per-op estimated_cycles (940 MHz -> ms) of the"
                 " non-Pallas entry ops in the AOT-compiled LM train"
                 " step - chipless estimate, not a measurement. The"
                 " total EXCLUDES the Pallas flash-attention and"
                 " fused-CE kernels (custom calls carry no estimate)"),
        "config": f"12L d1024 ff4096 v32k s{args.seq} bf16 dots-remat "
                  f"flash fused-CE adamw b{args.batch}",
        "bytes_accessed_gb": round(ca.get("bytes accessed", 0) / 1e9, 1),
        "bw_floor_ms": round(ca.get("bytes accessed", 0) / HBM_BW * 1e3, 1),
        "non_kernel_est_ms_total": round(sum(r[0] for r in rows), 1),
        "n_ops_with_estimates": len(rows),
        "top": [
            {"est_ms": round(ms, 2), "op": name, "op_name": op[:110]}
            for ms, name, op in rows[:args.top]
        ],
        "source": "chipless v5e AOT compile (tools/aot_lm_cycles.py)",
    }
    print(json.dumps(doc, indent=1))


if __name__ == "__main__":
    main()

"""On the chip: the flash kernels' gradients against float32 attention.

No benchmark cell holds an attention gradient at head size 64 (GPT-2's
check compares logits and the loss). This reads them directly, at the
three LM cells' attention shapes in bfloat16 — GPT-2's (S 1024, 64 / 64:
the packed form, two heads a block), Xing4's latent attention (S 4096,
192 / 128, its softmax scale: the padded form) and Nemotron's (S 8192,
128 / 128: packed, one head a block) — on a few heads:
``flash_attention``'s out, dq, dk, dv against
``ops.attention.causal_attention`` on the same (bf16-rounded) inputs in
float32 at ``highest`` matmul precision. Prints one JSON line a shape
with the relative RMS deviations (rms(got - want) / rms(want)); bf16's
own rounding is about 1e-2.

    python tools/attn_grad_check.py [--seed N]        # needs the TPU
    python tools/attn_grad_check.py --tiny            # CPU rehearsal of the code path

Calls only ``flash_attention(q, k, v, scale=...)``, so it runs unchanged
on an older checkout of this repository.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (B, S, H, D, Dv, scale); heads are independent, so a few of them at
# the cell's S and head sizes read the same deviations as all of them
SHAPES = {
    "gpt2m_s1024_64_64": (2, 1024, 4, 64, 64, None),
    "xing4_s4096_192_128": (1, 4096, 4, 192, 128, 192 ** -0.5 * 2.00474),
    "nemotron3s_s8192_128_128": (1, 8192, 4, 128, 128, None),
}
TINY = {
    "tiny_64_64": (1, 256, 2, 64, 64, None),
    "tiny_192_128": (1, 384, 2, 192, 128, 0.1),
    "tiny_128_128": (1, 256, 2, 128, 128, None),
}


def deviations(shape, seed):
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.ops.attention import causal_attention
    from tpu_sandbox.ops.pallas_attention import flash_attention

    b, s, h, d, dv, scale = shape
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k = (jax.random.normal(x, (b, s, h, d), jnp.bfloat16) for x in keys[:2])
    v, g = (jax.random.normal(x, (b, s, h, dv), jnp.bfloat16)
            for x in keys[2:])

    def run(attn, *xs):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, scale=scale), *xs)
        return (out, *vjp(g.astype(out.dtype)))

    # operands as arguments: closed over, they are constants XLA folds
    got = jax.jit(functools.partial(run, flash_attention))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(run, causal_attention))(
            *(x.astype(jnp.float32) for x in (q, k, v)))

    def rel_rms(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))

    return {n: rel_rms(a, w) for n, a, w in zip(("out", "dq", "dk", "dv"),
                                                got, want)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes in interpret mode, for the CPU")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit("no TPU: the cells' shapes are read on the chip only "
                 "(--tiny rehearses the code path on the CPU)")
    for name, shape in (TINY if args.tiny else SHAPES).items():
        print(json.dumps({"shape": name, "seed": args.seed,
                          "device": dev.device_kind,
                          **deviations(shape, args.seed)}), flush=True)


if __name__ == "__main__":
    main()

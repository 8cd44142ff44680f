"""Parity checks for the ConvNet: the on-device plan-vs-plain numerics
preflight, and a torch-replica twin for loss-curve experiments.

``numerics_preflight`` compares the execution plan under test with the
plain ``ConvNet`` on the device it runs on; ``chip_smoke.py`` fails on
its verdict.

The reference architecture is torch (mnist_onegpu.py:11-31); this framework
re-implements it in flax (models/convnet.py). To demonstrate end-to-end
loss-curve parity — not just per-op equality — this module builds the torch
model with weights COPIED from the flax params, so both frameworks start
from bit-identical init and can be trained on identical batches
(parity_run.py at the repo root records the experiment; tests/test_convnet.py
asserts it at short horizon).

Layout conversions: flax conv kernels are HWIO -> torch OIHW; the
framework's canonical fc row order is (h, c, w) (models/convnet.py)
while torch flattens NCHW as (c, h, w), so the fc weight is re-blocked
accordingly.
"""

from __future__ import annotations

import numpy as np


def torch_twin(torch, params, hw: int):
    """Torch replica of the reference stack (conv 1->16 k5 p2, BN, ReLU,
    pool /2; conv 16->32; fc -> 10) with weights copied from flax
    ``params``. ``hw`` = spatial size after the two pools (H/4 for square
    inputs)."""
    tnn = torch.nn

    class TorchNet(tnn.Module):
        def __init__(self):
            super().__init__()
            self.layer1 = tnn.Sequential(
                tnn.Conv2d(1, 16, 5, stride=1, padding=2),
                tnn.BatchNorm2d(16), tnn.ReLU(), tnn.MaxPool2d(2, 2))
            self.layer2 = tnn.Sequential(
                tnn.Conv2d(16, 32, 5, stride=1, padding=2),
                tnn.BatchNorm2d(32), tnn.ReLU(), tnn.MaxPool2d(2, 2))
            self.fc = tnn.Linear(32 * hw * hw, 10)

        def forward(self, x):
            x = self.layer2(self.layer1(x))
            return self.fc(x.reshape(x.shape[0], -1))

    tm = TorchNet()
    with torch.no_grad():
        for i, layer in enumerate([tm.layer1, tm.layer2], start=1):
            k = np.asarray(params[f"conv{i}"]["kernel"]).transpose(3, 2, 0, 1).copy()
            layer[0].weight.copy_(torch.from_numpy(k))
            layer[0].bias.copy_(torch.from_numpy(
                np.asarray(params[f"conv{i}"]["bias"]).copy()))
            layer[1].weight.copy_(torch.from_numpy(
                np.asarray(params[f"bn{i}"]["scale"]).copy()))
            layer[1].bias.copy_(torch.from_numpy(
                np.asarray(params[f"bn{i}"]["bias"]).copy()))
        fck = np.asarray(params["fc"]["kernel"])
        # ours: canonical (h, c, w) rows (models/convnet.py) -> torch:
        # NCHW flatten = (c, h, w) rows
        fck_chw = (fck.reshape(hw, 32, hw, 10)
                   .transpose(1, 0, 2, 3).reshape(32 * hw * hw, 10))
        tm.fc.weight.copy_(torch.from_numpy(fck_chw.T.copy()))
        tm.fc.bias.copy_(torch.from_numpy(np.asarray(params["fc"]["bias"]).copy()))
    return tm


_PREFLIGHT_CACHE: dict = {}


def numerics_preflight(model, width: int) -> dict:
    """The framework-regression gate (VERDICT r04 weak-4/next-4).

    A divergent loss at 3000^2 has a known innocent cause — the reference
    recipe's own measured chaos (BASELINE.md "Loss dynamics at 3000^2") —
    which on its own would also wave through a framework-INTRODUCED
    numerics bug. This check tells the two apart on the device itself: the
    execution plan under test must match the plain ConvNet on a
    [2, 16, width] slab in the model's dtype (at width=3000 that is the
    exact production 750-lane row geometry) for logits, loss and fc
    gradient, to the tolerances of tests/test_convnet_s2d_t.py::
    test_equality_at_production_row_width_bf16. ``ok`` False means the
    plan's arithmetic is wrong on this device.
    Memoized per (plan config, width): a sweep calls it for ~10 rows of
    the same plan, and each run costs two full jit compiles on chip."""
    key = (str(model), width)
    if key in _PREFLIGHT_CACHE:
        return _PREFLIGHT_CACHE[key]
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.convnet import ConvNet
    from tpu_sandbox.ops.losses import cross_entropy_loss

    if type(model).__name__ == "ConvNet":
        return {"ok": True,
                "skipped": "plain plan IS the reference formulation"}
    # Validate at the model's CONFIGURED dtype (ADVICE r5): an fp32 sweep
    # row gated by a bf16 proxy clone could hide an fp32-only numerics bug
    # (or fail a clean fp32 plan on bf16 rounding). Tolerances scale with
    # the dtype accordingly.
    dtype = jnp.dtype(getattr(model, "dtype", None) or jnp.bfloat16)
    if dtype == jnp.dtype(jnp.bfloat16):
        tol = {"logit_rel": 8e-3, "loss_abs": 8e-3, "fc_grad_rel": 0.05}
    else:
        tol = {"logit_rel": 1e-3, "loss_abs": 1e-3, "fc_grad_rel": 5e-3}
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, width, 1)), dtype)
    yl = jnp.asarray(rng.integers(0, 10, size=(2,)), jnp.int32)
    ref = ConvNet(dtype=dtype)
    variables = ref.init(jax.random.key(0), x)
    params, stats = variables["params"], variables["batch_stats"]

    def run(m):
        def f(p):
            logits, _ = m.apply(
                {"params": p, "batch_stats": stats}, x, train=True,
                mutable=["batch_stats"])
            return cross_entropy_loss(logits, yl), logits

        (loss, logits), g = jax.jit(
            jax.value_and_grad(f, has_aux=True))(params)
        return (float(loss), np.asarray(logits, np.float32),
                np.asarray(g["fc"]["kernel"], np.float32))

    l_r, lo_r, g_r = run(ref)
    # the plan under test, at ITS configured kernels and ITS dtype
    l_t, lo_t, g_t = run(model.clone(dtype=dtype))
    scale = float(np.max(np.abs(lo_r))) or 1.0
    logit_rel = float(np.max(np.abs(lo_r - lo_t))) / scale
    loss_abs = abs(l_r - l_t)
    fc_rel = float(np.max(np.abs(g_r - g_t))) / (float(np.max(np.abs(g_r)))
                                                 or 1.0)
    ok = (logit_rel < tol["logit_rel"] and loss_abs < tol["loss_abs"]
          and fc_rel < tol["fc_grad_rel"])
    out = {"ok": bool(ok), "plan": type(model).__name__, "width": width,
           "validated_dtype": str(dtype),
           "logit_rel_dev": round(logit_rel, 6),
           "loss_abs_dev": round(loss_abs, 6),
           "fc_grad_rel_dev": round(fc_rel, 6),
           "tolerances": tol}
    _PREFLIGHT_CACHE[key] = out
    return out

"""The models. Trained: the source paper's ConvNet in three execution plans
(``pick_convnet``), ``transformer.TransformerLM`` (GPT-2's shape),
``xing4`` (mHC streams, latent attention, an expert share), ``nemotron_h``
(Mamba-2, GQA, experts in a latent), ``olmo_hybrid`` (Gated DeltaNet and
full attention). **Served, four families** (``serve/decode.py`` picks a
family's programs by the configuration's type): ``transformer.TransformerLM``
(learned positions, a key/value page buffer a layer), ``jamba.JambaLM``
(Mamba-1 with multi-query attention: recurrent slot state beside the pages),
``longcat_flash.LongcatFlashLM`` (shortcut-connected double layers: latent
attention over a latent paged cache, rotary positions, routed and
zero-compute experts) and ``laguna.LagunaLM`` (full and window attention
layers of different head counts over pages of two kinds, two rotary rules,
a per-head output gate, routed experts with a shared one). ``latent`` holds
what several of them share (RMSNorm, RoPE and YaRN's frequencies, the
low-rank query and key/value paths, the gated MLP)."""

from tpu_sandbox.models.convnet import ConvNet  # noqa: F401
from tpu_sandbox.models.convnet_s2d import ConvNetS2D  # noqa: F401
from tpu_sandbox.models.convnet_s2d_t import ConvNetS2DT  # noqa: F401


def resolve_plan(image_size, plan: str = "auto") -> str:
    """Concrete plan for a request: 's2dt' | 's2d' | 'plain'.

    'auto' picks the transposed plan (models/convnet_s2d_t.py — the
    measured-fastest execution, always-Pallas) wherever the kernels
    COMPILE (TPU, or chipless AOT via TPU_SANDBOX_FORCE_COMPILED_KERNELS),
    the NHWC s2d plan where they would run interpreted (CPU tests), and
    the plain ConvNet when the image is not 4-divisible."""
    if plan in ("s2d", "s2dt"):
        return plan
    h, w = (image_size, image_size) if isinstance(image_size, int) else image_size
    if plan != "auto" or h % 4 or w % 4:
        return "plain"
    from tpu_sandbox.ops.pallas_common import default_interpret

    return "s2dt" if not default_interpret(None) else "s2d"


def pick_convnet(image_size, *, plan: str = "auto", **kwargs):
    """The execution-plan switch. Three plans, one function
    (tests/test_convnet_s2d.py, tests/test_convnet_s2d_t.py):

    - 's2dt' (TPU default): transposed space-to-depth, [N,H,C,W] Pallas
      conv + fused-tail kernels throughout — the round-3 measured-fastest
      plan (see models/convnet_s2d_t.py docstring for the numbers);
    - 's2d': NHWC space-to-depth; Pallas kernels gated by fused_tail /
      fused_conv (defaulting on where kernels compile);
    - 'plain': the direct NHWC ConvNet (the reference-shaped execution).

    fused_tail/fused_conv kwargs are accepted for every plan and applied
    where they mean something (the transposed plan has no unfused conv;
    the plain plan ignores both)."""
    resolved = resolve_plan(image_size, plan)
    fused = kwargs.pop("fused_tail", None)
    fused_conv = kwargs.pop("fused_conv", None)

    def drop_s2dt_only(kw):
        # s2dt-only toggles (sparse_conv1, fused_conv1_bwd) are
        # meaningless — and unknown — to the other plans; drop them so
        # the same kwargs still work when 'auto' resolves elsewhere
        # (e.g. s2d on CPU)
        return {k: v for k, v in kw.items()
                if k not in ("sparse_conv1", "fused_conv1_bwd")}

    if resolved == "plain":
        return ConvNet(**drop_s2dt_only(kwargs))
    from tpu_sandbox.ops.pallas_common import default_interpret

    compiled = not default_interpret(None)
    if resolved == "s2dt" and fused_conv is False:
        # the transposed plan has no unfused-conv mode; honor the kill
        # switch by dropping to the NHWC s2d plan instead of ignoring it
        # (ADVICE r03: fused_conv=False under plan='auto' must still
        # disable the Pallas convs)
        resolved = "s2d"
    if resolved == "s2dt":
        return ConvNetS2DT(fused_tail=compiled if fused is None else fused,
                           **kwargs)
    return ConvNetS2D(
        fused_tail=compiled if fused is None else fused,
        fused_conv=compiled if fused_conv is None else fused_conv,
        **drop_s2dt_only(kwargs),
    )

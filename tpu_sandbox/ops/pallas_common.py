"""Shared bits for the Pallas TPU kernels.

One home for the tile/padding conventions so the kernels can't drift:
the 128-lane tile width, the large-negative mask filler (chosen so
``exp(filler - max)`` underflows to 0 in fp32), alignment rounding, the
off-TPU interpret-mode fallback that lets the same call path run
compiled on TPU and interpreted in CPU tests, the tile rules' divisors,
``traced_once``, which makes a kernel's call a jitted function, and
``kernel_site``, what every kernel's call site records of itself at trace
time.
"""

from __future__ import annotations

import inspect
import os

import jax

NEG = -1e30
LANE = 128


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def divisors(total: int, unit: int, cap: int) -> list[int]:
    """Multiples of ``unit`` that divide ``total``, largest first, none
    above ``cap``."""
    top = min(total, cap) // unit * unit
    return [t for t in range(top, 0, -unit) if total % t == 0]


def traced_once(call):
    """``call`` (arrays in; what shapes its kernel as keyword-only
    arguments) under ``jax.jit``: every ``pallas_call`` site traces and
    lowers its kernel anew, and a model has one site a sub-layer, so the
    call is a jitted function, which JAX traces and lowers once for the
    operands' shapes and the keywords and calls from every site."""
    static = [name for name, p in inspect.signature(call).parameters.items()
              if p.kind is p.KEYWORD_ONLY]
    return jax.jit(call, static_argnames=static)


def default_interpret(interpret: bool | None) -> bool:
    """Kernels compile only on TPU; anywhere else, interpret.

    TPU_SANDBOX_FORCE_COMPILED_KERNELS=1 overrides the backend check for
    chipless AOT analysis (tools/aot_v5e.py): there the default backend is
    CPU but lowering targets a TPU topology, and interpret-mode kernels
    would make the compiler's memory/traffic numbers describe the
    interpreter's loop, not the Mosaic kernel. Compile-only — executing on
    CPU with this set would fail."""
    if interpret is None:
        if os.environ.get("TPU_SANDBOX_FORCE_COMPILED_KERNELS") == "1":
            return False
        return jax.default_backend() != "tpu"
    return interpret


class kernel_site:
    """A kernel's call site, as the program records it while it traces.

    ``with kernel_site("flash_fwd", choice):`` round the place where a
    kernel's ``pl.pallas_call`` is built and applied is the span
    ``trace:kernel`` (``obs/record.py``: the profiler's timeline, the JSONL,
    and the registry histogram ``trace.kernel_s{kernel=..., under=...}``,
    whose sum is the seconds spent at such sites and whose count is the
    sites traced): the kernel body's own trace, the specs, the tile rule.
    Inside a program's trace it ends where the jaxpr equation exists;
    lowering the kernel to Mosaic belongs to the program's lower phase
    (``compile.lower_s``). ``under`` is the launch's span the site lies in
    (``compile:lower_step``, ``setup:model_init``, ``none``): where the
    model's init runs op by op, a site under ``setup:model_init`` is an
    eager call, and its span also holds that kernel's compile-or-load and
    dispatch.

    ``choice`` is the site's counter of what it was built with
    (``attn.tile_choice``, ``mhc.kernel_choice``, ``ssd.chunk_choice``,
    ``moe.share_table``: made at the site, where its name is a literal,
    GL-O402), counted here, once a site, whether the span is entered or
    not: a kernel whose call is a jitted function (``traced_once``) counts
    at the site, ``kernel_site(name, choice)`` as a plain statement, and
    opens the span inside the jitted function, where it fires once a shape.
    The sites counted against the spans fired is what jitting the call
    saves."""

    __slots__ = ("kernel", "_span")

    def __init__(self, kernel: str, choice=None):
        self.kernel = kernel
        self._span = None
        if choice is not None:
            choice.inc()

    def __enter__(self) -> "kernel_site":
        from tpu_sandbox.obs import get_recorder

        rec = get_recorder()
        self._span = rec.span(
            "trace:kernel", hist="trace.kernel_s", loop=True,
            hist_labels={"kernel": self.kernel,
                         "under": rec.innermost(skip="trace:") or "none"},
            args={"kernel": self.kernel})
        return self

    def __exit__(self, *exc) -> None:
        self._span.close()

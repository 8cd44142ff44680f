"""chip_smoke.py's refusals, checked where there is no chip: it must exit
non-zero and print no verdict on the CPU, refuse a kernel kill-switch, and
fail when the compiled step lacks one of the Pallas kernels."""

import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from tests.helpers import run_child  # noqa: E402


def _run(**env):
    clean = {k: v for k, v in os.environ.items()
             if k not in chip_smoke.KILL_SWITCHES}
    return run_child(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env={**clean, "JAX_PLATFORMS": "cpu", **env}, timeout=120)


def test_exits_nonzero_on_cpu_naming_the_platform():
    proc = _run()
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no verdict without a TPU


def test_refuses_a_kernel_kill_switch():
    proc = _run(TPU_SANDBOX_NO_PALLAS_FC="1")
    assert proc.returncode != 0
    assert "TPU_SANDBOX_NO_PALLAS_FC" in proc.stderr
    assert proc.stdout == ""


def _call(path):
    return ('  %k = bf16[1] custom-call(%a), custom_call_target='
            f'"tpu_custom_call", metadata={{op_name="jit(train_step)/{path}'
            '/pallas_call"}')


def test_check_kernels_wants_every_scope_in_every_direction():
    fwd, bwd = "jvp(ConvNetS2DT)", "transpose(jvp(ConvNetS2DT))"
    paths = [f"{fwd}/bn1.fused_conv1", f"{bwd}/bn1.fused_conv1",
             f"{fwd}/conv2", f"{bwd}/conv2",
             f"{fwd}/ConvNetS2DT._tail/bn2.fused",
             f"{bwd}/ConvNetS2DT._tail/bn2.fused", f"{fwd}/fc",
             f"{bwd}/fc"]
    assert chip_smoke.check_kernels(
        "\n".join(_call(p) for p in paths), "t") == len(paths)
    # the fc input-grad fell back to an XLA dot: a failure, not a slower pass
    with pytest.raises(SystemExit):
        chip_smoke.check_kernels(
            "\n".join(_call(p) for p in paths[:-1]), "t")
    # forward kernel present, its backward gave way
    with pytest.raises(SystemExit):
        chip_smoke.check_kernels(
            "\n".join(_call(p) for p in paths if p != f"{bwd}/conv2"), "t")

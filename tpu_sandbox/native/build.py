"""Build-on-first-use for the in-tree C++ runtime components.

The reference's native layer ships precompiled inside torch wheels; here
the sources live in tpu_sandbox/native/src/ and compile once per machine
into native/lib/ (g++ -O3 -shared -fPIC). No pybind11 — plain C ABIs
loaded with ctypes.

The output is named by a hash of its source (``lib/<name>-<sha>.so``):
lib/ is git-ignored but travels with any copy of the tree as it stands on
disk, so a binary is only ever loaded if it was built from exactly the
source file beside it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path

_ROOT = Path(__file__).parent
_SRC = _ROOT / "src"
_LIB = _ROOT / "lib"


class NativeBuildError(RuntimeError):
    pass


def build_library(name: str, *, force: bool = False) -> Path:
    """Compile src/<name>.cpp -> lib/<name>-<sha>.so unless that exact
    build exists; return its path."""
    src = _SRC / f"{name}.cpp"
    if not src.exists():
        raise NativeBuildError(f"no such native source: {src}")
    _LIB.mkdir(exist_ok=True)
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    out = _LIB / f"{name}-{digest}.so"
    if not force and out.exists():
        return out
    # build to a temp file then atomic-rename: concurrent builders race safely
    with tempfile.NamedTemporaryFile(
        dir=_LIB, suffix=".so.tmp", delete=False
    ) as tmp:
        tmp_path = Path(tmp.name)
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        str(src), "-o", str(tmp_path),
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        tmp_path.unlink(missing_ok=True)
        raise NativeBuildError(
            f"g++ failed for {name}:\n{proc.stderr[-2000:]}"
        )
    os.replace(tmp_path, out)
    return out


def load_library(name: str) -> ctypes.CDLL:
    return ctypes.CDLL(str(build_library(name)))

"""graftlint: static analysis for the distributed-training stack.

Three passes over three failure planes (see ``tools/graftlint.py`` for
the CLI and ``analysis/baseline.toml`` for the ratchet):

- Pass 1 (:mod:`.collective_pass`) — AST collective-consistency: the
  SPMD-divergence deadlock class (rules GL-C1xx).
- Pass 2 (:mod:`.hlo_pass`) — jaxpr + chipless AOT HLO lint of the real
  step functions: donation, upcasts, host transfers, int8 padding
  (rules GL-H2xx).
- Pass 3 (:mod:`.control_pass`) — control-plane AST lint over
  ``runtime/``: claim scoping, clock-skew stamp math, thread hygiene,
  leader-section blocking reads (rules GL-R3xx).

Import note: only :mod:`.hlo_pass`'s driver needs jax; the AST passes
and the baseline machinery are stdlib-only so the tier-1 gate can run
them in-process.
"""

from tpu_sandbox.analysis.baseline import (
    BaselineError,
    Suppression,
    apply_baseline,
    load_baseline,
    parse_baseline,
    render_baseline,
)
from tpu_sandbox.analysis.collective_pass import run_collective_pass
from tpu_sandbox.analysis.control_pass import run_control_pass
from tpu_sandbox.analysis.findings import RULES, Finding, make_finding

__all__ = [
    "Finding",
    "RULES",
    "make_finding",
    "run_collective_pass",
    "run_control_pass",
    "Suppression",
    "BaselineError",
    "parse_baseline",
    "load_baseline",
    "apply_baseline",
    "render_baseline",
]

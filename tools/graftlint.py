"""graftlint CLI: run the three static-analysis passes over the repo.

Usage:
  python tools/graftlint.py                      # passes 1+3 (AST, fast)
  python tools/graftlint.py --pass hlo           # pass 2 only (compiles!)
  python tools/graftlint.py --all                # everything
  python tools/graftlint.py --all --no-aot       # pass 2 w/o AOT compiles
  python tools/graftlint.py --json               # machine-readable
  python tools/graftlint.py --update-baseline    # accept current findings
  python tools/graftlint.py --no-baseline        # raw findings, no ratchet

Exit codes: 0 clean (after baseline), 1 findings, 2 usage/internal error.

Pass 2 AOT-compiles the real step functions against a chipless v5e
topology. That path mutates process env (forced compiled Pallas kernels)
and is single-process like the other AOT tools — run it via this CLI
(the tier-1 gate shells out here), never import-and-run inside a pytest
process, and never run two AOT tools at once.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

# Pass 2 traces engines on 8 virtual CPU devices; both knobs must land
# before jax is imported (safe no-ops for the AST-only passes).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

from tpu_sandbox.analysis import (  # noqa: E402
    apply_baseline,
    load_baseline,
    render_baseline,
    run_collective_pass,
    run_control_pass,
)

BASELINE_PATH = os.path.join(_ROOT, "tpu_sandbox", "analysis",
                             "baseline.toml")
PASSES = ("collective", "hlo", "control")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--pass", dest="passes", action="append",
                   choices=PASSES, default=None,
                   help="pass to run (repeatable); default: collective + "
                        "control (the AST passes)")
    p.add_argument("--all", action="store_true",
                   help="run all three passes (hlo compiles the engines)")
    p.add_argument("--root", default=_ROOT, help="repo root to scan")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit one JSON object instead of text")
    p.add_argument("--baseline", default=BASELINE_PATH,
                   help="baseline file (default: analysis/baseline.toml)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report raw findings, ignoring the baseline")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline to accept current findings")
    p.add_argument("--no-aot", action="store_true",
                   help="pass 2 without the chipless AOT compiles "
                        "(donation reported as skipped)")
    p.add_argument("--steps",
                   default="dp,zero,pjit,pipeline,dp-int8,"
                           "sp,decode,prefill,fsdp,tp,ep,mpmd",
                   help="pass 2 step functions to trace")
    args = p.parse_args(argv)

    passes = tuple(args.passes or ())
    if args.all:
        passes = PASSES
    elif not passes:
        passes = ("collective", "control")

    findings = []
    report: dict = {"passes": list(passes)}
    if "collective" in passes:
        findings.extend(run_collective_pass(args.root))
    if "control" in passes:
        findings.extend(run_control_pass(args.root))
    if "hlo" in passes:
        from tpu_sandbox.analysis.hlo_pass import run_hlo_pass

        hlo_findings, hlo_report = run_hlo_pass(
            steps=tuple(s for s in args.steps.split(",") if s),
            aot=not args.no_aot,
        )
        findings.extend(hlo_findings)
        report["hlo"] = hlo_report

    findings.sort(key=lambda f: (f.file, f.line, f.rule))

    if args.update_baseline:
        with open(args.baseline, "w", encoding="utf-8") as f:
            f.write(render_baseline(findings))
        print(f"baseline rewritten with {len(findings)} finding(s): "
              f"{args.baseline}")
        return 0

    suppressions = [] if args.no_baseline else load_baseline(args.baseline)
    kept, suppressed, unused = apply_baseline(findings, suppressions)
    report.update({
        "findings": len(kept),
        "suppressed": len(suppressed),
        "unused_suppressions": len(unused),
    })

    if args.as_json:
        report["details"] = [f.__dict__ for f in kept]
        report["unused"] = [s.__dict__ for s in unused]
        print(json.dumps(report))
    else:
        for f in kept:
            print(f.format())
        for s in unused:
            print(f"note: unused baseline entry rule={s.rule} file={s.file} "
                  f"match={s.match!r} — delete it")
        if "hlo" in passes:
            print("pass 2 report: "
                  + json.dumps(report.get("hlo", {}), default=str))
        print(f"graftlint: {len(kept)} finding(s), "
              f"{len(suppressed)} suppressed, "
              f"{len(unused)} unused suppression(s)")
    return 1 if kept else 0


if __name__ == "__main__":
    sys.exit(main())

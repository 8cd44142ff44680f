"""One-screen summary of the measured/ story for a round.

Reads every ``measured/*_r{N}*.json[l]`` artifact plus the current-round
err files and prints a compact table: headline images/sec lines (with
plan, loss flag, fallbacks), capacity, kernel micro rows (min + spread),
lm/seq rows, and which rungs never produced output. Run any time, to see
what is still missing.

Usage: python tools/summarize_measured.py [--round 4]
"""

from __future__ import annotations

import argparse
import glob
import json
import os


def _rows(path):
    text = open(path).read()
    try:  # whole-file JSON (indented artifacts like hlo_cycles_*)
        doc = json.loads(text)
        if isinstance(doc, dict):
            return [doc]
        if isinstance(doc, list):
            return [d for d in doc if isinstance(d, dict)]
        return []  # scalar JSON (a partial write): report as empty
    except json.JSONDecodeError:
        pass
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(d, dict):  # bare strings inside indented JSON
            out.append(d)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--round", type=int, default=4)
    args = p.parse_args()
    tag = f"_r{args.round:02d}"
    base = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "measured")

    files = sorted(glob.glob(os.path.join(base, f"*{tag}*")))
    if not files:
        print(f"no measured/*{tag}* artifacts yet")
    for path in files:
        name = os.path.basename(path)
        if name.endswith(".err"):
            size = os.path.getsize(path)
            if size:
                tail = open(path, errors="replace").read()[-300:]
                print(f"-- {name}: {size} B of stderr; tail: ...{tail!r}")
            continue
        if not name.endswith((".json", ".jsonl")):
            # plain-text artifacts (probe transcripts etc.): present, not
            # a dead rung — show the first line instead of crying EMPTY
            first = open(path, errors="replace").readline().strip()
            print(f"-- {name}: text artifact ({os.path.getsize(path)} B): "
                  f"{first[:100]}")
            continue
        rows = _rows(path)
        if not rows:
            print(f"-- {name}: EMPTY (rung died before its JSON line)")
            continue
        print(f"-- {name}")
        shown = 0
        for r in rows:
            if "metric" in r:
                bits = [f"{r['metric']}={r.get('value')}",
                        f"unit={r.get('unit')}"]
                for k in ("execution_plan", "kernel_plan", "global_batch",
                          "sec_per_step", "mfu", "final_loss", "loss_flag",
                          "plan_fallback", "degraded", "spread_frac"):
                    if r.get(k) is not None:
                        bits.append(f"{k}={r[k]}")
                print("   " + "  ".join(str(b) for b in bits))
            elif "sec_per_call" in r:  # conv_micro kernel rows
                print(f"   {r.get('op')}: {r['sec_per_call']}s  "
                      f"tflops={r.get('tflops')}  "
                      f"spread={r.get('spread_frac')}"
                      + ("  INVALID" if r.get("invalid")
                         or r.get("degraded") else ""))
            elif "bytes_accessed" in r:  # AOT compile rows
                print(f"   plan={r.get('plan')} batch={r.get('batch')} "
                      f"bytes={r.get('bytes_accessed')} "
                      f"peak_gb={r.get('est_peak_gb')} "
                      f"fits={r.get('fits_16g_hbm')}")
            else:
                continue  # per-op traffic / breakdown rows: skip detail
            shown += 1
        if not shown:
            print(f"   ({len(rows)} rows, no summary-known shape)")


if __name__ == "__main__":
    main()

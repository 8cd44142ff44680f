"""From the result lines of repeated runs to the spread the bounds are set
from: for each cell, two sets of runs of the same code, and for each
end-to-end metric the median, the quartiles and the spread (distance
between the quartiles over the median) of each set; the wider of the two is
the cell's spread, and a bound is about five times the widest over the
cells, never under 1 %.

    python3 benchmark/sweeps/spread_study.py chiprun_out/spread > benchmark/sweeps/spread_study.json

reads ``<dir>/<cell>.set<k>.jsonl`` (one result line of ``run.py`` per
run, in the order made; the first run of a cell compiled and its
``setup_s`` is kept apart).
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import stats  # noqa: E402


def main() -> None:
    folder = Path(sys.argv[1])
    cells: dict = {}
    for path in sorted(folder.glob("*.set*.jsonl")):
        cell, set_name = path.name[:-len(".jsonl")].rsplit(".", 1)
        runs = [json.loads(line) for line in path.read_text().splitlines()
                if line.startswith("{")]
        metrics: dict = {}
        for run in runs:
            for name, m in run["metrics"].items():
                metrics.setdefault(name, []).append(m["value"])
        summary = {}
        for name, values in metrics.items():
            summary[name] = {
                "runs": values, "median": stats.median(values),
                "q1": stats.quantile(values, 0.25),
                "q3": stats.quantile(values, 0.75),
                "spread": stats.quartile_spread(values)}
        cells.setdefault(cell, {})[set_name] = {
            "n_runs": len(runs),
            "all_correct": all(r["correct"] for r in runs),
            "failed": [r["failed"] for r in runs],
            "attempted": [r["attempted"] for r in runs],
            "memory_peak_bytes": max(r["device"]["memory_peak_bytes"]
                                     for r in runs),
            "metrics": summary}
    widest: dict = {}
    for cell, sets in cells.items():
        by_metric = {}
        names = set().union(*(s["metrics"] for s in sets.values()))
        for name in sorted(names):
            having = [s["metrics"][name] for s in sets.values()
                      if name in s["metrics"]]
            spreads = [m["spread"] for m in having if m["spread"] is not None]
            entry = {"cell": cell, "spread": max(spreads) if spreads else None,
                     "set_medians": [m["median"] for m in having]}
            if len(having) == 2:
                entry["second_vs_first"] = (
                    having[1]["median"] / having[0]["median"] - 1.0)
            by_metric[name] = entry
            if entry["spread"] is not None and (
                    name not in widest
                    or entry["spread"] > widest[name]["spread"]):
                widest[name] = entry
        sets["_by_metric"] = by_metric
    bounds = {name: {"widest_spread": e["spread"], "in_cell": e["cell"],
                     "five_times": max(0.01, 5 * e["spread"])}
              for name, e in widest.items()}
    print(json.dumps({"cells": cells, "bounds_from_spread": bounds}, indent=1))


if __name__ == "__main__":
    main()

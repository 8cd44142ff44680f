"""``BENCHMARK.json`` and the data files it names, found by name.

A cell is one entry of ``workloads``. Its files:

- ``benchmark/workloads/<cell>.json``  the cell: config, traffic, chips, the
  ``runner`` kind that drives it, and optional ``deployment`` keys that
  override the configuration's;
- ``benchmark/configs/<config>.json``  the configuration as it is run, with
  the ``reference`` it is checked against;
- ``benchmark/traffic/<mix>.json``     the parameters one general generator
  (``lib/traffic.py``) reads.

Runner kinds (``benchmark/runners/<kind>.py``), references
(``benchmark/reference/<name>.py``) and per-layer metrics
(``benchmark/layer_metrics/<name>.py``) are modules found by the same rule.
Nothing here lists names: a later PR adds files and entries
(``benchmark/sweeps/NOTES.md`` lists which).
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
MAX_BOUND = 0.1


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def home(root: Path = ROOT) -> Path:
    """The benchmark's own directory: the one holding the command's script."""
    return root / "benchmark"


def cell(name: str, root: Path = ROOT) -> dict:
    """Everything one run needs to know about cell ``name``."""
    manifest = load(root)
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        known = [w["name"] for w in manifest["workloads"]]
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: {known}")
    entry = entries[0]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    cell_file = _read(home(root) / "workloads" / f"{name}.json")
    config = _read(root / config_entry["file"])
    traffic = _read(home(root) / "traffic" / f"{entry['traffic']}.json")
    deployment = {**config.get("deployment", {}),
                  **cell_file.get("deployment", {})}

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name, "chips": entry["chips"], "config_name": entry["config"],
        "config": config, "traffic": traffic, "deployment": deployment,
        "runner": cell_file["runner"], "reference": config["reference"],
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
    }


def module(kind: str, name: str, root: Path = ROOT):
    """Import ``benchmark/<kind>/<name>.py`` by its file: the name comes
    from a data file, so no import list has to know it."""
    path = home(root) / kind / f"{name}.py"
    if not NAME.match(name) or not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    modname = f"benchmark.{kind}.{name.replace('-', '_').replace('.', '_')}"
    loaded = sys.modules.get(modname)
    if loaded is not None and Path(loaded.__file__) == path:
        return loaded
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def validate(root: Path = ROOT) -> list[str]:
    """Every way this tree breaks the benchmark's contract, as sentences.
    The driver checks the same before any run; this is the copy a test can
    call."""
    errs: list[str] = []
    m = load(root)
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return errs
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        errs.append(f"run_seconds {m['run_seconds']!r} not a whole 1..51")
    paths = m["paths"]
    for word in m["command"]:
        if word.startswith("/") or ".." in Path(word).parts:
            errs.append(f"command word {word!r} leaves the repo")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/")
                   for p in paths)

    def names_unique(items, what):
        seen = [i["name"] for i in items]
        for n in seen:
            if not NAME.match(n):
                errs.append(f"{what} name {n!r} has characters outside "
                            "letters, digits, _ . -")
        if len(set(seen)) != len(seen):
            errs.append(f"duplicate {what} names in {seen}")

    names_unique(m["configs"], "config")
    names_unique(m["workloads"], "cell")
    names_unique(m["end_to_end"] + m["per_layer"], "metric")

    config_names = {c["name"] for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    if len(set(files)) != len(files):
        errs.append("two configurations share a file")
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config {c['name']}: keys {sorted(c)}")
        if not under_paths(c["file"]) or not (root / c["file"]).is_file():
            errs.append(f"config {c['name']}: file {c['file']} is not a "
                        "file under paths")
            continue
        reference = _read(root / c["file"]).get("reference")
        if not (home(root) / "reference" / f"{reference}.py").is_file():
            errs.append(f"{c['file']}: reference {reference!r} has no module")
        if c["name"] not in {w["config"] for w in m["workloads"]}:
            errs.append(f"config {c['name']} is used by no cell")

    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    if len(set(pairs)) != len(pairs):
        errs.append("a (config, traffic) pair appears twice")
    if not 2 <= len(m["workloads"]) <= 24:
        errs.append(f"{len(m['workloads'])} cells; the contract wants 2..24")
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(m["workloads"]) // 4):
        errs.append(f"{len(four)} four-chip cells {four} of "
                    f"{len(m['workloads'])}: more than a quarter")
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"cell {w['name']}: keys {sorted(w)}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips {w['chips']}")
        if w["config"] not in config_names:
            errs.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME.match(w["traffic"]):
            errs.append(f"cell {w['name']}: traffic name {w['traffic']!r}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            errs.append(f"cell {w['name']}: why must be 1..200 chars, one line")
        cell_path = home(root) / "workloads" / f"{w['name']}.json"
        traffic_path = home(root) / "traffic" / f"{w['traffic']}.json"
        if not traffic_path.is_file():
            errs.append(f"cell {w['name']}: no traffic file {traffic_path}")
        if not cell_path.is_file():
            errs.append(f"cell {w['name']}: no cell file {cell_path}")
            continue
        body = _read(cell_path)
        for key in ("config", "traffic", "chips"):
            if body.get(key) != w[key]:
                errs.append(f"{cell_path.name}: {key} {body.get(key)!r} "
                            f"disagrees with BENCHMARK.json's {w[key]!r}")
        runner = body.get("runner")
        if not (home(root) / "runners" / f"{runner}.py").is_file():
            errs.append(f"{cell_path.name}: runner {runner!r} has no module")

    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("no setup_s among end_to_end")
    for x in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(x["unit"]):
            errs.append(f"metric {x['name']}: unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            errs.append(f"metric {x['name']}: better {x['better']!r}")
        if x["source"] not in SOURCES:
            errs.append(f"metric {x['name']}: source {x['source']!r}")
        for cell_name in x.get("workloads", []):
            if cell_name not in cells:
                errs.append(f"metric {x['name']}: unknown cell {cell_name}")
    for x in m["end_to_end"]:
        if set(x) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            errs.append(f"end_to_end {x['name']}: keys {sorted(x)}")
        if not 0 < x["bound"] <= MAX_BOUND:
            errs.append(f"end_to_end {x['name']}: bound {x['bound']}")
        if x["source"] not in ("host_clock", "device_trace"):
            errs.append(f"end_to_end {x['name']}: source {x['source']} is "
                        "read from the program")
    for x in m["per_layer"]:
        if set(x) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            errs.append(f"per_layer {x['name']}: keys {sorted(x)}")
        if x["moves"] not in e2e:
            errs.append(f"per_layer {x['name']}: moves unknown metric "
                        f"{x['moves']!r}")
        if not (home(root) / "layer_metrics" / f"{x['name']}.py").is_file():
            errs.append(f"per_layer {x['name']}: no reader "
                        f"layer_metrics/{x['name']}.py")
    for w in m["workloads"]:
        def here(x):
            return w["name"] in x.get("workloads", [w["name"]])
        mine = [x["name"] for x in m["end_to_end"] if here(x)]
        if "setup_s" not in mine or len(mine) < 2:
            errs.append(f"cell {w['name']} reports {mine}: needs setup_s and "
                        "one more end-to-end metric")
        layer = [x for x in m["per_layer"] if here(x)]
        if not layer:
            errs.append(f"cell {w['name']} reports no per-layer metric")
        for x in layer:
            if x["moves"] not in mine:
                errs.append(f"per_layer {x['name']} is reported in "
                            f"{w['name']} but {x['moves']} is not")
    return errs

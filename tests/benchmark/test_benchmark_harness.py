"""The harness as data: traffic is a function of the seed, the percentile
rule, manifest validation, the command without a TPU, and a later PR's
additions found as new files and entries only."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest, stats, traffic  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "benchmark" / "traffic").glob("*.json"))


def generate(spec: dict, seed: int):
    if spec["kind"] == "image_batches":
        return traffic.image_batches(spec, seed)
    if spec["kind"] == "token_batches":
        stream = traffic.token_batches(spec, seed, vocab=50257)
        return [next(stream) for _ in range(3)]
    if spec["kind"] == "decode_replay":
        return traffic.decode_replay(spec, seed, vocab=50257)
    return traffic.requests(spec, seed, seconds=5.0, vocab=50257)


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_is_a_function_of_the_seed(mix):
    spec = json.loads((ROOT / "benchmark" / "traffic" / f"{mix}.json").read_text())
    a, b, c = generate(spec, 7), generate(spec, 7), generate(spec, 8)
    assert traffic.digest(a) == traffic.digest(b)
    assert traffic.digest(a) != traffic.digest(c)


def test_request_mix_follows_its_parameters():
    spec = json.loads((ROOT / "benchmark/traffic/chat_poisson.json").read_text())
    reqs = traffic.requests(spec, 5, seconds=60.0, vocab=50257)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] >= -spec["warmup_s"] and due[-1] < 60.0
    assert abs(len(reqs) / (60.0 + spec["warmup_s"]) - spec["rate_per_s"]) < 1.5
    plen = [len(r.prompt) for r in reqs]
    olen = [r.max_new_tokens for r in reqs]
    assert min(plen) >= 16 and max(plen) <= 768
    assert min(olen) >= 8 and max(olen) <= 256
    assert max(p + o for p, o in zip(plen, olen)) <= 1024
    assert len({r.prompt[:16] for r in reqs}) == len(reqs)  # nothing shared
    shared = dict(spec, shared_prefix={"count": 2, "len": 32})
    prefixes = {r.prompt[:32] for r in traffic.requests(shared, 5, 60.0, 50257)}
    assert len(prefixes) == 2
    bursty = dict(spec, arrivals={"process": "gamma", "cv": 3.0})
    gaps = [b.due_s - a.due_s for a, b in zip(
        traffic.requests(bursty, 5, 600.0, 50257)[:-1],
        traffic.requests(bursty, 5, 600.0, 50257)[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert 2.0 < cv < 4.0


@pytest.mark.parametrize("values,failed,q,want", [
    (list(range(1, 101)), 0, 90, 90.0),      # nearest rank
    (list(range(1, 101)), 0, 99, 99.0),
    ([5.0], 0, 90, 5.0),
    (list(range(1, 91)), 10, 90, 90.0),      # failures rank last ...
    (list(range(1, 91)), 11, 90, 999.0),     # ... and the percentile can land on one
    ([], 3, 90, 999.0),
    ([], 0, 90, None),
])
def test_percentile_ranks_failed_requests_last(values, failed, q, want):
    got = stats.percentile_failed_last(values, failed, q, failed_value=999.0)
    assert got == want
    if not failed:
        assert stats.percentile(values, q) == want


def test_median_and_spread():
    assert stats.median([3, 1, 2]) == 2.0
    assert stats.median([4, 1, 2, 3]) == 2.5
    assert stats.median([]) is None
    assert stats.quartile_spread([10, 10, 10, 10]) == 0.0
    assert stats.quartile_spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)


#: What must stay true of ``BENCHMARK.json`` however many cells, metrics and
#: configurations later PRs add (they add files and entries and edit
#: nothing, this file included): PR 22's cells with their chips and
#: configurations, the two bounds, and the cells each per-layer metric of
#: PRs 22 to 25 is read in. A later cell appends itself to such a list.
ONE, DP4, LM = ("convnet3000_1chip_bs5", "convnet3000_dp4_bs5",
                "gpt2m_train_s1024")
CELLS = {ONE: (1, "convnet3000"), DP4: (4, "convnet3000"),
         LM: (1, "gpt2-medium")}
BOUNDS = {"train_step_ms": 0.01, "setup_s": 0.1}
READ_IN = {
    "loader_wait_ms": [ONE, DP4, LM], "device_step_ms": [ONE, DP4, LM],
    "pallas_ms": [ONE, DP4, LM], "mfu_pct": [ONE, DP4, LM],
    "device_idle_pct": [ONE, DP4, LM],
    "allreduce_ms": [DP4], "allreduce_exposed_ms": [DP4],
    "loop_step_ms": [ONE, DP4], "loop_loader_wait_ms": [ONE, DP4],
    "place_batch_ms": [DP4, LM], "state_place_s": [DP4, LM],
    "model_init_s": [ONE, DP4, LM], "opt_init_s": [ONE, DP4, LM],
    "compile_cache_misses": [ONE, DP4, LM],
    "attn_ms": [LM], "flash_attn_roofline": [LM], "head_ms": [ONE, DP4],
    "optimizer_ms": [DP4],
}
#: what a one-chip training cell built through ``TrainState.create`` and an
#: engine's ``shard_state`` reports besides its own metrics
ONE_CHIP_TRAINING = ("model_init_s", "opt_init_s", "compile_cache_misses",
                     "place_batch_ms", "state_place_s", "device_step_ms",
                     "pallas_ms", "mfu_pct", "loader_wait_ms",
                     "device_idle_pct")


def assert_benchmark_invariants(root: Path) -> None:
    assert manifest.validate(root) == []
    m = manifest.load(root)
    assert m["run_seconds"] == 10
    assert len(json.dumps(m)) < 64 * 1024
    cells = {w["name"]: (w["chips"], w["config"]) for w in m["workloads"]}
    assert CELLS.items() <= cells.items()
    assert {c["name"] for c in m["configs"]} == {c for _, c in cells.values()}
    end_to_end = {x["name"]: x for x in m["end_to_end"]}
    for name, bound in BOUNDS.items():
        assert end_to_end[name]["bound"] == bound
    for metric in m["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
    assert set(CELLS) <= set(end_to_end["train_step_ms"]["workloads"])
    per_layer = {x["name"]: x for x in m["per_layer"]}
    for name, want in READ_IN.items():
        assert set(want) <= set(per_layer[name]["workloads"]), name
        assert (manifest.home(root) / "layer_metrics" / f"{name}.py").is_file()


def test_manifest_is_valid():
    assert_benchmark_invariants(ROOT)


def copy_benchmark(tmp_path: Path) -> Path:
    root = tmp_path / "tree"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "sweeps",
                                                  "fixtures"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


@pytest.mark.parametrize("breakage,expect", [
    (lambda m: m["workloads"][0].update(name="bad name"), "characters"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["end_to_end"][0].update(source="program_span"), "read from the program"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "four-chip"),
    (lambda m: m["workloads"][0].update(traffic="no_such_mix"), "no traffic file"),
    (lambda m: m["per_layer"][0].update(name="no_reader"), "no reader"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda m: m.update(extra=1), "top-level keys"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")),
     "appears twice"),
])
def test_manifest_validation_catches(tmp_path, breakage, expect):
    root = copy_benchmark(tmp_path)
    m = manifest.load(root)
    breakage(m)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    errors = manifest.validate(root)
    assert any(expect in e for e in errors), errors


#: What the kept open-loop serving cell needs to become a cell (PERF.md
#: section 7, first row): these entries and no new file. The bounds are
#: placeholders; the PR that adds the cell measures them. Where the replay
#: cell (PR 40) already brought a metric, the cell appends itself to it.
SERVE_CELL = "gpt2m_serve_chat"
SERVE_END_TO_END = [("serve_tok_per_s", "tokens/s", "higher"),
                    ("ttft_p90_ms", "ms", "lower"),
                    ("itl_p99_ms", "ms", "lower")]
SERVE_PER_LAYER = [
    ("decode_step_p50_ms", "ms", "host_clock", "serve engine", "itl_p99_ms"),
    ("decode_device_ms", "ms", "device_trace", "serve engine", "itl_p99_ms"),
    ("prefill_ms", "ms", "device_trace", "serve engine", "ttft_p90_ms"),
    ("batch_occupancy_pct", "%", "program_counter", "serve scheduler",
     "serve_tok_per_s"),
    ("queue_wait_ms", "ms", "host_clock", "serve scheduler", "ttft_p90_ms"),
    ("itl_p50_ms", "ms", "host_clock", "serve request view", "itl_p99_ms"),
    ("preemptions", "count", "program_counter", "serve cache", "itl_p99_ms"),
    ("gen_late_ms", "ms", "host_clock", "load generator", "ttft_p90_ms"),
    ("serve_device_idle_pct", "%", "device_trace", "device", "itl_p99_ms"),
]


def add_serving_cell(root: Path) -> None:
    """The kept serving cell as entries in ``root``'s BENCHMARK.json."""
    m = manifest.load(root)
    m["workloads"].append({
        "name": SERVE_CELL, "config": "gpt2-medium", "traffic": "chat_poisson",
        "chips": 1, "why": "the chat mix below the knee"})
    for name, unit, better in SERVE_END_TO_END:
        m["end_to_end"].append({
            "name": name, "unit": unit, "better": better, "bound": 0.05,
            "source": "host_clock", "workloads": [SERVE_CELL]})
    have = {x["name"]: x for x in m["end_to_end"] + m["per_layer"]}
    moved = set()
    for name, unit, source, layer, moves in SERVE_PER_LAYER:
        if name in have:
            have[name]["workloads"].append(SERVE_CELL)
            moved.add(have[name]["moves"])
            continue
        m["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves, "workloads": [SERVE_CELL]})
    for name in moved:  # a cell reports what its per-layer metrics move
        have[name]["workloads"].append(SERVE_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(m))


def test_the_kept_serving_cell_needs_entries_only(tmp_path):
    root = copy_benchmark(tmp_path)
    add_serving_cell(root)
    assert manifest.validate(root) == []
    cell = manifest.cell(SERVE_CELL, root)
    assert cell["runner"] == "lm_serve"
    assert {m["name"] for m in cell["end_to_end"]} >= {
        "serve_tok_per_s", "ttft_p90_ms", "itl_p99_ms", "setup_s"}
    # with the serving entries every entry has its reader (a reader kept
    # for a later cell may be there without an entry)
    listed = {x["name"] for x in manifest.load(root)["per_layer"]}
    on_disk = {p.stem for p in (root / "benchmark/layer_metrics").glob("*.py")}
    assert {name for name, *_ in SERVE_PER_LAYER} <= listed <= on_disk
    assert_benchmark_invariants(root)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A throw-away configuration, traffic mix, cell, per-layer metric,
    runner kind and reference, added as NEW files plus entries in
    BENCHMARK.json, are found and validated; no existing file is edited."""
    root = copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = root / "benchmark"
    (bench / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "reference": "toy_ref", "n_layer": 1,
         "deployment": {"dtype": "bf16"}}))
    (bench / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"kind": "requests", "rate_per_s": 1.0,
         "prompt_len": {"dist": "uniform", "min": 1, "max": 2},
         "output_len": {"dist": "uniform", "min": 1, "max": 2}}))
    (bench / "workloads" / "toy_cell.json").write_text(json.dumps(
        {"config": "toy", "traffic": "toy_mix", "chips": 1,
         "runner": "toy_runner", "deployment": {"max_batch": 2}}))
    (bench / "runners" / "toy_runner.py").write_text(
        "def setup(obs):\n    return 'session'\n")
    (bench / "reference" / "toy_ref.py").write_text("TOLERANCE = {}\n")
    (bench / "layer_metrics" / "toy_metric.py").write_text(
        "def read(obs):\n    return obs.facts.get('toy')\n")
    m = manifest.load(root)
    m["configs"].append({"name": "toy", "source": "a paper", "reduced": [],
                         "file": "benchmark/configs/toy.json", "why": "test"})
    m["workloads"].append({"name": "toy_cell", "config": "toy",
                           "traffic": "toy_mix", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "toy_metric", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "toy", "moves": "setup_s",
                           "workloads": ["toy_cell"]})
    # a one-chip training cell: it appends itself to train_step_ms and to
    # every per-layer metric such a cell reports
    for x in m["end_to_end"] + m["per_layer"]:
        if x["name"] in ("train_step_ms", *ONE_CHIP_TRAINING):
            x["workloads"].append("toy_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    assert_benchmark_invariants(root)
    cell = manifest.cell("toy_cell", root)
    assert {x["name"] for x in cell["per_layer"]} >= {
        "init_s", "trace_lower_s", "compile_s", "toy_metric",
        *ONE_CHIP_TRAINING}
    assert cell["runner"] == "toy_runner" and cell["reference"] == "toy_ref"
    assert cell["deployment"] == {"dtype": "bf16", "max_batch": 2}
    assert manifest.module("runners", "toy_runner", root).setup(None) == "session"
    assert manifest.module("reference", "toy_ref", root).TOLERANCE == {}

    class Obs:
        facts = {"toy": 3.0}

    assert manifest.module("layer_metrics", "toy_metric", root).read(Obs) == 3.0
    assert len(traffic.requests(cell["traffic"], 1, 30.0, vocab=11)) > 5
    for path, content in before.items():
        assert path.read_bytes() == content


def test_every_cell_resolves_to_existing_modules():
    for w in manifest.load(ROOT)["workloads"]:
        cell = manifest.cell(w["name"])
        runner = manifest.module("runners", cell["runner"])
        for hook in ("setup", "measure", "finish", "end_to_end"):
            assert callable(getattr(runner, hook))
        assert manifest.module("reference", cell["reference"]).TOLERANCE
        for metric in cell["per_layer"]:
            assert callable(manifest.module("layer_metrics", metric["name"]).read)
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    m = manifest.load(ROOT)
    cell = m["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/tmp", "PYTHONPATH": ""})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_kill_switch_in_the_environment_is_an_error():
    m = manifest.load(ROOT)
    out = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload",
         m["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/tmp",
             "TPU_SANDBOX_NO_PALLAS_FC": "1"})
    assert out.returncode != 0 and "kill-switch" in out.stderr
    assert out.stdout.strip() == ""

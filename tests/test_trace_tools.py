"""End-to-end smoke for the trace tooling over the committed fixture
trace dirs: tracecat (summary, per-request waterfall with critical-path
marks, profile export), tracediff gating, torn-log-tail resilience in
the collector, and the fleetop where-time-goes panel.

The CLI tests shell out with ``sys.executable`` — the tools are
scripts, not modules, and the test must exercise their argv surface and
exit codes exactly as a user would.
"""

import importlib.util
import json
import os
import shutil
import sys

import pytest

from tests.helpers import kv_pair, run_child  # noqa: F401 (kv_pair: fixture)
from tpu_sandbox.obs import critpath
from tpu_sandbox.obs.collect import (chain_check, load_dir, load_merged,
                                     read_log, request_waterfall)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")
TRACE_SMALL = os.path.join(FIXTURES, "trace_small")
TRACE_SLOW = os.path.join(FIXTURES, "trace_slow")


def _run(tool, *argv):
    return run_child(
        [sys.executable, os.path.join(REPO, "tools", tool), *argv],
        timeout=120)


# -- tracecat -----------------------------------------------------------------


def test_tracecat_summary():
    out = _run("tracecat.py", TRACE_SMALL)
    assert out.returncode == 0, out.stderr
    assert "3 process logs" in out.stdout
    assert "0 dropped lines" in out.stdout
    assert "7 traces, 7 fully connected" in out.stdout


def test_tracecat_waterfall_marks_critical_path():
    out = _run("tracecat.py", TRACE_SMALL, "--rid", "r01")
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    decode = next(ln for ln in lines if " decode " in ln or
                  ln.rstrip().endswith("decode  [serve-rep0/300]"))
    prefill = next(ln for ln in lines if "prefill" in ln)
    assert "*" in decode
    assert "*" not in prefill  # refines admit, not on the causal spine
    crit = next(ln for ln in lines if "critical path (ok" in ln)
    assert "decode=" in crit and "coverage 100" in crit


def test_tracecat_waterfall_blames_shed_request():
    out = _run("tracecat.py", TRACE_SMALL, "--rid", "r06")
    assert out.returncode == 0, out.stderr
    assert "critical path (shed:capacity" in out.stdout
    assert "blame: queue_wait" in out.stdout


def test_tracecat_unknown_rid_exits_nonzero():
    out = _run("tracecat.py", TRACE_SMALL, "--rid", "nope")
    assert out.returncode == 1


def test_tracecat_critpath_profile_export(tmp_path):
    prof_path = str(tmp_path / "prof.json")
    out = _run("tracecat.py", TRACE_SMALL, "--critpath", prof_path)
    assert out.returncode == 0, out.stderr
    assert "critpath profile: 7 requests (6 ok)" in out.stdout
    prof = critpath.load_profile(prof_path)
    assert prof["schema"] == critpath.PROFILE_SCHEMA


# -- tracediff ----------------------------------------------------------------


def test_tracediff_gates_decode_slowdown():
    out = _run("tracediff.py", TRACE_SMALL, TRACE_SLOW)
    assert out.returncode == 1, out.stdout
    assert "REGRESSED" in out.stdout
    assert "1 regression(s): decode" in out.stdout


def test_tracediff_identical_run_is_clean():
    out = _run("tracediff.py", TRACE_SMALL, TRACE_SMALL)
    assert out.returncode == 0, out.stdout
    assert "0 regression(s)" in out.stdout


def test_tracediff_json_mode():
    out = _run("tracediff.py", TRACE_SMALL, TRACE_SLOW, "--json")
    assert out.returncode == 1
    cmp = json.loads(out.stdout)
    assert cmp["regressions"] == ["decode"]


def test_tracediff_bad_input_exits_2(tmp_path):
    missing = str(tmp_path / "nope.json")
    out = _run("tracediff.py", TRACE_SMALL, missing)
    assert out.returncode == 2
    bad = tmp_path / "bad_schema.json"
    bad.write_text('{"schema": "not-a-profile"}\n', encoding="utf-8")
    out = _run("tracediff.py", TRACE_SMALL, str(bad))
    assert out.returncode == 2
    assert "schema" in out.stderr


# -- torn log tails -----------------------------------------------------------


def _torn_copy(tmp_path, victim="gateway-200.jsonl", keep_lines=None,
               tear_at=None):
    """Copy the fixture dir, then truncate ``victim`` mid-way through a
    record line — what a SIGKILL'd process leaves behind."""
    torn = tmp_path / "torn"
    shutil.copytree(TRACE_SMALL, torn)
    path = torn / victim
    lines = path.read_text(encoding="utf-8").splitlines(keepends=True)
    if keep_lines is None:
        keep_lines = len(lines) - 1
    partial = lines[keep_lines][:len(lines[keep_lines]) // 2]
    path.write_text("".join(lines[:keep_lines]) + partial,
                    encoding="utf-8")
    return str(torn)


def test_read_log_counts_torn_tail_as_dropped(tmp_path):
    torn = _torn_copy(tmp_path, victim="serve-rep0-300.jsonl")
    stats = {}
    path = os.path.join(torn, "serve-rep0-300.jsonl")
    full = os.path.join(TRACE_SMALL, "serve-rep0-300.jsonl")
    recs = read_log(path, stats)
    assert stats["dropped_records"] == 1
    assert len(recs) == len(read_log(full, {})) - 1


def test_torn_gateway_tail_leaves_dangling_chain_without_crash(tmp_path):
    # tear the gateway log inside r06's route record: r06 keeps its
    # client submit and replica claim/shed, but claim's parent (the
    # enqueue span) never made it to disk
    torn = _torn_copy(tmp_path, victim="gateway-200.jsonl", keep_lines=13)
    stats = {}
    merged = load_merged(torn, stats)
    assert stats["dropped_records"] == 1
    from tpu_sandbox.obs.collect import trace_chains
    chains = trace_chains(merged)
    check = chain_check(chains["t06"])
    assert not check["connected"]
    assert check["dangling"] >= 1
    # attribution still works on the torn chain (truncated walk), and
    # the waterfall says WHY the row floated free
    req = critpath.attribute_request(chains["t06"])
    assert req is not None and req["outcome"] == "shed:capacity"
    rows = request_waterfall(merged, rid="r06")
    assert any(r["orphan"] for r in rows)
    out = _run("tracecat.py", torn, "--rid", "r06")
    assert out.returncode == 0, out.stderr
    assert "[orphan]" in out.stdout


def test_load_dir_stats_shape(tmp_path):
    stats = {}
    logs = load_dir(TRACE_SMALL, stats)
    assert stats["files"] == 3
    assert stats.get("dropped_records", 0) == 0
    assert set(logs) == {"client/100", "gateway/200", "serve-rep0/300"}


# -- fleetop panel ------------------------------------------------------------


def _load_fleetop():
    spec = importlib.util.spec_from_file_location(
        "fleetop_under_test", os.path.join(REPO, "tools", "fleetop.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleetop_where_time_goes_panel(kv_pair):
    from tpu_sandbox.obs.metrics import MetricsRegistry
    from tpu_sandbox.obs.record import Recorder
    from tpu_sandbox.obs.tsdb import TimeSeriesFlusher

    _, kv, _ = kv_pair
    fleetop = _load_fleetop()
    # nothing published yet -> no panel
    assert "where time goes:" not in fleetop.render(kv)

    prof = critpath.analyze(load_merged(TRACE_SMALL))["profile"]
    critpath.publish_profile(kv, prof)
    reg = MetricsRegistry()
    reg.gauge("mpmd.bubble_fraction", labels={"stage": "0"}).set(0.21)
    reg.gauge("mpmd.bubble_fraction", labels={"stage": "1"}).set(0.19)
    TimeSeriesFlusher(kv, proc="mpmd-test", registry=reg,
                      recorder=Recorder(None)).flush()

    out = fleetop.render(kv)
    assert "where time goes:" in out
    assert "decode" in out
    assert "attribution coverage 100.0%" in out
    assert "mpmd bubble: stage0=0.210  stage1=0.190" in out

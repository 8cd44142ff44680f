"""Flight recorder tier-1 suite: recorder semantics, the metrics
registry, clock-offset calibration, Chrome export, postmortem windows —
and THE acceptance test: end-to-end trace completeness through a live
2-replica gateway fleet (every non-shed request yields one connected
submit→route→enqueue→claim→admit→decode→verdict chain with exactly one
root; door sheds terminate in a ``door:infeasible`` span).

Everything runs in-process with the stub decode step from
test_gateway.py — real sockets, real KV, no jax compiles. The recorder
is process-global, so the in-process "fleet" writes one log file; the
collector treats that as the degenerate single-process merge, which is
exactly what the chain checks exercise (causality is carried by span
ids, not by which file a record landed in).
"""

import json
import time

import pytest

from tpu_sandbox.obs import (ENV_TRACE_DIR, MetricsRegistry, Recorder,
                             TraceContext, collect, get_recorder,
                             reset_recorder)
from tpu_sandbox.obs.record import ENV_PROC_NAME

from tests.helpers import kv_pair, pumping  # noqa: F401 (kv_pair: fixture)
from tests.test_gateway import _gateway, _wait_for_report, _worker


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Route the process-global recorder into a temp dir for the test,
    and restore the (disabled) recorder afterwards."""
    monkeypatch.setenv(ENV_TRACE_DIR, str(tmp_path))
    monkeypatch.setenv(ENV_PROC_NAME, "test")
    reset_recorder()
    yield str(tmp_path)
    reset_recorder()


# -- recorder semantics -------------------------------------------------------


def test_disabled_recorder_passes_context_through():
    rec = Recorder(None)
    parent = TraceContext("t1", "s1")
    with rec.span("outer", parent=parent) as sp:
        # a dark process must not sever the chain: children still see
        # the upstream context
        assert sp.ctx == parent
    assert rec.complete("x", time.monotonic(), parent=parent) == parent
    assert rec.instant("x", parent=parent) == parent
    assert rec.complete("x", time.monotonic()) is None
    assert rec.stats() == {"events": 0, "dropped": 0}


def test_recorder_emits_nested_spans(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = Recorder(path, proc="unit", flush_every=1)
    with rec.span("outer", args={"rid": "r0"}) as outer:
        with rec.span("inner", parent=outer.ctx):
            pass
    rec.instant("mark", parent=outer.ctx)
    rec.close()
    records = collect.read_log(path)
    by_ph = {}
    for r in records:
        by_ph.setdefault(r["ph"], []).append(r)
    assert len(by_ph["P"]) == 1 and len(by_ph["X"]) == 2
    inner, outer_rec = by_ph["X"]  # inner closes first
    assert (inner["name"], outer_rec["name"]) == ("inner", "outer")
    assert inner["trace"] == outer_rec["trace"]
    assert inner["parent"] == outer_rec["span"]
    assert by_ph["i"][0]["parent"] == outer_rec["span"]
    assert outer_rec["parent"] is None
    assert all(r["proc"] == "unit" and r["pid"] > 0 for r in records)
    assert outer_rec["dur"] >= inner["dur"] >= 0.0


def test_trace_context_wire_roundtrip_is_tolerant():
    ctx = TraceContext("abc", "1.2")
    assert TraceContext.from_wire(ctx.to_wire()) == ctx
    assert TraceContext.from_wire(ctx) is ctx
    assert TraceContext.from_wire(None) is None
    # malformed wire dicts read as "no context", never raise
    assert TraceContext.from_wire({"t": "abc"}) is None
    assert TraceContext.from_wire("garbage") is None


def test_backpressure_drops_newest_and_counts(tmp_path):
    path = str(tmp_path / "bp.jsonl")
    # manual flush mode: the buffer is the only sink until flush()
    rec = Recorder(path, proc="bp", flush_every=0, max_buffered=8)
    for i in range(20):
        rec.instant(f"e{i}")
    # preamble was force-flushed at open; 8 instants buffered, 12 dropped
    assert rec.stats() == {"events": 9, "dropped": 12}
    rec.close()
    assert len(collect.read_log(path)) == 9


# -- metrics registry ---------------------------------------------------------


def test_metrics_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("req").inc()
    reg.counter("req").inc(2)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    snap = reg.snapshot()
    assert snap["counters"]["req"] == 3
    assert snap["gauges"]["depth"] == 7
    lat = snap["histograms"]["lat"]
    assert lat["count"] == 100 and lat["min"] == 1.0 and lat["max"] == 100.0
    assert lat["p50"] <= lat["p90"] <= lat["p99"] <= 100.0
    assert 40.0 <= lat["p50"] <= 60.0
    # same name returns the same instrument; reset drops everything
    assert reg.counter("req").value == 3
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_metrics_registry_label_series_are_distinct_and_stable():
    from tpu_sandbox.obs.metrics import series_key

    assert series_key("engine.shed", None) == "engine.shed"
    # label keys sort, so the same label SET is always the same series
    assert series_key("engine.shed", {"reason": "deadline", "a": "b"}) == \
        "engine.shed{a=b,reason=deadline}"
    reg = MetricsRegistry()
    reg.counter("engine.shed", labels={"reason": "deadline"}).inc()
    reg.counter("engine.shed", labels={"reason": "door"}).inc(2)
    reg.counter("engine.shed", labels={"reason": "deadline"}).inc()
    snap = reg.snapshot()["counters"]
    assert snap["engine.shed{reason=deadline}"] == 2
    assert snap["engine.shed{reason=door}"] == 2
    assert "engine.shed" not in snap  # the bare name was never minted


# -- clock calibration / merge ------------------------------------------------


def _cal(seq, mono, wall, **kw):
    return dict({"ph": "C", "seq": seq, "mono": mono, "rtt": 0.001,
                 "wall": wall}, **kw)


def _span(name, ts, trace, span, parent=None, dur=0.01, **kw):
    return dict({"ph": "X", "name": name, "ts": ts, "dur": dur,
                 "trace": trace, "span": span, "parent": parent,
                 "args": {}}, **kw)


def test_clock_offsets_repair_skewed_wall_clocks():
    # proc a: mono ~10, wall = mono + 1000 (the true offset)
    # proc b: mono ~20, wall = mono + 980 — its wall clock runs 10 s
    # behind, so the wall anchor alone would order b's seq-2 point
    # BEFORE a's seq-1 point. The sequencer repair must bump b forward.
    logs = {
        "a/1": [_cal(1, 10.0, 1010.0), _cal(3, 10.1, 1010.1),
                _span("first", 10.02, "T", "a.1")],
        "b/2": [_cal(2, 20.0, 1000.0), _cal(4, 20.1, 1000.1),
                _span("second", 20.05, "T", "b.1", parent="a.1")],
    }
    offsets = collect.clock_offsets(logs)
    assert offsets["a/1"] == pytest.approx(1000.0)
    # repaired: b's seq-2 point may not precede a's seq-1 point
    assert offsets["b/2"] == pytest.approx(990.0)
    merged = collect.merge(logs, offsets)
    assert [r["name"] for r in merged] == ["first", "second"]
    assert merged[0]["uts"] <= merged[1]["uts"]
    # and the chain across the two processes validates
    chk = collect.chain_check(merged)
    assert chk["connected"] and chk["roots"] == 1


def test_calibrate_against_live_kv_sequencer(tmp_path):
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        path = str(tmp_path / "cal.jsonl")
        rec = Recorder(path, proc="cal")
        last = rec.calibrate(kv, rounds=3)
        rec.close()
        cals = [r for r in collect.read_log(path) if r["ph"] == "C"]
        assert len(cals) == 3
        seqs = [c["seq"] for c in cals]
        assert seqs == sorted(seqs) and seqs[-1] == last
        assert all(c["rtt"] >= 0 for c in cals)
    finally:
        kv.close()
        server.stop()
    assert Recorder(None).calibrate(None) == 0  # disabled: no kv traffic


def test_chrome_trace_export_is_valid(tmp_path):
    path = str(tmp_path / "c.jsonl")
    rec = Recorder(path, proc="chrome")
    with rec.span("req", args={"rid": "r1"}) as sp:
        rec.instant("mark", parent=sp.ctx)
    rec.close()
    merged = collect.merge(collect.load_dir(str(tmp_path)))
    doc = collect.to_chrome_trace(merged)
    # survives a JSON round trip (what Perfetto actually loads)
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert len(meta) == 1 and meta[0]["name"] == "process_name"
    spans = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(spans) == 1 and len(instants) == 1
    assert spans[0]["ts"] >= 0 and spans[0]["dur"] >= 0
    assert isinstance(spans[0]["pid"], int)
    assert instants[0]["s"] == "p"
    assert spans[0]["args"]["trace"] == instants[0]["args"]["trace"]


def test_clock_offsets_fall_back_to_preamble_without_calibration():
    # headless run: nobody calibrated against the KV sequencer, so only
    # the "P" preambles anchor each process's monotonic clock
    logs = {
        "a/1": [{"ph": "P", "mono": 10.0, "wall": 1010.0},
                _span("first", 10.02, "T", "a.1")],
        "b/2": [{"ph": "P", "mono": 20.0, "wall": 2020.0},
                _span("second", 20.05, "T", "b.1", parent="a.1")],
    }
    offsets = collect.clock_offsets(logs)
    assert offsets["a/1"] == pytest.approx(1000.0)
    assert offsets["b/2"] == pytest.approx(2000.0)
    merged = collect.merge(logs)
    assert [r["name"] for r in merged] == ["first", "second"]


def test_clock_offsets_median_rides_out_wall_clock_step():
    # NTP steps the wall clock 100 s forward mid-run: the stepped
    # calibration point is an outlier the median anchor must shrug off
    logs = {
        "a/1": [_cal(1, 10.0, 1010.0), _cal(2, 10.1, 1010.1),
                _cal(3, 10.2, 1110.2)],
    }
    assert collect.clock_offsets(logs)["a/1"] == pytest.approx(1000.0)


def test_clock_offsets_single_process_defaults_to_zero():
    # no C and no P records at all (truncated log): offset 0.0, and the
    # degenerate single-process merge still works
    logs = {"solo/1": [_span("only", 5.0, "T", "s.1")]}
    assert collect.clock_offsets(logs) == {"solo/1": 0.0}
    assert collect.merge(logs)[0]["uts"] == pytest.approx(5.0)


def test_metric_samples_round_trip_as_chrome_counter_tracks(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = Recorder(path, proc="meter", flush_every=1)
    rec.metric("sched.queue.depth", 3.0)
    rec.metric("sched.queue.depth", 5.0)
    rec.close()
    merged = collect.merge(collect.load_dir(str(tmp_path)))
    assert [r["value"] for r in merged if r["ph"] == "m"] == [3.0, 5.0]
    doc = json.loads(json.dumps(collect.to_chrome_trace(merged)))
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(counters) == 2
    assert all(c["name"] == "sched.queue.depth" for c in counters)
    # Perfetto draws the track from args.value at each ts
    assert [c["args"]["value"] for c in counters] == [3.0, 5.0]
    assert counters[0]["ts"] <= counters[1]["ts"]
    assert all(isinstance(c["args"]["value"], float) for c in counters)


def test_last_window_measures_from_last_record_not_now():
    merged = [
        {"ph": "i", "name": "old", "uts": 100.0, "args": {}},
        {"ph": "i", "name": "kill", "uts": 200.0, "args": {"agent": 1}},
        {"ph": "i", "name": "requeue", "uts": 201.5, "args": {}},
    ]
    tail = collect.last_window(merged, 5.0)
    assert [r["name"] for r in tail] == ["kill", "requeue"]
    text = collect.format_timeline(tail)
    assert "! [?] kill  agent=1" in text
    assert text.splitlines()[0].startswith("+   0.000s")
    assert collect.format_timeline([]) == "(no records in window)"


# -- OP_METRICS scrape --------------------------------------------------------


def test_gateway_metrics_scrape_over_socket(kv_pair, traced):
    from tpu_sandbox.gateway.client import GatewayClient
    from tpu_sandbox.obs import get_registry

    _, kv, clone = kv_pair
    w = _worker(clone(), tag="w0")
    with _gateway(kv) as gw, pumping(w):
        _wait_for_report(kv, "w0")
        with GatewayClient(gw.port) as client:
            assert client.submit("m0", [1, 2, 3], 2) is True
            assert client.result("m0", timeout=30.0)["verdict"] == "ok"
            body = client.metrics()
    snap = body["registry"]
    assert snap == get_registry().snapshot()
    # the gateway's own recorder stats plus each replica's, scraped from
    # the TTL load reports — a silently-dropping recorder is visible
    assert body["recorder"]["events"] > 0
    assert body["recorder"]["dropped"] == 0
    assert "default/w0" in body["replica_recorders"]
    assert set(body["replica_recorders"]["default/w0"]) == \
        {"events", "dropped"}
    # the fleet-wide drop total the recorder_drops health rule keys on
    assert body["dropped_events"] == body["recorder"]["dropped"] + \
        body["replica_recorders"]["default/w0"]["dropped"]


# -- THE acceptance test: end-to-end trace completeness -----------------------

#: the full causal chain every successfully served request must leave
FULL_CHAIN = {"submit", "route", "enqueue", "claim", "admit", "decode",
              "verdict"}


def test_trace_completeness_two_replica_fleet(kv_pair, traced):
    from tpu_sandbox.gateway.client import GatewayClient

    _, kv, clone = kv_pair
    w0 = _worker(clone(), tag="w0")
    w1 = _worker(clone(), tag="w1")
    with _gateway(kv) as gw, pumping(w0, w1):
        _wait_for_report(kv, "w0")
        _wait_for_report(kv, "w1")
        get_recorder().calibrate(kv, rounds=3)
        with GatewayClient(gw.port) as client:
            rids = [f"r{i}" for i in range(10)]
            for i, rid in enumerate(rids):
                assert client.submit(rid, [i + 1, i + 2, i + 3], 3)
            for rid in rids:
                assert client.result(rid, timeout=30.0)["verdict"] == "ok"
            # one request the feasibility door must refuse: no fleet can
            # finish anything in a nanosecond
            assert client.submit("doomed", [9, 9, 9], 3,
                                 deadline_s=1e-9) is False
    get_recorder().flush()

    merged = collect.load_merged(traced)
    chains = collect.trace_chains(merged)
    full, shed = 0, 0
    for tid, records in chains.items():
        chk = collect.chain_check(records)
        # exactly one root, and it is the client's submit span
        assert chk["connected"], (tid, chk)
        assert chk["root_names"] == ["submit"], (tid, chk)
        names = set(chk["names"])
        if any(n.startswith("door:") for n in names):
            shed += 1
            assert "door:infeasible" in names, names
            # a door shed never reaches the engine
            assert not names & {"claim", "admit", "decode"}, names
        elif FULL_CHAIN <= names:
            full += 1
    assert full >= len(rids), (full, {t: c["names"] for t, c in
                                      ((t, collect.chain_check(r))
                                       for t, r in chains.items())})
    assert shed == 1

    # the merged output is valid Chrome trace-event JSON
    doc = json.loads(json.dumps(collect.to_chrome_trace(merged)))
    assert len(doc["traceEvents"]) > len(merged)

    # and the waterfall renders a served request's life
    rows = collect.request_waterfall(merged, rid="r0")
    assert rows and rows[0]["name"] == "submit"
    text = collect.format_waterfall(rows)
    assert "submit" in text and "decode" in text

"""The reduction from a profiler trace to numbers, on a hand-written trace
whose answers are known, and on the small trace recorded on the chip that
is committed under ``benchmark/fixtures/``."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import trace_reduce as tr  # noqa: E402

#: one device, times in ns after the line's timestamp (picoseconds here):
#: fusion.1 0-100, all-reduce.1 100-200, fusion.2 150-250 (hides half of the
#: all-reduce), then nothing until fusion.3 400-450. The host was in
#: bench:next_batch 260-380 and bench:step_dispatch 380-400.
TEXT = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 150000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 400000 duration_ps: 50000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 250000 }
    events { metadata_id: 5 offset_ps: 400000 duration_ps: 50000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.1" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.2" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.3" } }
  event_metadata { key: 5 value { id: 5 name: "jit_step(123)" } }
}
planes { name: "/host:CPU"
  lines { name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 260000 duration_ps: 120000 }
    events { metadata_id: 3 offset_ps: 380000 duration_ps: 20000 }
    events { metadata_id: 4 offset_ps: 10000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
  event_metadata { key: 2 value { id: 2 name: "bench:next_batch" } }
  event_metadata { key: 3 value { id: 3 name: "bench:step_dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(step)" } }
}
planes { name: "/host:metadata" }
"""


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    return tr.planes_of(ProfileData.from_text_proto(TEXT), host_prefix="bench:")


def test_interval_arithmetic():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tr.total([(0, 4), (5, 9)]) == 8
    assert tr.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []


def test_planes_lines_and_host_filter(planes):
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU"]
    assert len(planes[0].lines["XLA Ops"]) == 4
    host = [n for events in planes[1].lines.values() for n, _, _ in events]
    assert "PjitFunction(step)" not in host and "bench:window" in host


def test_reduction_of_the_hand_written_trace(planes):
    out = tr.reduce(planes)
    assert out["n_devices"] == 1
    assert out["window_s"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx(300e-9)     # 0-250 and 400-450
    assert out["idle_pct_worst"] == pytest.approx(40.0)
    dev = out["devices"][0]
    assert dev["collective_ns"] == 100
    assert dev["collective_exposed_ns"] == 50         # 100-150; fusion.2 hides the rest
    assert dev["modules_ns"] == {"jit_step(123)": [250, 50]}
    assert dict(map(tuple, out["device_ops"]))["fusion.1"] == pytest.approx(100e-9)
    gaps = dict(map(tuple, out["idle_gaps"]))
    # 250-400: next_batch covers 120 ns of it; 450-500: nothing annotated
    assert gaps["bench:next_batch"] == pytest.approx(150e-9)
    assert gaps["(no annotation)"] == pytest.approx(50e-9)


def test_without_the_window_marker_the_window_is_first_to_last_op(planes):
    out = tr.reduce(planes, marker="bench:absent")
    assert out["window_s"] == pytest.approx(450e-9)


def test_a_trace_without_device_operations_reduces_to_nothing(planes):
    assert tr.reduce([p for p in planes if p.name == "/host:CPU"]) is None


FIXTURE = ROOT / "benchmark" / "fixtures" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    import json

    expect = json.loads(FIXTURE.with_name("tiny.expect.json").read_text())
    return expect, tr.load(FIXTURE, host_prefix="bench:")


def test_the_recorded_trace_has_a_plane_per_chip(recorded):
    expect, planes = recorded
    devices = [p for p in planes if tr.DEVICE_PLANE.match(p.name)]
    assert len(devices) == expect["devices"]
    assert all(tr.OPS_LINE in p.lines and tr.MODULES_LINE in p.lines
               for p in devices)
    assert any(p.name == tr.HOST_PLANE for p in planes)


def test_the_recorded_trace_reduces_to_what_was_run(recorded):
    expect, planes = recorded
    out = tr.reduce(planes)
    assert out["n_devices"] == expect["devices"]
    # six steps, the host asleep 3 ms before each: the window is longer than
    # the sleeps, the device is busy for part of it and idle for the sleeps
    assert out["window_s"] > expect["steps"] * expect["sleep_s"]
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert 0.0 < out["idle_pct_worst"] < 100.0
    for dev in out["devices"]:
        step_runs = [runs for name, runs in dev["modules_ns"].items()
                     if "jit_" in name]
        assert sum(len(r) for r in step_runs) == expect["steps"]
        if expect["collective"]:
            assert 0 < dev["collective_exposed_ns"] <= dev["collective_ns"]
            assert dev["collective_ns"] < dev["busy_ns"]
        else:
            assert dev["collective_ns"] == 0
    # names are instruction names, not the instructions' whole text
    assert all(" = " not in name for name, _ in out["device_ops"])
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["bench:next_batch"] >= 0.8 * expect["steps"] * expect["sleep_s"]
    assert max(gaps, key=gaps.get) == "bench:next_batch"

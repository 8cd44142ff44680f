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


# --- time by scope: the join of event names with the programs' op_names ---

from benchmark.lib import observe, readers  # noqa: E402

#: a compiled module as ``compiled.as_text()`` prints it, cut to what the
#: join reads: a fused computation, a ``while`` body, and an entry with a
#: fusion, a Pallas custom call, a custom call that is none, a collective, a
#: wrapper, and a copy the compiler inserted (no metadata)
HLO = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jvp(Net)/block0/mlp/add" source_file="m.py" source_line=3}
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %dynamic-slice.2 = f32[8]{0} dynamic-slice(%p), metadata={op_name="jit(step)/transpose(jvp(Net))/fc/dynamic_slice"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p, %dynamic-slice.2)
}

ENTRY %main.9 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.4 = f32[8]{0} copy(%x.1)
  %fusion.1 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(Net)/block0/mlp/add"}
  %attn.7 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(Net)/block0/attn/pallas_call"}
  %custom-call.2 = f32[8]{0} custom-call(%attn.7), custom_call_target="Sharding", metadata={op_name="jit(step)/jvp(Net)/block0/attn/sharding_constraint"}
  %pallas_call.3 = f32[8]{0} get-tuple-element(%attn.7), index=0, metadata={op_name="jit(step)/jvp(Net)/block0/attn/pallas_call"}
  %while.1 = (s32[], f32[8]{0}) while(%custom-call.2), condition=%cond.1, body=%body.1
  %psum.5 = f32[8]{0} all-reduce(%attn.7), replica_groups={}, to_apply=%region, metadata={op_name="jit(step)/grad_sync/psum"}
  ROOT %multiply_add_fusion = f32[] fusion(%psum.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/add"}
}
'''


def test_instruction_scopes_of_a_hand_written_module():
    scopes = observe.instruction_scopes(HLO)
    assert observe.program_name(HLO) == "jit_step"
    assert scopes["fusion.1"] == "jit(step)/jvp(Net)/block0/mlp/add"
    assert scopes["dynamic-slice.2"].endswith("/fc/dynamic_slice")  # while body
    assert scopes["multiply_add_fusion"] == "jit(step)/optimizer/add"  # ROOT
    assert scopes["psum.5"] == "jit(step)/grad_sync/psum"
    assert "copy.4" not in scopes and "while.1" not in scopes  # no metadata
    # the Pallas kernels are the subset they are: a custom call that is no
    # pallas_call and a get-tuple-element under a kernel's scope are not
    kernels = observe.pallas_instructions(HLO)
    assert kernels == {"attn.7": "jit(step)/jvp(Net)/block0/attn/pallas_call"}
    assert kernels.items() <= scopes.items()


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/"
     "rematted_computation/block3/attn/qkv/add", "TransformerLM/block3/attn/qkv"),
    ("jit(step)/jvp(TransformerLM)/block3/attn/pallas_call",
     "TransformerLM/block3/attn"),
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/gather", "loss"),
    ("jit(train_step)/transpose(jvp(ConvNetS2DT))/fc/nf,nk->kf/dot_general",
     "ConvNetS2DT/fc/nf,nk->kf"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(serve_decode)/TransformerLM/block0/attn/gather_ctx/gather",
     "TransformerLM/block0/attn/gather_ctx"),
    ("jit(f)/a/b/c/d/e/mul", "a/b/c/d"),
    ("jit(step)/shard_map/transpose(jvp(ConvNetS2DT))/ConvNetS2DT._tail/"
     "bn2.fused/pallas_call", "ConvNetS2DT/ConvNetS2DT._tail/bn2.fused"),
    ("jit(step)/shard_map/grad_sync/psum", "grad_sync"),
    ("jit(s)/jvp(M)/conv2/reshape;jit(s)/jvp(M)/conv2/tile", "M/conv2"),
    ("jit(train_step)/add", tr.NO_SCOPE),
    (None, tr.NO_SCOPE),
    (observe.AMBIGUOUS, observe.AMBIGUOUS),
])
def test_scope_path(op_name, want):
    assert tr.scope_path(op_name) == want


def test_numbered_siblings_fold_into_one_row_when_they_are_many():
    table = {f"M/block{i}/attn": 10 for i in range(6)}
    table.update({"M/conv1": 1, "M/conv2": 2, "M/block0/mlp": 5, "loss": 3})
    assert tr.fold_siblings(table) == {
        "M/block*/attn": 60, "M/block*/mlp": 5, "M/conv1": 1, "M/conv2": 2,
        "loss": 3}
    few = {f"M/block{i}/attn": 10 for i in range(tr.MAX_SIBLINGS)}
    assert tr.fold_siblings(few) == few


def test_two_programs_of_one_name_mark_what_they_scope_differently():
    obs = observe.Observations(cell={}, seed=0, seconds=1.0, traced=True)
    obs.note_program(HLO)
    obs.note_program(HLO.replace("block0/mlp/add", "block0/mlp/mul"))
    assert obs.scopes["jit_step"]["fusion.1"] == observe.AMBIGUOUS
    assert obs.scopes["jit_step"]["attn.7"].endswith("/attn/pallas_call")


#: two chips; on each, ``jit_step`` runs 0-400: fusion.1 0-100, attn.7
#: 100-200, while.1 200-300 holding dynamic-slice.2 200-300, psum.5 300-350
#: (chip 1: 300-370), copy.4 350-400 (chip 0 only). Then another program,
#: ``jit_other``, 500-560, runs its own fusion.1. The names are the module
#: ``HLO``'s.
def _device(n: int, psum_ps: int, with_copy: bool) -> str:
    copy = "events { metadata_id: 6 offset_ps: 350000 duration_ps: 50000 }"
    return f"""
planes {{ name: "/device:TPU:{n}"
  lines {{ name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000 }}
    events {{ metadata_id: 2 offset_ps: 100000 duration_ps: 100000 }}
    events {{ metadata_id: 3 offset_ps: 200000 duration_ps: 100000 }}
    events {{ metadata_id: 4 offset_ps: 200000 duration_ps: 100000 }}
    events {{ metadata_id: 5 offset_ps: 300000 duration_ps: {psum_ps} }}
    {copy if with_copy else ""}
    events {{ metadata_id: 1 offset_ps: 500000 duration_ps: 60000 }} }}
  lines {{ name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 7 offset_ps: 0 duration_ps: 400000 }}
    events {{ metadata_id: 8 offset_ps: 500000 duration_ps: 60000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %copy.4), kind=kLoop" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%attn.7 = f32[8]{{0}} custom-call(f32[8]{{0}} %fusion.1), custom_call_target=\\"tpu_custom_call\\"" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%while.1 = (s32[], f32[8]{{0}}) while(%custom-call.2)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%dynamic-slice.2 = f32[8]{{0}} dynamic-slice(%p)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%psum.5 = f32[8]{{0}} all-reduce(f32[8]{{0}} %attn.7)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%copy.4 = f32[8]{{0}} copy(%x.1)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "jit_step(77)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "jit_other(78)" }} }}
}}"""


@pytest.fixture(scope="module")
def scoped():
    """The two-chip trace reduced with the module's scopes, as a traced run
    of two steps holds it."""
    from jax.profiler import ProfileData

    obs = observe.Observations(cell={}, seed=0, seconds=1.0, traced=True)
    obs.note_program(HLO)
    text = _device(0, 50000, True) + _device(1, 70000, False)
    obs.trace = tr.reduce(tr.planes_of(ProfileData.from_text_proto(text)),
                          scopes=obs.scopes)
    obs.attempted = 2
    return obs


def test_device_scopes_regroup_device_ops_and_lose_nothing(scoped):
    out = scoped.trace
    ops = dict(map(tuple, out["device_ops"]))
    assert "while.1" not in ops  # its body's operation is listed
    assert ops["fusion.1"] == pytest.approx(160e-9)  # both programs, per chip
    got = dict(map(tuple, out["device_scopes"]))
    assert got == {
        "Net/block0/mlp": pytest.approx(100e-9),
        "Net/block0/attn": pytest.approx(100e-9),
        "Net/fc": pytest.approx(100e-9),             # the while's body
        "grad_sync": pytest.approx(60e-9),           # 50 and 70 on two chips
        tr.NO_SCOPE: pytest.approx(25e-9),           # the copy, one chip of two
        tr.OTHER_PROGRAM: pytest.approx(60e-9),      # jit_other's fusion.1
    }
    assert sum(got.values()) == pytest.approx(sum(ops.values()))
    # without the programs' scopes there is no such table, and nothing else moves
    from jax.profiler import ProfileData

    text = _device(0, 50000, True) + _device(1, 70000, False)
    bare = tr.reduce(tr.planes_of(ProfileData.from_text_proto(text)))
    assert bare["device_scopes"] == [] and bare["device_ops"] == out["device_ops"]


def test_cut_keeps_the_sum_of_a_ranked_table():
    rows = [["a", 5.0], ["b", 3.0], ["c", 2.0], ["d", 1.0]]
    assert tr.cut(rows, 10, "(other)") == rows
    assert tr.cut(rows, 2) == rows[:2]
    assert tr.cut(rows, 3, "(other)") == [["a", 5.0], ["b", 3.0], ["(other)", 3.0]]


@pytest.mark.parametrize("pattern,ms,calls", [
    (r"/attn/", 100e-6 / 2, 0.5),                 # one call a chip in two steps
    (r"/fc(/|$)", 100e-6 / 2, 0.5),               # inside the wrapper
    (r"(^|/)grad_sync(/|$)", 60e-6 / 2, 0.5),     # averaged over the chips
    (r"/block0/", 200e-6 / 2, 1.0),               # two modules of one block
])
def test_scope_ms_and_calls_per_step_and_chip(scoped, pattern, ms, calls):
    assert readers.scope_ms(scoped, pattern) == pytest.approx(ms)
    assert readers.scope_calls(scoped, pattern) == pytest.approx(calls)
    assert scoped.problems == []


def test_scope_ms_says_what_it_could_not_read(scoped):
    import copy

    untraced = observe.Observations(cell={}, seed=0, seconds=1.0, traced=False)
    assert readers.scope_ms(untraced, r"/attn/") is None
    assert untraced.problems == []  # an untraced run has nothing to read
    obs = copy.copy(scoped)
    obs.problems = []
    assert readers.scope_ms(obs, r"/no_such_module/") is None
    assert "no operation of the trace" in obs.problems[0]
    obs.problems, obs.scopes = [], {}
    assert readers.scope_ms(obs, r"/attn/") is None
    assert "noted no compiled program" in obs.problems[0]


def test_the_recorded_trace_joins_through_its_program(recorded):
    """On the chip's own recording every operation starts inside an
    execution of the one program, and the join moves seconds, not loses
    them."""
    expect, planes = recorded
    out = tr.reduce(planes, scopes={
        "jit_body": {"psum.7": "jit(body)/grad_sync/psum"}})
    assert all(set(d["by_program"]) == {"jit_body"} for d in out["devices"])
    ops = dict(map(tuple, out["device_ops"]))
    got = dict(map(tuple, out["device_scopes"]))
    assert set(got) == {"grad_sync", tr.NO_SCOPE}
    assert got["grad_sync"] == pytest.approx(ops["psum.7"])
    assert sum(got.values()) == pytest.approx(sum(ops.values()))

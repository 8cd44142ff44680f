"""The two readers that keep a step's gradient sync and its Pallas kernels
in sight when the compiler makes the collective asynchronous (PR 34):
``allreduce_wait_ms`` and ``pallas_scope_ms``, on two hand-written traces of
one step each, one in the form the data-parallel step compiled to before
(a synchronous ``all-reduce`` behind the backward kernels) and one in the
form it compiles to now (an ``async-collective-start`` / ``-done`` pair of
fusions, the backward kernel between them a fusion that took the kernel's
name). The accepted readers are run beside them: what they see of the
second form is the reason these two exist."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest, observe  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

PALLAS = 'op_name="jit(step)/transpose(jvp(Net))/conv/pallas_call"'
FWD = 'op_name="jit(step)/jvp(Net)/conv/pallas_call"'
SYNC = 'op_name="jit(step)/grad_sync/psum"'

#: the step as it compiled before: forward kernel 0-100, backward kernel
#: 100-300, a reduction of its per-channel output 300-302, the all-reduce
#: 302-502 alone on the chip, the update 502-552
SYNC_HLO = f"""HloModule jit_step, is_scheduled=true

ENTRY %main {{
  %conv.1 = f32[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{{FWD}}}
  %conv.2 = f32[8]{{0}} custom-call(%conv.1), custom_call_target="tpu_custom_call", metadata={{{PALLAS}}}
  %reduce.1 = f32[]{{}} reduce(%conv.2), metadata={{{PALLAS}}}
  %psum.3 = f32[8]{{0}} all-reduce(%conv.2), metadata={{{SYNC}}}
  ROOT %multiply_add_fusion = f32[8]{{0}} fusion(%psum.3), kind=kLoop, metadata={{op_name="jit(step)/optimizer/add"}}
}}
"""
SYNC_EVENTS = [
    ("%conv.1 = f32[8]{0} custom-call(%p), custom_call_target=\\\"tpu_custom_call\\\"", 0, 100),
    ("%conv.2 = f32[8]{0} custom-call(%conv.1), custom_call_target=\\\"tpu_custom_call\\\"", 100, 200),
    ("%reduce.1 = f32[]{} reduce(%conv.2)", 300, 2),
    ("%psum.3 = f32[8]{0} all-reduce(%conv.2)", 302, 200),
    ("%multiply_add_fusion = f32[8]{0} fusion(%psum.3), kind=kLoop", 502, 50),
]

#: the step as it compiles now: the start 100-104, the backward kernel
#: wrapped with a share of the collective (the fusion is ``conv.2``, the
#: kernel inside it ``conv.3``) and stretched to 104-344, its reduction, the
#: wait 346-406, the update, and the small leaves' all-reduce 456-457
ASYNC_HLO = f"""HloModule jit_step, is_scheduled=true

%async_collective_fusion.1 (p: f32[8]) -> f32[8] {{
  ROOT %conv.3 = f32[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{{PALLAS}}}
}}

ENTRY %main {{
  %conv.1 = f32[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{{FWD}}}
  %async-collective-start = (f32[8]{{0}}, u32[]) fusion(%g), kind=kCustom, calls=%fused_computation.1
  %conv.2 = (f32[8]{{0}}, u32[]) fusion(%conv.1, %async-collective-start), kind=kCustom, calls=%async_collective_fusion.1, metadata={{{PALLAS}}}
  %reduce.1 = f32[]{{}} reduce(%conv.2), metadata={{{PALLAS}}}
  %async-collective-done = f32[8]{{0}} fusion(%conv.2), kind=kCustom, calls=%fused_computation.2, metadata={{{SYNC}}}
  %multiply_add_fusion = f32[8]{{0}} fusion(%async-collective-done), kind=kLoop, metadata={{op_name="jit(step)/optimizer/add"}}
  ROOT %all-reduce.9 = f32[2]{{0}} all-reduce(%small), metadata={{{SYNC}}}
}}
"""
ASYNC_EVENTS = [
    ("%conv.1 = f32[8]{0} custom-call(%p), custom_call_target=\\\"tpu_custom_call\\\"", 0, 100),
    ("%async-collective-start = (f32[8]{0}, u32[]) fusion(%g), kind=kCustom, calls=%fused_computation.1", 100, 4),
    ("%conv.2 = (f32[8]{0}, u32[]) fusion(%conv.1, %async-collective-start), kind=kCustom, calls=%async_collective_fusion.1", 104, 240),
    ("%reduce.1 = f32[]{} reduce(%conv.2)", 344, 2),
    ("%async-collective-done = f32[8]{0} fusion(%conv.2), kind=kCustom, calls=%fused_computation.2", 346, 60),
    ("%multiply_add_fusion = f32[8]{0} fusion(%async-collective-done), kind=kLoop", 406, 50),
    ("%all-reduce.9 = f32[2]{0} all-reduce(%small)", 456, 1),
]


def _obs(hlo: str, events, chips: int = 2) -> observe.Observations:
    """One step of ``events`` (name, start, length: ns as written, on every
    chip alike) reduced with ``hlo``'s scopes, as a traced run holds it."""
    from jax.profiler import ProfileData

    end = max(s + d for _, s, d in events)
    text = ""
    for chip in range(chips):
        ops = "\n".join(
            f"    events {{ metadata_id: {i + 1} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}" for i, (_, s, d) in enumerate(events))
        names = "\n".join(
            f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
            f'name: "{name}" }} }}' for i, (name, _, _) in enumerate(events))
        text += f"""
planes {{ name: "/device:TPU:{chip}"
  lines {{ name: "XLA Ops" timestamp_ns: 1000
{ops} }}
  lines {{ name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {end * 1000} }} }}
{names}
  event_metadata {{ key: 99 value {{ id: 99 name: "jit_step(7)" }} }}
}}"""
    obs = observe.Observations(cell={}, seed=0, seconds=1.0, traced=True)
    obs.note_program(hlo)
    obs.trace = tr.reduce(tr.planes_of(ProfileData.from_text_proto(text)),
                          scopes=obs.scopes)
    obs.attempted = 1
    return obs


def _read(name: str, obs):
    return manifest.module("layer_metrics", name).read(obs)


@pytest.fixture(scope="module")
def sync_step():
    return _obs(SYNC_HLO, SYNC_EVENTS)


@pytest.fixture(scope="module")
def async_step():
    return _obs(ASYNC_HLO, ASYNC_EVENTS)


@pytest.mark.parametrize("metric,want", [
    ("allreduce_ms", 200e-6),
    ("allreduce_exposed_ms", 200e-6),
    ("allreduce_wait_ms", 200e-6),          # no pair: the accepted reading
    ("pallas_ms", 300e-6),
    ("pallas_scope_ms", 302e-6),            # the kernels and their reduction
])
def test_a_synchronous_step_reads_as_the_accepted_readers_read_it(
        sync_step, metric, want):
    assert _read(metric, sync_step) == pytest.approx(want)
    assert sync_step.problems == []


@pytest.mark.parametrize("metric,want", [
    # what the accepted readers see of it: the small leaves' all-reduce and
    # the forward kernel
    ("allreduce_ms", 1e-6),
    ("allreduce_exposed_ms", 1e-6),
    ("pallas_ms", 100e-6),
    # start + done + the small all-reduce; both kernels and the reduction
    ("allreduce_wait_ms", (4 + 60 + 1) * 1e-6),
    ("pallas_scope_ms", (100 + 240 + 2) * 1e-6),
])
def test_an_asynchronous_step_stays_in_sight(async_step, metric, want):
    assert _read(metric, async_step) == pytest.approx(want)
    assert async_step.problems == []


def test_the_two_new_readers_account_for_what_the_step_won(
        sync_step, async_step):
    """The device step fell by what the wait fell less what the kernels
    stretched: the check the accepted pair cannot make on the second form."""
    def busy(obs):
        return _read("device_step_ms", obs)

    moved = sum(_read(m, async_step) - _read(m, sync_step)
                for m in ("allreduce_wait_ms", "pallas_scope_ms"))
    assert busy(async_step) - busy(sync_step) == pytest.approx(moved)


@pytest.mark.parametrize("metric", ["allreduce_wait_ms", "pallas_scope_ms"])
def test_an_untraced_run_reads_nothing(metric):
    obs = observe.Observations(cell={}, seed=0, seconds=1.0, traced=False)
    assert _read(metric, obs) is None
    assert obs.problems == []


@pytest.mark.parametrize("name", [
    "async-collective-start", "async-collective-done",
    "async-collective-start.1", "async-collective-done.12"])
def test_the_pair_is_known_by_its_names(name):
    pair = manifest.module("layer_metrics", "allreduce_wait_ms").ASYNC_PAIR
    assert pair.match(name)
    assert not pair.match("fusion." + name)
    assert not pair.match(name + "-more")


def test_both_are_listed_for_the_four_chip_cell_alone():
    per_layer = {m["name"]: m for m in manifest.load()["per_layer"]}
    layers = {"allreduce_wait_ms": "data parallel", "pallas_scope_ms": "kernels"}
    for name, layer in layers.items():
        entry = per_layer[name]
        assert entry["workloads"] == ["convnet3000_dp4_bs5"]
        assert entry["layer"] == layer and entry["moves"] == "train_step_ms"
        assert entry["source"] == "device_trace"

"""``mixer_conv_ms``: the entry stands at the end of ``per_layer`` for the two
hybrid cells, its reader sums the device time under either mixer's ``conv``
scope on a hand-written trace, whatever runs there (a Pallas call or XLA's
fusions: the parent's program reads too), and reads nothing, without a
problem, from an untraced run or a program that has neither scope."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest, observe  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

NAME = "mixer_conv_ms"
CELLS = ["nemotron3s_train_s8192", "olmoh_train_s8192"]

OP = ('  %{name} = f32[8]{{0}} {kind}(%x), {extra}metadata={{op_name='
      '"jit(step)/{scope}"}}\n')
#: (instruction, scope, ns an execution, a Pallas call)
OLMO, BACK = "jvp(OlmoHybridLM)/block0", "transpose(jvp(OlmoHybridLM))/block0"
KERNELS = [
    ("fwd.1", f"{OLMO}/gdn/conv/jit(_fwd)/pallas_call", 300, True),
    ("fusion.2", f"{OLMO}/gdn/conv/checkpoint/square", 80, False),
    ("bwd.3", f"{BACK}/checkpoint/gdn/conv/jit(_bwd)/pallas_call", 500, True),
    ("fusion.4", f"{OLMO}/gdn/delta_rule/while/body/dot_general", 900, False),
    ("fusion.5", "jvp(OlmoHybridLM)/block3/attn/o/dot_general", 40, False)]
NEMOTRON = "jvp(NemotronHLM)/block1/mamba"
FUSIONS = [
    ("fusion.1", f"{NEMOTRON}/conv/mul", 700, False),
    ("fusion.2", f"transpose({NEMOTRON}/conv/reduce_sum)", 500, False),
    ("fusion.3", f"{NEMOTRON}/ssd/dot_general", 900, False)]
NEITHER = [
    ("fusion.1", "jvp(Transformer)/block0/attn/o/dot_general", 100, False),
    ("fusion.2", "jvp(ConvNet)/conv1/conv_general_dilated", 100, False)]


def traced(ops, steps=2):
    """One chip's trace of ``steps`` steps of a program made of ``ops``."""
    from jax.profiler import ProfileData

    hlo = ("HloModule jit_step, is_scheduled=true\n\nENTRY %main (x: f32[8]) "
           "-> f32[8] {\n  %x = f32[8]{0} parameter(0)\n")
    events, meta, offset = [], [], 0
    for i, (name, scope, ns, pallas) in enumerate(ops, 1):
        hlo += OP.format(
            name=name, scope=scope,
            kind="custom-call" if pallas else "fusion",
            extra='custom_call_target="tpu_custom_call", ' if pallas
            else "kind=kLoop, calls=%f, ")
        for _ in range(steps):
            events.append(f"events {{ metadata_id: {i} offset_ps: "
                          f"{offset * 1000} duration_ps: {ns * 1000} }}")
            offset += ns
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"%{name} = f32[8]{{0}} fusion()" }} }}')
    text = f"""
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Ops" timestamp_ns: 1000 {' '.join(events)} }}
  lines {{ name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {offset * 1000} }} }}
  {' '.join(meta)}
  event_metadata {{ key: 99 value {{ id: 99 name: "jit_step(7)" }} }}
}}"""
    obs = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                               traced=True, device_kind="TPU v5 lite")
    obs.note_program(hlo + "}\n")
    obs.trace = tr.reduce(tr.planes_of(ProfileData.from_text_proto(text)),
                          scopes=obs.scopes)
    obs.attempted = steps
    return obs


def read(obs):
    return manifest.module("layer_metrics", NAME).read(obs)


def test_the_entry_is_appended_for_the_two_hybrid_cells():
    per_layer = manifest.load()["per_layer"]
    # an invariant, not the list's end: later PRs append their entries
    assert [m for m in per_layer if m["name"] == NAME] == [{
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_step_ms", "workloads": CELLS}]
    for cell in CELLS:
        assert NAME in {m["name"] for m in manifest.cell(cell)["per_layer"]}
    others = {w["name"] for w in manifest.load()["workloads"]} - set(CELLS)
    for cell in others:
        assert NAME not in {m["name"]
                            for m in manifest.cell(cell)["per_layer"]}
    assert manifest.validate() == []


@pytest.mark.parametrize("ops,want", [
    (KERNELS, 880e-6), (FUSIONS, 1200e-6)], ids=["kernels", "fusions"])
def test_the_reader_sums_what_runs_under_either_conv_scope(ops, want):
    """ms a step: the kernels' program (two Pallas calls and the norm's
    fusion beside them) and the parent's (XLA's fusions under the scope)."""
    obs = traced(ops)
    assert read(obs) == pytest.approx(want)
    assert obs.problems == []


def test_the_reader_reads_nothing_where_there_is_nothing_to_read():
    bare = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                                traced=False, device_kind="TPU v5 lite")
    assert read(bare) is None and bare.problems == []
    other = traced(NEITHER)          # a ConvNet's ``conv1`` is no mixer's
    assert read(other) is None and other.problems == []
    untraced = traced(KERNELS)
    untraced.trace = None            # the program has the scope, no trace
    assert read(untraced) is None and untraced.problems == []

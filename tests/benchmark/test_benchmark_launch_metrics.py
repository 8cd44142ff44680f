"""The nine per-layer metrics of PR 35, which read the program's record of
its own launch (``runtime/bootstrap.py``'s compile listener, the
``setup:*`` / ``compile:lower_step`` / ``trace:kernel`` spans, the counter
``compile.in_loop``): each reads nothing — ``None`` — from a program that
keeps no such record (the parent commit, an empty registry) and creates no
series by reading; each returns a number after a tiny CPU run of its cell's
own ``build()`` and lowering, and ``None`` in the cells the table leaves it
out of. Counts and orderings only: no CPU second stands for a chip's."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

ALL_FIVE = ("step_trace_s", "step_lower_s", "step_backend_s",
            "kernel_trace_s", "kernel_trace_sites", "init_programs",
            "init_compile_s")
CONVNET_ONLY = ("data_build_s", "compiles_in_loop")
CONVNET_CELLS = ("convnet3000_1chip_bs5", "convnet3000_dp4_bs5")


@pytest.fixture
def registry():
    from tpu_sandbox.obs import get_registry

    reg = get_registry()
    reg.reset()
    yield reg
    reg.reset()


@pytest.fixture
def launch(launch):
    """``tests/conftest.py``'s record of the launch, and nothing an earlier
    test left behind: no open recorder, no compiled program."""
    from tpu_sandbox.obs import reset_recorder

    reset_recorder()
    jax.clear_caches()
    return launch


def _obs() -> Observations:
    return Observations(cell={}, seed=0, seconds=1.0, traced=True)


def _read(name, obs):
    return manifest.module("layer_metrics", name).read(obs)


def _read_all(compiled) -> tuple[dict, Observations]:
    obs = _obs()
    obs.note_program(compiled.as_text())
    return {name: _read(name, obs) for name in ALL_FIVE + CONVNET_ONLY}, obs


@pytest.mark.parametrize("name", ALL_FIVE + CONVNET_ONLY)
def test_reader_reads_nothing_from_a_program_without_the_record(
        name, registry):
    """The parent commit: no listener, no span. A step ran through the loop
    and a program is noted, and still there is nothing to read."""
    obs = _obs()
    obs.note_program("HloModule jit_train_step, is_scheduled=true\n")
    registry.counter("train.steps").inc(5)
    registry.counter("compile.cache_misses")
    registry.histogram("setup.model_init_s").observe(12.5)
    before = registry.snapshot()
    assert _read(name, obs) is None
    assert registry.snapshot() == before   # reading makes no series


def test_the_entries_are_appended_with_a_reader_each():
    """An invariant, not today's contents: later PRs append entries and
    cells, so the nine appear, in this order, each with its reader, in the
    cells of PR 35 at least (a later training cell appends itself)."""
    per_layer = manifest.load()["per_layer"]
    names = [m["name"] for m in per_layer]
    mine = list(ALL_FIVE + CONVNET_ONLY)
    assert [n for n in names if n in mine] == mine    # all, in this order
    training = ("convnet3000_1chip_bs5", "convnet3000_dp4_bs5",
                "gpt2m_train_s1024", "xing4_train_s4096",
                "nemotron3s_train_s8192")                   # PR 35's cells
    for m in (m for m in per_layer if m["name"] in mine):
        assert m["source"] in ("program_span", "program_counter")
        if m["name"] in CONVNET_ONLY:
            assert tuple(m["workloads"]) == CONVNET_CELLS, m["name"]
        else:
            assert set(training) <= set(m["workloads"]), m["name"]
        assert m["moves"] == ("train_step_ms" if m["name"]
                              == "compiles_in_loop" else "setup_s")
        assert callable(manifest.module("layer_metrics", m["name"]).read)
    assert manifest.validate() == []


def _convnet_args(parser, *more):
    return parser.parse_args(
        ["--image-size", "32", "--batch-size", "2", "--synthetic-n", "8",
         "--plan", "s2dt", "--epochs", "1", "--log-every", "1000", *more])


def _check_convnet(values, obs, *, kernels_a_step):
    for name in ALL_FIVE + ("data_build_s",):
        assert values[name] is not None and values[name] >= 0, name
    assert values["step_trace_s"] > 0 and values["step_lower_s"] > 0
    assert values["step_backend_s"] > 0
    assert set(obs.notes["step_backend_cache"]) <= {"hit", "miss", "none"}
    # the eager init: a program an operation, and every one under the span
    assert values["init_programs"] >= 20
    phases = obs.notes["init_compile_phases"]
    assert phases["backend"]["count"] == values["init_programs"]
    assert values["init_compile_s"] == pytest.approx(
        sum(p["sum"] for p in phases.values()))
    # the step's kernel sites, the init's apart
    sites = obs.notes["kernel_sites"]
    assert values["kernel_trace_sites"] == kernels_a_step == sum(
        k["sites"] for k in sites.values())
    assert obs.notes["kernel_sites_in_init"]    # forward kernels, eager
    assert values["kernel_trace_s"] == pytest.approx(
        sum(k["seconds"] for k in sites.values()))


def test_convnet_one_chip_cell_reads_all_nine(registry, launch):
    import mnist_onegpu
    from tpu_sandbox.train import Trainer

    args = _convnet_args(mnist_onegpu.build_parser())
    model, state, step, loader = mnist_onegpu.build(args)
    first = next(iter(loader))
    # as the runner: the caller lowers the jitted step itself, so the step
    # is found by its program's name and lies under no span
    compiled = step.lower(state, *first).compile()
    state = Trainer(compiled, verbose=False).fit(state, [first, first], 1)
    values, obs = _read_all(compiled)
    assert set(obs.scopes) == {"jit_train_step"}
    _check_convnet(values, obs, kernels_a_step=7)
    assert values["compiles_in_loop"] == 0      # the loop ran: a true zero
    hists = registry.snapshot()["histograms"]
    assert hists["compile.lower_s{under=none}"]["count"] >= 1
    assert not any("under=compile:lower_step" in k for k in hists)


def test_convnet_data_parallel_cell_reads_all_nine(registry, launch):
    import mnist_distributed
    from tpu_sandbox.train import Trainer

    args = _convnet_args(mnist_distributed.build_parser(), "-g", "2")
    dp, state, loader = mnist_distributed.build(args, 2)
    dstate = dp.shard_state(state)
    placed = dp.shard_batch(*next(iter(loader)))
    compiled = dp.lower_step(dstate, *placed).compile()
    Trainer(compiled, verbose=False).fit(dstate, [placed], 1)
    values, obs = _read_all(compiled)
    _check_convnet(values, obs, kernels_a_step=7)
    assert values["compiles_in_loop"] == 0
    # through the engine the step's phases lie under the span, whole
    hists = registry.snapshot()["histograms"]
    # (with the few operations on constants that run eagerly as it traces)
    under = hists["compile.lower_s{under=compile:lower_step}"]
    assert 0.8 * values["step_lower_s"] <= under["max"] \
        <= values["step_lower_s"]
    assert any(k.startswith("trace.kernel_s{") and
               "under=compile:lower_step" in k for k in hists)
    # place:state ran under no setup span of build(): its programs count
    assert "compile.backend_s{under=place:state}" in hists


def _check_lm(values, obs, registry, *, jitted_init):
    for name in ALL_FIVE:
        assert values[name] is not None and values[name] >= 0, name
    for name in CONVNET_ONLY:       # no setup:data, no LoopSpans step
        assert values[name] is None, name
    assert values["step_trace_s"] > 0 and values["step_lower_s"] > 0
    assert values["kernel_trace_sites"] >= 3    # flash forward, dkv, dq
    # the model's own init: one program where it is jitted, one an
    # operation where it is not (the optimizer's init is eager either way)
    hists = registry.snapshot()["histograms"]
    in_model_init = hists["compile.backend_s{under=setup:model_init}"]["count"]
    assert in_model_init <= values["init_programs"]
    assert in_model_init <= 3 if jitted_init else in_model_init >= 20


def test_gpt2_cell_reads_seven_and_leaves_two_out(registry, launch):
    """The runner's mirror of ``lm_train``'s dp branch: no ``setup:build``
    root, and every reader that needs none reads."""
    runner = manifest.module("runners", "lm_train")
    config = {"vocab_size": 128, "n_embd": 32, "n_head": 2, "n_layer": 1,
              "n_inner": 64}
    dep = {"flash": True, "dtype": "fp32", "remat": False,
           "remat_policy": None, "learning_rate": 1e-3}
    model, eng, state = runner.build(config, dep, 16, 0, jax.devices()[:1])
    tokens = np.zeros((2, 16), np.int32)
    compiled = eng.lower_step(state, *eng.shard_batch(tokens, tokens)) \
        .compile()
    values, obs = _read_all(compiled)
    _check_lm(values, obs, registry, jitted_init=False)
    spans = {k.split("under=")[1].rstrip("}")
             for k in registry.snapshot()["histograms"] if "under=" in k}
    assert "setup:build" not in spans and "compile:lower_step" in spans


@pytest.mark.parametrize("model_name", ["xing4", "nemotron_h"])
def test_config_built_lm_cells_read_seven_and_leave_two_out(
        model_name, registry, launch):
    import lm_train

    if model_name == "xing4":
        from tests.test_xing4_model import tiny

        # a width the mHC kernels tile (64 falls back to ``jnp``)
        config = tiny(hidden_size=128, num_nextn_predict_layers=0,
                      hc_sinkhorn_iters=3)
    else:
        from tests.test_nemotron_h_model import TINY as config
    args = lm_train.build_parser().parse_args(
        ["--model", model_name, "--parallelism", "dp", "--batch", "2",
         "--seq-len", "16", "--dtype", "fp32", "--flash", "--remat"])
    args.config = config
    model, tx, state, eng = lm_train.build(args, jax.devices()[:1])
    tokens = jnp.zeros((2, 16), jnp.int32)
    compiled = eng.lower_step(state, *eng.shard_batch(tokens, tokens)) \
        .compile()
    values, obs = _read_all(compiled)
    _check_lm(values, obs, registry, jitted_init=True)
    sites = obs.notes["kernel_sites"]
    if model_name == "xing4":
        # a jitted call fires once a shape and a trace context, not once a
        # site: fewer spans than the sites its choice counter counted
        counted = sum(v for k, v in registry.snapshot()["counters"].items()
                      if k.startswith("mhc.kernel_choice")
                      and "kernel=pre_fwd" in k)
        in_init = obs.notes["kernel_sites_in_init"]["mhc_pre_fwd"]["sites"]
        assert 1 <= sites["mhc_pre_fwd"]["sites"] + in_init < counted
        assert {"gmm", "tgmm", "flash_fwd"} <= set(sites)
    else:
        assert {"ssd_scan", "gmm", "flash_fwd"} <= set(sites)
    hists = registry.snapshot()["histograms"]
    assert "compile.backend_s{under=setup:model_init}" in hists
    assert "compile.lower_s{under=compile:lower_step}" in hists

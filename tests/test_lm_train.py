"""lm_train entry script: every parallelism trains and the loss drops, and
``build`` returns a working engine for every ``--model``.

Runs the script's train() in-process on the conftest's 8-device virtual CPU
mesh (tiny configs — the script itself raises SystemExit if the loss does
not decrease, so convergence is part of the contract under test).
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import lm_train  # noqa: E402


def _args(**over):
    """Complete args from the real parser (new flags inherit CLI defaults),
    with the small-shape test base applied on top."""
    args = lm_train.build_parser().parse_args([])
    base = dict(
        parallelism="dp", devices=4, steps=24, batch=4, seq_len=32, vocab=16,
        d_model=16, n_heads=2, n_layers=2, d_ff=32, lr=1e-2, microbatches=2,
        log_every=8, dtype="fp32", attn="ring", flash=False, remat=False,
        force_cpu=False, dp=1, circular_chunks=1, router_top_k=1,
    )
    base.update(over)
    for k, v in base.items():
        setattr(args, k, v)
    return args


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("parallelism", ["dp", "tp", "sp", "ep"])
def test_parallelism_trains(parallelism, devices):
    # tp shards the head and d_ff dims over 4 devices -> need 4 heads
    heads = 4 if parallelism == "tp" else 2
    lm_train.train(_args(parallelism=parallelism, n_heads=heads))


@pytest.mark.usefixtures("light_compile")
def test_pp_trains(devices):
    lm_train.train(_args(parallelism="pp", n_layers=4, devices=4))


@pytest.mark.usefixtures("light_compile")
def test_pp_circular_trains(devices):
    lm_train.train(_args(parallelism="pp", n_layers=8, devices=4,
                         microbatches=4, circular_chunks=2))


@pytest.mark.usefixtures("light_compile")
def test_ep_top2_trains(devices):
    lm_train.train(_args(parallelism="ep", router_top_k=2))


@pytest.mark.usefixtures("light_compile")
def test_tp_composes_with_dp(devices):
    # data=2 x model=4: the full megatron ruleset under a composed mesh
    lm_train.train(_args(parallelism="tp", devices=8, dp=2, n_heads=4,
                         vocab=16, batch=4))


@pytest.mark.usefixtures("light_compile")
def test_3d_mesh_trains(devices):
    # data=2 x model=2 x pipe=2: TP stages inside the pipeline
    lm_train.train(_args(parallelism="3d", devices=8, n_layers=2, batch=4))


def test_remat_matches_plain(devices, capsys):
    lm_train.train(_args(steps=8, log_every=4))
    plain = capsys.readouterr().out
    lm_train.train(_args(steps=8, log_every=4, remat=True))
    remat = capsys.readouterr().out
    # remat changes memory, not math: identical logged losses
    pick = lambda s: [l for l in s.splitlines() if "Loss" in l]  # noqa: E731
    assert pick(plain) == pick(remat)


# --- build(): the model, the optimizer, the state and the engine ---

def _xing4_built(eng, stats):
    bias = stats["block1"]["moe"]["e_score_correction_bias"]
    assert float(jnp.abs(bias).max()) == pytest.approx(1e-3)


def _nemotron_h_built(eng, stats):
    from tpu_sandbox.models import nemotron_h

    assert eng.mtp_weight == nemotron_h.MTP_LOSS_WEIGHT
    assert float(jnp.abs(stats["block0"]["moe"][
        "e_score_correction_bias"]).max()) == pytest.approx(1e-3)
    assert int(stats["block3"]["moe"]["steps"]) == 1
    assert int(stats["mtp_block1"]["moe"]["rows_dropped"]) == 0


def _tiny_of(module):
    import importlib

    return importlib.import_module(f"tests.test_{module}_model").TINY


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("model,cls,also", [
    ("gpt2", "TransformerLM", None),
    ("xing4", "Xing4LM", _xing4_built),
    ("nemotron_h", "NemotronHLM", _nemotron_h_built),
    ("olmo_hybrid", "OlmoHybridLM", None)],
    ids=["gpt2", "xing4", "nemotron_h", "olmo_hybrid"])
def test_build_returns_model_optimizer_state_engine(model, cls, also, tmp_path):
    """One step through what ``build`` returns, for every ``--model``: the
    loss is finite, the step counted, and a model that holds a share of its
    experts has moved its router's bias by gamma."""
    flags = ["--force-cpu", "--batch", "2", "--seq-len", "16"]
    if model in lm_train.CONFIG_MODELS:
        path = tmp_path / "tiny.json"
        path.write_text(json.dumps(_tiny_of(model)))
        flags += ["--model", model, "--config", str(path)]
    args = lm_train.build_parser().parse_args(flags)
    built, tx, state, eng = lm_train.build(args, jax.devices()[:1])
    assert type(built).__name__ == cls
    assert bool(jax.tree.leaves(state.batch_stats)) == (also is not None)
    batch = next(lm_train.make_batches(built.config.vocab_size, 2, 16, 1, 0))
    new, loss = eng.train_step(state, *eng.shard_batch(*batch))
    assert np.isfinite(float(loss)) and int(new.step) == 1
    if also:
        also(eng, new.batch_stats)


def test_xing4_needs_its_config_and_dp():
    args = lm_train.build_parser().parse_args(
        ["--force-cpu", "--model", "xing4"])
    with pytest.raises(SystemExit, match="--config"):
        lm_train.build(args, jax.devices()[:1])

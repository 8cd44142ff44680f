"""``tools/paged_attn_race.py`` without a chip: its arguments, its table of
geometries held against the cells' own files, the lengths and tables it
deals, and the three readings and the check at tiny shapes through the
interpreter (a CPU time is no reading; the lines' fields are)."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark.lib import manifest
from tools import paged_attn_race as race
from tpu_sandbox.serve.decode import pages_per_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# geometry -> (the cell's workload file, what its pool holds a block table
# of); the ring of a window layer is its own pool
CELLS = {
    "kv1024_g1": "gpt2m_serve_decode_replay",
    "kv1024_g6": "laguna_serve_decode_replay",
    "kv1024_g8_w512": "laguna_serve_decode_replay",
    "kv128_g20": "jamba2_serve_decode_replay",
    "latent640_h64": "longcat_serve_decode_replay",
}


def workload(name: str) -> dict:
    """The cell's deployment (slots, blocks, tables) and its traffic's
    name, as the benchmark itself reads them."""
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           name + ".json")) as f:
        spec = json.load(f)
    return {**manifest.cell(name)["deployment"], "traffic": spec["traffic"]}


@pytest.mark.parametrize("name", CELLS)
def test_a_geometry_is_its_cells(name):
    """Slots, traffic and table as the cell's workload file has them; a
    window layer's ring and pool as ``window_blocks`` and the window give
    them."""
    geo, spec = race.GEOMETRIES[name], workload(CELLS[name])
    assert geo.batch == spec["max_batch"]
    assert geo.traffic == spec["traffic"]
    assert spec["block_size"] == race.BLOCK
    if geo.window is None:
        assert geo.max_blocks == spec["max_blocks_per_seq"]
        assert geo.num_blocks == spec["num_blocks"]
    else:
        assert geo.max_blocks == -(-geo.window // race.BLOCK) + 1
        assert geo.num_blocks == spec["window_blocks"]


def test_the_heads_are_the_configurations():
    def config(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            return json.load(f)

    laguna, jamba, longcat, gpt2 = map(config, (
        "laguna-xs.2", "ai21-jamba2-3b", "longcat-flash-omni", "gpt2-medium"))
    geo = race.GEOMETRIES
    for name, heads in (("kv1024_g6", 48), ("kv1024_g8_w512", 64)):
        assert heads in laguna["num_attention_heads_per_layer"]
        assert geo[name].kv_heads == laguna["num_key_value_heads"]
        assert geo[name].kv_heads * geo[name].group == heads
        assert geo[name].head_dim == laguna["head_dim"]
    assert geo["kv1024_g8_w512"].window == laguna["sliding_window"]
    assert geo["kv128_g20"].kv_heads == jamba["num_key_value_heads"]
    assert geo["kv128_g20"].group == jamba["num_attention_heads"]
    assert geo["kv1024_g1"].kv_heads == gpt2["n_head"]
    latent = geo["latent640_h64"]
    assert latent.group == longcat["num_attention_heads"]
    assert latent.v_dim == longcat["kv_lora_rank"]
    assert latent.head_dim == latent.v_dim + longcat["qk_rope_head_dim"]
    assert latent.scale == (longcat["qk_nope_head_dim"]
                            + longcat["qk_rope_head_dim"]) ** -0.5


@pytest.mark.parametrize("name", race.GEOMETRIES)
def test_every_geometry_takes_the_kernel_and_fits_its_pool(name):
    geo = race.GEOMETRIES[name]
    assert geo.width == (640 if geo.latent else geo.kv_heads * geo.head_dim)
    pages = race.pages_of(geo)
    assert pages == pages_per_step(geo.width, race.BLOCK, jnp.bfloat16,
                                   geo.max_blocks)
    lengths = race.lengths_of(geo, seed=3, grown=128)
    assert lengths.shape == (geo.batch,) and lengths.min() > 128
    tables = race.tables_of(geo, lengths, seed=3, dealt=False)
    live = tables[tables > 0]
    assert len(np.unique(live)) == len(live) and live.max() < geo.num_blocks
    # every block a row's walk copies is one of its own
    reach = -(-lengths // race.BLOCK)
    if geo.window is None:
        assert ((tables > 0).sum(axis=1) == reach).all()
    else:
        assert (tables > 0).all()
    blocks = race.live_blocks(geo, lengths)
    assert (blocks <= (reach if geo.window is None else geo.max_blocks)).all()
    assert race.steps_of(geo, lengths, pages) >= geo.batch
    assert race.needed_bytes(geo, lengths) == blocks.sum() * race.BLOCK \
        * geo.width * 2 * (1 if geo.latent else 2)


def test_lengths_are_the_traffic_files_in_the_seeds_order():
    geo = race.GEOMETRIES["latent640_h64"]
    a, b = (race.lengths_of(geo, seed=s, grown=0) for s in (1, 2))
    assert sorted(a) == sorted(b) and (a != b).any()
    assert a.sum() == 270_278            # the traffic file's own note
    assert (race.lengths_of(geo, seed=1, grown=64) == a + 64).all()


def test_dealt_tables_run_in_order():
    geo = race.GEOMETRIES["kv128_g20"]
    lengths = race.lengths_of(geo, seed=0, grown=0)
    tables = race.tables_of(geo, lengths, seed=0, dealt=True)
    live = tables[tables > 0]
    assert (live == np.arange(1, len(live) + 1)).all()


def test_a_pool_too_small_is_refused():
    geo = race.GEOMETRIES["kv128_g20"]
    lengths = race.lengths_of(geo, seed=0, grown=0)
    with pytest.raises(ValueError, match="pool"):
        race.tables_of(race.replace(geo, num_blocks=100), lengths, 0, False)


def test_arguments():
    args = race.parse([])
    assert list(args.geometries) == list(race.GEOMETRIES)
    assert args.probe == list(race.PROBES) and not args.check
    assert args.tables == "churned" and tuple(args.calls) == (4, 36)
    args = race.parse(["--geometry", "latent640_h64", "--probe", "copies",
                       "--tables", "dealt", "--seed", "2147483999",
                       "--calls", "2", "10", "--grown", "0"])
    assert list(args.geometries) == ["latent640_h64"]
    assert args.probe == ["copies"] and args.seed == 2147483999
    assert list(race.parse(["--tiny"]).geometries) == list(race.TINY)
    for bad in (["--geometry", "gpt3"], ["--calls", "8", "8"],
                ["--probe", "half"], ["--tiny", "--geometry", "kv1024_g1"]):
        with pytest.raises(SystemExit):
            race.parse(bad)


def test_without_a_chip_it_says_so():
    with pytest.raises(SystemExit, match="no TPU"):
        race.main(["--geometry", "kv128_g20"])


@pytest.mark.parametrize("name", race.TINY)
def test_the_three_readings_at_tiny_shapes(name, capsys):
    race.main(["--tiny", "--geometry", name, "--calls", "1", "2",
               "--repeats", "1"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["probe"] for x in lines] == list(race.PROBES)
    geo = race.TINY[name]
    for line in lines:
        assert line["geometry"] == name and line["device"] == "cpu"
        assert line["pages_per_step"] == geo.pages
        assert line["steps_a_call"] >= geo.batch and line["needed_mb"] > 0
        assert {"us_a_call", "us_a_step", "gb_s"} <= set(line)


@pytest.mark.parametrize("name", race.TINY)
def test_the_check_at_tiny_shapes(name, capsys):
    """The whole kernel gives the ``jnp`` form's answer and the same bits
    over poisoned buffers (interpreted, a wait is no wait: the chip's run of
    this check is the proof)."""
    race.main(["--tiny", "--check", "--geometry", name])
    line, = (json.loads(x) for x in capsys.readouterr().out.splitlines())
    assert line["check"] and line["finite"] and line["poison_same_bits"]
    assert line["jnp_rows"] == race.TINY[name].batch
    assert line["jnp_max_abs"] < 3e-2

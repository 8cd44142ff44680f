"""parallel/expert.py::ExpertShare and ops/pallas_grouped_matmul.py at a
small size on the CPU (kernels interpreted): the share test of the
model-configs guide, the drop rule, the layout's invariants, the grouped
product against dense einsums, and the compiled step's HLO."""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import xing4 as ref  # noqa: E402
from tpu_sandbox.ops.pallas_grouped_matmul import grouped_matmul  # noqa: E402
from tpu_sandbox.parallel.expert import ExpertShare, share_layout  # noqa: E402

C, F, E, K, T = 32, 16, 16, 4, 96


def share(held, local_rows, shared=1, row_tile=8):
    return ExpertShare(d_model=C, d_ff=F, n_routed_experts=E, top_k=K,
                       held=tuple(held), local_rows=local_rows,
                       n_shared_experts=shared, routed_scaling_factor=2.0,
                       dtype=jnp.float32, row_tile=row_tile)


def whole_layer():
    """One layer holding all 16 experts, its variables, an input."""
    x = jax.random.normal(jax.random.key(0), (T, C))
    layer = share(range(E), local_rows=T * K)
    variables = layer.init(jax.random.key(1), x)
    bias = 0.05 * jax.random.normal(jax.random.key(2), (E,))
    variables = {"params": variables["params"], "batch_stats": {
        **variables["batch_stats"], "e_score_correction_bias": bias}}
    return x, layer, variables


def cut(variables, held):
    """The variables a share holding ``held`` has: its experts' weights."""
    params = dict(variables["params"])
    for name in ("w_gate", "w_up", "w_down"):
        params[name] = params[name][jnp.asarray(held)]
    return {"params": params, "batch_stats": variables["batch_stats"]}


def reference_params(variables):
    p = jax.tree.map(lambda a: a, dict(variables["params"]))
    p["bias"] = variables["batch_stats"]["e_score_correction_bias"]
    return p


REF_CFG = {"num_experts_per_tok": K, "routed_scaling_factor": 2.0,
           "n_shared_experts": 1}


def test_the_shares_add_up_to_the_whole_layer():
    """Held = 0-1, 2-3, ..., 14-15: the eight shares' routed parts plus the
    shared expert counted once are the uncut reference's whole layer."""
    x, layer, variables = whole_layer()
    want, _ = ref.expert_share(
        reference_params(variables), x,
        {**REF_CFG, "held": list(range(E)), "local_rows": T * K})
    total = 0.0
    for first in range(0, E, 2):
        held = (first, first + 1)
        part = share(held, local_rows=T * K, shared=0).apply(
            cut(variables, held), x)
        ref_part, _ = ref.expert_share(
            reference_params(cut(variables, held)), x,
            {**REF_CFG, "held": list(held), "local_rows": T * K},
            with_shared=False)
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
    shared_only = (layer.apply(variables, x)
                   - share(range(E), T * K, shared=0).apply(variables, x))
    np.testing.assert_allclose(total + shared_only, want, atol=5e-5)
    np.testing.assert_allclose(layer.apply(variables, x), want, atol=5e-5)


def skewed(x, variables, expert, gain=50.0):
    """Variables whose router sends every token to ``expert`` first."""
    params = dict(variables["params"])
    bias = jnp.zeros((E,)).at[expert].set(gain)
    return {"params": params, "batch_stats": {
        **variables["batch_stats"], "e_score_correction_bias": bias}}


@pytest.mark.parametrize("local_rows,dropped", [(T * 2, 0), (T, 0), (T - 24, 24)])
def test_one_expert_takes_every_row_and_only_the_total_drops(local_rows, dropped):
    """All T tokens choose expert 3; the share holds 3 and 5. Per-expert
    imbalance drops nothing while the share's total is at most R; above R
    the tail (order: expert, then position) goes, as in the reference."""
    x, _, variables = whole_layer()
    held = (3, 5)
    v = cut(skewed(x, variables, 3), held)
    layer = share(held, local_rows=local_rows)
    y, mutated = layer.apply(v, x, mutable=["batch_stats", "intermediates"])
    sel = np.asarray(mutated["intermediates"]["sel"][0])
    assert (sel == 3).any(-1).all()
    total = int(np.isin(sel, held).sum())
    stats = mutated["batch_stats"]
    if not dropped:
        assert total <= local_rows or local_rows == T
    assert float(stats["rows_held"]) == min(total, local_rows)
    assert float(stats["rows_dropped"]) == total - min(total, local_rows)
    assert float(stats["expert_rows_max"]) == T
    want, _ = ref.expert_share(
        reference_params(v), x,
        {**REF_CFG, "held": list(held), "local_rows": local_rows})
    np.testing.assert_allclose(y, want, atol=5e-5)
    kept = np.asarray(ref.kept_assignments(jnp.asarray(sel), held, local_rows))
    assert kept.sum() == min(total, local_rows)
    if total > local_rows:  # the tail: expert 5's rows go first, then 3's last
        assert not kept[sel == 5].any() or kept[sel == 3].all()


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("local_rows", [32, 64, 512])
def test_layout_invariants(seed, local_rows):
    held, tile = (1, 4, 7, 12), 8
    sel = jax.random.randint(jax.random.key(seed), (T, K), 0, E)
    lay = jax.tree.map(np.asarray, share_layout(sel, E, held, local_rows, tile))
    p = local_rows + len(held) * tile
    group = lay["tile_group"]
    assert group.shape == (p // tile,) and (np.diff(group) >= 0).all()
    assert set(group) == set(range(len(held)))        # every expert owns a tile
    valid, kept = lay["row_valid"], lay["kept"]
    assert valid.sum() == kept.sum() == lay["rows_held"] <= local_rows
    total = int(np.isin(np.asarray(sel), held).sum())
    assert lay["rows_held"] + lay["rows_dropped"] == total
    assert lay["rows_held"] == min(total, local_rows)
    # a valid row holds an assignment to the expert its tile belongs to, the
    # assignment points back at the row, and no two share one
    rows = np.flatnonzero(valid)
    assignment = lay["row_assignment"][rows]
    flat_sel = np.asarray(sel).reshape(-1)
    assert (np.asarray(held)[group[rows // tile]] == flat_sel[assignment]).all()
    assert (lay["dest"].reshape(-1)[assignment] == rows).all()
    assert len(set(assignment)) == len(assignment)
    np.testing.assert_array_equal(
        kept, np.asarray(ref.kept_assignments(sel, held, local_rows)))


@pytest.mark.parametrize("k_dim,n_dim", [(32, 16), (16, 32)])
def test_grouped_matmul_matches_dense_forward_and_backward(k_dim, n_dim):
    tile, groups = 8, 3
    tile_group = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    x = jax.random.normal(jax.random.key(0), (6 * tile, k_dim))
    w = jax.random.normal(jax.random.key(1), (groups, k_dim, n_dim))
    row_group = jnp.repeat(tile_group, tile)

    def dense(x, w):
        return jnp.einsum("pk,pkn->pn", x, w[row_group])

    def kernel(x, w):
        return grouped_matmul(x, w, tile_group, tile)

    np.testing.assert_allclose(kernel(x, w), dense(x, w), rtol=1e-5, atol=1e-5)
    g = jax.random.normal(jax.random.key(2), (6 * tile, n_dim))
    got = jax.grad(lambda x, w: (kernel(x, w) * g).sum(), (0, 1))(x, w)
    want = jax.grad(lambda x, w: (dense(x, w) * g).sum(), (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_empty_tiles_cost_a_product_and_give_zeros():
    """A tile of zero rows under some expert's weights: multiplied like any
    other, gives zeros, and leaves that expert's weight gradient alone."""
    tile = 8
    tile_group = jnp.asarray([0, 1, 1], jnp.int32)
    x = jnp.concatenate([jax.random.normal(jax.random.key(0), (2 * tile, 16)),
                         jnp.zeros((tile, 16))])
    w = jax.random.normal(jax.random.key(1), (2, 16, 16))
    out = grouped_matmul(x, w, tile_group, tile)
    assert float(jnp.abs(out[2 * tile:]).max()) == 0.0
    dw = jax.grad(lambda w: grouped_matmul(x, w, tile_group, tile).sum())(w)
    np.testing.assert_allclose(dw[1], jnp.broadcast_to(
        x[tile:2 * tile].sum(0)[:, None], (16, 16)), rtol=1e-5, atol=1e-5)


def lowered_step_text():
    x, _, variables = whole_layer()
    held = (0, 1, 2, 3)
    layer = share(held, local_rows=64)
    v = cut(variables, held)

    def step(params, x):
        def loss(params, x):
            y, mutated = layer.apply(
                {"params": params, "batch_stats": v["batch_stats"]}, x,
                mutable=["batch_stats"])
            return jnp.square(y).sum(), mutated
        return jax.grad(loss, (0, 1), has_aux=True)(params, x)

    return jax.jit(step).lower(v["params"], x).compile().as_text()


def test_compiled_share_has_no_colliding_scatter_and_no_data_dependent_loop():
    """On the CPU the interpreted kernels lower to loops over their static
    grids; outside them nothing loops, and no scatter may collide: every
    gather's transpose is a gather through the inverse mapping."""
    text = lowered_step_text()
    scatters = [line for line in text.splitlines()
                if re.search(r"= [^=]*\bscatter\(", line)]
    assert all("unique_indices=true" in line for line in scatters), scatters
    # a loop's trip count is a constant of the program: XLA prints it
    whiles = [line for line in text.splitlines()
              if re.search(r"= [^=]*\bwhile\(", line)]
    for line in whiles:
        assert "known_trip_count" in line, line
    assert "ragged" not in text and "dynamic-reshape" not in text


def test_bias_and_counters_do_not_move_without_a_mutable_collection():
    x, layer, variables = whole_layer()
    y = layer.apply(variables, x)
    y2, mutated = layer.apply(variables, x, mutable=["batch_stats"])
    np.testing.assert_allclose(y, y2, atol=0)
    moved = (mutated["batch_stats"]["e_score_correction_bias"]
             - variables["batch_stats"]["e_score_correction_bias"])
    np.testing.assert_allclose(np.abs(np.asarray(moved)), 1e-3, rtol=1e-3)
    assert float(mutated["batch_stats"]["steps"]) == 1.0
    with pytest.raises(ValueError, match="row tile"):
        share((0, 1), local_rows=12).init(jax.random.key(0), x)

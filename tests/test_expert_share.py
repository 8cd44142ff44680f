"""parallel/expert.py::ExpertShare and ops/pallas_grouped_matmul.py at a
small size on the CPU (kernels interpreted): the drop rule, the layout's
invariants, the grouped product against dense einsums, and the compiled
step's HLO. The share test of the model-configs guide is
tests/test_shares_add_up.py."""

import functools
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
from tpu_sandbox.parallel.expert import (  # noqa: E402
    ExpertShare, _collect, _spread, share_layout)

C, F, E, K, T = 32, 16, 16, 4, 96


def share(held, local_rows, shared=1, row_tile=8):
    return ExpertShare(d_model=C, d_ff=F, n_routed_experts=E, top_k=K,
                       held=tuple(held), local_rows=local_rows,
                       n_shared_experts=shared, routed_scaling_factor=2.0,
                       dtype=jnp.float32, row_tile=row_tile)


def applied(layer, variables, x, mutable=False):
    """``layer.apply`` as one compiled program, not one a primitive."""
    return jax.jit(lambda v, x: layer.apply(v, x, mutable=mutable))(
        variables, x)


@functools.cache
def whole_layer():
    """One layer holding all 16 experts, its variables, an input."""
    x = jax.random.normal(jax.random.key(0), (T, C))
    layer = share(range(E), local_rows=T * K)
    variables = jax.jit(layer.init)(jax.random.key(1), x)
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


def skewed(x, variables, expert, gain=50.0):
    """Variables whose router sends every token to ``expert`` first."""
    params = dict(variables["params"])
    bias = jnp.zeros((E,)).at[expert].set(gain)
    return {"params": params, "batch_stats": {
        **variables["batch_stats"], "e_score_correction_bias": bias}}


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("held", [(3, 5), (3, 5, 8, 9, 11)],
                         ids=["held<k", "k<held"])
@pytest.mark.parametrize("local_rows,dropped", [(T * 2, 0), (T, 0), (T - 24, 24)])
def test_one_expert_takes_every_row_and_only_the_total_drops(
        local_rows, dropped, held):
    """All T tokens choose expert 3; the share holds 3 and 5 (or three more).
    Per-expert imbalance drops nothing while the share's total is at most R;
    above R the tail (order: expert, then token) goes, as in the reference."""
    x, _, variables = whole_layer()
    v = cut(skewed(x, variables, 3), held)
    layer = share(held, local_rows=local_rows)
    y, mutated = applied(layer, v, x, ["batch_stats", "intermediates"])
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
    if total > local_rows:  # the tail: the last expert's rows go first
        assert not kept[sel == held[-1]].any() or kept[sel == 3].all()


def distinct_choices(seed, t=T, k=K, e=E):
    """What the router gives: the top-k of a token's scores, distinct ids."""
    return jax.lax.top_k(jax.random.normal(jax.random.key(seed), (t, e)), k)[1]


def kept_by_choice(lay, sel, held):
    """The table's ``kept [T, m]`` as ``[T, k]`` of the choices: a slot is
    a choice (m = k) or a held expert (m = len(held))."""
    kept = np.asarray(lay["kept"])
    if kept.shape[1] == sel.shape[1] <= len(held):
        return kept
    hit = np.asarray(sel)[:, :, None] == np.asarray(held)
    return (hit & kept[:, None, :]).any(-1)


HELD = {"held<k": (4, 12), "held=k": (1, 4, 7, 12),
        "k<held": (1, 4, 7, 9, 12, 13)}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("local_rows", [32, 64, 512])
@pytest.mark.parametrize("regime", list(HELD))
def test_layout_invariants(seed, local_rows, regime):
    held, tile = HELD[regime], 8
    sel = distinct_choices(seed)
    lay = jax.tree.map(np.asarray, jax.jit(share_layout, static_argnums=(
        1, 2, 3))(sel, held, local_rows, tile))
    p = local_rows + len(held) * tile
    group = lay["tile_group"]
    assert group.shape == (p // tile,) and (np.diff(group) >= 0).all()
    assert set(group) == set(range(len(held)))        # every expert owns a tile
    valid, kept = lay["row_valid"], lay["kept"]
    m = min(K, len(held))           # what a token can hold here, and no wider
    assert kept.shape == lay["dest"].shape == (T, m)
    assert valid.sum() == kept.sum() == lay["rows_held"] <= local_rows
    total = int(np.isin(np.asarray(sel), held).sum())
    assert lay["rows_held"] + lay["rows_dropped"] == total
    assert lay["rows_held"] == min(total, local_rows)
    # a valid row holds a slot of a token that chose the expert its tile
    # belongs to, the slot points back at the row, and no two share one
    rows = np.flatnonzero(valid)
    slot = lay["row_assignment"][rows]
    chosen = np.asarray(sel)[slot // m]
    assert (chosen == np.asarray(held)[group[rows // tile]][:, None]).any(1).all()
    assert (lay["dest"].reshape(-1)[slot] == rows).all()
    assert kept.reshape(-1)[slot].all() and len(set(slot)) == len(slot)
    # rows of one expert hold its tokens in order
    for g in range(len(held)):
        mine = rows[group[rows // tile] == g]
        assert (np.diff(lay["row_assignment"][mine] // m) > 0).all()
    np.testing.assert_array_equal(
        kept_by_choice(lay, sel, held),
        np.asarray(ref.kept_assignments(sel, held, local_rows)))


def dense_map(lay, t):
    """``M [T, P]``: 1 where buffer row r holds a slot of token t."""
    valid = np.asarray(lay["row_valid"])
    m = lay["dest"].shape[1]
    dense = np.zeros((t, valid.shape[0]), np.float32)
    rows = np.flatnonzero(valid)
    dense[np.asarray(lay["row_assignment"])[rows] // m, rows] = 1.0
    return dense


@pytest.mark.parametrize("regime", list(HELD))
def test_collect_and_spread_are_each_others_transpose(regime):
    """Against the dense map of a random valid layout, rows dropped and
    all: ``_collect`` is ``M @ buf`` through the table, ``_spread`` is
    ``M.T @ src`` through the rows' tokens, and each one's ``jax.vjp`` is
    the other."""
    held, tile, local_rows = HELD[regime], 8, 32
    lay = share_layout(distinct_choices(5), held, local_rows, tile)
    assert int(lay["rows_dropped"]) > 0
    m = lay["dest"].shape[1]
    tok_r = lay["row_assignment"] // m
    args = (lay["dest"], lay["kept"], tok_r, lay["row_valid"])
    dense = dense_map(lay, T)
    buf = jax.random.normal(jax.random.key(1), (dense.shape[1], C))
    src = jax.random.normal(jax.random.key(2), (T, C))
    collected, back = jax.vjp(lambda b: _collect(b, *args), buf)
    np.testing.assert_allclose(collected, dense @ np.asarray(buf), atol=1e-5)
    spread, forth = jax.vjp(
        lambda s: _spread(s, tok_r, lay["row_valid"], lay["dest"], lay["kept"]),
        src)
    np.testing.assert_array_equal(spread, dense.T @ np.asarray(src))
    np.testing.assert_array_equal(back(src)[0], spread)
    np.testing.assert_array_equal(forth(buf)[0], collected)


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


def lowered_step_text(held=(0, 1, 2, 3)):
    x, _, variables = whole_layer()
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


def gathers_and_sorts(text):
    """Of a compiled program's text: (dtype, rows, numbers a row) of every
    gather, and the keys every sort orders along its dimension."""
    gathers, sorts = [], []
    for line in text.splitlines():
        found = re.search(r"= (\w+)\[([\d,]+)\]\S* gather\(", line)
        if found:
            dims = [int(d) for d in found.group(2).split(",")]
            gathers.append((found.group(1), dims[0], int(np.prod(dims[1:]))))
        found = re.search(r"= \(?\w+\[([\d,]+)\].* sort\(.*dimensions=\{(\d)\}",
                          line)
        if found:
            sorts.append(int(found.group(1).split(",")[int(found.group(2))]))
    return gathers, sorts


@pytest.mark.parametrize("regime", list(HELD))
def test_compiled_share_is_sized_by_what_a_token_can_hold_here(regime):
    """Forward and backward: the widest gather fetches T min(k, h) rows (a
    collect) and the rest one a buffer row; the layout's one sort has as
    many keys; nothing has T k of either where fewer experts are held than
    a token chooses. And the weights travel as rows of a token's m slot
    weights: the only single numbers gathered are the layout's integers."""
    held = HELD[regime]
    m, p = min(K, len(held)), 64 + len(held) * 8
    gathers, sorts = gathers_and_sorts(lowered_step_text(held))
    assert {rows for _, rows, _ in gathers} == {T * m, p}
    assert sorted(sorts) == [E, T * m]              # the top-k, the layout
    widths = {(dtype, width) for dtype, _, width in gathers}
    assert widths == {("f32", C), ("f32", m), ("s32", 1)}
    assert all(rows == p for _, rows, width in gathers if width == 1)


@pytest.mark.parametrize("regime", list(HELD))
def test_a_traced_share_counts_its_table(regime):
    """``moe.share_table``: one count a traced ``routed`` call site, with
    the table's width and the buffer it serves."""
    from tpu_sandbox.obs import get_registry

    held = HELD[regime]
    x, _, variables = whole_layer()
    counter = get_registry().counter("moe.share_table", labels={
        "tokens": T, "top_k": K, "held": len(held),
        "width": min(K, len(held)), "buffer_rows": 64 + 8 * len(held), "c": C,
        "collect": "gather"})
    before = counter.value
    layer = share(held, local_rows=64)
    jax.jit(layer.apply)(cut(variables, held), x)
    assert counter.value == before + 1


def test_bias_and_counters_do_not_move_without_a_mutable_collection():
    x, layer, variables = whole_layer()
    y = applied(layer, variables, x)
    y2, mutated = applied(layer, variables, x, ["batch_stats"])
    np.testing.assert_allclose(y, y2, atol=0)
    moved = (mutated["batch_stats"]["e_score_correction_bias"]
             - variables["batch_stats"]["e_score_correction_bias"])
    np.testing.assert_allclose(np.abs(np.asarray(moved)), 1e-3, rtol=1e-3)
    assert float(mutated["batch_stats"]["steps"]) == 1.0
    with pytest.raises(ValueError, match="row tile"):
        share((0, 1), local_rows=12).init(jax.random.key(0), x)


# --- what PR 31 added: two-product experts, a router on another input, and
# --- a router that builds nothing of tokens x choices x experts elements

@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("held", [tuple(range(E)), (1, 4)],
                         ids=["k<held", "held<k"])
def test_relu2_experts_match_a_dense_loop(held):
    """``kind="relu2"``: two products and no gate, for the routed experts
    and for the shared expert alike, against a loop over the held experts."""
    x, _, _ = whole_layer()
    layer = ExpertShare(d_model=C, d_ff=F, n_routed_experts=E, top_k=K,
                        held=held, local_rows=T * K, n_shared_experts=2,
                        routed_scaling_factor=2.0, dtype=jnp.float32,
                        row_tile=8, kind="relu2")
    variables = jax.jit(layer.init)(jax.random.key(1), x)
    p = variables["params"]
    assert "w_gate" not in p and "shared_gate" not in p
    assert p["shared_up"]["kernel"].shape == (C, 2 * F)

    def dense_loop(p):
        _, sel, w = ref.route({"router": p["router"]}, x, REF_CFG)
        want = jnp.square(jax.nn.relu(x @ p["shared_up"]["kernel"])
                          ) @ p["shared_down"]["kernel"]
        for i, e in enumerate(held):
            weight = jnp.where(sel == e, w, 0.0).sum(-1)
            want = want + weight[:, None] * (
                jnp.square(jax.nn.relu(x @ p["w_up"][i])) @ p["w_down"][i])
        return want

    np.testing.assert_allclose(applied(layer, variables, x),
                               jax.jit(dense_loop)(p), atol=5e-5)
    with pytest.raises(ValueError, match="unknown expert kind"):
        ExpertShare(d_model=C, d_ff=F, n_routed_experts=E, top_k=K, held=held,
                    local_rows=T * K, kind="gelu").init(jax.random.key(0), x)


@pytest.mark.usefixtures("light_compile")
def test_the_router_may_score_another_input_than_the_experts_read():
    """``routed(x, route_on=u)``: experts in a latent of width C, scores from
    the full width 2 C, as a model that wraps the share calls it; against the
    benchmark's plain reference of such a layer."""
    from benchmark.reference import nemotron_h as latent_ref
    from tpu_sandbox.models.nemotron_h import LatentMoE

    u = jax.random.normal(jax.random.key(3), (T, 2 * C))
    held = (1, 4, 7, 12)
    layer = LatentMoE(d_model=C, d_ff=F, n_routed_experts=E, top_k=K,
                      held=held, local_rows=T * K, kind="relu2",
                      shared_width=3 * F, routed_scaling_factor=2.0,
                      dtype=jnp.float32, row_tile=8)
    variables = jax.jit(layer.init)(jax.random.key(1), u)
    p = variables["params"]
    assert p["router"].shape == (2 * C, E) and p["w_up"].shape == (4, C, F)
    assert p["shared_up"]["kernel"].shape == (2 * C, 3 * F)
    want, _ = latent_ref.latent_moe(
        dict(p), u, {**REF_CFG, "held": list(held), "local_rows": T * K})
    np.testing.assert_allclose(applied(layer, variables, u), want, atol=5e-5)


def _shapes_of(jaxpr):
    """Every array shape a jaxpr builds, its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield getattr(var.aval, "shape", ())
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _shapes_of(sub)


def test_nothing_of_tokens_x_choices_x_experts_elements_is_built():
    """At T 512, k 22, E 512 (Nemotron's router at a sixteenth of its
    tokens) no array of the step's jaxpr, forward or backward, has T k E
    elements: the chosen scores and the counts are sums of ``[T, E]``
    comparisons, a choice at a time (a one-hot of the choices was 369 MB a
    layer)."""
    t, k, e = 512, 22, 512
    layer = ExpertShare(d_model=C, d_ff=F, n_routed_experts=e, top_k=k,
                        held=tuple(range(8)), local_rows=512, dtype=jnp.float32,
                        kind="relu2")
    x = jax.ShapeDtypeStruct((t, C), jnp.float32)
    variables = jax.eval_shape(layer.init, jax.random.key(0), x)

    def step(variables, x):
        def loss(params, x):
            y, mutated = layer.apply({**variables, "params": params}, x,
                                     mutable=["batch_stats"])
            return jnp.square(y).sum(), mutated
        return jax.grad(loss, (0, 1), has_aux=True)(variables["params"], x)

    sizes = [int(np.prod(s)) for s in _shapes_of(
        jax.make_jaxpr(step)(variables, x).jaxpr)]
    assert sizes and max(sizes) < t * k * e
    assert max(sizes) >= t * e              # the scores themselves


def test_gathered_scores_and_counts_are_bitwise_what_the_one_hot_gave():
    """Xing4's router (top-4 of 64): the chosen scores, their gradient, the
    per-expert counts and the layout's local expert index, bit for bit what
    the one-hot formulation computed before (its expressions are here)."""
    from tpu_sandbox.parallel.expert import _chosen, _members, _pick

    t, k, e = 256, 4, 64
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (t, e)))
    _, sel = jax.lax.top_k(scores, k)
    g = jax.random.normal(jax.random.key(1), (t, k))

    def before(scores):
        chosen = jax.nn.one_hot(sel, e, dtype=jnp.float32)
        return (chosen * scores[:, None, :]).sum(-1)

    np.testing.assert_array_equal(_chosen(scores, sel), before(scores))
    np.testing.assert_array_equal(
        jax.grad(lambda s: (_chosen(s, sel) * g).sum())(scores),
        jax.grad(lambda s: (before(s) * g).sum())(scores))
    np.testing.assert_array_equal(
        _members(sel, e).sum(0),
        jax.nn.one_hot(sel, e, dtype=jnp.float32).sum((0, 1)))
    held = (3, 9, 17, 40)
    local_of = [len(held)] * e
    for i, expert in enumerate(held):
        local_of[expert] = i
    lay = share_layout(sel, held, 64, 8)
    loc = _pick(jnp.asarray(local_of, jnp.int32), sel.reshape(-1))
    np.testing.assert_array_equal(
        np.asarray(lay["kept"]).reshape(-1) <= (np.asarray(loc) < len(held)),
        True)
    assert int(lay["rows_held"] + lay["rows_dropped"]) == int(
        (loc < len(held)).sum())


def test_share_rows_is_the_buffer_both_configs_size():
    from tpu_sandbox.parallel.expert import share_rows

    assert share_rows(8192, 4, 8, 64, 2, 256) == 8192      # Xing4's cell
    assert share_rows(8192, 22, 8, 512, 2, 256) == 5632    # Nemotron's
    assert share_rows(32, 2, 4, 8, 2, 256) == 256          # rounded up to a tile

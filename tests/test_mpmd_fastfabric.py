"""Fast-fabric tier-1: ZB-H1 schedules, measured autotuning, the device
transport, and chunk-streamed npz staging.

The load-bearing claims, each pinned here at process-free scale (the
process-level twins live in tests/test_mpmd_integration.py):

- ZB-H1 op lists split the backward into B (grad-input) and W
  (grad-weight) without raising the activation-stash bound above 1F1B's,
  and training under them is BITWISE equal on params to the fused
  backward — schedules move work, never values.
- ``simulate_step`` reproduces the analytic 1F1B bubble on uniform costs
  and predicts ZB-H1 below it; ``autotune_plan`` picks from measured
  per-stage op costs.
- ``DeviceTransport`` keeps the produce-once/claim-once contract of the
  host wires (the journal is authoritative) while serving gets from the
  published device buffers; a bufferless rebuild falls back to journal
  bytes bitwise.
- ``stream_load_npz`` returns arrays bitwise equal to ``np.load``'s for
  every dtype/order/compression shape we ship.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM  # noqa: E402
from tpu_sandbox.mpmd.driver import MPMDPipeline  # noqa: E402
from tpu_sandbox.mpmd.program import check_layer_split  # noqa: E402
from tpu_sandbox.mpmd.schedule import (  # noqa: E402
    autotune_plan,
    bubble_fraction,
    max_in_flight,
    one_f_one_b,
    ops_for,
    simulate_step,
    zb_h1,
)
from tpu_sandbox.mpmd.transport import (  # noqa: E402
    DeviceTransport,
    LocalTransport,
    iter_chunks,
    pack_arrays,
    pack_views,
    unpack_arrays,
)
from tpu_sandbox.runtime.staging import stream_load_npz  # noqa: E402

from tests.test_obs import traced  # noqa: E402, F401 (fixture)

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=4,
                        d_ff=64, max_len=128)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_stages,microbatches", [(2, 4), (3, 4), (3, 8),
                                                   (4, 2), (4, 16)])
def test_zb_h1_op_list_is_complete_and_ordered(n_stages, microbatches):
    for s in range(n_stages):
        ops = zb_h1(s, n_stages, microbatches)
        by_op = {}
        for op, m in ops:
            by_op.setdefault(op, []).append(m)
        # every microbatch gets exactly one F, one B, one W
        for op in ("F", "B", "W"):
            assert sorted(by_op[op]) == list(range(microbatches)), (s, op)
        # per-microbatch order is F before B before W
        for m in range(microbatches):
            fi = ops.index(("F", m))
            bi = ops.index(("B", m))
            wi = ops.index(("W", m))
            assert fi < bi < wi, (s, m)


def _activation_stash_peak(ops):
    """Peak microbatches forwarded but not yet through B — the
    activation-stash bound proper (W holds only the (input, cotangent)
    pair, which is the bounded extra state the schedule docstring
    documents)."""
    live = peak = 0
    for op, _m in ops:
        if op == "F":
            live += 1
        elif op == "B":
            live -= 1
        peak = max(peak, live)
    return peak


@pytest.mark.parametrize("n_stages,microbatches", [(2, 4), (3, 4), (3, 8),
                                                   (4, 16)])
def test_zb_h1_stash_bounds_match_1f1b(n_stages, microbatches):
    """ZB-H1 is the memory-neutral variant: the activation stash (held
    F -> B) never exceeds 1F1B's, and the deferred (input, cotangent)
    pairs for W are bounded by the warmup reserve + the one in hand."""
    for s in range(n_stages):
        zb = zb_h1(s, n_stages, microbatches)
        fused = one_f_one_b(s, n_stages, microbatches)
        assert _activation_stash_peak(zb) == _activation_stash_peak(fused)
        warmup = min(microbatches, n_stages - 1 - s)
        assert (max_in_flight(zb) - max_in_flight(fused)) <= warmup + 1


def test_ops_for_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown schedule kind"):
        ops_for("gpipe", 0, 2, 4)


def test_simulate_step_reproduces_analytic_1f1b_bubble():
    """Uniform F=B costs, no wire: the simulated 1F1B bubble is the
    closed-form (S-1)/(M+S-1) the analytic gauge promises."""
    S, M = 3, 4
    ops = {s: one_f_one_b(s, S, M) for s in range(S)}
    costs = {s: {"F": 1.0, "B": 1.0} for s in range(S)}
    sim = simulate_step(ops, costs)
    assert sim["bubble_max"] == pytest.approx(bubble_fraction(S, M), abs=1e-9)


def test_simulate_step_zb_h1_beats_1f1b_on_split_costs():
    """With the backward split in half, ZB-H1's drain-phase W fill
    drops the simulated bubble below fused 1F1B's."""
    S, M = 3, 4
    fused = simulate_step({s: one_f_one_b(s, S, M) for s in range(S)},
                          {s: {"F": 1.0, "B": 1.0} for s in range(S)})
    split = simulate_step({s: zb_h1(s, S, M) for s in range(S)},
                          {s: {"F": 1.0, "B": 0.5, "W": 0.5}
                           for s in range(S)})
    assert split["step_seconds"] < fused["step_seconds"]
    assert split["bubble_mean"] < fused["bubble_mean"]


def test_simulate_step_detects_deadlock():
    # stage 0's B waits on stage 1's B, which never runs
    ops = {0: [("B", 0)], 1: [("F", 0)]}
    costs = {0: {"B": 1.0}, 1: {"F": 1.0}}
    with pytest.raises(RuntimeError, match="deadlock"):
        simulate_step(ops, costs)


def test_autotune_plan_prefers_zb_and_reports_frontier():
    S = 3
    measured = {s: {"F": 0.01, "B": 0.005, "W": 0.005, "A": 0.002}
                for s in range(S)}
    # at small M the drain dominates and ZB-H1 strictly wins; at large M
    # the steady phase saturates either way and the kinds tie (argmin
    # tie-breaks to the simpler 1f1b), so candidates stay small here
    plan = autotune_plan(measured, n_stages=S, measured_microbatches=4,
                         candidates=(2, 4))
    assert plan["kind"] == "zb_h1"
    # the whole frontier rides along: every (kind, M) candidate priced
    assert len(plan["candidates"]) == 2 * 2
    assert all({"kind", "microbatches", "predicted_step_s",
                "predicted_bubble"} <= set(r) for r in plan["candidates"])
    best = plan["predicted"]
    assert all(best["predicted_step_s"] <= r["predicted_step_s"]
               for r in plan["candidates"])


# ---------------------------------------------------------------------------
# uneven layer splits
# ---------------------------------------------------------------------------


def test_check_layer_split_validates():
    assert check_layer_split(8, 4, None) == [2, 2, 2, 2]
    assert check_layer_split(8, 3, [4, 3, 1]) == [4, 3, 1]
    with pytest.raises(ValueError, match="layer_split"):
        check_layer_split(8, 3, None)  # not divisible: must be explicit
    with pytest.raises(ValueError):
        check_layer_split(8, 3, [4, 4])  # wrong length
    with pytest.raises(ValueError):
        check_layer_split(8, 3, [4, 3, 2])  # wrong sum
    with pytest.raises(ValueError):
        check_layer_split(8, 3, [8, 0, 0])  # empty stage


# ---------------------------------------------------------------------------
# transport: chunk iteration + the device tier
# ---------------------------------------------------------------------------


def _sample_arrays():
    rng = np.random.default_rng(7)
    return [
        rng.standard_normal((13, 5)).astype(np.float32),
        np.arange(11, dtype=np.int32),
        rng.standard_normal(()).astype(np.float64),
        np.zeros((0, 4), np.float32),
    ]


def test_iter_chunks_matches_joined_payload():
    arrays = _sample_arrays()
    meta, views = pack_views(arrays)
    _meta2, payload = pack_arrays(arrays)
    for chunk_bytes in (1, 7, 64, 1 << 20):
        chunks = list(iter_chunks(views, chunk_bytes))
        assert all(len(c) <= chunk_bytes for c in chunks)
        assert b"".join(chunks) == payload
    back = unpack_arrays(meta, payload)
    for a, b in zip(arrays, back):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)


def test_device_transport_contract():
    tr = DeviceTransport()
    arrays = [jnp.arange(6, dtype=jnp.float32).reshape(2, 3)]
    assert tr.put("e", 0, 0, arrays) is True
    assert tr.put("e", 0, 0, arrays) is False  # produce-once via journal
    assert tr.poll("e", 0, 0)
    assert tr.claim("e", 0, 0, generation=0) is True
    assert tr.claim("e", 0, 0, generation=0) is False  # claim-once
    assert tr.claim("e", 0, 0, generation=1) is True   # new generation
    (got,) = tr.get("e", 0, 0, timeout=1.0)
    assert np.array_equal(np.asarray(got), np.asarray(arrays[0]))
    assert tr.stats.device_hits == 1
    assert tr.stats.journal_fallbacks == 0
    # the journal recorded the same slot durably
    assert tr.journal.poll("e", 0, 0)
    audit = tr.audit()
    assert audit["commits"]["e/0/0"] == 2  # both put attempts counted


def test_device_transport_journal_fallback_is_bitwise():
    """A transport rebuilt over a persisted journal (driver crash: the
    device buffers are gone) serves journal bytes — bitwise what the
    buffer held."""
    journal = LocalTransport()
    tr = DeviceTransport(journal)
    x = np.random.default_rng(3).standard_normal((4, 4)).astype(np.float32)
    tr.put("e", 1, 0, [x])
    rebuilt = DeviceTransport(journal)  # no buffers, same journal
    (got,) = rebuilt.get("e", 1, 0, timeout=1.0)
    assert got.tobytes() == x.tobytes()
    assert rebuilt.stats.journal_fallbacks == 1
    assert rebuilt.stats.device_hits == 0


def test_device_transport_release_step_clears_both_tiers():
    tr = DeviceTransport()
    tr.put("e", 0, 0, [np.zeros(3, np.float32)])
    tr.put("e", 1, 0, [np.ones(3, np.float32)])
    tr.release_step("e", 0)
    assert not tr.poll("e", 0, 0)
    assert not tr.journal.poll("e", 0, 0)
    assert tr.poll("e", 1, 0)  # later steps untouched


def test_device_transport_get_timeout():
    tr = DeviceTransport()
    with pytest.raises(TimeoutError):
        tr.get("never", 0, 0, timeout=0.05)


# ---------------------------------------------------------------------------
# streamed npz staging
# ---------------------------------------------------------------------------


def test_stream_load_npz_bitwise_vs_np_load(tmp_path):
    rng = np.random.default_rng(11)
    trees = {
        "f32": rng.standard_normal((17, 9)).astype(np.float32),
        "f64_scalar": rng.standard_normal(()),
        "i8": rng.integers(-100, 100, size=(33,), dtype=np.int8),
        "bools": rng.integers(0, 2, size=(5, 5)).astype(bool),
        "empty": np.zeros((0, 3), np.float32),
        "fortran": np.asfortranarray(
            rng.standard_normal((12, 7)).astype(np.float32)),
    }
    for name, saver in (("plain.npz", np.savez),
                        ("compressed.npz", np.savez_compressed)):
        path = tmp_path / name
        saver(path, **trees)
        streamed = stream_load_npz(path, chunk_bytes=64)  # force chunking
        with np.load(path) as z:
            assert sorted(streamed) == sorted(z.files)
            for k in z.files:
                ref = z[k]
                got = streamed[k]
                assert got.dtype == ref.dtype and got.shape == ref.shape
                assert got.tobytes() == ref.tobytes(), (name, k)


def test_stream_load_npz_only_filter(tmp_path):
    path = tmp_path / "s.npz"
    np.savez(path, a=np.arange(4), b=np.arange(8))
    out = stream_load_npz(path, only={"b"})
    assert sorted(out) == ["b"]
    assert np.array_equal(out["b"], np.arange(8))


def test_stream_load_npz_rejects_object_arrays(tmp_path):
    path = tmp_path / "obj.npz"
    np.savez(path, bad=np.array([{"a": 1}], dtype=object), allow_pickle=True)
    with pytest.raises(ValueError, match="object"):
        stream_load_npz(path)


# ---------------------------------------------------------------------------
# ZB-H1 end-to-end parity (in-process, 3 uneven stages, device transport)
# ---------------------------------------------------------------------------


def _train(kind, transport, layer_split, steps=3, microbatches=4):
    tx = optax.sgd(0.1)
    pipe = MPMDPipeline(CFG, tx, n_stages=3, microbatches=microbatches,
                        transport=transport, kind=kind,
                        layer_split=layer_split)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG.vocab_size, size=(8, 16)).astype(np.int32)
    targets = ((tokens + 7) % CFG.vocab_size).astype(np.int32)
    flat = jax.tree.map(
        np.asarray,
        TransformerLM(CFG).init(jax.random.key(0), tokens)["params"])
    pipe.init_from_flat(flat)
    losses = pipe.train(steps, tokens, targets)
    return pipe, losses


def test_zb_h1_grad_parity_vs_fused_backward():
    """The tentpole numerics claim: ZB-H1's per-layer split backward is
    the same math as the fused 1F1B backward — losses and params agree
    to float32 ulps (NOT bitwise — the per-layer vjps compile as
    separate XLA units whose reduction grouping differs from the fused
    scan transpose). Bitwise ZB determinism, which is what
    replay-after-fault leans on, is the slow twin test below."""
    split = [2, 1, 1]  # uneven on purpose: stage 0 is the heavy one
    fused_pipe, fused_losses = _train("1f1b", LocalTransport(), split)
    zb_pipe, zb_losses = _train("zb_h1", DeviceTransport(), split)
    assert zb_losses == pytest.approx(fused_losses, abs=1e-6)
    ref = fused_pipe.merged_params()
    got = zb_pipe.merged_params()
    ref_leaves = jax.tree.leaves(ref)
    got_leaves = jax.tree.leaves(got)
    assert len(ref_leaves) == len(got_leaves)
    for a, b in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6)
    # the device tier actually carried the traffic
    assert zb_pipe.transport.stats.device_hits > 0
    assert zb_pipe.transport.stats.journal_fallbacks == 0
    # and the measured costs feed a well-formed autotuned plan: stage 0
    # times no B (its grad-input is never shipped anywhere — all its
    # backward work is W), the last stage no F (fused into loss B)
    costs = zb_pipe.measured_op_costs()
    assert {"F", "W", "A"} <= set(costs[0]) and "B" not in costs[0]
    assert {"F", "B", "W", "A"} <= set(costs[1])
    assert {"B", "W", "A"} <= set(costs[2]) and "F" not in costs[2]
    plan = autotune_plan(costs, n_stages=3, measured_microbatches=4,
                         candidates=(2, 4, 8))
    assert plan["kind"] in ("1f1b", "zb_h1")
    assert plan["microbatches"] in (2, 4, 8)


def test_online_bubble_gauge_agrees_with_the_trace(traced):
    """A stage worker reports its bubble twice: as the per-step figure
    behind the ``mpmd.bubble_fraction`` gauge, and as ``stage:op`` /
    ``stage:step`` spans that ``critpath.bubble_fractions`` reduces
    offline. Both describe the same run, so they agree; steady state
    only (step 0 compiles), per-stage medians so that one descheduled
    thread between the two clock reads cannot decide it."""
    import statistics

    from tpu_sandbox.obs import collect, critpath, get_recorder

    pipe, _ = _train("1f1b", LocalTransport(), [2, 1, 1], steps=4)
    get_recorder().flush()
    offline: dict[int, list[float]] = {}
    for row in critpath.bubble_fractions(
            collect.load_merged(traced))["per_step"]:
        if row["step"] >= 1:
            offline.setdefault(row["stage"], []).append(row["bubble"])
    assert sorted(offline) == [0, 1, 2]
    for stage, worker in enumerate(pipe.workers):
        online = statistics.median(
            b for step, b in worker.bubble_by_step.items() if step >= 1)
        assert 0.0 <= online < 1.0
        assert statistics.median(offline[stage]) == pytest.approx(
            online, abs=0.05)


@pytest.mark.slow
def test_zb_h1_rerun_is_bitwise_deterministic():
    """Same split programs, same data, twice over -> bitwise-equal
    params. This is the guarantee replay-after-fault actually leans on
    (a respawned stage re-runs the SAME compiled B/W programs, only
    interleaved differently)."""
    split = [2, 1, 1]
    pipe_a, losses_a = _train("zb_h1", DeviceTransport(), split)
    pipe_b, losses_b = _train("zb_h1", DeviceTransport(), split)
    assert losses_a == losses_b
    for a, b in zip(jax.tree.leaves(pipe_a.merged_params()),
                    jax.tree.leaves(pipe_b.merged_params())):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

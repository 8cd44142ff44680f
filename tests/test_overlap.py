"""Overlapped step pipeline (parallel/buckets.py + data/loader.py
PrefetchLoader + tools/hlo_schedule.py).

The correctness bar:
  - bucket planning is a pure, total function of (sizes, target, dtypes);
  - the bucketed sync with overlap ON and grad_compress='none' is the
    monolithic engine to a few ulps — bucketing reorders collectives, never
    values (and with overlap off the code path is literally the old one);
  - int8 + per-bucket error feedback still converges like fp32 (the PR-3
    acceptance bound, now with bucket-local residual blocks);
  - the prefetch loader yields exactly the wrapped loader's stream, in
    order, under crash/resume — elastic parity must not depend on whether
    the input pipeline is threaded;
  - schedule_report() reads a canned scheduled-HLO fixture correctly
    (the real chipless v5e receipt is tools/hlo_schedule.py's job).
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tests.helpers import ulps_apart
from tests.test_grad_compress import _run_steps, setup
from tpu_sandbox.data import synthetic_mnist
from tpu_sandbox.data.loader import BatchLoader, PrefetchLoader
from tpu_sandbox.parallel import (
    DataParallel,
    PjitEngine,
    plan_buckets,
)

WORLD = 8

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


# -- bucket planning --------------------------------------------------------


def test_plan_buckets_grouping():
    # consecutive greedy fill to the target
    assert plan_buckets([100] * 5, 250) == [(0, 2), (2, 4), (4, 5)]
    # a single over-target leaf still gets its own bucket
    assert plan_buckets([100, 1000, 100], 250) == [(0, 1), (1, 2), (2, 3)]
    # one giant bucket when everything fits
    assert plan_buckets([1, 2, 3], 1 << 20) == [(0, 3)]
    # a dtype-key change forces a boundary even under the target
    assert plan_buckets([4, 4, 4, 4], 1 << 20,
                        keys=["f32", "f32", "i32", "i32"]) == [(0, 2), (2, 4)]
    assert plan_buckets([], 100) == []


def test_plan_buckets_covers_every_leaf_once():
    rng = np.random.default_rng(0)
    sizes = [int(s) for s in rng.integers(1, 5000, size=40)]
    spans = plan_buckets(sizes, 4096)
    flat = [i for a, b in spans for i in range(a, b)]
    assert flat == list(range(len(sizes)))


def test_plan_buckets_validation():
    with pytest.raises(ValueError, match="positive"):
        plan_buckets([1, 2], 0)
    with pytest.raises(ValueError, match="length"):
        plan_buckets([1, 2], 100, keys=["f32"])


# -- DataParallel wiring ----------------------------------------------------


class _DropsOneBucket:
    """The planted fault: the first bucket issued never meets its peers."""
    needs_residual = False

    def __init__(self):
        self.issued = 0

    def pmean(self, buf, axis_name, size, residual):
        self.issued += 1
        return (buf if self.issued == 1 else jax.lax.pmean(buf, axis_name),
                residual)


@pytest.mark.parametrize("fault", [None, "dropped_bucket"])
def test_overlap_none_agrees_to_rounding(mesh8, fault, monkeypatch):
    """Bucketed sync with 'none' compression is a plain pmean over each
    flat bucket — the same sums, so the training trajectory is the
    monolithic engine's to rounding. Not to the bit: they are two compiled
    programs, and XLA:CPU is free to order the gradients' sums differently
    in each. Measured over six seeds at this size: parameters 1 ulp of
    their leaf's largest entry apart after 3 steps, losses 1 ulp; held to
    4. A bucket that misses its all-reduce is thousands apart. bucket_mb is
    sized so the ~116KB ConvNet grad really splits into several buckets."""
    if fault:
        from tpu_sandbox.parallel import buckets
        monkeypatch.setattr(buckets, "as_compress_policy",
                            lambda _: _DropsOneBucket())
    model, tx, state, images, labels = setup(momentum=0.9)
    base = DataParallel(model, tx, mesh8, donate=False)
    over = DataParallel(model, tx, mesh8, donate=False,
                        overlap_grad_sync=True, bucket_mb=0.02)
    s_base, l_base = _run_steps(base, state, images, labels, 3)
    s_over, l_over = _run_steps(over, state, images, labels, 3)
    apart = max(ulps_apart(s_over.params, s_base.params),
                ulps_apart(np.float32(l_over), np.float32(l_base)))
    assert (apart > 1000) if fault else (apart <= 4), apart
    assert s_over.grad_residual is None


@pytest.mark.usefixtures("light_compile")
def test_overlap_int8_ef_convergence(mesh8):
    """PR-3's acceptance bound survives bucketing: int8 with PER-BUCKET
    error-feedback residuals lands on the fp32 final loss (5e-2 relative,
    1e-3 abs floor) over >= 50 momentum-SGD steps, and the residual still
    checkpoints leaf-shaped and per-rank."""
    model, tx, state, images, labels = setup(momentum=0.9)
    n_steps = 55
    _, l_fp32 = _run_steps(
        DataParallel(model, tx, mesh8, donate=False),
        state, images, labels, n_steps)
    s_ef, l_ef = _run_steps(
        DataParallel(model, tx, mesh8, donate=False, grad_compress="int8",
                     overlap_grad_sync=True, bucket_mb=0.02),
        state, images, labels, n_steps)
    assert abs(l_ef[-1] - l_fp32[-1]) <= max(5e-2 * l_fp32[-1], 1e-3)
    res_leaves = jax.tree.leaves(s_ef.grad_residual)
    params = jax.tree.leaves(s_ef.params)
    assert len(res_leaves) == len(params)
    # leaf-shaped (bucket concat/split is internal), per-rank expanded
    assert all(r.shape == (WORLD, *p.shape)
               for r, p in zip(res_leaves, params))
    assert any(float(jnp.abs(r).max()) > 0 for r in res_leaves)


def test_overlap_zero_composes(mesh8):
    """ZeRO-1 under the bucketed sync: full bucketed mean, then each rank
    slices its optimizer block — elementwise update math, so it matches
    plain bucketed DP to fp reassociation."""
    model, tx, state, images, labels = setup(momentum=0.9)
    s_plain, l_plain = _run_steps(
        DataParallel(model, tx, mesh8, donate=False,
                     overlap_grad_sync=True, bucket_mb=0.02),
        state, images, labels, 4)
    s_zero, l_zero = _run_steps(
        DataParallel(model, tx, mesh8, donate=False,
                     overlap_grad_sync=True, bucket_mb=0.02, zero=True),
        state, images, labels, 4)
    np.testing.assert_allclose(l_zero, l_plain, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        s_zero.params, s_plain.params)


def test_bucketed_step_lowers_to_one_collective_a_bucket(mesh8):
    """Where the program decides them — the lowered module, before any XLA
    pass — the step carries one all-reduce PER BUCKET, chained by the
    barriers of sync_buckets; ~116KB of ConvNet grads at a 0.02MB target is
    4 buckets. What a backend's combiner then makes of them is its own
    (XLA:CPU merges both forms into one all-reduce); on the TPU it is the
    receipt of tools/hlo_schedule.py."""
    from hlo_schedule import lower_overlapped_step, schedule_report

    def lowered_hlo(**step):
        lowered = lower_overlapped_step(
            np.array(jax.devices()[:WORLD]), **step)
        return lowered.compiler_ir(dialect="hlo").as_hlo_text()

    bucketed_hlo = lowered_hlo(bucket_mb=0.02)
    bucketed = schedule_report(bucketed_hlo)
    mono = schedule_report(lowered_hlo(overlap=False))
    assert bucketed["collective_count"] == 4
    assert bucketed_hlo.count(" opt-barrier(") == 3  # the chain between them
    # the monolithic path syncs per leaf (6 ConvNet grads)
    assert mono["collective_count"] == 6
    # same payload either way: bucketing splits bytes, never adds any
    assert bucketed["comm_bytes_total"] == mono["comm_bytes_total"]


def test_engine_validation(mesh8):
    model, tx, state, images, labels = setup()
    with pytest.raises(ValueError, match="bucket_mb"):
        DataParallel(model, tx, mesh8, donate=False, bucket_mb=0.0)
    with pytest.raises(ValueError, match="bucket_mb"):
        PjitEngine(model, tx, mesh8, donate=False, bucket_mb=-1)
    # overlap inherits the compressed path's pure-DP restriction
    with pytest.raises(ValueError, match="overlap_grad_sync"):
        PjitEngine(model, tx, mesh8, donate=False, overlap_grad_sync=True,
                   rules=[("fc/kernel", P(None, "model"))])


def test_pjit_engine_overlap_matches(mesh8):
    model, tx, state, images, labels = setup()
    ref = PjitEngine(model, tx, mesh8, donate=False)
    sstate = ref.shard_state(state)
    _, l_ref = ref.train_step(sstate, *ref.shard_batch(images, labels))
    eng = PjitEngine(model, tx, mesh8, donate=False,
                     overlap_grad_sync=True, bucket_mb=0.02)
    sstate = eng.shard_state(state)
    _, loss = eng.train_step(sstate, *eng.shard_batch(images, labels))
    assert float(loss) == float(l_ref)


# -- prefetch loader --------------------------------------------------------


def _loader_stream(loader, epochs):
    out = []
    for e in range(epochs):
        loader.set_epoch(e)
        out.extend((x.copy(), y.copy()) for x, y in loader)
    return out


def test_prefetch_stream_identical_to_wrapped_loader():
    images, labels = synthetic_mnist(n=30, seed=1)
    mk = lambda: BatchLoader(images, labels, 8, shuffle=True, seed=3)
    sync = _loader_stream(mk(), epochs=2)
    pre = _loader_stream(PrefetchLoader(mk()), epochs=2)
    assert len(pre) == len(sync)
    for (xa, ya), (xb, yb) in zip(pre, sync):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    assert len(PrefetchLoader(mk())) == len(mk())


def test_prefetch_stage_runs_in_producer():
    images, labels = synthetic_mnist(n=8, seed=0)
    seen_threads = []

    def stage(x, y):
        seen_threads.append(threading.current_thread().name)
        return x + 1.0, y

    pl = PrefetchLoader(BatchLoader(images, labels, 4), stage=stage)
    batches = list(pl)
    assert len(batches) == 2
    np.testing.assert_array_equal(batches[0][0], images[:4] + 1.0)
    assert set(seen_threads) == {"prefetch-loader"}


def test_prefetch_propagates_producer_error():
    class Exploding:
        def __iter__(self):
            yield (np.zeros(1), np.zeros(1))
            raise RuntimeError("disk on fire")

    it = iter(PrefetchLoader(Exploding()))
    next(it)
    with pytest.raises(RuntimeError, match="disk on fire"):
        next(it)


def test_prefetch_consumer_break_stops_producer():
    images, labels = synthetic_mnist(n=64, seed=0)
    pl = PrefetchLoader(BatchLoader(images, labels, 4), depth=2)
    for i, _ in enumerate(pl):
        if i == 1:
            break  # preemption raising out of the loop looks like this
    # the producer thread is joined by the generator's finally
    assert not [t for t in threading.enumerate()
                if t.name == "prefetch-loader" and t.is_alive()]
    with pytest.raises(ValueError, match="depth"):
        PrefetchLoader(BatchLoader(images, labels, 4), depth=0)


# -- prefetch x elastic resume ---------------------------------------------


class _Loader:
    def __init__(self, batches):
        self.batches = batches

    def set_epoch(self, epoch):
        pass

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        yield from self.batches


def _toy_batches(n_batches=8, bs=4, dim=3, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(dim,)).astype(np.float32)
    out = []
    for _ in range(n_batches):
        x = rng.normal(size=(bs, dim)).astype(np.float32)
        out.append((x, (x @ w_true).astype(np.float32)))
    return out


@pytest.mark.parametrize("preempt_step", [3, 11])
def test_prefetch_elastic_resume_parity(tmp_path, preempt_step):
    """Kill mid-epoch WITH the prefetcher active, resume WITH the
    prefetcher: final weights bitwise equal to the synchronous
    uninterrupted run, and the applied-batch order identical — the
    (epoch, offset) metadata means the same thing threaded or not."""
    from tpu_sandbox.train.checkpoint import HostCheckpoint
    from tpu_sandbox.train.trainer import (
        Preempted,
        PreemptionHandler,
        train_resumable,
    )

    batches = _toy_batches()
    ids = {id(x): i for i, (x, _) in enumerate(batches)}

    def make_step(seq):
        @jax.jit
        def sgd(state, x, y):
            loss, g = jax.value_and_grad(
                lambda w: jnp.mean((x @ w - y) ** 2))(state["w"])
            return {"w": state["w"] - 0.05 * g}, loss

        def step(state, x, y):
            seq.append(ids[id(x)])
            return sgd(state, x, y)

        return step

    fresh = lambda: {"w": jnp.zeros(3, jnp.float32)}
    ref_seq = []
    ref_state, _ = train_resumable(
        make_step(ref_seq), fresh(), _Loader(batches), 2, verbose=False)

    hc = HostCheckpoint(tmp_path)
    template = jax.tree.map(np.asarray, fresh())

    def save_fn(state, step, epoch, offset):
        hc.save(jax.tree.map(np.asarray, state), step,
                epoch=epoch, offset=offset)

    def restore_fn():
        res = hc.restore(template)
        if res is None:
            return None
        state, meta = res
        return jax.tree.map(jnp.asarray, state), meta

    class PreemptAt:
        def __init__(self, handler, step):
            self.handler, self.step = handler, step

        def maybe_fire(self, step):
            if step == self.step:
                self.handler.preempt_now()

    seq = []
    handler = PreemptionHandler()
    with pytest.raises(Preempted) as exc:
        train_resumable(
            make_step(seq), fresh(), _Loader(batches), 2,
            save_fn=save_fn, restore_fn=restore_fn, ckpt_every=2,
            preemption=handler, injector=PreemptAt(handler, preempt_step),
            prefetch=True, verbose=False)
    assert exc.value.step == preempt_step
    assert len(seq) == preempt_step  # nothing stepped past the boundary
    assert not [t for t in threading.enumerate()
                if t.name == "prefetch-loader" and t.is_alive()]

    state, report = train_resumable(
        make_step(seq), fresh(), _Loader(batches), 2,
        save_fn=save_fn, restore_fn=restore_fn, ckpt_every=2,
        preemption=PreemptionHandler(), prefetch=True, verbose=False)
    assert report.resumed_step == preempt_step
    np.testing.assert_array_equal(
        np.asarray(state["w"]), np.asarray(ref_state["w"]))
    assert seq == ref_seq  # no batch replayed, none skipped, same order


# -- schedule report fixture ------------------------------------------------

# Hand-written scheduled module covering both collective spellings: one
# async -start/-done pair bridging a backward dot, one sync all-reduce
# scheduled before the last backward dot (an interleaved issue point), one
# after it (exposed). Shapes sized to make the byte math obvious.
_CANNED_HLO = """\
HloModule canned, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %a = f32[] add(f32[] %x, f32[] %y)
}

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %dot.fwd = f32[256]{0} dot(f32[256]{0} %p0, f32[256]{0} %p0), metadata={op_name="jit(step)/fwd/dot_general"}
  %ar-start.1 = f32[256]{0} all-reduce-start(f32[256]{0} %dot.fwd), replica_groups={{0,1}}, to_apply=%add
  %dot.bwd1 = f32[256]{0} dot(f32[256]{0} %p0, f32[256]{0} %dot.fwd), metadata={op_name="jit(step)/transpose(jvp(fwd))/dot_general"}
  %ar-done.1 = f32[256]{0} all-reduce-done(f32[256]{0} %ar-start.1)
  %sync.early = f32[256]{0} all-reduce(f32[256]{0} %dot.bwd1), replica_groups={{0,1}}, to_apply=%add
  %dot.bwd2 = f32[128]{0} dot(f32[128]{0} %p0, f32[128]{0} %p0), metadata={op_name="jit(step)/transpose(fwd)/dot_general"}
  %sync.late = f32[128]{0} all-reduce(f32[128]{0} %dot.bwd2), replica_groups={{0,1}}, to_apply=%add
  ROOT %out = f32[256]{0} add(f32[256]{0} %ar-done.1, f32[256]{0} %sync.early)
}
"""


def test_schedule_report_on_canned_hlo():
    from hlo_schedule import schedule_report

    rep = schedule_report(_CANNED_HLO)
    assert rep["collective_count"] == 3
    assert rep["async_pairs"] == 1
    assert rep["sync_collectives"] == 2
    # async pair bridges dot.bwd1; sync.early precedes the last backward
    # dot; sync.late is scheduled after it -> exposed
    assert rep["overlapped_collectives"] == 2
    assert rep["last_bwd_compute_op"] == "dot.bwd2"
    assert rep["all_reduce_issues_before_last_bwd_compute"] == 2
    assert rep["comm_bytes_total"] == 1024 + 1024 + 512
    assert rep["comm_bytes_exposed"] == 512
    assert rep["exposed_comm_fraction"] == pytest.approx(512 / 2560)
    by_op = {c["op"]: c for c in rep["collectives"]}
    assert by_op["ar-start.1"]["form"] == "async"
    assert by_op["ar-start.1"]["compute_ops_between"] == 1
    assert by_op["sync.early"]["overlapped"] is True
    assert by_op["sync.late"]["overlapped"] is False


def test_schedule_report_monolithic_shape():
    """A single all-reduce after the last backward op — the monolithic
    baseline — must read as fully exposed with zero early issues."""
    from hlo_schedule import schedule_report

    text = _CANNED_HLO.splitlines()
    mono = "\n".join(
        l for l in text
        if "ar-start" not in l and "ar-done" not in l and "sync.early" not in l
    ).replace("f32[256]{0} %ar-done.1", "f32[256]{0} %dot.bwd1")
    rep = schedule_report(mono)
    assert rep["collective_count"] == 1
    assert rep["overlapped_collectives"] == 0
    assert rep["exposed_comm_fraction"] == 1.0
    assert rep["all_reduce_issues_before_last_bwd_compute"] == 0

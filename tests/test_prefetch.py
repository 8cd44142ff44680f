"""The double-buffered batch prefetch (data/loader.py PrefetchLoader).

The correctness bar: the prefetch loader yields exactly the wrapped
loader's stream, in order, under crash/resume — elastic parity must not
depend on whether the input pipeline is threaded.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.data import synthetic_mnist
from tpu_sandbox.data.loader import BatchLoader, PrefetchLoader


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

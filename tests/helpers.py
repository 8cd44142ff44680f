"""What more than one test module uses and no fixture can carry."""

import contextlib
import re
import subprocess
import threading
import time

import numpy as np
import pytest


def run_child(argv, *, timeout, **popen):
    """``subprocess.run`` with the child's output captured as text. A child
    that outlives its deadline is killed, and the test fails with what the
    child had printed by then."""
    try:
        return subprocess.run(argv, capture_output=True, text=True,
                              timeout=timeout, **popen)
    except subprocess.TimeoutExpired as late:
        said = "\n".join(
            part.decode(errors="replace") if isinstance(part, bytes) else part
            for part in (late.stdout, late.stderr) if part)
        pytest.fail(f"{argv} outlived its {timeout} s and was killed:\n{said}",
                    pytrace=False)


def ulps_apart(got, want):
    """The largest difference between two float pytrees, each leaf's in
    units in the last place (of ``want``'s type: float32, or bfloat16's 65536
    times coarser one) of that leaf's largest magnitude: what two
    compiled programs that sum the same terms in another order differ by."""
    import jax
    import jax.numpy as jnp

    def leaf(a, b):
        coarser = 2.0 ** (23 - jnp.finfo(np.asarray(b).dtype).nmant)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max()
                     / (np.spacing(np.abs(b).max()) * coarser))

    return max(jax.tree.leaves(jax.tree.map(leaf, got, want)))


def counters(prefix: str, since: dict | None = None) -> dict:
    """The registry's counters whose series key starts with ``prefix`` (a
    name, with its labels behind it): what each reads, or, given an earlier
    reading ``since``, what each has gained (those that gained nothing left
    out)."""
    from tpu_sandbox.obs import get_registry

    now = {key: n for key, n in get_registry().snapshot()["counters"].items()
           if key.startswith(prefix)}
    if since is None:
        return now
    return {key: n - since.get(key, 0) for key, n in now.items()
            if n != since.get(key, 0)}


def label(series_key: str, name: str) -> str:
    """The value of label ``name`` in a registry series key."""
    return re.search(rf"{name}=([^,}}]+)", series_key).group(1)


def assert_same_model(a, b):
    """Two checkpoints' leaves, by name, to 1e-6."""
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=0, atol=1e-6, err_msg=k)


class StubStep:
    """DecodeStep stand-in: next token = (last token + 1) % vocab, no jax.
    Deterministic like the real step, so requeue-replay still reproduces."""

    def __init__(self, buckets=(8, 16), vocab=64):
        self.buckets = tuple(buckets)
        self.vocab = vocab
        self.prefill = {b: self._prefill for b in self.buckets}

    def pick_bucket(self, plen):
        for b in self.buckets:
            if plen <= b:
                return b
        raise ValueError(f"prompt of {plen} exceeds buckets {self.buckets}")

    def _prefill(self, params, k, v, toks, dest, last):
        toks = np.asarray(toks)
        logits = np.zeros((self.vocab,), np.float32)
        logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
        return logits, k, v

    def decode(self, params, k, v, tokens, lengths, tables):
        tokens = np.asarray(tokens)
        logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
        for i in range(tokens.shape[0]):
            logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
        return logits, k, v


@pytest.fixture
def kv_pair():
    """A KV server, a client of it, and a maker of that client's clones;
    everything closed when the test ends."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    server = KVServer()
    kv = KVClient(port=server.port)
    clones = []

    def clone():
        c = kv.clone()
        clones.append(c)
        return c

    yield server, kv, clone
    for c in clones:
        c.close()
    kv.close()
    server.stop()


@contextlib.contextmanager
def pumping(*workers):
    """Tick workers from one background thread (each worker was built on
    its own KV clone, so the main thread's client stays unshared)."""
    stop = threading.Event()

    def run():
        while not stop.is_set():
            for w in workers:
                w.tick()
            time.sleep(0.001)

    t = threading.Thread(target=run, name="pump", daemon=True)
    t.start()
    try:
        yield stop
    finally:
        stop.set()
        t.join(timeout=10.0)

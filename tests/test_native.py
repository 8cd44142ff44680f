"""Native (C++) runtime tests: data loader parity with the Python loader,
prefetch correctness under threading, and the KV store's rendezvous
primitives (set/get/add/barrier) across threads and processes."""

import threading

import numpy as np
import pytest

pytest.importorskip("ctypes")

from tpu_sandbox.data import BatchLoader, DistributedSampler, synthetic_mnist
from tpu_sandbox.data.mnist import normalize

try:
    from tpu_sandbox.native.build import build_library

    build_library("dataloader")
    build_library("kvstore")
    HAVE_NATIVE = True
except Exception as e:  # no g++ in env
    HAVE_NATIVE = False
    NATIVE_ERR = e

needs_native = pytest.mark.skipif(not HAVE_NATIVE, reason="native build unavailable")


@needs_native
def test_library_is_named_by_the_hash_of_its_source(tmp_path, monkeypatch):
    """lib/ travels with any copy of the tree as it stands on disk, so a
    binary may only be picked up if it was built from exactly the source
    beside it: the name carries the source's hash, and a source edit means
    a different file — a stale build is never loaded, whatever its mtime."""
    import hashlib
    import shutil

    from tpu_sandbox.native import build

    src = build._SRC / "dataloader.cpp"
    out = build.build_library("dataloader")
    sha = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert out.name == f"dataloader-{sha}.so"

    # same tree with an edited source: the old binary is not what we get
    monkeypatch.setattr(build, "_SRC", tmp_path / "src")
    monkeypatch.setattr(build, "_LIB", tmp_path / "lib")
    (tmp_path / "src").mkdir()
    (tmp_path / "lib").mkdir()
    shutil.copy(out, tmp_path / "lib" / out.name)
    (tmp_path / "src" / "dataloader.cpp").write_text(
        src.read_text() + "\n// edited\n")
    rebuilt = build.build_library("dataloader")
    assert rebuilt.name != out.name and rebuilt.exists()


@needs_native
def test_native_loader_matches_python_loader():
    from tpu_sandbox.data.native_loader import NativeBatchLoader

    images, labels = synthetic_mnist(n=53, seed=0)
    py = BatchLoader(normalize(images), labels.astype("int32"), 8, shuffle=True, seed=3)
    nat = NativeBatchLoader(images, labels, 8, shuffle=True, seed=3, threads=3)
    py_batches, nat_batches = list(py), list(nat)
    assert len(py_batches) == len(nat_batches) == 7
    for (pi, pl), (ni, nl) in zip(py_batches, nat_batches):
        np.testing.assert_array_equal(pl, nl)
        np.testing.assert_allclose(pi, ni, atol=1e-7)
    assert nat_batches[-1][0].shape[0] == 53 % 8  # partial tail kept


@needs_native
def test_native_loader_epochs_reshuffle():
    from tpu_sandbox.data.native_loader import NativeBatchLoader

    images, labels = synthetic_mnist(n=64, seed=0)
    nat = NativeBatchLoader(images, labels, 16, shuffle=True, threads=2)
    first = np.concatenate([l for _, l in nat])
    again = np.concatenate([l for _, l in nat])
    np.testing.assert_array_equal(first, again)  # same epoch -> same order
    nat.set_epoch(1)
    third = np.concatenate([l for _, l in nat])
    assert not np.array_equal(first, third)


@needs_native
def test_native_loader_with_distributed_sampler():
    from tpu_sandbox.data.native_loader import NativeBatchLoader

    images, labels = synthetic_mnist(n=40, seed=0)
    loaders = [
        NativeBatchLoader(
            images, labels, 5,
            sampler=DistributedSampler(40, num_replicas=2, rank=r), threads=2,
        )
        for r in range(2)
    ]
    seen = [np.concatenate([l for _, l in ld]) for ld in loaders]
    assert len(seen[0]) == len(seen[1]) == 20


@needs_native
def test_native_loader_rejects_bad_input():
    from tpu_sandbox.data.native_loader import NativeBatchLoader

    images, labels = synthetic_mnist(n=8, seed=0)
    with pytest.raises(TypeError, match="uint8"):
        NativeBatchLoader(normalize(images), labels, 4)


@needs_native
def test_kvstore_set_get_add():
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    with KVServer() as srv:
        with KVClient(port=srv.port) as c:
            c.set("alpha", b"hello")
            assert c.get("alpha") == b"hello"
            assert c.add("counter", 5) == 5
            assert c.add("counter", 2) == 7
            c.set("alpha", "world")
            assert c.get("alpha") == b"world"
            c.delete("alpha")
            c.set("alpha", b"back")  # delete then set works
            assert c.get("alpha") == b"back"


@needs_native
def test_kvstore_blocking_get():
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    with KVServer() as srv:
        results = {}

        def waiter():
            with KVClient(port=srv.port) as c:
                results["value"] = c.get("later")

        t = threading.Thread(target=waiter)
        t.start()
        import time

        time.sleep(0.2)
        assert "value" not in results  # still blocked
        with KVClient(port=srv.port) as c:
            c.set("later", b"released")
        t.join(timeout=5)
        assert results["value"] == b"released"


@needs_native
def test_kvstore_barrier_across_threads():
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    with KVServer() as srv:
        n = 4
        passed = []
        lock = threading.Lock()

        def rank(i):
            with KVClient(port=srv.port) as c:
                c.barrier(n, key="b0")
                with lock:
                    passed.append(i)

        threads = [threading.Thread(target=rank, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert sorted(passed) == list(range(n))


@needs_native
def test_kvstore_multiprocess_rendezvous():
    """The reference smoke test's shape (test_init.py:112-117): N processes
    rendezvous through the store and all exit 0."""
    import multiprocessing as mp

    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    def worker(port, rank, world, q):
        try:
            with KVClient(port=port) as c:
                c.set(f"rank/{rank}", str(rank))
                c.barrier(world, key="join")
                got = sorted(int(c.get(f"rank/{r}")) for r in range(world))
                q.put((rank, got))
        except Exception as e:  # pragma: no cover
            q.put((rank, repr(e)))

    ctx = mp.get_context("fork")
    with KVServer() as srv:
        q = ctx.Queue()
        procs = [
            ctx.Process(target=worker, args=(srv.port, r, 3, q)) for r in range(3)
        ]
        for p in procs:
            p.start()
        results = [q.get(timeout=15) for _ in range(3)]
        for p in procs:
            p.join(timeout=5)
            if p.is_alive():
                p.kill()
                pytest.fail(f"rank {p.name} outlived its rendezvous")
    assert all(got == [0, 1, 2] for _, got in results), results


@needs_native
def test_kvstore_token_auth(monkeypatch):
    """Shared-secret hello frame: a tokened server serves only connections
    that present the matching token first; a tokenless server ignores the
    whole mechanism (including a client that sends a hello anyway)."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    monkeypatch.delenv("TPU_SANDBOX_KV_TOKEN", raising=False)
    with KVServer(token="s3cret") as srv:
        with KVClient(port=srv.port, token="s3cret") as c:
            c.set("k", b"v")
            assert c.get("k") == b"v"
            with c.clone() as c2:  # clone re-authenticates
                assert c2.get("k") == b"v"
        with pytest.raises(ConnectionError, match="token"):
            KVClient(port=srv.port, token="wrong")
        # no token at all: the TCP connect succeeds but the first store op
        # is rejected before touching the map
        c3 = KVClient(port=srv.port)
        try:
            with pytest.raises(RuntimeError):
                c3.get("k")
        finally:
            c3.close()
    with KVServer() as srv:  # tokenless server: hello is a harmless no-op
        with KVClient(port=srv.port, token="ignored") as c:
            c.set("k", b"v")
            assert c.get("k") == b"v"


@needs_native
def test_kvstore_env_token_and_bind_all(monkeypatch):
    """TPU_SANDBOX_KV_TOKEN is the default token for BOTH ends (respawned
    workers inherit auth through the environment), and bind="0.0.0.0"
    accepts non-loopback-addressed connections."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    monkeypatch.setenv("TPU_SANDBOX_KV_TOKEN", "env-tok")
    with KVServer(bind="0.0.0.0") as srv:
        assert srv.token == "env-tok"
        with KVClient(port=srv.port) as c:  # token from env, no kwarg
            assert c.token == "env-tok"
            assert c.add("n", 1) == 1
        monkeypatch.delenv("TPU_SANDBOX_KV_TOKEN")
        with pytest.raises(ConnectionError, match="token"):
            KVClient(port=srv.port, token="not-it")

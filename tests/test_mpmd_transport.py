"""Stage-transport contract tests (tier-1, stub + KV wire, no jax).

Both implementations must honor the same delivery discipline — the
driver and worker are written against the interface, so every property
here is parametrized over LocalTransport and KVTransport: produce-once
(idempotent replay puts), claim-once per generation, blocking get with
timeout, release_step GC, and the byte-exact pack/unpack framing the
bitwise replay parity rests on.
"""

import numpy as np
import pytest

from tests.helpers import kv_pair  # noqa: F401 (fixture)
from tpu_sandbox.mpmd.schedule import (
    bubble_fraction,
    fetch_plan,
    max_in_flight,
    one_f_one_b,
    publish_plan,
)
from tpu_sandbox.mpmd.transport import (
    EdgeNames,
    KVTransport,
    LocalTransport,
    pack_arrays,
    unpack_arrays,
)


@pytest.fixture(params=["local", "kv"])
def transport(request, kv_pair):
    if request.param == "local":
        return LocalTransport()
    _, kv, _ = kv_pair
    return KVTransport(kv, prefix="mpmd/pipe0")


def _arrays(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(3, 5)).astype(np.float32),
            rng.integers(0, 100, size=(7,)).astype(np.int32)]


# -- framing ------------------------------------------------------------------


def test_pack_unpack_roundtrip_bitwise():
    arrays = _arrays()
    meta, payload = pack_arrays(arrays)
    out = unpack_arrays(meta, payload)
    assert len(out) == len(arrays)
    for a, b in zip(arrays, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def test_unpack_rejects_corrupt_payload():
    meta, payload = pack_arrays(_arrays())
    with pytest.raises(ValueError, match="meta describes"):
        unpack_arrays(meta, payload + b"\x00")  # trailing garbage
    with pytest.raises(ValueError):
        unpack_arrays(meta, payload[:-1])  # truncated mid-array


# -- delivery discipline (both wires) -----------------------------------------


def test_put_get_roundtrip_and_stats(transport):
    arrays = _arrays()
    assert transport.put("act0", 3, 1, arrays) is True
    got = transport.get("act0", 3, 1, timeout=5.0)
    for a, b in zip(arrays, got):
        assert a.tobytes() == b.tobytes() and a.dtype == b.dtype
    s = transport.stats
    assert s.puts == 1 and s.gets == 1
    assert s.bytes_out == s.bytes_in > 0


def test_put_is_produce_once(transport):
    arrays = _arrays()
    assert transport.put("act0", 0, 0, arrays) is True
    # a replaying producer re-puts the same slot and loses the claim
    assert transport.put("act0", 0, 0, arrays) is False
    got = transport.get("act0", 0, 0, timeout=5.0)
    assert got[0].tobytes() == arrays[0].tobytes()
    assert transport.audit()["commits"].popitem()[1] == 2


def test_get_times_out_on_absent_slot(transport):
    with pytest.raises(TimeoutError):
        transport.get("act0", 9, 9, timeout=0.05)


def test_poll_and_release_step(transport):
    transport.put("act0", 4, 0, _arrays())
    transport.put("act0", 4, 1, _arrays(1))
    transport.put("act0", 5, 0, _arrays(2))
    transport.put("grad0", 4, 0, _arrays(3))
    assert transport.poll("act0", 4, 0) and transport.poll("act0", 4, 1)
    transport.release_step("act0", 4)
    assert not transport.poll("act0", 4, 0)
    assert not transport.poll("act0", 4, 1)
    # other steps and other edges survive the GC
    assert transport.poll("act0", 5, 0)
    assert transport.poll("grad0", 4, 0)


def test_claim_once_per_generation(transport):
    # within one generation a slot feeds exactly one consumer op
    assert transport.claim("act0", 2, 0, generation=0) is True
    assert transport.claim("act0", 2, 0, generation=0) is False
    # a relaunched generation legitimately re-claims for replay
    assert transport.claim("act0", 2, 0, generation=1) is True
    assert transport.claim("act0", 2, 1, generation=0) is True
    claims = transport.audit()["claims"]
    dup = {k: v for k, v in claims.items() if v != 1}
    assert list(dup.values()) == [2]  # exactly the double-claim we made


# -- KV wire specifics --------------------------------------------------------


def test_kv_transport_chunks_large_payload(kv_pair):
    _, kv, _ = kv_pair
    tr = KVTransport(kv, prefix="mpmd/pipe0", chunk_bytes=1024)
    big = np.arange(5000, dtype=np.float64)  # 40000 bytes -> 40 chunks
    assert tr.put("act0", 0, 0, [big]) is True
    (got,) = tr.get("act0", 0, 0, timeout=5.0)
    assert got.tobytes() == big.tobytes()
    import json
    meta = json.loads(kv.get("mpmd/pipe0/mpmd/slot/act0/0/0/meta"))
    assert meta["nchunks"] == 40 and meta["bytes"] == 40000


def test_kv_transport_finishes_dead_writers_slot(kv_pair):
    """Commit claimed, meta never landed (writer died mid-put): the
    replayer loses the claim but completes the slot with its own
    deterministic bytes."""
    _, kv, _ = kv_pair
    tr = KVTransport(kv, prefix="mpmd/pipe0")
    kv.add("mpmd/pipe0/mpmd/slot/act0/0/0/commit", 1)  # the dead writer
    arrays = _arrays()
    assert tr.put("act0", 0, 0, arrays) is False  # lost claim, finished slot
    (a, b) = tr.get("act0", 0, 0, timeout=5.0)
    assert a.tobytes() == arrays[0].tobytes()


def test_kv_transport_prefix_isolation(kv_pair):
    _, kv, _ = kv_pair
    t0 = KVTransport(kv, prefix="mpmd/pipe0")
    t1 = KVTransport(kv, prefix="mpmd/pipe1")
    t0.put("act0", 0, 0, _arrays())
    assert not t1.poll("act0", 0, 0)
    assert t1.claim("act0", 0, 0, generation=0) is True  # own claim plane


def test_kv_transport_rejects_oversized_chunks(kv_pair):
    _, kv, _ = kv_pair
    with pytest.raises(ValueError, match="read cap"):
        KVTransport(kv, chunk_bytes=1 << 20)


# -- schedule properties ------------------------------------------------------


def test_one_f_one_b_op_counts_and_order():
    S, M = 4, 8
    for s in range(S):
        ops = one_f_one_b(s, S, M)
        fs = [m for op, m in ops if op == "F"]
        bs = [m for op, m in ops if op == "B"]
        # every microbatch forwarded and backwarded exactly once, in order
        assert fs == list(range(M)) and bs == list(range(M))
        # a microbatch's B never precedes its F
        seen_f = set()
        for op, m in ops:
            if op == "F":
                seen_f.add(m)
            else:
                assert m in seen_f


def test_one_f_one_b_stash_bound():
    S = 4
    for M in (1, 2, 4, 16):
        for s in range(S):
            # the 1F1B point: in-flight bounded by S - stage, however
            # large M grows (GPipe would stash M)
            assert max_in_flight(one_f_one_b(s, S, M)) == min(M, S - s)


def test_one_f_one_b_validates_args():
    with pytest.raises(ValueError):
        one_f_one_b(4, 4, 2)
    with pytest.raises(ValueError):
        one_f_one_b(0, 2, 0)


def test_bubble_fraction_formula():
    assert bubble_fraction(1, 4) == 0.0
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    # more microbatches amortize the bubble
    assert bubble_fraction(4, 32) < bubble_fraction(4, 4)


def test_publish_fetch_plan_roundtrip(kv_pair):
    _, kv, _ = kv_pair
    publish_plan(kv, n_stages=2, microbatches=4, steps=10, seed=7,
                 prefix="mpmd/pipe0", extra={"model": {"d_model": 32}})
    plan = fetch_plan(kv, prefix="mpmd/pipe0")
    assert plan["steps"] == 10 and plan["seed"] == 7
    assert plan["model"] == {"d_model": 32}
    assert plan["ops"][0] == one_f_one_b(0, 2, 4)
    assert plan["ops"][1] == one_f_one_b(1, 2, 4)
    with pytest.raises(TimeoutError):
        fetch_plan(kv, prefix="mpmd/other", timeout=0.05)


def test_edge_names():
    e = EdgeNames(2)
    assert e.act == "act2" and e.grad == "grad2"

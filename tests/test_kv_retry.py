"""KV client edge cases around the elastic restart path: the server coming
up LATE (every relaunched worker races rank 0's listen()), the server dying
mid-conversation (rank 0 crashed while peers still hold connections), and
the TTL/prefix hygiene ops the sharded-checkpoint commit leans on."""

import threading
import time

import pytest

from tpu_sandbox.runtime.bootstrap import find_free_port
from tpu_sandbox.runtime.kvstore import KVClient, KVServer, _backoff_delays


# -- the backoff schedule itself -------------------------------------------


def test_backoff_delays_grow_exponentially_with_jitter():
    # list() never sleeps, so the generator busy-yields for the whole
    # wall-clock window — keep it short
    delays = list(_backoff_delays(0.2, base=0.02, cap=10.0))
    assert delays, "deadline should allow at least one retry"
    # every delay is its exponential envelope scaled by a factor in
    # [0.5, 1.5): never zero (no busy-spin), never a lockstep constant
    for i, d in enumerate(delays[:5]):
        envelope = 0.02 * (2 ** i)
        assert 0.5 * envelope <= d < 1.5 * envelope or d <= envelope, (
            i, d, envelope)
    assert all(d > 0 for d in delays)
    # jitter: a second schedule should not replay the first exactly
    again = list(_backoff_delays(0.2, base=0.02, cap=10.0))
    assert delays[:3] != again[:3]


def test_backoff_delays_respect_cap_and_deadline():
    t0 = time.monotonic()
    total = 0.0
    for d in _backoff_delays(0.4, base=0.05, cap=0.1):
        assert d <= 0.1 * 1.5 + 1e-9  # capped envelope x max jitter factor
        assert d <= 0.4 + 1e-9  # no single sleep overshoots the deadline
        total += d
        time.sleep(d)
    # the generator exhausts AT the deadline: the loop above slept through
    # ~the whole window and not multiples of it
    elapsed = time.monotonic() - t0
    assert 0.3 <= elapsed < 2.0, elapsed


def test_backoff_delays_zero_timeout_gives_up_immediately():
    assert list(_backoff_delays(0.0)) == []
    assert list(_backoff_delays(-1.0)) == []


def test_connect_retries_until_server_appears():
    port = int(find_free_port())
    started = {}

    def late_start():
        time.sleep(0.4)  # client spins on ECONNREFUSED meanwhile
        started["server"] = KVServer(port=port)

    t = threading.Thread(target=late_start)
    t.start()
    try:
        kv = KVClient(port=port, connect_timeout=10.0)
        kv.set("hello", b"world")
        assert kv.try_get("hello") == b"world"
        kv.close()
    finally:
        t.join(timeout=10)
        assert not t.is_alive(), "the late server never started"
        started["server"].stop()


def test_connect_timeout_is_bounded():
    port = int(find_free_port())  # nothing ever listens here
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="retried for"):
        KVClient(port=port, connect_timeout=0.5)
    # bounded: gave up near the deadline, not after hanging minutes
    assert time.monotonic() - t0 < 5.0


def test_server_death_mid_claim_raises_not_hangs():
    server = KVServer()
    kv = KVClient(port=server.port)
    kv.set("ckpt/g1/5/shard_done/1", b"claimed")
    server.stop()
    # the next request on the dead connection must fail loud (the caller —
    # a rank mid-commit — turns this into its own crash and the supervisor
    # restarts the generation); a silent hang would wedge the commit window
    with pytest.raises(RuntimeError):
        for _ in range(3):  # first call can still ride the closing socket
            kv.set("ckpt/g1/5/shard_done/0", b"claimed")
            time.sleep(0.05)
    kv.close()


def test_ttl_key_expires_and_plain_set_clears_ttl():
    with KVServer() as server:
        kv = KVClient(port=server.port)
        kv.set_ttl("claim/a", b"x", ttl=0.2)
        kv.set_ttl("claim/b", b"y", ttl=0.2)
        assert kv.try_get("claim/a") == b"x"
        kv.set("claim/b", b"y2")  # plain set = permanent: TTL dropped
        time.sleep(0.35)
        assert kv.try_get("claim/a") is None      # reaped
        assert kv.keys("claim/") == ["claim/b"]   # survivor
        assert kv.try_get("claim/b") == b"y2"
        with pytest.raises(ValueError):
            kv.set_ttl("claim/c", b"z", ttl=0)
        kv.close()


def test_keys_and_delete_prefix():
    with KVServer() as server:
        kv = KVClient(port=server.port)
        for k in ("ckpt/g1/5/shard_done/0", "ckpt/g1/5/shard_done/1",
                  "ckpt/g2/5/shard_done/0", "fault/0/claimed"):
            kv.set(k, b"1")
        assert kv.keys("ckpt/g1/") == [
            "ckpt/g1/5/shard_done/0", "ckpt/g1/5/shard_done/1",
        ]
        assert kv.delete_prefix("ckpt/g1/") == 2
        assert kv.keys("ckpt/") == ["ckpt/g2/5/shard_done/0"]
        assert kv.try_get("fault/0/claimed") == b"1"  # untouched namespace
        with pytest.raises(ValueError):
            kv.delete_prefix("")  # whole-store wipe must not be a typo away
        kv.close()

# -- read-retry (host-agent control plane) ---------------------------------
#
# Reads (get/try_get/keys) are idempotent, so the client retries them with
# jittered backoff and a fresh connection — an agent polling `elastic/
# generation` across a KV hiccup should see a blip, not a crash. Writes
# stay single-shot: a retried add() could double-claim a charge budget.


def test_read_survives_server_restart_on_same_port():
    port = int(find_free_port())
    first = KVServer(port=port)
    kv = KVClient(port=port)
    kv.set("elastic/generation", b"3")
    first.stop()  # connection now dead; next read must redial, not raise

    second = {}

    def restart():
        time.sleep(0.3)
        second["srv"] = KVServer(port=port)
        c = KVClient(port=port)
        c.set("elastic/generation", b"4")  # restarted store, new contents
        c.close()

    t = threading.Thread(target=restart)
    t.start()
    try:
        # the property under test: the read redials instead of raising.
        # The redial may legitimately land in the gap after the restarted
        # server is listening but before the helper's set() — poll through
        # that window rather than flake on scheduler timing.
        deadline = time.monotonic() + 10.0
        got = kv.try_get("elastic/generation")
        while got != b"4" and time.monotonic() < deadline:
            time.sleep(0.05)
            got = kv.try_get("elastic/generation")
        assert got == b"4"
        assert kv.keys("elastic/") == ["elastic/generation"]
    finally:
        t.join(timeout=10)
        assert not t.is_alive(), "the restarted server never came up"
        second["srv"].stop()
        kv.close()


def test_read_retry_is_bounded_when_server_stays_dead():
    server = KVServer()
    kv = KVClient(port=server.port, connect_timeout=0.3)
    kv.set("k", b"v")
    server.stop()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="kv"):
        for _ in range(3):  # first read can still drain the closing socket
            kv.try_get("k")
            time.sleep(0.05)
    # five attempts x short backoff x bounded reconnect — seconds, not forever
    assert time.monotonic() - t0 < 30.0
    kv.close()


def test_writes_do_not_retry_across_server_death():
    """add() is the election/charge primitive — replaying it after a
    reconnect could hand two agents the same claim. It must fail loud on
    the very path where reads quietly recover."""
    server = KVServer()
    kv = KVClient(port=server.port, connect_timeout=0.3)
    kv.set("budget/claim/1", b"0")
    server.stop()
    with pytest.raises(RuntimeError):
        for _ in range(3):
            kv.add("budget/claim/1", 1)
            time.sleep(0.05)
    kv.close()

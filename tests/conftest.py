"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference's test strategy (SURVEY.md §4) simulates multi-node with
multi-process + gloo on localhost. The TPU-native analogue is JAX's CPU
backend with XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT fake devices — single
process, 8 devices, real mesh/collective semantics.

Must run before any `import jax` in test modules, hence conftest-level env.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# backend init is lazy: the config still decides as long as nothing has
# queried a device yet
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)

import faulthandler  # noqa: E402
import gc  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# A 400+-test session grows jax's jit caches monotonically (gigabytes of
# live objects), and CPython's cyclic GC walks the entire live set on every
# full collection. Trace-time allocation churn trips the default thresholds
# constantly, so by the later test files each collection costs seconds and
# the suite visibly crawls (same tests run 1.5-2x faster in isolation).
# Tracing produces garbage, not leaks — collect far less often, and keep
# the live set the collector walks bounded by dropping the compile caches
# at module boundaries (modules don't share jitted functions, so the only
# cost is re-tracing the handful of library-level jits like
# resize_on_device).
gc.set_threshold(50_000, 20, 20)
gc.freeze()  # startup world (jax, numpy, flax) is permanent: never scan it


#: every test's limit, set-up and tear-down included. The slowest tier-1 case
#: takes 25 s on the six-worker run (42 s before PR 33), a module's first
#: case also builds its module's fixtures, and on a host whose cores were
#: being stolen one case read 98 s: three times that, and far less than the
#: suite's own clock, so that a hang reads as one failed test.
TEST_LIMIT_S = 300.0
#: behind it, for a hang inside native code that no Python handler can
#: interrupt: the process dumps its stacks and exits, xdist reports the
#: worker down with the test's name and goes on with a new one
HARD_LIMIT_S = 420.0


_STDERR = pytest.StashKey()


def pytest_configure(config):
    # capture is suspended here, so this is the run's own stderr, which the
    # hard limit's dump still reaches while a test's output is captured
    config.stash[_STDERR] = os.fdopen(os.dup(2), "w")


def _over_the_limit(signum, frame):
    with tempfile.TemporaryFile("w+") as stacks:
        faulthandler.dump_traceback(file=stacks, all_threads=True)
        stacks.seek(0)
        pytest.fail(f"over the limit of {TEST_LIMIT_S:g} s a test; every "
                    f"thread's stack:\n{stacks.read()}", pytrace=False)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """One limit for every test (no ``pytest-timeout`` here): an interval
    timer whose handler fails the test by name with all threads' stacks."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)  # no signal reaches another thread
    signal.signal(signal.SIGALRM, _over_the_limit)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True,
                                      file=item.config.stash[_STDERR])
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def light_compile():
    """XLA:CPU without its expensive passes, for a test (or, by
    ``pytestmark``, a module) whose claim is a tolerance against a
    reference and never one that is bitwise: the tiny shapes here cost
    their compilation, which takes half the CPU time this way."""
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", False)


@pytest.fixture
def launch():
    """The program's record of its own launch (``runtime/bootstrap.py``: the
    compile listeners and the program table) over an empty registry, on for
    one test and off again."""
    from tpu_sandbox.obs import get_registry
    from tpu_sandbox.runtime import bootstrap

    get_registry().reset()
    bootstrap.reset_launch_record()
    bootstrap._count_cache_events()
    yield bootstrap
    bootstrap.reset_launch_record()
    get_registry().reset()


@pytest.fixture(autouse=True, scope="module")
def _bounded_jit_caches():
    yield
    jax.clear_caches()
    gc.collect()
    # whatever survived the module's teardown is long-lived by definition
    # (session fixtures, module caches jax keeps internally) — exempt it
    # from every future collection instead of rescanning it per module
    gc.freeze()


@pytest.fixture(autouse=True)
def _no_resource_leaks():
    """Fail any test that leaks a live KVServer or a new non-daemon thread.

    A leaked server holds its port for the rest of the session and turns
    later find_free_port races into one-in-N flakes that reproduce only in
    full runs; a leaked non-daemon thread hangs interpreter shutdown. Both
    were historically found by CI timeouts instead of by the guilty test —
    this pins the blame at the source. Daemon threads get a pass (wedged
    Heartbeat threads are abandoned by design), and stragglers get a short
    join grace first so tests that are merely slow to wind down don't trip.

    Serve engines count too: an engine still holding admitted or queued
    requests after a test means the test abandoned in-flight work (the
    replica drain/requeue paths exist precisely so nothing is ever
    abandoned), so it fails the same way a leaked server does.

    Gateways count the same way a KVServer does: a live one holds its
    listening port and a cloned KV connection for the rest of the session.
    """
    from tpu_sandbox.runtime import kvstore

    threads_before = set(threading.enumerate())
    servers_before = set(kvstore.live_servers())
    gateways_before = set()
    if "tpu_sandbox.gateway.server" in sys.modules:
        from tpu_sandbox.gateway.server import live_gateways

        gateways_before = set(live_gateways())
    yield
    me = threading.current_thread()

    def stragglers():
        return [t for t in threading.enumerate()
                if t not in threads_before and t is not me
                and not t.daemon and t.is_alive()]

    deadline = time.monotonic() + 2.0
    leaked_threads = stragglers()
    while leaked_threads and time.monotonic() < deadline:
        for t in leaked_threads:
            t.join(timeout=0.2)
        leaked_threads = stragglers()

    leaked_servers = [s for s in kvstore.live_servers()
                      if s not in servers_before]
    problems = []
    if "tpu_sandbox.serve.engine" in sys.modules:
        from tpu_sandbox.serve.engine import live_engines

        busy = live_engines()
        if busy:
            loads = [(e.active_requests, len(e.waiting)) for e in busy]
            for e in busy:  # unwedge the rest of the session
                e.drain_to_requests()
            problems.append(
                f"{len(busy)} serve engine(s) abandoned with in-flight "
                f"work (active, waiting): {loads}"
            )
    if "tpu_sandbox.gateway.server" in sys.modules:
        from tpu_sandbox.gateway.server import live_gateways

        open_gateways = [g for g in live_gateways()
                         if g not in gateways_before]
        if open_gateways:
            gw_ports = [g.port for g in open_gateways]
            for g in open_gateways:  # free ports/threads for the session
                g.close()
            problems.append(
                f"{len(gw_ports)} gateway(s) left running on port(s) "
                f"{gw_ports}"
            )
    if leaked_servers:
        ports = [s.port for s in leaked_servers]
        for s in leaked_servers:  # free the ports for the rest of the run
            s.stop()
        problems.append(
            f"{len(ports)} KVServer(s) left running on port(s) {ports}"
        )
    if leaked_threads:
        names = ", ".join(repr(t.name) for t in leaked_threads)
        problems.append(f"non-daemon thread(s) still alive: {names}")
    if problems:
        pytest.fail("resource leak: " + "; ".join(problems), pytrace=False)


#: PR 41's manifest test holds ``decode_step_ms``'s cells to exactly [gpt2m,
#: jamba2] with ``==``. Every later serving cell has to append its name to
#: that list (PR 45 did) and a file under ``tests/benchmark/`` is a
#: ``benchmark`` PR's to edit, so the test cannot pass and cannot be repaired
#: here. Strict: the day its ``==`` is a prefix it passes, this marker fails
#: it, and marker and stand-in go. Until then
#: ``test_benchmark_longcat_serve.py::test_jambas_manifest_test_but_for_the_pinned_list``
#: runs the test's own body with that one line turned into a prefix.
PINNED_TO_TWO_SERVING_CELLS = (
    "test_benchmark_jamba_serve.py::"
    "test_the_cell_is_in_the_manifest_as_the_issue_sets_it")

#: PR 40's fault test plants its altered token by replacing
#: ``eng._pick_token(slot, row)`` (and takes the vocabulary from
#: ``len(row)``). Since PR 46 ``TransformerLM``'s programs give the greedy
#: pick on the device, as the other two families' do, and a greedy request
#: never visits ``_pick_token``: the fault is not planted and ``assert
#: obs.problems`` fails. A file under ``tests/benchmark/`` is a ``benchmark``
#: PR's to edit: that PR plants the fault where a token is emitted
#: (``_emit_token``, which every path passes), after which this marker fails
#: the case (strict) and marker and stand-in go. Until then
#: ``tests/test_serve_replay_fault.py`` runs the same ``drive`` with the
#: token altered there and asserts what the case asserts.
PLANTED_IN_THE_HOSTS_PICK = (
    "test_benchmark_serve_replay.py::"
    "test_a_fault_under_the_timed_path_comes_out_not_correct[token_altered]")

#: PR 45's two manifest tests hold the expert share's readers and the dense
#: MLP's (``serve_moe_*``, ``dense_mlp_ms``) to LongCat's cell alone with
#: ``== [CELL]``. The next cell that serves through ``ExpertShare`` has to
#: append its name to those lists (PR 48 did, as its issue names them), and
#: a file under ``tests/benchmark/`` is a ``benchmark`` PR's to edit. Strict,
#: as above; until then ``test_benchmark_laguna_serve.py::
#: test_longcats_manifest_tests_but_for_the_pinned_lists`` runs each one's
#: own body with that one line turned into a prefix.
PINNED_TO_LONGCATS_CELL = tuple(
    "test_benchmark_longcat_serve.py::" + name for name in (
        "test_the_cell_is_in_the_manifest_as_the_issue_sets_it",
        "test_the_accepted_serving_cells_lists_stay_theirs"))

#: a test of ``tests/benchmark/`` that cannot pass until a ``benchmark`` PR
#: edits it -> why; strict, so that the marker fails the day it could
STRICT_XFAILS = {
    **{case: "pins the served expert share's readers to LongCat's cell "
       "with `== [CELL]`; a `benchmark` PR turns it into a prefix (see "
       "PINNED_TO_LONGCATS_CELL)" for case in PINNED_TO_LONGCATS_CELL},
    PINNED_TO_TWO_SERVING_CELLS:
        "pins decode_step_ms's cells to two with `==`; a `benchmark` PR "
        "turns it into a prefix (see PINNED_TO_TWO_SERVING_CELLS)",
    PLANTED_IN_THE_HOSTS_PICK:
        "plants its fault in `_pick_token`, which a greedy request no "
        "longer visits; a `benchmark` PR plants it in `_emit_token` (see "
        "PLANTED_IN_THE_HOSTS_PICK)",
}


def pytest_collection_modifyitems(config, items):
    """Safety net: any ``*_integration`` test module is slow by construction
    (it spawns real worker processes and waits on supervisors/timeouts), so
    mark the whole module rather than trusting each test to remember the
    decorator. Tier-1 (`-m 'not slow'`) stays fast unit tests only."""
    slow = pytest.mark.slow
    for item in items:
        mod = item.module.__name__ if item.module else ""
        if mod.endswith("_integration"):
            item.add_marker(slow)
        for case, reason in STRICT_XFAILS.items():
            if item.nodeid.endswith(case):
                item.add_marker(pytest.mark.xfail(
                    reason=reason, raises=AssertionError, strict=True))


@pytest.fixture(scope="session")
def devices():
    assert jax.device_count() == 8, "expected 8 virtual CPU devices"
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8(devices):
    from tpu_sandbox.runtime.mesh import make_mesh

    return make_mesh({"data": 8})

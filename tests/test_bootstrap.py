"""Runtime/bootstrap tests — capability parity with reference test_init.py.

The reference smoke test spawns 4 processes that rendezvous and exit
(test_init.py:112-117). Here: init() on the 8-virtual-device CPU backend,
topology introspection, serial sentinel, cleanup idempotence.
"""

import jax

from tpu_sandbox.runtime import bootstrap


def test_find_free_port_is_string_and_bindable():
    import socket

    port = bootstrap.find_free_port()
    assert isinstance(port, str)  # string: it feeds an env var
    with socket.socket() as s:
        s.bind(("127.0.0.1", int(port)))  # genuinely free


def test_coordinator_address_honors_env(monkeypatch):
    monkeypatch.setenv("MASTER_ADDR", "10.0.0.7")
    monkeypatch.setenv("MASTER_PORT", "29500")
    assert bootstrap.coordinator_address() == "10.0.0.7:29500"


def test_coordinator_address_defaults_to_loopback(monkeypatch):
    monkeypatch.delenv("MASTER_ADDR", raising=False)
    monkeypatch.delenv("MASTER_PORT", raising=False)
    host, port = bootstrap.coordinator_address().split(":")
    assert host == "127.0.0.1"
    assert 1024 <= int(port) <= 65535


def test_init_single_process_topology():
    topo = bootstrap.init()
    assert bootstrap.is_initialized()
    assert topo.process_id == 0
    assert topo.num_processes == 1
    assert topo.global_devices == 8
    assert topo.backend == "cpu"
    assert "process 0/1" in topo.summary()
    bootstrap.cleanup()
    assert not bootstrap.is_initialized()


def test_serial_sentinel_skips_init():
    # reference rank==-1 semantics (test_init.py:73): serial mode, no group.
    topo = bootstrap.init(process_id=bootstrap.SERIAL_RANK)
    assert bootstrap.is_initialized()
    assert topo.num_processes == 1
    bootstrap.cleanup()


def test_cleanup_idempotent():
    bootstrap.cleanup()
    bootstrap.cleanup()
    assert not bootstrap.is_initialized()


def test_backend_name_matches_jax():
    assert bootstrap.backend_name() == jax.default_backend()


def test_multiprocess_init_requires_shared_coordinator(monkeypatch):
    import pytest

    monkeypatch.delenv("MASTER_PORT", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="shared coordinator"):
        bootstrap.init(num_processes=2, process_id=0)
    with pytest.raises(ValueError, match="process_id"):
        bootstrap.init(coordinator="127.0.0.1:1234", num_processes=2)


def test_init_twice_is_idempotent():
    bootstrap.init()
    topo = bootstrap.init()
    assert topo.num_processes == 1
    bootstrap.cleanup()


def test_compile_cache_honours_the_environment(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: JAX reads it
    by itself, and the helper must set no other directory in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert bootstrap.configure_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, tmp_path):
    """Unset, the cache is <checkout>/.jax_cache whatever the working
    directory: the path is part of the cache key, so it may depend on
    nothing that changes between runs."""
    from pathlib import Path

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    try:
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert bootstrap.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

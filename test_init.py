"""Distributed-init smoke test — TPU-native rebuild of the reference
``test_init.py`` (same flow, same log lines, same exit-0-on-success contract).

Reference behavior (test_init.py:112-117): spawn 4 processes, each sets
MASTER_ADDR/MASTER_PORT, picks gloo or nccl, calls
``dist.init_process_group``, prints progress, exits; the parent prints
``successful test_setup!``. Rank -1 is a "serial code, skip init" sentinel
(test_init.py:73).

Two modes:
- default: ranks are devices of one process (the TPU-native shape — one
  process per HOST, so there is nothing to spawn on a single host).
- ``--multiprocess``: spawns world_size real OS processes that rendezvous
  through ``jax.distributed`` on the CPU backend (collectives over Gloo —
  the same fabric as the reference's CPU fallback) and run a psum sanity
  check. This is the reference's actual process topology, for parity.

Unlike the reference — which defines ``cleanup()`` but never calls it —
the group is actually torn down at the end.
"""

import argparse
import os
import subprocess
import sys


def setup_rank(rank: int, world_size: int, port: str, backend: str) -> None:
    """Per-rank progress report, line-for-line with reference :74-94."""
    if rank != -1:  # -1 rank indicates serial code
        print(f"setting up rank={rank} (with world_size={world_size})")
        MASTER_ADDR = "127.0.0.1"
        print(f"{MASTER_ADDR=}")
        print(f"{port=}")
        print(f"{backend=}")
        print(f"--> done setting up rank={rank}", flush=True)


def worker(rank: int, world_size: int, port: str) -> None:
    """One spawned process: rendezvous, collective sanity check, teardown."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from tpu_sandbox.runtime import bootstrap

    bootstrap.init(
        coordinator=f"127.0.0.1:{port}",
        num_processes=world_size,
        process_id=rank,
    )
    setup_rank(rank, world_size, port, bootstrap.backend_name())

    # the reference's smoke test stops at rendezvous; we also prove the
    # group works: a cross-process psum must see every rank
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")),
        np.full((jax.local_device_count(), 1), float(rank + 1), np.float32),
        (jax.device_count(), 1),
    )
    total = float(jax.jit(lambda a: a.sum(), out_shardings=NamedSharding(mesh, P()))(x))
    expected = sum(
        (r + 1) * (jax.device_count() // world_size) for r in range(world_size)
    )
    assert total == expected, (total, expected)
    print(f"rank {rank}: psum check {total} == {expected}", flush=True)
    bootstrap.cleanup()


def test_setup(world_size: int, multiprocess: bool,
               force_cpu: bool = False) -> None:
    print("test_setup")
    from tpu_sandbox.runtime import bootstrap

    port = bootstrap.find_free_port()
    if multiprocess:
        procs = [
            subprocess.Popen(
                [sys.executable, __file__, "--worker", "--rank", str(r),
                 "--world-size", str(world_size), "--port", port],
                env={**os.environ},
            )
            for r in range(world_size)
        ]
        codes = [p.wait(timeout=180) for p in procs]
        if any(codes):
            raise SystemExit(f"worker exit codes: {codes}")
    else:
        from tpu_sandbox.runtime.mesh import make_mesh
        from tpu_sandbox.utils.cli import ensure_devices

        devices = ensure_devices(world_size, force_cpu=force_cpu)
        bootstrap.init()
        backend = bootstrap.backend_name()
        mesh = make_mesh({"data": world_size}, devices=devices)
        assert mesh.shape["data"] == world_size
        for rank in range(world_size):
            setup_rank(rank, world_size, port, backend)
        print(bootstrap.topology_summary())
        bootstrap.cleanup()
    print("successful test_setup!")


def main():
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    parser = argparse.ArgumentParser()
    parser.add_argument("--world-size", type=int, default=4)
    parser.add_argument("--multiprocess", action="store_true",
                        help="spawn real OS processes (reference topology)")
    parser.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--rank", type=int, default=0, help=argparse.SUPPRESS)
    parser.add_argument("--port", type=str, default="", help=argparse.SUPPRESS)
    parser.add_argument("--force-cpu", action="store_true",
                        help="virtual CPU ranks only; skip the accelerator "
                             "(same flag as the training entry scripts)")
    args = parser.parse_args()
    if args.worker:
        worker(args.rank, args.world_size, args.port)
    else:
        test_setup(args.world_size, args.multiprocess, args.force_cpu)


if __name__ == "__main__":
    main()

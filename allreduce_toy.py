"""All-reduce-sum toy across N ranks — TPU-native rebuild of the reference
``allreduce_toy.py`` (same flags, same output lines).

Reference behavior (allreduce_toy.py:20-48): N processes each draw a random
int in [0, 10), all-reduce-sum it over NCCL, barrier, and ranks 0 and 1 print
``rank: R, step: S, value: V, reduced sum: T.`` for 10 steps (the ``--steps``
flag existed but was ignored — setup() hardcoded 10 at :48; here the flag
works, defaulting to 10 so the default launch matches the reference output).

TPU-native shape: ranks are devices of ONE process (no mp.spawn), the group
is built once (the reference created a fresh ``dist.new_group`` every step,
:26-27 — a communicator leak XLA has no analogue of), the all-reduce is a
jit'd ``lax.psum`` over the mesh axis, and the barrier is a psum'd unit
token. ``--backend`` / ``--init-method`` / ``--rank`` are accepted for
launch-compatibility; backend and rendezvous are JAX's concern now.
"""

import argparse

import numpy as np


def run(group, world_size: int, steps: int) -> None:
    for step in range(1, steps + 1):
        # per-rank host RNG, unseeded — parity with torch.randint at :23
        values = np.random.randint(0, 10, size=(world_size,)).astype(np.int32)
        reduced = np.asarray(group.all_reduce(values, "sum"))
        group.barrier()
        for rank in range(min(2, world_size)):
            print(
                "rank: {}, step: {}, value: {}, reduced sum: {}.".format(
                    rank, step, values[rank], reduced[rank]
                )
            )


def main():
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    parser = argparse.ArgumentParser()
    parser.add_argument("--backend", type=str, default="xla",
                        help="Accepted for reference parity; XLA picks the fabric.")
    parser.add_argument("-i", "--init-method", type=str,
                        default="tcp://127.0.0.1:23456",
                        help="Accepted for reference parity; rendezvous is jax.distributed.")
    parser.add_argument("-s", "--world_size", type=int, default=None,
                        help="Number of ranks participating in the job.")
    parser.add_argument("-r", "--rank", type=int, default=None,
                        help="Accepted for reference parity; ranks are devices here.")
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--force-cpu", action="store_true",
                        help="Use virtual CPU devices even if an accelerator is present.")
    args = parser.parse_args()

    from tpu_sandbox.utils.cli import ensure_devices

    world_size = args.world_size or 1
    devices = ensure_devices(world_size, force_cpu=args.force_cpu)

    from tpu_sandbox.parallel.collectives import CollectiveGroup
    from tpu_sandbox.runtime import bootstrap
    from tpu_sandbox.runtime.mesh import make_mesh

    bootstrap.init()
    mesh = make_mesh({"data": world_size}, devices=devices)
    group = CollectiveGroup(mesh, "data")
    for rank in range(world_size):
        print(f"--> done setting up rank={rank}")

    run(group, world_size, args.steps)
    bootstrap.cleanup()


if __name__ == "__main__":
    main()

"""The measured window of a training cell: the program's own loop runs, and
the benchmark wraps the two things it hands to that loop — the batch
iterator (ends at the deadline, clocks every ``next``) and the step (clocks
the dispatch, waits for the device on every K-th step only, so dispatch
overlaps the device as in a real run)."""

from __future__ import annotations

import time

from benchmark.lib.observe import Observations


def compile_clocked(obs: Observations, lower):
    """Trace + lower (``lower()``), then compile, clocked apart and added to
    ``trace_lower_s`` / ``compile_s``: a warm cache saves the second and
    never the first. The compiled program is what the window then calls, so
    tracing is paid once."""
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    obs.facts["trace_lower_s"] = obs.facts.get("trace_lower_s", 0.0) + t1 - t0
    obs.facts["compile_s"] = (obs.facts.get("compile_s", 0.0)
                              + time.perf_counter() - t1)
    return compiled


class Window:
    def __init__(self, obs: Observations, seconds: float, steps_per_chunk: int):
        self.obs = obs
        self.k = int(steps_per_chunk)
        self.start = time.perf_counter()
        self.deadline = self.start + seconds
        self.steps = 0
        self.losses: list = []
        self.chunk_step_s: list[float] = []
        self._chunk_start = self.start

    def open(self) -> bool:
        return time.perf_counter() < self.deadline

    def batches(self, iterator):
        """Yield from ``iterator`` until the deadline, clocking each wait."""
        iterator = iter(iterator)
        while self.open():
            try:
                with self.obs.span("next_batch"):
                    batch = next(iterator)
            except StopIteration:
                return
            yield batch

    def step(self, call, *args):
        """One optimizer step through ``call``; returns what it returns
        (``state, loss``)."""
        import jax

        with self.obs.span("step_dispatch"):
            state, loss = call(*args)
        self.losses.append(loss)
        self.steps += 1
        if self.steps % self.k == 0:
            with self.obs.span("wait_device"):
                jax.block_until_ready(loss)
            now = time.perf_counter()
            self.chunk_step_s.append((now - self._chunk_start) / self.k)
            self._chunk_start = now
        return state, loss

    def close(self) -> None:
        """After the loop: losses to the host, counts into the record."""
        import numpy as np

        obs = self.obs
        host = [float(np.ravel(np.asarray(x))[0]) for x in self.losses]
        obs.series["loss"] = host
        obs.series["chunk_step_s"] = self.chunk_step_s
        obs.attempted = self.steps
        obs.failed = sum(1 for x in host if not np.isfinite(x))
        if obs.failed:
            obs.problem(f"{obs.failed} of {self.steps} losses not finite")
        if not self.chunk_step_s:
            obs.problem(f"no whole chunk of {self.k} steps fit the window")
        obs.notes.update(steps=self.steps, chunks=len(self.chunk_step_s),
                         steps_per_chunk=self.k,
                         first_loss=host[0] if host else None,
                         last_loss=host[-1] if host else None)


class BoundedLoader:
    """The program's loader, ending at the window's deadline. ``Trainer.fit``
    asks a loader for ``len`` and iterates it once per epoch."""

    def __init__(self, loader, window: Window):
        self.loader, self.window = loader, window

    def __len__(self) -> int:
        return len(self.loader)

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __iter__(self):
        return self.window.batches(self.loader)


def train_step_ms(obs: Observations) -> float | None:
    from benchmark.lib.stats import median

    m = median(obs.series.get("chunk_step_s", []))
    return None if m is None else 1e3 * m

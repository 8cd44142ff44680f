"""Runner kind ``lm_serve_replay``: the real ``serve.ContinuousEngine`` with
every decode slot full **by construction**. Set-up submits the mix's
sessions (``traffic.decode_replay``: one a slot) and steps the engine, so its
own ``_admit_waiting`` prefills each through the engine's own programs; the
window then times ``eng.step()`` while every slot decodes, and ends before
the first session would retire. No arrivals, no admission and no prefill
inside the window: whatever the engine's speed, every measured step is a
full batch (the open-loop cell of the same configuration is
``runners/lm_serve.py``'s, kept for later).

``correct``: every session gains exactly one token in every measured step,
none is preempted, and after the window (``verify``, once the memory peak
is read and the engine's pages are freed) the plain float32 reference runs
one full forward over **every** session's prompt and served tokens: each
served token has to be nearly the reference's first choice, and the
engine's own mean log-probability of a session's tokens the reference's
(``reference/gpt2.py::compare_served``)."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from benchmark.lib import (gpt2_serve_counts, manifest, serve_readers,
                           traffic)
from benchmark.lib.observe import Observations
from benchmark.runners.lm_serve import build

#: fewer whole steps than this in a window is no sample
MIN_STEPS = 6
#: sessions a reference forward: [8, 1024] tokens keep float32 scores and
#: logits of a block under 2.5 GB
REFERENCE_BLOCK = 8
#: the program's spans the per-layer readers take: fact -> registry histogram
ENGINE_SPANS = {"decode_call_s": "engine.decode_call_s",
                "sample_s": "engine.sample_s"}


@dataclass
class Session:
    eng: Any
    params: Any
    sessions: list
    steps: int = 0
    window_s: float = 0.0
    #: rid -> measured steps in which it did not gain exactly one token
    stalled: dict = field(default_factory=dict)
    #: what the engine served, as ``verify`` gave it to the reference
    batch: dict | None = None


def engine_span_sums() -> dict:
    """Sum of each of ``ENGINE_SPANS`` so far (the registry is always on and
    counts the whole process: the window's share is a difference)."""
    from tpu_sandbox.obs import get_registry

    hists = get_registry().snapshot()["histograms"]
    return {fact: hists.get(name, {}).get("sum", 0.0)
            for fact, name in ENGINE_SPANS.items()}


def served(eng) -> dict:
    """rid -> tokens generated so far, of the sessions that hold a slot."""
    return {s.request.rid: len(s.generated) for s in eng.slots if s is not None}


def setup(obs: Observations) -> Session:
    from tpu_sandbox.serve import Request

    cell = obs.cell
    spec = cell["traffic"]
    eng, params = build(cell["config"], cell["deployment"], obs.seed,
                        obs.facts)
    # the one program the window runs: its scopes give write_kv and
    # gather_ctx a device time
    obs.note_program(eng.step_fns.decode.as_text())
    sessions = traffic.decode_replay(spec, obs.seed,
                                     cell["config"]["vocab_size"])
    t0 = time.perf_counter()
    for s in sessions:
        eng.submit(Request(rid=s.rid, prompt=list(s.prompt),
                           max_new_tokens=s.max_new_tokens,
                           arrival=time.perf_counter()))
    eng.step()  # admits, so prefills, every session; then one decode
    obs.facts["session_prefill_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(int(spec["warmup_steps"])):
        eng.step()
    obs.facts["warmup_s"] = time.perf_counter() - t0
    held = served(eng)
    if len(held) < len(sessions) or eng.waiting or len(sessions) \
            != eng.config.max_batch:
        obs.problem(f"{len(held)} of {len(sessions)} sessions hold one of "
                    f"{eng.config.max_batch} slots after set-up, "
                    f"{len(eng.waiting)} wait")
    lengths = [len(s.prompt) for s in sessions]
    obs.notes.update(prompt_lens=lengths, prompt_tokens=sum(lengths))
    return Session(eng, params, sessions)


def measure(obs: Observations, session: Session, seconds: float) -> None:
    """``eng.step()`` until ``seconds`` have passed or the next step would
    retire a session, whichever comes first."""
    eng = session.eng
    before = served(eng)
    room = min((s.request.max_new_tokens - len(s.generated) - 1
                for s in eng.slots if s is not None), default=0)
    spans_before = engine_span_sums()
    contexts = []
    t0 = end = time.perf_counter()
    while end - t0 < seconds and session.steps < room:
        contexts.append([len(s.tokens) for s in eng.slots if s is not None])
        with obs.span("eng.step"):
            eng.step()
        end = time.perf_counter()
        with obs.span("after_step", record=False):
            now = served(eng)
            grew = [rid for rid, n in before.items() if now.get(rid) == n + 1]
            for rid in set(before) - set(grew):
                session.stalled[rid] = session.stalled.get(rid, 0) + 1
            obs.add("occupancy_pct", 100.0 * len(grew) / eng.config.max_batch)
            before = now
            session.steps += 1
    session.window_s = end - t0
    for fact, value in engine_span_sums().items():
        obs.facts[fact] = value - spans_before[fact]
    # what the measured steps needed, on the contexts they had (a context
    # grows by one a step: the mean step)
    config = obs.cell["config"]
    if contexts:
        mean = np.mean(np.asarray(contexts, np.float64), axis=0)
        obs.facts["decode_flops_per_step"] = \
            gpt2_serve_counts.decode_step_flops(config, mean)
        obs.facts["decode_bytes_per_step"] = \
            gpt2_serve_counts.decode_step_bytes(config, mean)
        obs.notes["live_context_tokens"] = float(mean.sum())


def finish(obs: Observations, session: Session) -> None:
    eng = session.eng
    held = served(eng)
    missing = [s.rid for s in session.sessions if s.rid not in held]
    obs.attempted = len(session.sessions)
    obs.failed = len(set(missing) | set(session.stalled))
    obs.facts["window_steps"] = float(session.steps)
    obs.facts["window_s"] = session.window_s
    obs.series["decode_step_s"] = list(obs.spans.get("eng.step", []))
    obs.facts["preemptions"] = float(
        sum(s.request.preemptions for s in eng.slots if s is not None)
        + sum(r.preemptions for r in eng.waiting)
        + sum(r.preemptions for r in eng.results.values()))
    if session.steps < MIN_STEPS:
        obs.problem(f"{session.steps} whole steps in the window: fewer than "
                    f"{MIN_STEPS}")
    low = [o for o in obs.series.get("occupancy_pct", []) if o < 100.0]
    if low or obs.failed:
        obs.problem(f"{len(low)} measured steps under full occupancy (least "
                    f"{min(low, default=100.0):.1f} %); {obs.failed} sessions "
                    f"did not gain one token in every step: "
                    f"{sorted(set(missing) | set(session.stalled))[:5]}")
    if obs.facts["preemptions"] or eng.results:
        obs.problem(f"{obs.facts['preemptions']:.0f} preemptions, "
                    f"{len(eng.results)} sessions retired inside the window")
    obs.notes["steps"] = session.steps


def served_batch(eng, config: dict, spec: dict) -> dict | None:
    """What the engine served, as the reference takes it: per session that
    holds a slot its prompt and served tokens but the last (``tokens``, zero
    padded to the model's positions), the positions whose logits chose a
    served token (``rows``) and those tokens (``chosen``), how many
    (``counts``), and the engine's own mean log-probability of them
    (``system``, what its ``engine.logprob`` series observes at retirement).
    Padded with empty rows to whole blocks of ``REFERENCE_BLOCK``."""
    slots = [s for s in eng.slots if s is not None]
    if not slots:
        return None
    n = len(slots)
    total = n + (-n % REFERENCE_BLOCK)
    width, rows_n = int(config["n_positions"]), int(spec["max_new_tokens"])
    batch = {"n": n, "tokens": np.zeros((total, width), np.int32),
             "rows": np.zeros((total, rows_n), np.int32),
             "chosen": np.zeros((total, rows_n), np.int32),
             "counts": np.ones(total, np.int64), "system": np.zeros(total)}
    for i, s in enumerate(slots):
        plen, g = len(s.request.prompt), len(s.generated)
        batch["tokens"][i, :plen + g - 1] = s.tokens[:-1]
        batch["rows"][i, :g] = plen - 1 + np.arange(g)
        batch["chosen"][i, :g] = s.generated
        batch["counts"][i] = g
        batch["system"][i] = s.logprob_sum / g
    return batch


def reference_rows(reference, tree, batch: dict, config: dict, chosen=None,
                   matmul_dtype=None) -> dict:
    """``reference.served_rows`` over the batch, a block at a time."""
    chosen = batch["chosen"] if chosen is None else chosen
    parts = [reference.served_rows(
        tree, batch["tokens"][b:b + REFERENCE_BLOCK],
        batch["rows"][b:b + REFERENCE_BLOCK], chosen[b:b + REFERENCE_BLOCK],
        n_head=config["n_head"], eps=config["layer_norm_epsilon"],
        matmul_dtype=matmul_dtype)
        for b in range(0, len(chosen), REFERENCE_BLOCK)]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def verify(obs: Observations, session: Session) -> None:
    """After the window, the memory peak read: free the engine's pages, then
    the reference over every session, ``REFERENCE_BLOCK`` at a time."""
    t0 = time.perf_counter()
    eng, config = session.eng, obs.cell["config"]
    reference = manifest.module("reference", obs.cell["reference"])
    batch = session.batch = served_batch(eng, config, obs.cell["traffic"])
    if batch is None:
        obs.problem("no session holds a slot after the window: nothing to "
                    "compare with the reference")
        return
    eng.drain_to_requests()
    eng.k_pages.delete()
    eng.v_pages.delete()
    tree = reference.from_program_tree(session.params, config["n_layer"])
    out = reference_rows(reference, tree, batch, config)
    n = batch["n"]
    dev, bad = reference.compare_served(out["gap_rel"][:n], out["logprob"][:n],
                                        batch["counts"][:n], batch["system"][:n])
    for text in bad:
        obs.problem(text)
    obs.notes["reference_deviation"] = dev
    obs.notes["compared_tokens"] = int(batch["counts"][:n].sum())
    obs.notes["compared"] = {k: {"value": v, "limit": reference.TOLERANCE[k]}
                             for k, v in dev.items()}
    obs.facts["after_window_check_s"] = time.perf_counter() - t0


def end_to_end(obs: Observations) -> dict:
    step_s = serve_readers.step_s(obs)
    return {"decode_step_ms": None if step_s is None else 1e3 * step_s}

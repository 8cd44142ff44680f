"""Runner kind ``jamba_serve_replay``: ``runners/lm_serve_replay.py``'s cell
for a ``JambaLM`` -- the real ``serve.ContinuousEngine`` with every decode
slot full by construction, sessions prefilled in set-up through the
engine's own admission, the window timing ``eng.step()`` -- with what is
tied to the model's keys brought here: ``build`` (a ``JambaConfig``, bfloat16
weights, the slot state beside the pages), the counts behind
``decode_mfu_pct`` and ``mamba_state_roofline`` (``lib/jamba_serve_counts``),
and the reference's share of the sessions. ``served``, ``engine_span_sums``,
``finish``, ``end_to_end`` and the window's rule are ``lm_serve_replay``'s.
The engine dispatches a step's decode call ahead of its reading of the one
before (``engine._decode_ahead``), so a step is the device's and a pause of
the host longer than that shows whole: set-up ends with the heap settled
(``settle_heap``), and ``served_batch`` settles the engine before it reads
the state.

``correct``: as that runner's (every session gains exactly one token in
every measured step, none is preempted or retires, six whole steps), and
after the window (``verify``: the memory peak read, pages and state freed)
the plain float32 reference's one full forward over prompt and served
tokens of ``reference_sessions`` sessions -- the longest, the shortest and
the rest dealt by the seed -- a session at a time, compared as
``reference/jamba.py::compare_served`` compares. Prefill through a padded
bucket and then every served token through the recurrent state and the
paged cache must agree with a forward that has neither."""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from benchmark.lib import jamba_serve_counts, manifest, traffic
from benchmark.lib.observe import Observations
from benchmark.runners.lm_serve_replay import (Session,  # noqa: F401
                                               end_to_end, engine_span_sums,
                                               finish, served)

#: a reference forward's width: a session's positions rounded up to this
#: (five shapes at most between the shortest session and 5120 positions)
REFERENCE_PAD = 1024
_STREAM_REFERENCE = 41  # beside ``lib/traffic.py``'s streams of the seed


def serve_config(config: dict, dep: dict):
    """The cell's ``ServeConfig``: the model as published, the deployment's
    types and geometry."""
    import jax.numpy as jnp

    from tpu_sandbox.models.jamba import JambaConfig
    from tpu_sandbox.serve import CacheConfig, ServeConfig

    types = {"bf16": jnp.bfloat16, "float32": jnp.float32,
             "fp32": jnp.float32}
    mcfg = JambaConfig.from_dict(
        config, dtype=types[dep["dtype"]],
        param_dtype=types[dep["param_dtype"]],
        state_dtype=types[dep["state_dtype"]],
        flash=bool(dep.get("flash", False)),
        scan_chunk=int(dep.get("scan_chunk", 64)))
    cache = CacheConfig(num_blocks=dep["num_blocks"],
                        block_size=dep["block_size"],
                        max_blocks_per_seq=dep["max_blocks_per_seq"])
    return ServeConfig(model=mcfg, cache=cache, max_batch=dep["max_batch"],
                       buckets=tuple(dep["prefill_buckets"]),
                       cache_dtype=types[dep["cache_dtype"]], eos_token=None)


def build(config: dict, dep: dict, seed: int, facts: dict):
    """As ``lm_serve.build``: the weights in one jitted call from the seed,
    then the five programs, then the engine."""
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.jamba import JambaLM
    from tpu_sandbox.serve import ContinuousEngine
    from tpu_sandbox.serve.decode import build_decode_step

    scfg = serve_config(config, dep)
    t0 = time.perf_counter()
    inputs = jax.block_until_ready(
        (jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))
    # the kernels of the prefill path play no part in what init draws
    init = JambaLM(dataclasses.replace(scfg.model, flash=False)).init
    params = jax.jit(init)(*inputs)["params"]
    jax.block_until_ready(params)
    del inputs
    t1 = time.perf_counter()
    facts["init_s"] = t1 - t0
    facts["parameters"] = float(sum(x.size for x in jax.tree.leaves(params)))
    compiled_before = facts.get("jax_compile_s", 0.0)
    step = build_decode_step(scfg.model, scfg.cache, max_batch=scfg.max_batch,
                             buckets=scfg.buckets,
                             cache_dtype=scfg.cache_dtype)
    facts["compile_s"] = facts.get("jax_compile_s", 0.0) - compiled_before
    facts["trace_lower_s"] = time.perf_counter() - t1 - facts["compile_s"]
    eng = ContinuousEngine(params, scfg, step=step, clock=time.perf_counter)
    return eng, params


def settle_heap(obs: Observations):
    """What a replica's launcher does once it is warm: one full collection,
    then everything start-up left on the heap (the five programs' traces,
    the modules: a quarter of a million objects) goes out of the
    collector's sight. Left there, a collection of the oldest generation
    inside a window walks all of it — 100-200 ms of the host while the
    device holds one call ahead: 3 of 14 runs read 0.2-0.4 ms a step more
    (PERF.md section 6). Those that still happen in a window are noted, in
    ms (``full_collections_ms``). ``verify`` undoes both."""
    gc.collect()
    gc.freeze()
    started = []

    def note(phase: str, info: dict) -> None:
        if info["generation"] < 2 or not obs.in_window:
            return
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            obs.notes.setdefault("full_collections_ms", []).append(
                1e3 * (time.perf_counter() - started.pop()))

    gc.callbacks.append(note)
    return note


def setup(obs: Observations) -> Session:
    from tpu_sandbox.serve import Request

    cell = obs.cell
    spec = cell["traffic"]
    eng, params = build(cell["config"], cell["deployment"], obs.seed,
                        obs.facts)
    # the one program the window runs: its scopes give the mixers, the
    # state's update, write_kv and gather_ctx a device time
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
    session = Session(eng, params, sessions)
    session.heap_watch = settle_heap(obs)
    return session


def measure(obs: Observations, session: Session, seconds: float) -> None:
    """``lm_serve_replay.measure``, line for line down to the counts:
    ``eng.step()`` until ``seconds`` have passed or the next step would
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
            jamba_serve_counts.decode_step_flops(config, mean)
        obs.facts["decode_bytes_per_step"] = \
            jamba_serve_counts.decode_step_bytes(config, mean)
        obs.facts["mamba_state_bytes_per_step"] = \
            jamba_serve_counts.state_update_bytes(config, len(mean))
        obs.notes["live_context_tokens"] = float(mean.sum())


def reference_sessions(slots: list, count: int, seed: int) -> list:
    """The sessions the reference sees: the longest prompt, the shortest,
    and the rest of ``count`` dealt by the seed."""
    by_length = sorted(slots, key=lambda s: len(s.request.prompt))
    ends = by_length[:1] + by_length[-1:] if len(slots) > 1 else by_length
    rest = by_length[1:-1]
    rng = np.random.default_rng([int(seed), _STREAM_REFERENCE])
    take = rng.permutation(len(rest))[:max(0, count - len(ends))]
    return ends + [rest[i] for i in sorted(take)]


def served_batch(eng, dep: dict, seed: int) -> dict | None:
    """What the engine served, as the reference takes it: for each chosen
    session that holds a slot its prompt and served tokens but the last
    (``tokens``, a list: the sessions' lengths differ), the positions whose
    logits chose a served token (``rows``) and those tokens (``chosen``),
    how many (``counts``), and the engine's own mean log-probability of
    them (``system``); and the first Mamba layer's scan state of its slot
    as the window left it (``state [n, N, D]``: every token but the last
    served one has passed through it). The engine dispatches a step's call
    ahead of the step: the last one is settled first — its tokens emitted,
    a session's last among them perhaps, so the slots are held before."""
    held = [s for s in eng.slots if s is not None]
    if not held:
        return None
    slot_of = {id(s): i for i, s in enumerate(eng.slots) if s is not None}
    eng.settle()
    slots = reference_sessions(held, int(dep.get("reference_sessions", 16)),
                               seed)
    where = [slot_of[id(s)] for s in slots]
    n, width = len(slots), max(len(s.generated) for s in slots)
    batch = {"n": n, "rids": [s.request.rid for s in slots], "tokens": [],
             "rows": np.zeros((n, width), np.int32),
             "chosen": np.zeros((n, width), np.int32),
             "counts": np.ones(n, np.int64), "system": np.zeros(n),
             "state": np.asarray(eng.state["ssm"][0][0][np.asarray(where)],
                                 np.float32)}
    for i, s in enumerate(slots):
        plen, g = len(s.request.prompt), len(s.generated)
        batch["tokens"].append(np.asarray(s.tokens[:-1], np.int32))
        batch["rows"][i, :g] = plen - 1 + np.arange(g)
        batch["chosen"][i, :g] = s.generated
        batch["counts"][i] = g
        batch["system"][i] = s.logprob_sum / g
    return batch


def reference_rows(reference, tree, batch: dict, config: dict, chosen=None,
                   pad: int = REFERENCE_PAD, **precision) -> dict:
    """``reference.served_rows`` over the batch, a session at a time, each
    at its own width (rounded up to ``pad``: causal, so the zeros behind a
    session reach no row that counts)."""
    chosen = batch["chosen"] if chosen is None else chosen
    parts = []
    for i, tokens in enumerate(batch["tokens"]):
        padded = np.zeros((1, len(tokens) + -len(tokens) % pad), np.int32)
        padded[0, :len(tokens)] = tokens
        parts.append(reference.served_rows(
            tree, padded, batch["rows"][i:i + 1], chosen[i:i + 1], config,
            last=[len(tokens) - 1], **precision))
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def verify(obs: Observations, session: Session) -> None:
    """After the window, the memory peak read: free the engine's pages and
    state, then the reference over the chosen sessions."""
    import jax

    t0 = time.perf_counter()
    gc.callbacks.remove(session.heap_watch)
    gc.unfreeze()
    eng, config, dep = session.eng, obs.cell["config"], obs.cell["deployment"]
    reference = manifest.module("reference", obs.cell["reference"])
    batch = session.batch = served_batch(eng, dep, obs.seed)
    if batch is None:
        obs.problem("no session holds a slot after the window: nothing to "
                    "compare with the reference")
        return
    eng.drain_to_requests()
    for buffer in jax.tree.leaves((eng.k_pages, eng.v_pages, eng.state)):
        buffer.delete()
    tree = reference.from_program_tree(session.params, config)
    out = reference_rows(reference, tree, batch, config,
                         pad=int(dep.get("reference_pad", REFERENCE_PAD)))
    dev, bad = reference.compare_served(
        out["gap_rel"], out["logprob"], batch["counts"], batch["system"],
        out["slow_state"], reference.slow_states(tree, batch["state"]))
    for text in bad:
        obs.problem(text)
    obs.notes["reference_deviation"] = dev
    obs.notes["reference_sessions"] = batch["rids"]
    obs.notes["compared_tokens"] = int(batch["counts"].sum())
    obs.notes["compared"] = {k: {"value": v, "limit": reference.TOLERANCE[k]}
                             for k, v in dev.items()}
    obs.facts["after_window_check_s"] = time.perf_counter() - t0

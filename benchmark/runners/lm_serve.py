"""Runner kind ``lm_serve``: the in-process continuous-batching engine
(``serve.ContinuousEngine`` over ``build_decode_step``) under an open-loop
arrival schedule. One thread submits what is due and steps the engine, as a
replica's loop does. The benchmark sees what a caller of the engine sees:
a token exists when the ``step()`` that produced it has returned.

No cell of ``BENCHMARK.json`` uses this runner yet (PERF.md section 7, first
row): it is kept, with ``workloads/gpt2m_serve_chat.json`` and
``traffic/chat_poisson.json``, for the ``benchmark`` PR that adds the serving
cell once a decode step no longer takes a second."""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from benchmark.lib import manifest, stats, traffic
from benchmark.lib.observe import Observations

#: requests still without a first token this long after the window (as a
#: share of the window) count as failed
DRAIN_SHARE = 0.1


@dataclass
class Session:
    eng: Any
    arrivals: list
    log: "TokenLog | None" = None
    window_rids: set = field(default_factory=set)


@dataclass
class TokenLog:
    """Per request: when it was due, admitted, and when each token became
    visible (all on the benchmark's clock, seconds from the window start)."""
    due: dict = field(default_factory=dict)
    seen: dict = field(default_factory=dict)       # rid -> tokens visible
    last: dict = field(default_factory=dict)       # rid -> last stamp
    first: dict = field(default_factory=dict)      # rid -> first-token stamp
    admitted: dict = field(default_factory=dict)   # rid -> admit stamp
    gaps: list = field(default_factory=list)       # (stamp, gap)
    tokens: list = field(default_factory=list)     # (stamp, count)
    step_ends: list = field(default_factory=list)  # stamps of step() returns
    progress: dict = field(default_factory=dict)   # rid -> tokens in its slot
    decoded: int = 0                               # slots that decoded, this step
    done: dict = field(default_factory=dict)       # rid -> tokens at retire
    inflight: set = field(default_factory=set)     # submitted, not retired

    def token(self, rid: str, n: int, now: float) -> None:
        had = self.seen.get(rid, 0)
        if n != self.progress.get(rid, 0):
            self.decoded += 1  # the engine spent a slot on it this step
        self.progress[rid] = n
        if n <= had:
            return  # a preempted request replaying tokens already seen
        if had == 0:
            self.first[rid] = now
            fresh = n - 1
        else:
            self.gaps.append((now, now - self.last[rid]))
            fresh = n - had - 1
        # tokens that became visible together arrived with no gap
        self.gaps.extend((now, 0.0) for _ in range(fresh))
        self.tokens.append((now, n - had))
        self.seen[rid], self.last[rid] = n, now

    def after_step(self, eng, step_start: float, now: float) -> int:
        """Read the engine's slots and results; returns how many requests
        this step admitted."""
        admitted = 0
        self.decoded = 0
        self.step_ends.append(now)
        for slot in eng.slots:
            if slot is None:
                continue
            rid = slot.request.rid
            if rid not in self.admitted:
                self.admitted[rid] = step_start
                admitted += 1
            self.token(rid, len(slot.generated), now)
        # retired this step; a short request can be admitted and retired in
        # one step and never be seen in a slot
        for rid in [r for r in self.inflight if r in eng.results]:
            if rid not in self.admitted:
                self.admitted[rid] = step_start
                admitted += 1
            self.token(rid, len(eng.results[rid].tokens), now)
            self.done[rid] = len(eng.results[rid].tokens)
            self.inflight.discard(rid)
        return admitted


def build(config: dict, dep: dict, seed: int, facts: dict):
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.serve import (CacheConfig, ContinuousEngine, ServeConfig)
    from tpu_sandbox.serve.decode import build_decode_step

    dtype = jnp.bfloat16 if dep["dtype"] == "bf16" else jnp.float32
    cache_dtype = jnp.bfloat16 if dep["cache_dtype"] == "bf16" else jnp.float32
    mcfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=config["n_positions"], dtype=dtype)
    cache = CacheConfig(num_blocks=dep["num_blocks"],
                        block_size=dep["block_size"],
                        max_blocks_per_seq=dep["max_blocks_per_seq"])
    scfg = ServeConfig(model=mcfg, cache=cache, max_batch=dep["max_batch"],
                       buckets=tuple(dep["prefill_buckets"]),
                       cache_dtype=cache_dtype, eos_token=None)
    t0 = time.perf_counter()
    # the weights in one jitted call from the seed (eagerly, flax's init is
    # a program an operation: 14 s of set-up at gpt2-medium, PR 40). Its two
    # small inputs are ready before it starts and die after it has ended, so
    # that no release is deferred past the call (this alone did not settle
    # where the allocator puts the pages: PERF.md section 2, the two modes)
    inputs = jax.block_until_ready(
        (jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))
    params = jax.jit(TransformerLM(mcfg).init)(*inputs)["params"]
    jax.block_until_ready(params)
    del inputs
    t1 = time.perf_counter()
    facts["init_s"] = t1 - t0
    # build_decode_step lowers and compiles in one call: the split comes
    # from jax's own compile clock, which the harness adds up
    # (run.watch_compiles); the rest of the call is tracing and lowering
    compiled_before = facts.get("jax_compile_s", 0.0)
    step = build_decode_step(mcfg, cache, max_batch=scfg.max_batch,
                             buckets=scfg.buckets, cache_dtype=cache_dtype)
    facts["compile_s"] = facts.get("jax_compile_s", 0.0) - compiled_before
    facts["trace_lower_s"] = time.perf_counter() - t1 - facts["compile_s"]
    eng = ContinuousEngine(params, scfg, step=step, clock=time.perf_counter)
    return eng, params


def check_against_reference(obs: Observations, eng, params) -> None:
    """One seeded prompt through the engine as a caller drives it
    (``submit``, ``step`` until idle), against the reference's full forward
    over the prompt and the engine's own tokens. Nothing of the engine's
    inside is touched — not its compiled programs, cache or allocator — so
    a PR that changes those cannot break the check. Tokens are not compared
    with the reference's argmax (with random weights the largest logit
    changes on rounding): each chosen token must be *nearly* the largest
    under the reference, and the mean log-probability the program reports
    for its chosen tokens (its ``engine.logprob`` series) must be the
    reference's log-softmax at those tokens."""
    from tpu_sandbox.obs.metrics import get_registry
    from tpu_sandbox.serve import Request

    config, dep = obs.cell["config"], obs.cell["deployment"]
    reference = manifest.module("reference", obs.cell["reference"])
    plen, n_new = int(dep["check_prompt_len"]), int(dep["check_decode_steps"]) + 1
    rng = np.random.default_rng([obs.seed, 97])
    prompt = [int(t) for t in rng.integers(1, config["vocab_size"], size=plen)]
    series = get_registry().histogram("engine.logprob")
    count, total = series.count, series.total
    eng.submit(Request(rid="check", prompt=prompt, max_new_tokens=n_new,
                       arrival=time.perf_counter()))
    eng.run_until_idle()
    result = eng.results.pop("check", None)
    eng.cache.flush_prefix_cache()
    if result is None or len(result.tokens) != n_new \
            or series.count != count + 1:
        obs.problem(f"the check request did not return {n_new} tokens and "
                    "one engine.logprob sample")
        return
    tokens = np.asarray(result.tokens)
    full = np.asarray(prompt + result.tokens[:-1], np.int32)[None, :]
    ref_logits, _ = reference.logits_and_loss(
        reference.from_program_tree(params, config["n_layer"]), full,
        np.zeros_like(full), n_head=config["n_head"],
        eps=config["layer_norm_epsilon"])
    dev, bad = reference.compare_chosen_tokens(
        np.asarray(ref_logits)[0, plen - 1:], tokens, series.total - total)
    obs.notes["reference_deviation"] = dev
    for text in bad:
        obs.problem(text)


def warm(obs: Observations, eng) -> None:
    """Run every prefill bucket and the decode step once through the
    engine, and hold each finished request to its token count."""
    from tpu_sandbox.serve import Request

    rng = np.random.default_rng([obs.seed, 96])
    vocab = obs.cell["config"]["vocab_size"]
    for i, bucket in enumerate(eng.step_fns.buckets):
        prompt = [int(t) for t in rng.integers(1, vocab, size=bucket - 3)]
        eng.submit(Request(rid=f"warm{i}", prompt=prompt, max_new_tokens=4,
                           arrival=time.perf_counter()))
    eng.run_until_idle()
    for i in range(len(eng.step_fns.buckets)):
        got = len(eng.results[f"warm{i}"].tokens)
        if got != 4:
            obs.problem(f"warm-up request {i} holds {got} tokens, not 4")
    eng.results.clear()
    eng.cache.flush_prefix_cache()


def setup(obs: Observations) -> Session:
    cell = obs.cell
    eng, params = build(cell["config"], cell["deployment"], obs.seed,
                        obs.facts)
    # jit_serve_prefill (one program a bucket) and jit_serve_decode: their
    # scopes, so that write_kv / gather_ctx have a device time
    for program in (*eng.step_fns.prefill.values(), eng.step_fns.decode):
        obs.note_program(program.as_text())
    t0 = time.perf_counter()
    check_against_reference(obs, eng, params)
    obs.facts["reference_check_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm(obs, eng)
    obs.facts["warmup_s"] = time.perf_counter() - t0
    arrivals = traffic.requests(cell["traffic"], obs.seed, obs.seconds,
                                cell["config"]["vocab_size"])
    return Session(eng, arrivals)


def measure(obs: Observations, session: Session, seconds: float) -> None:
    """Open loop: a request is submitted when it is due whether or not
    earlier ones have finished, and is timed from its due time."""
    from tpu_sandbox.serve import Request

    eng = session.eng
    spec = obs.cell["traffic"]
    warmup = float(spec.get("warmup_s", 0.0))
    pending = deque(a for a in session.arrivals if a.due_s < seconds)
    log = session.log = TokenLog()
    window = {a.rid for a in pending if a.due_s >= 0.0}
    t0 = time.perf_counter() + warmup  # the window starts after the warm-up mix
    limit = seconds * (1.0 + DRAIN_SHARE)
    while True:
        now = time.perf_counter() - t0
        with obs.span("submit", record=False):
            while pending and pending[0].due_s <= now:
                a = pending.popleft()
                log.due[a.rid] = a.due_s
                log.inflight.add(a.rid)
                eng.submit(Request(rid=a.rid, prompt=list(a.prompt),
                                   max_new_tokens=a.max_new_tokens,
                                   arrival=t0 + a.due_s))
                if a.due_s >= 0.0:
                    obs.add("gen_late_s", time.perf_counter() - t0 - a.due_s)
        if now >= seconds and all(r in log.first or r in eng.shed
                                  for r in window):
            break
        if now >= limit:
            break
        if eng.idle:
            with obs.span("idle_wait", record=False):
                time.sleep(max(0.0, min(1e-3, pending[0].due_s - now))
                           if pending else 1e-3)
            continue
        start = time.perf_counter() - t0
        with obs.span("eng.step", record=False):
            eng.step()
        end = time.perf_counter() - t0
        admitted = log.after_step(eng, start, end)
        if 0.0 <= start and end < seconds:
            obs.add("step_s", end - start)
            if not admitted:
                obs.add("decode_step_s", end - start)
            obs.add("occupancy_pct",
                    100.0 * log.decoded / eng.config.max_batch)
            obs.add("waiting", float(len(eng.waiting)))
    obs.notes["window_requests"] = len(window)
    session.window_rids = window


def finish(obs: Observations, session: Session) -> None:
    eng, log, seconds = session.eng, session.log, obs.seconds
    window = session.window_rids
    want = {a.rid: a.max_new_tokens for a in session.arrivals}
    eng.drain_to_requests()  # leave nothing in flight
    wrong = [r for r, n in log.done.items() if n != want[r]]
    if wrong:
        obs.problem(f"{len(wrong)} finished requests do not hold exactly "
                    f"their max_new_tokens tokens: {wrong[:5]}")
    obs.attempted = len(window)
    obs.failed = (len([r for r in window if r not in log.first])
                  + len([r for r in wrong if r in window]))
    ttft = [log.first[r] - log.due[r] for r in window if r in log.first]
    obs.series["ttft_s"] = ttft
    obs.series["itl_s"] = [g for t, g in log.gaps if 0.0 <= t < seconds]
    obs.series["queue_wait_s"] = [log.admitted[r] - log.due[r]
                                  for r in window if r in log.admitted]
    # tokens per second over whole engine steps: from the first to the last
    # step that ended inside the window, so that a window's edges cutting a
    # one-second step do not move the rate by a step's worth of tokens
    ends = [t for t in log.step_ends if 0.0 <= t < seconds]
    if len(ends) >= 2:
        obs.facts["rate_span_s"] = ends[-1] - ends[0]
        obs.facts["rate_tokens"] = float(sum(
            n for t, n in log.tokens if ends[0] < t <= ends[-1]))
    obs.facts["preemptions"] = float(sum(
        r.preemptions for r in eng.results.values()))
    obs.notes.update(
        ttft_n=len(ttft), itl_n=len(obs.series["itl_s"]),
        finished=len([r for r in window if r in log.done]),
        shed=len([r for r in window if r in eng.shed]),
        backlog=len(log.inflight - set(log.admitted)),
        steps=len(obs.series.get("step_s", [])),
        rate_per_s=obs.cell["traffic"]["rate_per_s"])


def end_to_end(obs: Observations) -> dict:
    ms = lambda v: None if v is None else 1e3 * v  # noqa: E731
    return {
        "serve_tok_per_s": (obs.facts["rate_tokens"] / obs.facts["rate_span_s"]
                            if obs.facts.get("rate_span_s") else None),
        "ttft_p90_ms": ms(stats.percentile_failed_last(
            obs.series["ttft_s"], obs.failed, 90,
            failed_value=obs.seconds * (1.0 + DRAIN_SHARE))),
        "itl_p99_ms": ms(stats.percentile(obs.series["itl_s"], 99)),
    }

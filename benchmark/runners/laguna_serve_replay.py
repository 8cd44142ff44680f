"""Runner kind ``laguna_serve_replay``: ``runners/longcat_serve_replay.py``'s
cell for a ``LagunaLM`` -- the real ``serve.ContinuousEngine`` with every
decode slot full by construction, sessions prefilled in set-up through the
engine's own admission, the window timing ``eng.step()`` -- with what is
tied to the model's keys brought here: ``build`` (a ``LagunaConfig``,
bfloat16 weights with the router's drawn bias, pages by the layer's kind from
the cache's two pools), the counts behind ``decode_mfu_pct``,
``full_ctx_roofline``, ``window_ctx_roofline`` and
``serve_moe_experts_roofline`` (``lib/laguna_serve_counts``), the allocator's
window counters, and the reference's share of the sessions. ``served``,
``engine_span_sums``, ``finish``, ``end_to_end`` and the window's rule are
``lm_serve_replay``'s; ``settle_heap`` and ``reference_sessions`` Jamba's
runner's; ``share_counters``, ``served_batch`` and ``reference_rows``
LongCat's.

``correct``: as that runner's (every session gains exactly one token in
every measured step, none is preempted or retires, six whole steps), no row
of an expert share dropped, no sequence over its ring of window blocks, and
after the window (``verify``: the memory peak read, pages freed) the plain
float32 reference's one full forward over prompt and served tokens of
``reference_sessions`` sessions -- the longest, the shortest and the rest
dealt by the seed -- a session at a time, compared as
``reference/laguna.py::compare_served`` compares. Prefill through a padded
bucket (the band of the flash kernel on the window layers, a prompt's last
window alone stored for them) and then every served token through the two
paged caches must agree with a forward that has neither."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import laguna_serve_counts, manifest, traffic
from benchmark.lib.observe import Observations
from benchmark.runners.jamba_serve_replay import settle_heap
from benchmark.runners.lm_serve_replay import (Session,  # noqa: F401
                                               end_to_end, engine_span_sums,
                                               finish, served)
from benchmark.runners.longcat_serve_replay import (reference_rows,
                                                    served_batch,
                                                    share_counters)

#: a reference forward's width: a session's positions rounded up to this
#: (the reference's own block of queries)
REFERENCE_PAD = 1024


def serve_config(config: dict, dep: dict):
    """The cell's ``ServeConfig``: the model as published, the deployment's
    types and geometry; the cache's window is the model's."""
    import jax.numpy as jnp

    from tpu_sandbox.models.laguna import LagunaConfig
    from tpu_sandbox.serve import CacheConfig, ServeConfig

    types = {"bf16": jnp.bfloat16, "float32": jnp.float32,
             "fp32": jnp.float32}
    mcfg = LagunaConfig.from_dict(
        {**config, "deployment": dep}, dtype=types[dep["dtype"]],
        param_dtype=types[dep["param_dtype"]],
        flash=bool(dep.get("flash", False)))
    cache = CacheConfig(num_blocks=dep["num_blocks"],
                        block_size=dep["block_size"],
                        max_blocks_per_seq=dep["max_blocks_per_seq"],
                        window=mcfg.sliding_window,
                        window_blocks=dep["window_blocks"])
    return ServeConfig(model=mcfg, cache=cache, max_batch=dep["max_batch"],
                       buckets=tuple(dep["prefill_buckets"]),
                       cache_dtype=types[dep["cache_dtype"]], eos_token=None)


def random_weights(mcfg, key, random_init: dict) -> dict:
    """``{"params", "router_bias"}`` from ``key``: the model's own init
    (norm scales 1, a zero ``e_score_correction_bias``) with the router's
    bias drawn (``router_bias_std``: a zero bias would leave its add
    untested). Nothing else is touched: under this init the scores' spread
    is 1.0 on a window layer and 1.58 on a full one (the configuration
    file's ``assumed.score_spread``)."""
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models import laguna
    from tpu_sandbox.models.longcat_flash import split_stats

    k_init, k_bias = jax.random.split(key)
    variables = laguna.LagunaLM(mcfg).init(k_init,
                                           jnp.zeros((1, 8), jnp.int32))
    bias, _ = split_stats(variables["batch_stats"])
    for i, name in enumerate(sorted(bias)):
        bias[name] = random_init["router_bias_std"] * jax.random.normal(
            jax.random.fold_in(k_bias, i), (mcfg.num_experts,), jnp.float32)
    return {"params": variables["params"], "router_bias": bias}


def build(config: dict, dep: dict, seed: int, facts: dict):
    """As ``lm_serve.build``: the weights (and the router's bias) in one
    jitted call from the seed, then the programs, then the engine."""
    import dataclasses

    import jax

    from tpu_sandbox.serve import ContinuousEngine
    from tpu_sandbox.serve.decode import build_decode_step

    scfg = serve_config(config, dep)
    t0 = time.perf_counter()
    key = jax.block_until_ready(jax.random.key(seed))
    # the kernels of the prefill path play no part in what init draws
    plain = dataclasses.replace(scfg.model, flash=False)
    params = jax.block_until_ready(jax.jit(
        lambda key: random_weights(plain, key, config["random_init"]))(key))
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


def recycled() -> float:
    """``cache.window_blocks_recycled`` so far (the registry counts the
    whole process: the window's share is a difference)."""
    from tpu_sandbox.obs import get_registry

    return float(get_registry().snapshot()["counters"].get(
        "cache.window_blocks_recycled", 0))


def setup(obs: Observations) -> Session:
    from tpu_sandbox.serve import Request

    cell = obs.cell
    spec = cell["traffic"]
    eng, params = build(cell["config"], cell["deployment"], obs.seed,
                        obs.facts)
    # the one program the window runs: its scopes give the layers, the
    # expert share, write_kv and the two gather_ctx reads a device time
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
    session.counters_before = share_counters(eng)
    session.recycled_before = recycled()
    if session.counters_before.get("rows_dropped"):
        obs.problem(f"{session.counters_before['rows_dropped']} rows of an "
                    "expert share dropped in set-up (prefill or warm-up)")
    session.heap_watch = settle_heap(obs)
    return session


def measure(obs: Observations, session: Session, seconds: float) -> None:
    """``lm_serve_replay.measure``, line for line down to the counts:
    ``eng.step()`` until ``seconds`` have passed or the next step would
    retire a session, whichever comes first; then the shares' counters and
    the allocator's, read once."""
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
    after = share_counters(eng)
    moved = {k: after[k] - session.counters_before.get(k, 0) for k in after}
    config = {**obs.cell["config"], "deployment": obs.cell["deployment"]}
    counts = laguna_serve_counts
    # the device's own count of the calls between the two readings, every
    # sparse layer's counted; the rows the held experts were given a call a
    # layer: the even router's mean where the program counted none
    calls = moved.get("steps", 0)
    rows = counts.mean_held_rows(config, eng.config.max_batch)
    if calls:
        from tpu_sandbox.models.laguna import expert_share

        share = expert_share(eng.config.model, eng.config.max_batch, "moe")
        rows = moved["rows_held"] / calls  # a call, a layer
        obs.facts["serve_moe_pad_pct"] = \
            100.0 * (1.0 - rows / share.local_rows)
        obs.facts["serve_moe_rows_dropped"] = moved["rows_dropped"] / calls
        obs.notes["share_counters"] = dict(
            moved, buffer_rows=share.local_rows, row_tile=share.row_tile,
            expert_rows_max=after["expert_rows_max"])
        if moved["rows_dropped"]:
            obs.problem(f"{moved['rows_dropped']} rows of an expert share "
                        "dropped inside the window")
    if session.steps:
        obs.facts["window_blocks_recycled"] = \
            (recycled() - session.recycled_before) / session.steps
    stats, ring = eng.cache.stats, eng.config.cache.ring_blocks
    obs.notes["window_blocks"] = {
        "seq_max": stats.get("window_blocks_seq_max", 0), "ring": ring,
        "free": eng.cache.free_window_blocks, "free_full": eng.cache.free_blocks,
        "prefix_reuse_declined": stats["prefix_reuse_declined"]}
    if stats.get("window_blocks_seq_max", 0) > ring:
        obs.problem(f"a sequence owned {stats['window_blocks_seq_max']} "
                    f"window blocks: more than its ring of {ring}")
    # what the measured steps needed, on the contexts they had (a context
    # grows by one a step: the mean step) and the rows the router gave
    if contexts:
        mean = np.mean(np.asarray(contexts, np.float64), axis=0)
        obs.facts["decode_flops_per_step"] = \
            counts.decode_step_flops(config, mean, rows)
        obs.facts["decode_bytes_per_step"] = \
            counts.decode_step_bytes(config, mean, rows)
        for kind in ("full", "window"):
            obs.facts[f"{kind}_ctx_flops_per_step"] = \
                counts.ctx_flops(config, mean, kind)
            obs.facts[f"{kind}_ctx_bytes_per_step"] = \
                counts.ctx_bytes(config, mean, kind)
        obs.facts["moe_experts_bytes_per_step"] = \
            counts.experts_bytes(config, rows)
        obs.facts["moe_experts_flops_per_step"] = \
            counts.experts_flops(config, rows)
        obs.notes["live_context_tokens"] = float(mean.sum())
        obs.notes["live_window_rows"] = counts.live_rows(config, mean,
                                                         "window")


def verify(obs: Observations, session: Session) -> None:
    """After the window, the memory peak read: free the engine's pages,
    then the reference over the chosen sessions."""
    t0 = time.perf_counter()
    gc.callbacks.remove(session.heap_watch)
    gc.unfreeze()
    eng, dep = session.eng, obs.cell["deployment"]
    # the reference reads the deployment (the experts held, the router's
    # published width) where the cell's file may override the config's
    config = {**obs.cell["config"], "deployment": dep}
    reference = manifest.module("reference", obs.cell["reference"])
    batch = session.batch = served_batch(eng, dep, obs.seed)
    if batch is None:
        obs.problem("no session holds a slot after the window: nothing to "
                    "compare with the reference")
        return
    eng.drain_to_requests()
    eng.k_pages.delete()
    eng.v_pages.delete()
    tree = reference.from_program_tree(session.params, config)
    out = reference_rows(reference, tree, batch, config,
                         pad=int(dep.get("reference_pad", REFERENCE_PAD)))
    session.reference_rows = out   # the sweep reads the sessions one by one
    dev, bad = reference.compare_served(
        out["gap_rel"], out["logprob"], batch["counts"], batch["system"])
    for text in bad:
        obs.problem(text)
    obs.notes["reference_deviation"] = dev
    obs.notes["reference_sessions"] = batch["rids"]
    obs.notes["compared_tokens"] = int(batch["counts"].sum())
    obs.notes["compared"] = {k: {"value": v, "limit": reference.TOLERANCE[k]}
                             for k, v in dev.items()}
    obs.facts["after_window_check_s"] = time.perf_counter() - t0

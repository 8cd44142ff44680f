"""Runner kind ``longcat_serve_replay``: ``runners/lm_serve_replay.py``'s cell
for a ``LongcatFlashLM`` -- the real ``serve.ContinuousEngine`` with every
decode slot full by construction, sessions prefilled in set-up through the
engine's own admission, the window timing ``eng.step()`` -- with what is
tied to the model's keys brought here: ``build`` (a ``LongcatFlashConfig``,
bfloat16 weights with the router's drawn bias, the latent pages and the
expert shares' counters beside them), the counts behind ``decode_mfu_pct``,
``latent_ctx_roofline`` and ``serve_moe_experts_roofline``
(``lib/longcat_serve_counts``), the shares' device counters read once before
and once after the window, and the reference's share of the sessions.
``served``, ``engine_span_sums``, ``finish``, ``end_to_end`` and the window's
rule are ``lm_serve_replay``'s; ``settle_heap`` and ``reference_sessions``
Jamba's runner's. The engine dispatches a step's decode call ahead of its
reading of the one before (``engine._decode_ahead``), so set-up ends with the
heap settled, and ``served_batch`` settles the engine before it reads.

``correct``: as that runner's (every session gains exactly one token in
every measured step, none is preempted or retires, six whole steps), no row
of an expert share dropped, and after the window (``verify``: the memory
peak read, pages freed) the plain float32 reference's one full forward over
prompt and served tokens of ``reference_sessions`` sessions -- the longest,
the shortest and the rest dealt by the seed -- a session at a time,
compared as ``reference/longcat_flash.py::compare_served`` compares.
Prefill in the expanded form through a padded bucket and then every served
token in the absorbed form through the latent paged cache must agree with a
forward that has neither."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import longcat_serve_counts, manifest, traffic
from benchmark.lib.observe import Observations
from benchmark.runners.jamba_serve_replay import (reference_sessions,
                                                  settle_heap)
from benchmark.runners.lm_serve_replay import (Session,  # noqa: F401
                                               end_to_end, engine_span_sums,
                                               finish, served)

#: a reference forward's width: a session's positions rounded up to this
#: (seven shapes at most between the shortest session and 7168 positions)
REFERENCE_PAD = 1024


def serve_config(config: dict, dep: dict):
    """The cell's ``ServeConfig``: the model as published, the deployment's
    types and geometry."""
    import jax.numpy as jnp

    from tpu_sandbox.models.longcat_flash import LongcatFlashConfig
    from tpu_sandbox.serve import CacheConfig, ServeConfig

    types = {"bf16": jnp.bfloat16, "float32": jnp.float32,
             "fp32": jnp.float32}
    mcfg = LongcatFlashConfig.from_dict(
        {**config, "deployment": dep}, dtype=types[dep["dtype"]],
        param_dtype=types[dep["param_dtype"]],
        flash=bool(dep.get("flash", False)))
    cache = CacheConfig(num_blocks=dep["num_blocks"],
                        block_size=dep["block_size"],
                        max_blocks_per_seq=dep["max_blocks_per_seq"])
    return ServeConfig(model=mcfg, cache=cache, max_batch=dep["max_batch"],
                       buckets=tuple(dep["prefill_buckets"]),
                       cache_dtype=types[dep["cache_dtype"]], eos_token=None)


def random_weights(mcfg, key, random_init: dict) -> dict:
    """``{"params", "router_bias"}`` from ``key``: the model's own init
    (norm scales 1, a zero ``e_score_correction_bias``) with the two things
    the configuration file's ``random_init`` gives random weights in a
    trained model's place (its ``assumed`` says why):

    - ``router_bias_std``: the router's bias drawn with it (a zero bias
      would leave its add untested);
    - ``inner_norm_scales`` ``inverse_mla_scale``: the two inner norms'
      scales at the inverse of the published factors (``q_a_norm`` at ``1 /
      sqrt(hidden / q_lora_rank)``, ``kv_a_norm`` at ``1 / sqrt(hidden /
      kv_lora_rank)``), so that the scores start at a standard deviation of
      1 where the factors 2 and 3.46 alone give 5.7 -- a regime in which no
      bfloat16 program can be held to a float32 reference (PERF.md section
      7). Every equation, the two factors among them, is as published."""
    import math

    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models import longcat_flash

    k_init, k_bias = jax.random.split(key)
    variables = longcat_flash.LongcatFlashLM(mcfg).init(
        k_init, jnp.zeros((1, 8), jnp.int32))
    if random_init["inner_norm_scales"] != "inverse_mla_scale":
        raise ValueError(f"unknown inner_norm_scales in {random_init}")
    gains = {
        "q_a_norm": math.sqrt(mcfg.hidden_size / mcfg.q_lora_rank)
        if mcfg.mla_scale_q_lora else 1.0,
        "kv_a_norm": math.sqrt(mcfg.hidden_size / mcfg.kv_lora_rank)
        if mcfg.mla_scale_kv_lora else 1.0}

    def start(path, leaf):
        names = {getattr(step, "key", None) for step in path}
        for norm, gain in gains.items():
            if norm in names:
                return leaf / gain
        return leaf

    params = jax.tree_util.tree_map_with_path(start, variables["params"])
    bias, _ = longcat_flash.split_stats(variables["batch_stats"])
    for i, name in enumerate(sorted(bias)):
        bias[name] = random_init["router_bias_std"] * jax.random.normal(
            jax.random.fold_in(k_bias, i), (mcfg.router_width,), jnp.float32)
    return {"params": params, "router_bias": bias}


def build(config: dict, dep: dict, seed: int, facts: dict):
    """As ``lm_serve.build``: the weights (and the router's bias) in one
    jitted call from the seed, then the six programs, then the engine."""
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


def share_counters(eng) -> dict:
    """The expert shares' device counters, summed over the layers: one
    small read (it waits for the call the engine holds ahead)."""
    import jax

    totals: dict = {}
    for layer in jax.device_get(eng.state).values():
        for name, value in layer.items():
            join = max if name == "expert_rows_max" else int.__add__
            totals[name] = join(totals.get(name, 0), int(value))
    return totals


def setup(obs: Observations) -> Session:
    from tpu_sandbox.serve import Request

    cell = obs.cell
    spec = cell["traffic"]
    eng, params = build(cell["config"], cell["deployment"], obs.seed,
                        obs.facts)
    # the one program the window runs: its scopes give the sub-layers, the
    # expert share, write_kv and gather_ctx a device time
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
    if session.counters_before.get("rows_dropped"):
        obs.problem(f"{session.counters_before['rows_dropped']} rows of an "
                    "expert share dropped in set-up (prefill or warm-up)")
    session.heap_watch = settle_heap(obs)
    return session


def measure(obs: Observations, session: Session, seconds: float) -> None:
    """``lm_serve_replay.measure``, line for line down to the counts:
    ``eng.step()`` until ``seconds`` have passed or the next step would
    retire a session, whichever comes first; then the shares' counters,
    read once."""
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
    # the device's own count of the calls between the two readings (each
    # waits for the call the engine holds ahead: a window's steps are as
    # many calls), every layer's counted
    config = obs.cell["config"]
    calls = moved.get("steps", 0)
    # the rows the held experts were given a call a layer: the even
    # router's mean where the program counted none
    rows = longcat_serve_counts.mean_held_rows(config, eng.config.max_batch)
    if calls:
        from tpu_sandbox.models.longcat_flash import expert_share

        share = expert_share(eng.config.model, eng.config.max_batch, "moe")
        buffer_rows = share.local_rows
        rows = moved["rows_held"] / calls  # a call, a layer
        obs.facts["serve_moe_pad_pct"] = 100.0 * (1.0 - rows / buffer_rows)
        obs.facts["serve_moe_rows_dropped"] = moved["rows_dropped"] / calls
        choices = moved["real_choices"] + moved["zero_choices"]
        obs.facts["zero_choice_pct"] = 100.0 * moved["zero_choices"] / choices
        obs.notes["share_counters"] = dict(
            moved, buffer_rows=buffer_rows, row_tile=share.row_tile,
            expert_rows_max=after["expert_rows_max"])
        if moved["rows_dropped"]:
            obs.problem(f"{moved['rows_dropped']} rows of an expert share "
                        "dropped inside the window")
    # what the measured steps needed, on the contexts they had (a context
    # grows by one a step: the mean step) and the rows the router gave
    if contexts:
        mean = np.mean(np.asarray(contexts, np.float64), axis=0)
        counts = longcat_serve_counts
        obs.facts["decode_flops_per_step"] = \
            counts.decode_step_flops(config, mean, rows)
        obs.facts["decode_bytes_per_step"] = \
            counts.decode_step_bytes(config, mean, rows)
        obs.facts["latent_ctx_flops_per_step"] = \
            counts.latent_ctx_flops(config, mean)
        obs.facts["latent_ctx_bytes_per_step"] = \
            counts.latent_ctx_bytes(config, mean)
        obs.facts["moe_experts_bytes_per_step"] = \
            counts.experts_bytes(config, rows)
        obs.facts["moe_experts_flops_per_step"] = \
            counts.experts_flops(config, rows)
        obs.notes["live_context_tokens"] = float(mean.sum())


def served_batch(eng, dep: dict, seed: int) -> dict | None:
    """What the engine served, as the reference takes it: for each chosen
    session that holds a slot its prompt and served tokens but the last
    (``tokens``, a list: the sessions' lengths differ), the positions whose
    logits chose a served token (``rows``) and those tokens (``chosen``),
    how many (``counts``), and the engine's own mean log-probability of
    them (``system``). The call the engine holds ahead is settled first --
    its tokens emitted, a session's last among them perhaps, so the slots
    are held before."""
    held = [s for s in eng.slots if s is not None]
    if not held:
        return None
    eng.settle()
    slots = reference_sessions(held, int(dep.get("reference_sessions", 16)),
                               seed)
    n, width = len(slots), max(len(s.generated) for s in slots)
    batch = {"n": n, "rids": [s.request.rid for s in slots], "tokens": [],
             "rows": np.zeros((n, width), np.int32),
             "chosen": np.zeros((n, width), np.int32),
             "counts": np.ones(n, np.int64), "system": np.zeros(n)}
    for i, s in enumerate(slots):
        plen, g = len(s.request.prompt), len(s.generated)
        batch["tokens"].append(np.asarray(s.tokens[:-1], np.int32))
        batch["rows"][i, :g] = plen - 1 + np.arange(g)
        batch["chosen"][i, :g] = s.generated
        batch["counts"][i] = g
        batch["system"][i] = s.logprob_sum / g
    return batch


def reference_rows(reference, tree, batch: dict, config: dict, chosen=None,
                   pad: int = REFERENCE_PAD, **controls) -> dict:
    """``reference.served_rows`` over the batch, a session at a time, each
    at its own width (rounded up to ``pad``: causal, so the zeros behind a
    session reach no row that counts); the rows behind a session's count
    are the position 0's and count nowhere."""
    chosen = batch["chosen"] if chosen is None else chosen
    parts = []
    for i, tokens in enumerate(batch["tokens"]):
        padded = np.zeros((1, len(tokens) + -len(tokens) % pad), np.int32)
        padded[0, :len(tokens)] = tokens
        parts.append(reference.served_rows(
            tree, padded, batch["rows"][i:i + 1], chosen[i:i + 1], config,
            **controls))
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


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

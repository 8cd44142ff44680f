"""Disaggregated prefill/decode prototype over the MPMD stage transport.

Splits one request's serving between two replicas the way the MPMD
pipeline splits training between stages: a PREFILL replica runs the
prompt pass, then ships exactly this request's paged-KV block rows plus
the next-token logits over a
:class:`~tpu_sandbox.mpmd.transport.Transport`; a DECODE replica with
its own (differently laid out) page buffers imports the rows at its own
freshly-allocated block ids and runs the decode loop. Block ids are
private to each cache — attention only ever gathers through the block
table — so the handoff re-homes the pages without touching their
contents, and the generated tokens are bitwise identical to a
single-replica engine serving the same request (same compiled step
geometry, same ``sample_token`` keyed by (seed, step index); held by
tests/test_mpmd.py).

This is the serving face of the tentpole: the same durable claim-once
slots that carry activations between training stages carry KV pages
between serving roles. A real deployment would put a DCN wire behind
the Transport interface; everything above it stays as written.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.serve.cache import CacheConfig, PagedKVCache
from tpu_sandbox.serve.decode import (
    DecodeStep,
    build_decode_step,
    init_buffers,
    sample_token,
)


@dataclass
class DisaggRequest:
    rid: str
    prompt: list[int]
    max_new_tokens: int
    seed: int = 0
    temperature: float = 0.0
    top_k: int = 0
    eos_token: int | None = None


def _edge(rid: str) -> str:
    return f"kvpage/{rid}"


def _pick_token(req: DisaggRequest, logits_row: np.ndarray,
                step_index: int) -> int:
    """Greedy or replay-exact sampled — byte-for-byte the engine's
    ``_pick_token`` policy, keyed by (request seed, decode-step index)."""
    if req.temperature <= 0.0:
        return int(logits_row.argmax())
    return sample_token(logits_row, seed=req.seed, step_index=step_index,
                        temperature=req.temperature, top_k=req.top_k)


class _Replica:
    def __init__(self, params, model_cfg: TransformerConfig,
                 cache_cfg: CacheConfig, transport, *,
                 step: DecodeStep | None = None, max_batch: int = 4,
                 buckets: tuple[int, ...] = (16, 32, 64)):
        self.params = params
        self.model_cfg = model_cfg
        self.cache_cfg = cache_cfg
        self.transport = transport
        # replicas may share one compiled DecodeStep (same geometry)
        self.step = step if step is not None else build_decode_step(
            model_cfg, cache_cfg, max_batch=max_batch, buckets=buckets)
        self.cache = PagedKVCache(cache_cfg)
        self.k_pages, self.v_pages = init_buffers(self.step)


class PrefillReplica(_Replica):
    """Runs the prompt pass and ships the request's KV rows + logits."""

    def prefill_and_ship(self, req: DisaggRequest) -> None:
        alloc = self.cache.alloc(req.prompt, 0)
        if alloc is None:
            raise RuntimeError("prefill cache out of blocks")
        plen = len(req.prompt)
        bucket = self.step.pick_bucket(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        dest = self.cache.dest_indices(alloc, bucket).astype(np.int32)
        # the program's own greedy pick stays unread: the decode side
        # chooses from the shipped logits (``_pick_token``)
        next_logits, _, self.k_pages, self.v_pages = \
            self.step.prefill[bucket](
                self.params, self.k_pages, self.v_pages,
                jnp.asarray(toks), jnp.asarray(dest),
                jnp.asarray(plen - 1, jnp.int32))
        alloc.length = plen
        self.cache.commit_prefix(alloc)
        # exactly this request's block rows, in block-table order — the
        # decode side re-homes them at its own ids, contents untouched.
        # The gather happens ON DEVICE: only the request's rows are ever
        # staged (pack_views stages them for host wires; DeviceTransport
        # ships the device buffers as-is), never the whole page pool; the
        # layers' rows travel as one array [L, n, block, H, D].
        ids = jnp.asarray(alloc.block_ids)
        k_rows = self.k_pages.blocks(ids, self.model_cfg.n_heads)
        v_rows = self.v_pages.blocks(ids, self.model_cfg.n_heads)
        self.transport.put(_edge(req.rid), 0, 0,
                           [k_rows, v_rows, next_logits])
        self.cache.free(alloc)


class DecodeReplica(_Replica):
    """Imports shipped KV rows into its own page layout and decodes."""

    def __init__(self, *args, generation: int = 0, **kwargs):
        super().__init__(*args, **kwargs)
        self.generation = generation

    def decode_from_handoff(self, req: DisaggRequest, *,
                            timeout: float = 60.0) -> list[int]:
        if not self.transport.claim(_edge(req.rid), 0, 0,
                                    generation=self.generation):
            raise RuntimeError(f"request {req.rid!r} already decoded "
                               f"in generation {self.generation}")
        k_rows, v_rows, next_logits = self.transport.get(
            _edge(req.rid), 0, 0, timeout=timeout)
        plen = len(req.prompt)
        alloc = self.cache.alloc(req.prompt, 0)
        if alloc is None:
            raise RuntimeError("decode cache out of blocks")
        if len(alloc.block_ids) > alloc.n_shared:
            idx = jnp.asarray(alloc.block_ids[alloc.n_shared:])
            self.k_pages = self.k_pages.with_blocks(
                idx, k_rows[:, alloc.n_shared:])
            self.v_pages = self.v_pages.with_blocks(
                idx, v_rows[:, alloc.n_shared:])
        alloc.length = plen
        self.cache.commit_prefix(alloc)

        generated = [_pick_token(req, np.asarray(next_logits), 0)]
        tokens = list(req.prompt) + generated
        B = self.step.max_batch
        bs = self.cache_cfg.block_size
        while (len(generated) < req.max_new_tokens
               and (req.eos_token is None
                    or generated[-1] != req.eos_token)):
            # the incoming token's kv slot, grown exactly like the
            # engine's _ensure_capacity (one block at a time)
            if (alloc.length % bs == 0
                    and alloc.length // bs >= len(alloc.block_ids)):
                if not self.cache.grow(alloc):
                    raise RuntimeError("decode cache out of blocks")
            toks = np.zeros((B, 1), np.int32)
            toks[0, 0] = tokens[-1]
            lengths = np.zeros((B,), np.int32)
            lengths[0] = len(tokens)
            tables = np.zeros((B, self.cache_cfg.max_blocks_per_seq),
                              np.int32)
            tables[0] = self.cache.block_table(alloc)
            logits, _, self.k_pages, self.v_pages = self.step.decode(
                self.params, self.k_pages, self.v_pages,
                jnp.asarray(toks), jnp.asarray(lengths),
                jnp.asarray(tables))
            alloc.length = len(tokens)
            tok = _pick_token(req, np.asarray(logits)[0], len(generated))
            generated.append(tok)
            tokens.append(tok)
        self.cache.free(alloc)
        return generated


def serve_disaggregated(prefill: PrefillReplica, decode: DecodeReplica,
                        req: DisaggRequest, *,
                        timeout: float = 60.0) -> list[int]:
    """One request through the split path: prompt on the prefill replica,
    pages over the transport, tokens from the decode replica."""
    prefill.prefill_and_ship(req)
    return decode.decode_from_handoff(req, timeout=timeout)

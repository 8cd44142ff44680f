"""On the chip: the paged-attention kernel alone, split three ways, at the
four serving cells' page geometries.

``ops/pallas_paged_attention.py`` copies a row's pages out of HBM a compute
step at a time and runs two products on them. Whether a step is its copies,
its products, or the two one after the other decides what is worth changing
in it, and a traced cell cannot tell: the kernel is one device operation.
This reads, a geometry, three times of the same call on the same lengths:

- ``whole``    the kernel as the decode programs call it;
- ``copies``   its copies and waits, the products left out;
- ``products`` its products on a buffer that is already there, no copy;

one JSON line a reading: microseconds a call and a compute step, the live
rows' bytes a second. ``copies + products == whole`` says the step runs them
in series; ``max(copies, products) == whole`` that they overlap.

The geometries are the cells' own (``GEOMETRIES``: the pages' shape, the
heads, the slots, a row's table and the pool, from ``benchmark/workloads``
and ``benchmark/configs``), the lengths each cell's traffic file's (the
prompt quantiles of ``benchmark/lib/traffic.py``, dealt by the seed, ``--grown``
tokens into the answer), the tables a churned pool's (a random block each;
``--tables dealt`` for the replay cells' in-order blocks). The pages are
drawn *inside* the timed program (PR 39: a parameter's layout is not an
intermediate's), the call sits in a loop whose trip count is an argument,
and a call's time is the slope between two trip counts, so what the
program does beside the kernel cancels.

``--check`` compares instead: the whole kernel against the ``jnp`` form on
the first rows, and against itself with every buffer filled with NaN before
its copies start (``poison``): a wait that returned before its copies had
landed would let one through.

    python tools/paged_attn_race.py [--geometry longcat ...] [--seed N]
    python tools/paged_attn_race.py --check
    python tools/paged_attn_race.py --tiny     # CPU rehearsal of the code path
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, replace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK = 16
PROBES = ("whole", "copies", "products")


@dataclass(frozen=True)
class Geometry:
    """One call site's shapes: pages ``[num_blocks, BLOCK, width]`` (K and
    V, or one latent buffer where ``v_dim``), ``kv_heads * group`` query
    heads of ``head_dim``, ``batch`` rows of ``max_blocks`` table entries."""

    width: int
    kv_heads: int
    group: int
    head_dim: int
    batch: int
    max_blocks: int
    num_blocks: int
    traffic: str
    window: int | None = None
    v_dim: int | None = None
    scale: float | None = None
    pages: int | None = None    # where not ``pages_per_step``'s (``TINY``)

    @property
    def latent(self) -> bool:
        return self.v_dim is not None


_GPT2 = Geometry(1024, 16, 1, 64, 64, 64, 4097, "decode_replay_s64")
_LAGUNA = Geometry(1024, 8, 6, 128, 64, 2112, 29824,
                   "decode_replay_s64_code_mixed")
# a window layer's table is a ring of ceil(512 / 16) + 1 blocks
_RING = {"window": 512, "max_blocks": 33, "num_blocks": 2120}
GEOMETRIES = {
    # gpt2m_serve_decode_replay: 16 heads of 64, a query head each
    "kv1024_g1": _GPT2,
    "kv1024_g1_w512": replace(_GPT2, **_RING),
    # laguna_serve_decode_replay: 8 heads of 128; 48 query heads on a full
    # layer, 64 on a window layer
    "kv1024_g6": _LAGUNA,
    "kv1024_g6_w512": replace(_LAGUNA, **_RING),
    "kv1024_g8": replace(_LAGUNA, group=8),
    "kv1024_g8_w512": replace(_LAGUNA, group=8, **_RING),
    # jamba2_serve_decode_replay: one head of 128 for twenty query heads
    "kv128_g20": Geometry(128, 1, 20, 128, 128, 320, 40961,
                          "decode_replay_s128_reason"),
    # longcat_serve_decode_replay: a latent row [c 512 | k_pe 64 | padding]
    # for 64 query heads, the values its first 512 lanes
    "latent640_h64": Geometry(640, 1, 64, 576, 128, 448, 25152,
                              "decode_replay_s128_chat", v_dim=512,
                              scale=192 ** -0.5),
}
TINY = {
    "tiny_kv128_g4": Geometry(128, 1, 4, 128, 4, 8, 40, "decode_replay_s64",
                              pages=2),
    "tiny_kv128_g4_w32": Geometry(128, 1, 4, 128, 4, 3, 40,
                                  "decode_replay_s64", window=32, pages=2),
    "tiny_latent256_h8": Geometry(256, 1, 8, 192, 4, 8, 40,
                                  "decode_replay_s64", v_dim=128, scale=0.1,
                                  pages=2),
}


def lengths_of(geo: Geometry, seed: int, grown: int, *, tiny: bool = False):
    """``geo.batch`` context lengths as the cell's traffic file deals them:
    its prompt lengths' quantiles in the seed's order, ``grown`` tokens
    later, no longer than a full table holds."""
    import numpy as np

    from benchmark.lib import manifest, traffic

    spec = json.loads(
        (manifest.home() / "traffic" / f"{geo.traffic}.json").read_text())
    lengths = traffic._length_quantiles(spec["prompt_len"], geo.batch) + grown
    if tiny:
        lengths = lengths % (geo.max_blocks * BLOCK) + 1
    room = geo.max_blocks * BLOCK if geo.window is None else 1 << 30
    return np.random.default_rng(seed).permutation(
        np.minimum(lengths, room)).astype(np.int32)


def live_blocks(geo: Geometry, lengths):
    """Blocks a row's walk copies: those its length reaches, from the one
    that holds its window's first position."""
    import numpy as np

    first = 0 if geo.window is None else \
        np.maximum(lengths - geo.window, 0) // BLOCK
    return -(-lengths // BLOCK) - first


def tables_of(geo: Geometry, lengths, seed: int, dealt: bool):
    """A block table ``[batch, max_blocks]``: every entry a block of the
    row's own (never the null block 0), from all over the pool, or
    (``dealt``) in order as a replay cell's set-up deals them."""
    import numpy as np

    held = (np.minimum(-(-lengths // BLOCK), geo.max_blocks)
            if geo.window is None else np.full(len(lengths), geo.max_blocks))
    if held.sum() > geo.num_blocks - 1:
        raise ValueError(f"{held.sum()} blocks in a pool of {geo.num_blocks}")
    pool = np.arange(1, geo.num_blocks)
    if not dealt:
        pool = np.random.default_rng(seed + 1).permutation(pool)
    tables = np.zeros((len(lengths), geo.max_blocks), np.int32)
    at = 0
    for row, n in enumerate(held):
        tables[row, :n] = pool[at:at + n]
        at += n
    return tables


def steps_of(geo: Geometry, lengths, pages: int) -> int:
    """Compute steps a call makes over ``lengths``."""
    return int((-(-live_blocks(geo, lengths) // pages)).sum())


def needed_bytes(geo: Geometry, lengths, itemsize: int = 2) -> int:
    """Bytes of the pages a call's rows reach (K and V, or the latent row),
    whole blocks."""
    return int(live_blocks(geo, lengths).sum()) * BLOCK * geo.width \
        * itemsize * (1 if geo.latent else 2)


def pages_of(geo: Geometry) -> int:
    import jax.numpy as jnp

    from tpu_sandbox.serve.decode import pages_per_step

    if geo.pages is not None:
        return geo.pages
    pages = pages_per_step(geo.width, BLOCK, jnp.bfloat16, geo.max_blocks)
    if pages is None:
        raise ValueError(f"the kernel does not take pages of {geo.width}")
    return pages


def make_call(geo: Geometry, pages: int, probe: str):
    """``call(q, k_pages, v_pages, tables, lengths)``: the kernel's jitted
    call at ``geo``. ``whole`` goes through the public function, so it runs
    on a checkout that knows no probe."""
    from tpu_sandbox.ops import pallas_paged_attention as ppa

    kwargs = {"pages_per_step": pages, "scale": geo.scale,
              "v_dim": geo.v_dim, "window": geo.window}
    if probe == "whole":
        return lambda *operands: ppa.paged_attention(*operands, **kwargs)
    return lambda *operands: ppa._paged_attn(
        *operands, **kwargs, interpret=ppa.default_interpret(None),
        probe=probe)


def draw_operands(geo: Geometry, key):
    """Queries and pages from ``key``, inside the program that uses them."""
    import jax
    import jax.numpy as jnp

    kq, kk, kv = jax.random.split(key, 3)
    shape = (geo.num_blocks, BLOCK, geo.width)
    q = jax.random.normal(kq, (geo.batch, geo.kv_heads * geo.group,
                               geo.head_dim), jnp.bfloat16)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pages = None if geo.latent else \
        jax.random.normal(kv, shape, jnp.bfloat16)
    return q, k_pages, v_pages


def timed_program(geo: Geometry, pages: int, probe: str):
    """``run(key, tables, lengths, n)``: the operands drawn, then ``n``
    calls one after the other (each call's table waits for the call before:
    a comparison XLA cannot fold adds 0 to it)."""
    import jax
    import jax.numpy as jnp

    call = make_call(geo, pages, probe)

    @jax.jit
    def run(key, tables, lengths, n):
        q, k_pages, v_pages = draw_operands(geo, key)

        def body(_, carry):
            tables, total = carry
            out = call(q, k_pages, v_pages, tables, lengths)
            probe_value = out[0, 0, 0].astype(jnp.float32)
            nan = (probe_value != probe_value).astype(jnp.int32)
            return tables + nan, total + probe_value

        return jax.lax.fori_loop(0, n, body, (tables, jnp.float32(0)))[1]

    return run


def case_of(geo: Geometry, args):
    """The lengths, the tables and the pages a step that ``args`` ask for."""
    lengths = lengths_of(geo, args.seed, args.grown, tiny=args.tiny)
    return (lengths, tables_of(geo, lengths, args.seed,
                               args.tables == "dealt"), pages_of(geo))


def read(geo_name: str, geo: Geometry, probe: str, args, device) -> dict:
    import jax

    lengths, tables, pages = case_of(geo, args)
    run = timed_program(geo, pages, probe)
    key = jax.random.key(args.seed)
    few, many = args.calls
    jax.block_until_ready(run(key, tables, lengths, few))    # compiles

    def best(n):
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(key, tables, lengths, n))
            times.append(time.perf_counter() - t0)
        return min(times)

    call_s = (best(many) - best(few)) / (many - few)
    steps, needed = steps_of(geo, lengths, pages), needed_bytes(geo, lengths)
    return {
        "geometry": geo_name, "probe": probe, "seed": args.seed,
        "device": device.device_kind, "tables": args.tables,
        "pages_per_step": pages, "steps_a_call": steps,
        "live_tokens": int(lengths.sum()) if geo.window is None else
        int((live_blocks(geo, lengths) * BLOCK).sum()),
        "needed_mb": needed / 1e6,
        "us_a_call": call_s * 1e6, "us_a_step": call_s * 1e6 / steps,
        "gb_s": needed / call_s / 1e9,
    }


def check(geo_name: str, geo: Geometry, args, device) -> dict:
    """The whole kernel against the ``jnp`` form on the first
    ``--check-rows`` rows, and against itself over poisoned buffers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.serve.decode import _attend_jnp

    lengths, tables, pages = case_of(geo, args)
    rows = min(args.check_rows, geo.batch)

    def reference(q, k_pages, v_pages, tables, lengths):
        if geo.latent:
            from tpu_sandbox.models.longcat_flash import absorbed_attention

            ctx = k_pages[tables].reshape(
                tables.shape[0], -1, geo.width)[..., :geo.head_dim]
            return absorbed_attention(q, ctx, lengths, v_dim=geo.v_dim,
                                      scale=geo.scale)
        return _attend_jnp(q, k_pages, v_pages, tables, lengths,
                           geo.kv_heads, geo.window)

    @jax.jit
    def run(key, tables, lengths):
        operands = draw_operands(geo, key)
        out = {p: make_call(geo, pages, p)(*operands, tables, lengths)
               for p in ("whole", "poison")}
        out["jnp"] = reference(operands[0][:rows], *operands[1:],
                               tables[:rows], lengths[:rows])
        return out

    out = {k: np.asarray(v, np.float32) for k, v in
           run(jax.random.key(args.seed), tables, lengths).items()}
    return {
        "geometry": geo_name, "check": True, "seed": args.seed,
        "device": device.device_kind, "pages_per_step": pages,
        "finite": bool(np.isfinite(out["whole"]).all()),
        "poison_same_bits": bool(
            np.array_equal(out["whole"], out["poison"])),
        "jnp_rows": rows,
        "jnp_max_abs": float(np.abs(out["whole"][:rows] - out["jnp"]).max()),
    }


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--geometry", nargs="*", default=None,
                    help=f"of {', '.join(GEOMETRIES)} (all where none)")
    ap.add_argument("--probe", nargs="*", default=list(PROBES),
                    choices=PROBES)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grown", type=int, default=128,
                    help="tokens each session has answered so far")
    ap.add_argument("--pages", type=int, default=None,
                    help="pages a compute step, where not pages_per_step's")
    ap.add_argument("--tables", choices=("churned", "dealt"),
                    default="churned")
    ap.add_argument("--calls", type=int, nargs=2, default=(4, 36),
                    metavar=("FEW", "MANY"),
                    help="the two trip counts a call's time is the slope of")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--check", action="store_true",
                    help="compare (jnp form, poisoned buffers), not time")
    ap.add_argument("--check-rows", type=int, default=8)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes in interpret mode, for the CPU")
    args = ap.parse_args(argv)
    table = TINY if args.tiny else GEOMETRIES
    unknown = [g for g in args.geometry or () if g not in table]
    if unknown:
        ap.error(f"unknown geometry {unknown}; of {', '.join(table)}")
    args.geometries = {
        g: table[g] if args.pages is None else replace(
            table[g], pages=min(args.pages, table[g].max_blocks))
        for g in args.geometry or table}
    if args.calls[0] >= args.calls[1]:
        ap.error("--calls FEW MANY: FEW < MANY")
    return args


def main(argv=None):
    args = parse(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit("no TPU: the cells' geometries are read on the chip only "
                 "(--tiny rehearses the code path on the CPU)")
    for name, geo in args.geometries.items():
        if args.check:
            print(json.dumps(check(name, geo, args, device)), flush=True)
            continue
        for probe in args.probe:
            print(json.dumps(read(name, geo, probe, args, device)),
                  flush=True)


if __name__ == "__main__":
    main()

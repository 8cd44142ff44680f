"""Per-op HBM traffic breakdown of the AOT-compiled ConvNet train step.

VERDICT r02 next-#3: after the s2d plan + fused tail, XLA's aggregate cost
analysis still charges ~5.45 GB/img (bs=16). This tool answers WHERE, from
the optimized HLO itself: every top-level instruction in the ENTRY
computation materializes its output once and reads its operands, so
(padded output bytes + padded operand bytes) per instruction is the
traffic model — the same accounting XLA's own `bytes accessed` uses,
but attributable to individual ops and op classes (conv fwd / dgrad /
wgrad, packed-form copies, Mosaic kernels, fusions). A ``while`` is
counted as its body's instructions times its trip count, and every
``while`` is printed with the shapes it carries: up to PR 24 this tool
read ENTRY alone and said "while/cond absent from this step", which is
how two per-class loops over the fc weight (36 ms a step on the chip)
went unseen (PERF.md section 6, PR 24).

Padded bytes honor the TPU tiling in the dump: layout T(8,128)(2,1) pads
the two minor physical dims to (8·(32/bits), 128), T(a,b) to (a, b) — the
[.,.,.,16]-lane pathology this repo's s2d plan exists to kill shows up
directly here.

Chipless (uses the local libtpu via jax.experimental.topologies, like
tools/aot_v5e.py — single-process: do not run two AOT tools at once).
Estimates, not measurements; the benchmark (benchmark/run.py) owns measured
truth.

Usage: python tools/hlo_traffic.py [--plan s2d] [--batch 16] [--top 25]
       python tools/hlo_traffic.py --head --batch 5   (the fc head alone)
Exit code 3: the TPU topology could not be described (no libtpu here, or
another process holds it).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)            # import aot_v5e as a sibling
sys.path.insert(0, os.path.dirname(_HERE))  # import tpu_sandbox from the repo

# aot_v5e (and with it libtpu topologies) is imported lazily in main():
# the pure-text analyzers below (shape_bytes / collective_bytes) must be
# importable on CPU-only boxes — tests/test_grad_compress.py runs them
# against a CPU SPMD compile.

_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](?:\{([^}]*)\})?")
_BITS = {
    "pred": 8, "s8": 8, "u8": 8, "bf16": 16, "f16": 16, "s16": 16,
    "u16": 16, "f32": 32, "s32": 32, "u32": 32, "f64": 64, "s64": 64,
    "u64": 64,
}


def shape_bytes(text: str) -> int:
    """Sum padded bytes over every 'dtype[dims]{layout}' in text (handles
    tuple shapes by matching each element)."""
    total = 0
    for dt, dims_s, layout in _SHAPE.findall(text):
        if dt not in _BITS:
            continue  # e.g. token[], opaque
        bits = _BITS[dt]
        dims = [int(d) for d in dims_s.split(",") if d] or [1]
        perm_s = layout.split(":")[0] if layout else ""
        if perm_s and all(t.strip().isdigit() for t in perm_s.split(",")):
            # HLO layouts list dims MINOR-to-major; reverse for major-to-minor
            perm = [int(t) for t in perm_s.split(",")]
            phys = [dims[i] for i in reversed(perm)]
        else:
            phys = list(dims)
        tile = re.search(r"T\((\d+)(?:,(\d+))?\)(\(\d+,1\))?", layout or "")
        if tile and tile.group(2) and len(phys) >= 2:
            # T(8,128)(2,1): the second-level tiling packs 32/bits rows
            # into a sublane, so a bf16 tile is 16 rows; T(1,128) pads
            # the lanes only
            sub = int(tile.group(1)) * (32 // bits if tile.group(3) else 1)
            phys[-2] = -(-phys[-2] // sub) * sub
            phys[-1] = -(-phys[-1] // int(tile.group(2))) * int(tile.group(2))
        elif tile:
            phys[-1] = -(-phys[-1] // int(tile.group(1))) * int(tile.group(1))
        n = 1
        for d in phys:
            n *= d
        total += n * bits // 8
    return total


#: Cross-replica collective opcodes (plus their async -start halves; the
#: -done halves carry no payload of their own and are skipped).
_COLLECTIVES = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute",
)

_INST = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([a-z][\w\-]*)\((.*)$"
)


def operand_region(rest: str) -> str:
    """The operand list of one instruction: everything up to the first ')'
    that is outside layout braces and balanced parens. TPU layouts carry
    parens INSIDE braces (``{0:T(8,128)S(1)}``), so a bare split on ')'
    truncates mid-layout; tuple-shaped operands open parens of their own.
    """
    brace = paren = 0
    for i, ch in enumerate(rest):
        if ch == "{":
            brace += 1
        elif ch == "}":
            brace -= 1
        elif brace == 0 and ch == "(":
            paren += 1
        elif brace == 0 and ch == ")":
            if paren == 0:
                return rest[:i]
            paren -= 1
    return rest


def operand_tokens(rest: str) -> list[str]:
    """Candidate operand names, '%' sigil optional (dumps come both ways).
    Shape/dtype tokens ride along; callers filter by known names."""
    return re.findall(r"%?([\w.\-]+)", operand_region(rest))


def operand_bytes(rest: str, result_bytes: dict[str, int]) -> int:
    """Padded bytes of one instruction's operands. The installed XLA prints
    operands by name only (``all-reduce(%fusion.3)``), so names resolve
    through ``result_bytes``: the result shapes of the instructions seen so
    far (names are unique within a module). A dump printed with operand
    shapes carries them inline, and those win."""
    inline = shape_bytes(operand_region(rest))
    if inline:
        return inline
    return sum(result_bytes.get(tok, 0) for tok in operand_tokens(rest))


def collective_bytes(hlo_text: str) -> dict:
    """Per-participant payload bytes of the cross-replica collectives in an
    optimized HLO module, bucketed by opcode.

    Counts each collective instruction's OPERAND bytes — the data every
    participant contributes to the fabric per step (for all-gather that is
    the local shard, for all-reduce the full buffer; ring-algorithm wire
    amplification is deliberately not modeled, so ratios between compiles
    are like-for-like). Scans every computation, not just ENTRY: shard_map
    bodies compile to nested computations.

    Returns ``{"total": int, "by_opcode": {opcode: int}}``.
    """
    by_opcode: dict[str, int] = collections.defaultdict(int)
    result_bytes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        result_bytes[name] = shape_bytes(shape)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base not in _COLLECTIVES or opcode.endswith("-done"):
            continue
        by_opcode[base] += operand_bytes(rest, result_bytes)
    return {"total": sum(by_opcode.values()), "by_opcode": dict(by_opcode)}


_COMPUTATION = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*?\)\s*->\s*.*?\{\s*$", re.M)
_INST_HEAD = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*", re.M)
_OPCODE = re.compile(r"\s*([\w\-]+)\(")


def instructions(comp_text: str):
    """(name, result shape, opcode, text after ``opcode(``, whole line) of
    every instruction of one computation. A tuple shape is taken to its
    balancing paren: it may hold ``/*index=5*/`` comments and layouts
    with parens of their own."""
    for line in comp_text.splitlines():
        m = _INST_HEAD.match(line)
        if not m:
            continue
        tail = line[m.end():]
        if tail.startswith("("):
            shape_s = "(" + operand_region(tail[1:]) + ")"
        else:
            shape_s = tail.split(" ", 1)[0]
        op = _OPCODE.match(tail[len(shape_s):])
        if op:
            yield (m.group(1), shape_s, op.group(1),
                   tail[len(shape_s) + op.end():], line)


_NO_TRAFFIC = ("parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "while")


def computations(hlo_text: str) -> tuple[dict[str, str], str | None]:
    """Split an HLO module into ``{computation name: body text}``; also
    returns the ENTRY computation's name."""
    heads = list(_COMPUTATION.finditer(hlo_text))
    comps, entry = {}, None
    for m, nxt in zip(heads, heads[1:] + [None]):
        end = nxt.start() if nxt else len(hlo_text)
        comps[m.group(2)] = hlo_text[m.end():end]
        if m.group(1):
            entry = m.group(2)
    return comps, entry


def _trip_count(cond_text: str) -> int | None:
    """Trip count of the canonical counted loop XLA emits: the condition
    compares the induction variable (from 0, step 1) ``LT`` against one
    integer constant. Anything else: unknown."""
    consts = re.findall(r"=\s*[su]\d+\[\][^ ]*\s+constant\((\d+)\)", cond_text)
    if len(consts) == 1 and "direction=LT" in cond_text:
        return int(consts[0])
    return None


def while_loops(hlo_text: str) -> list[dict]:
    """Every ``while`` of the module, wherever it sits: its name, the
    computation holding it, body and condition, trip count (None when
    the condition is not a counted loop) and the shapes it carries with
    their padded bytes, largest first."""
    comps, _ = computations(hlo_text)
    loops = []
    for comp, body_text in comps.items():
        for name, shape_s, opcode, rest, _ in instructions(body_text):
            if opcode != "while":
                continue
            body = re.search(r"body=%?([\w.\-]+)", rest)
            cond = re.search(r"condition=%?([\w.\-]+)", rest)
            carried = sorted(
                ((f"{dt}[{dims}]", shape_bytes(f"{dt}[{dims}]{{{lay}}}"))
                 for dt, dims, lay in _SHAPE.findall(shape_s)),
                key=lambda kv: -kv[1])
            loops.append({
                "while": name, "in": comp,
                "body": body.group(1) if body else None,
                "trip_count": _trip_count(
                    comps.get(cond.group(1), "") if cond else ""),
                "carried": [{"shape": sh, "mb": round(b / 1e6, 1)}
                            for sh, b in carried if b >= 1 << 20],
                "carried_max_bytes": carried[0][1] if carried else 0,
            })
    return loops


def _sliced_params(fused_text: str) -> dict[int, int]:
    """Parameters of a fused computation that only ``dynamic-slice`` ops
    read -> the bytes of those slices (what the fusion really loads)."""
    insts = [i[:4] for i in instructions(fused_text)]
    params = {}
    for name, _, opcode, rest in insts:
        if opcode == "parameter":
            params[name] = int(re.match(r"\s*(\d+)", rest).group(1))
    out = {}
    for pname, idx in params.items():
        users = [(op, shape_s, rest) for _, shape_s, op, rest in insts
                 if pname in operand_tokens(rest) and op != "parameter"]
        if users and all(op == "dynamic-slice"
                         and operand_tokens(rest)[0] == pname
                         for op, _, rest in users):
            out[idx] = sum(shape_bytes(sh) for _, sh, _ in users)
    return out


def traffic_rows(hlo_text: str) -> tuple[list[dict], list[dict]]:
    """(rows, loops): one row per instruction that moves bytes — the
    ENTRY computation's, and each ``while`` body's with its traffic
    multiplied by the trip count (nested loops multiply; an unknown
    trip count counts once and says so). A ``dynamic-slice`` reads, and
    a ``dynamic-update-slice`` reads and writes, the slice and not the
    buffer; a fusion that only slices an operand reads the slices."""
    comps, entry = computations(hlo_text)
    loops = while_loops(hlo_text)
    by_holder = collections.defaultdict(list)
    for lp in loops:
        by_holder[lp["in"]].append(lp)
    rows = []

    def walk(comp: str, times: int, where: str) -> None:
        shapes: dict[str, int] = {}
        for name, shape_s, opcode, rest, line in instructions(
                comps.get(comp, "")):
            out_b = shape_bytes(shape_s)
            shapes[name] = out_b
            if opcode in _NO_TRAFFIC:
                continue
            # tokens are filtered through the name table, so shape,
            # comment and keyword tokens count as 0
            reads = [shapes[o] for o in operand_tokens(rest) if o in shapes]
            if opcode == "dynamic-slice":
                reads = [out_b]
            elif opcode == "dynamic-update-slice" and len(reads) > 1:
                out_b, reads = reads[1], [reads[1]]
            elif opcode == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", rest)
                sliced = _sliced_params(
                    comps.get(called.group(1), "")) if called else {}
                reads = [sliced.get(i, b) for i, b in enumerate(reads)]
            rows.append({
                "op": name, "class": classify(opcode, line, out_b),
                "opcode": opcode, "in": where, "times": times,
                "write_mb": out_b * times / 1e6,
                "read_mb": sum(reads) * times / 1e6,
            })
        for lp in by_holder.get(comp, []):
            walk(lp["body"], times * (lp["trip_count"] or 1),
                 f"{where}/{lp['while']}")

    walk(entry, 1, "ENTRY")
    return rows, loops


_OPNAME = re.compile(r'op_name="jit\(train_step\)/([^"]*)"')


def classify(opcode: str, line: str, out_bytes: int) -> str:
    """Attribute by the op's jaxpr provenance (metadata op_name): XLA:TPU
    wraps convolutions inside fusion instructions, so opcode alone cannot
    see them — but the metadata names the model op and whether it came
    from the forward (jvp) or backward (transpose(jvp)) pass."""
    m = _OPNAME.search(line)
    if m:
        path = m.group(1)
        bwd = "transpose(" in path
        if "fused_input_stage" in path:  # jvp(Model.fused_input_stage)/...
            return f"input-stage-{'bwd' if bwd else 'fwd'}"
        for tag in ("conv1", "conv2", "fc", "_resize", "bn1", "bn2"):
            if f"/{tag}/" in path or path.startswith(f"jvp(jit({tag}))"):
                if tag.startswith("conv") and bwd:
                    # wgrad writes a kernel-small buffer; dgrad an activation
                    kind = "wgrad" if out_bytes < (1 << 24) else "dgrad"
                    return f"{tag}-{kind}"
                return f"{tag}-{'bwd' if bwd else 'fwd'}"
        if "tpu_custom_call" in line:
            return "pallas-kernel"
        return ("optimizer/other-bwd" if bwd else "other-fwd")
    if opcode in ("copy", "copy-start", "copy-done", "transpose"):
        return "copy/transpose(no-provenance)"
    return opcode


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--plan", choices=["s2dt", "s2d", "plain"],
                   default="s2dt")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--image-size", type=int, default=3000)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--head", action="store_true",
                   help="compile the s2dt fc head alone (fc_t forward, "
                        "gradients, SGD add: ~10 s) instead of the step")
    p.add_argument("--hlo-file", default=None,
                   help="re-analyze an existing optimized-HLO dump instead "
                        "of recompiling (~5 min saved per iteration)")
    p.add_argument("--dump-hlo", default=None,
                   help="also write the optimized HLO text here")
    args = p.parse_args()

    if args.hlo_file:
        text = open(args.hlo_file).read()
    else:
        from aot_v5e import compile_head, compile_step, make_topology

        try:
            topo = make_topology()
        except Exception as e:  # no libtpu, or another process holds it
            print(f"no TPU topology: {e}", file=sys.stderr)
            sys.exit(3)
        if args.head:
            compiled = compile_head(topo, args.batch, args.image_size)
        else:
            compiled = compile_step(topo, args.plan, args.batch,
                                    args.image_size)
        text = compiled.as_text()
        if args.dump_hlo:
            open(args.dump_hlo, "w").write(text)

    # fusions count once (their internals stay in registers/VMEM); a
    # while counts its body times its trip count
    rows, loops = traffic_rows(text)

    per_img = args.batch
    by_class = collections.defaultdict(float)
    for r in rows:
        by_class[r["class"]] += r["write_mb"] + r["read_mb"]
    total = sum(by_class.values())
    print(json.dumps({
        "plan": args.plan, "batch": args.batch,
        "total_traffic_gb": round(total / 1e3, 2),
        "gb_per_img": round(total / 1e3 / per_img, 3),
        "by_class_gb": {k: round(v / 1e3, 2) for k, v in sorted(
            by_class.items(), key=lambda kv: -kv[1])},
        "while_loops": len(loops),
        "source": "optimized-HLO padded-buffer accounting "
                  "(chipless AOT estimate, not a measurement)",
    }))
    for lp in loops:
        print(json.dumps(lp))
    for r in sorted(rows, key=lambda r: -(r["write_mb"] + r["read_mb"]))[
            : args.top]:
        r["write_mb"] = round(r["write_mb"], 1)
        r["read_mb"] = round(r["read_mb"], 1)
        print(json.dumps(r))


if __name__ == "__main__":
    main()

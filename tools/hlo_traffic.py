"""Per-op HBM traffic breakdown of the AOT-compiled ConvNet train step.

VERDICT r02 next-#3: after the s2d plan + fused tail, XLA's aggregate cost
analysis still charges ~5.45 GB/img (bs=16). This tool answers WHERE, from
the optimized HLO itself: every top-level instruction in the ENTRY
computation materializes its output once and reads its operands, so
(padded output bytes + padded operand bytes) per instruction is the
traffic model — the same accounting XLA's own `bytes accessed` uses,
but attributable to individual ops and op classes (conv fwd / dgrad /
wgrad, packed-form copies, Mosaic kernels, fusions).

Padded bytes honor the TPU tiling in the dump: layout T(8,128) pads the
two minor physical dims to (8·(32/bits), 128) — the [.,.,.,16]-lane
pathology this repo's s2d plan exists to kill shows up directly here.

Chipless (uses the local libtpu via jax.experimental.topologies, like
tools/aot_v5e.py — single-process: do not run two AOT tools at once).
Estimates, not measurements; the bench owns measured truth.

Usage: python tools/hlo_traffic.py [--plan s2d] [--batch 16] [--top 25]
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
# importable on CPU-only boxes — bench.py's grad-compress traffic metric
# runs them against a CPU SPMD compile.

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
        if "T(" in (layout or "") and len(phys) >= 2:
            sub = 8 * (32 // bits)  # bf16: (16,128) second-level tiling
            phys[-2] = -(-phys[-2] // sub) * sub
            phys[-1] = -(-phys[-1] // 128) * 128
        elif "T(" in (layout or "") and len(phys) == 1:
            phys[-1] = -(-phys[-1] // 128) * 128
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
    p.add_argument("--hlo-file", default=None,
                   help="re-analyze an existing optimized-HLO dump instead "
                        "of recompiling (~5 min saved per iteration)")
    p.add_argument("--dump-hlo", default=None,
                   help="also write the optimized HLO text here")
    args = p.parse_args()

    if args.hlo_file:
        text = open(args.hlo_file).read()
    else:
        from aot_v5e import compile_step, make_topology

        topo = make_topology()
        compiled = compile_step(topo, args.plan, args.batch, args.image_size)
        text = compiled.as_text()
        if args.dump_hlo:
            open(args.dump_hlo, "w").write(text)

    # ENTRY computation only: fusions count once (their internals stay in
    # registers/VMEM); while/cond absent from this step.
    entry = text[text.index("ENTRY "):]
    shapes: dict[str, int] = {}
    rows = []
    inst = re.compile(
        r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]+?\)?)\s+([\w\-]+)\((.*)",
        re.M,
    )
    for m in inst.finditer(entry):
        name, shape_s, opcode, rest = m.groups()
        out_b = shape_bytes(shape_s)
        shapes[name] = out_b
        if opcode in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"):
            continue
        # tokens are filtered through the name table, so shape, comment
        # and keyword tokens count as 0
        in_b = sum(shapes.get(o, 0) for o in operand_tokens(rest))
        rows.append({
            "op": name, "class": classify(opcode, m.group(0), out_b),
            "opcode": opcode, "write_mb": out_b / 1e6, "read_mb": in_b / 1e6,
        })

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
        "source": "optimized-HLO padded-buffer accounting "
                  "(chipless AOT estimate, not a measurement)",
    }))
    for r in sorted(rows, key=lambda r: -(r["write_mb"] + r["read_mb"]))[
            : args.top]:
        r["write_mb"] = round(r["write_mb"], 1)
        r["read_mb"] = round(r["read_mb"], 1)
        print(json.dumps(r))


if __name__ == "__main__":
    main()

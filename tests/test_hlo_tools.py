"""Canned-HLO coverage for the pure-text analyzers in tools/.

The `-done` opcode bug class: async collectives appear twice in scheduled
HLO (`all-reduce-start` + `all-reduce-done`); counting both doubles the
traffic number, counting neither drops it. These tests pin the parsing
contracts of ``hlo_traffic.collective_bytes`` (per-opcode bucketing) and
``hlo_schedule.issue_order_report`` (where the step's largest all-reduce
stands among its kernels) against hand-written modules where every byte is
computable by eye — no compiles, CPU-only.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

from hlo_schedule import issue_order_report  # noqa: E402
from hlo_traffic import (  # noqa: E402
    collective_bytes,
    shape_bytes,
    traffic_rows,
    while_loops,
)

# ---------------------------------------------------------------------------
# shape_bytes: TPU tiling padding
# ---------------------------------------------------------------------------


def test_shape_bytes_unpadded_and_padded():
    # no layout: logical bytes
    assert shape_bytes("f32[256,128]") == 256 * 128 * 4
    # T(8,128) tiling pads the two minor physical dims to (8, 128) for f32
    assert shape_bytes("f32[4,100]{1,0:T(8,128)}") == 8 * 128 * 4
    # bf16 second-level tiling pads sublanes to 16
    assert shape_bytes("bf16[4,100]{1,0:T(8,128)(2,1)}") == 16 * 128 * 2
    # tuple shapes sum element-wise; unknown dtypes (token) are skipped
    assert shape_bytes("(f32[16], s32[4])") == 16 * 4 + 4 * 4
    # the tile's own numbers decide: one row a tile pads the lanes only
    assert shape_bytes("f32[1,1,300]{2,1,0:T(1,128)}") == 384 * 4
    assert shape_bytes("bf16[5,300]{1,0:T(2,128)(2,1)}") == 8 * 384 * 2
    assert shape_bytes("f32[1000]{0:T(1024)}") == 1024 * 4


# ---------------------------------------------------------------------------
# while_loops / traffic_rows: loops are seen, and counted by trip count
# ---------------------------------------------------------------------------

_LOOP_HLO = """\
HloModule mod, is_scheduled=true

%fused_slice (param_0.1: f32[10,4096], param_1.1: u32[]) -> f32[4096] {
  %param_0.1 = f32[10,4096]{1,0:T(8,128)} parameter(0)
  %param_1.1 = u32[] parameter(1)
  %zero = u32[] constant(0)
  %dynamic-slice.1 = f32[1,4096]{1,0:T(1,128)} dynamic-slice(%param_0.1, %param_1.1, %zero), dynamic_slice_sizes={1,4096}
  ROOT %reshape.1 = f32[4096]{0:T(1024)} reshape(%dynamic-slice.1)
}

%body (wide.param: (u32[], f32[10,4096], f32[40960])) -> (u32[], f32[10,4096], f32[40960]) {
  %wide.param = (u32[]{:T(128)}, f32[10,4096]{1,0:T(8,128)}, /*index=2*/f32[40960]{0:T(1024)}) parameter(0)
  %i = u32[] get-tuple-element(%wide.param), index=0
  %one = u32[] constant(1)
  %next = u32[] add(%i, %one)
  %w = f32[10,4096]{1,0:T(8,128)} get-tuple-element(%wide.param), index=1
  %flat = f32[40960]{0:T(1024)} get-tuple-element(%wide.param), index=2
  %row.1 = f32[4096]{0:T(1024)} fusion(%w, %i), kind=kLoop, calls=%fused_slice
  %dynamic-update-slice.1 = f32[40960]{0:T(1024)} dynamic-update-slice(%flat, %row.1, %i)
  ROOT %tuple.1 = (u32[]{:T(128)}, f32[10,4096]{1,0:T(8,128)}, /*index=2*/f32[40960]{0:T(1024)}) tuple(%next, %w, %dynamic-update-slice.1)
}

%cond (wide.param.1: (u32[], f32[10,4096], f32[40960])) -> pred[] {
  %wide.param.1 = (u32[]{:T(128)}, f32[10,4096]{1,0:T(8,128)}, /*index=2*/f32[40960]{0:T(1024)}) parameter(0)
  %j = u32[] get-tuple-element(%wide.param.1), index=0
  %ten = u32[] constant(10)
  ROOT %lt = pred[] compare(%j, %ten), direction=LT
}

ENTRY %main.1 (p0: f32[10,4096]) -> f32[40960] {
  %p0 = f32[10,4096]{1,0:T(8,128)} parameter(0)
  %c0 = u32[] constant(0)
  %buf = f32[40960]{0:T(1024)} broadcast(%c0), dimensions={}
  %tuple.0 = (u32[]{:T(128)}, f32[10,4096]{1,0:T(8,128)}, /*index=2*/f32[40960]{0:T(1024)}) tuple(%c0, %p0, %buf)
  %while.1 = (u32[]{:T(128)}, f32[10,4096]{1,0:T(8,128)}, /*index=2*/f32[40960]{0:T(1024)}) while(%tuple.0), condition=%cond, body=%body
  ROOT %out = f32[40960]{0:T(1024)} get-tuple-element(%while.1), index=2
}
"""


def test_while_loops_are_reported_with_shapes_and_trips():
    (lp,) = while_loops(_LOOP_HLO)
    assert (lp["while"], lp["in"], lp["body"]) == ("while.1", "main.1", "body")
    assert lp["trip_count"] == 10
    # the weight pads 10 -> 16 sublanes; the flat buffer does not
    assert lp["carried_max_bytes"] == 16 * 4096 * 4


def test_loop_bodies_count_times_their_trip_count():
    rows, loops = traffic_rows(_LOOP_HLO)
    assert len(loops) == 1
    by_op = {r["op"]: r for r in rows}
    # ENTRY: the zero-fill, once; the while itself moves nothing
    assert by_op["buf"]["times"] == 1 and "while.1" not in by_op
    # the slicing fusion reads one 4096-float row an iteration, not the
    # whole weight; ten iterations
    row = by_op["row.1"]
    assert (row["in"], row["times"]) == ("ENTRY/while.1", 10)
    assert row["read_mb"] == 10 * (4096 * 4 + 4) / 1e6
    assert row["write_mb"] == 10 * 4096 * 4 / 1e6
    # the in-place update writes (and reads) the slice, not the buffer
    dus = by_op["dynamic-update-slice.1"]
    assert dus["write_mb"] == dus["read_mb"] == 10 * 4096 * 4 / 1e6


# ---------------------------------------------------------------------------
# collective_bytes: per-opcode bucketing + the -start/-done split
# ---------------------------------------------------------------------------

_TRAFFIC_HLO = """\
HloModule mod, is_scheduled=true

ENTRY %main.1 (p0: f32[256,128]) -> f32[256,128] {
  %p0 = f32[256,128] parameter(0)
  %ar.0 = f32[256,128] all-reduce(f32[256,128] %p0), to_apply=%add
  %ags.0 = f32[64,128] all-gather-start(f32[64,128] %p0), dimensions={0}
  %agd.0 = f32[256,128] all-gather-done(f32[256,128] %ags.0)
  %cp.0 = f32[16,128] collective-permute(f32[16,128] %p0)
  ROOT %add.0 = f32[256,128] add(f32[256,128] %ar.0, f32[256,128] %agd.0)
}
"""


def test_collective_bytes_per_opcode():
    out = collective_bytes(_TRAFFIC_HLO)
    # all-reduce counts its full operand
    assert out["by_opcode"]["all-reduce"] == 256 * 128 * 4
    # the async all-gather counts ONCE, from the -start operand (the local
    # shard); the -done half carries no payload and must be skipped
    assert out["by_opcode"]["all-gather"] == 64 * 128 * 4
    assert out["by_opcode"]["collective-permute"] == 16 * 128 * 4
    assert out["total"] == sum(out["by_opcode"].values())
    # nothing leaked in under the -done spelling
    assert "all-gather-done" not in out["by_opcode"]


def test_collective_bytes_resolves_operands_printed_by_name():
    """The installed XLA prints operands without shapes
    (``all-reduce(%fusion.3)``): bytes come from the result shape of the
    instruction that defined each name — also across computations, and for
    a tuple all-reduce over several operands."""
    out = collective_bytes("""\
HloModule mod, is_scheduled=true

%body (p: f32[64,128]) -> f32[64,128] {
  %p = f32[64,128]{1,0} parameter(0)
  ROOT %ag = f32[256,128]{1,0} all-gather(%p), dimensions={0}
}

ENTRY %main.1 (p0: f32[256,128], p1: bf16[10]) -> f32[256,128] {
  %p0 = f32[256,128]{1,0} parameter(0)
  %p1 = bf16[10]{0} parameter(1)
  %fusion.3 = f32[256,128]{1,0} fusion(%p0), kind=kLoop, calls=%f
  %ar = (f32[256,128]{1,0}, bf16[10]{0}) all-reduce(%fusion.3, %p1), to_apply=%add
  ROOT %gte = f32[256,128]{1,0} get-tuple-element(%ar), index=0
}
""")
    assert out["by_opcode"]["all-reduce"] == 256 * 128 * 4 + 10 * 2
    assert out["by_opcode"]["all-gather"] == 64 * 128 * 4


def test_collective_bytes_ignores_non_collectives():
    assert collective_bytes("""\
ENTRY %m (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %c = f32[8] copy(f32[8] %p0)
}
""") == {"total": 0, "by_opcode": {}}


# ---------------------------------------------------------------------------
# issue_order_report: where the dp4 step's large all-reduce is scheduled
# ---------------------------------------------------------------------------

_FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "fixtures", "hlo")
_BACKWARD = ["fc.3", "bn2.fused.4", "bn2.fused.5", "conv2.4", "conv2.5",
             "bn1.fused_conv1.6", "bn1.fused_conv1.7"]


def _issue_order(name):
    with open(os.path.join(_FIXTURES, name)) as f:
        return issue_order_report(f.read())


def test_issue_order_of_one_trailing_pmean():
    """The step as it was scheduled before PR 34: the fc gradient's
    producer last, its all-reduce one instruction between that and the
    update, every kernel in front of all three."""
    r = _issue_order("dp4_step_parent.txt")
    assert (r["collective"], r["form"]) == ("psum.79", "one instruction")
    assert r["collective_bytes"] == 18000000 * 16 * 4  # ten classes pad to 16
    assert (r["gradient"], r["consumer"]) == ("fusion.33",
                                              "multiply_add_fusion")
    assert r["kernels_between_issue_and_consumer"] == 0
    assert r["kernels_under_the_collective"] == 0
    ops = [row["op"] for row in r["order"]]
    assert ops[-3:] == ["fusion.33", "psum.79", "multiply_add_fusion"]
    assert ops.index("all-reduce") < ops.index("psum.79")
    assert sorted(row["op"] for row in r["order"]
                  if row["role"] == "bwd kernel") == sorted(_BACKWARD)


def test_issue_order_of_the_largest_leaf_summed_first():
    """The step as the engine's options make the compiler schedule it: an
    async pair, started behind the gradient's producer and ended in front
    of the update, each of the seven backward kernels wrapped in a fusion
    that runs a share of the collective; the other leaves' all-reduce
    behind the update."""
    r = _issue_order("dp4_step_largest_first.txt")
    assert (r["collective"], r["form"]) == ("async-collective-start",
                                            "async pair")
    assert r["collective_bytes"] == 18000000 * 16 * 4
    assert (r["gradient"], r["consumer"]) == ("fusion.34",
                                              "multiply_add_fusion")
    assert r["kernels_between_issue_and_consumer"] == 7
    assert r["kernels_under_the_collective"] == 7
    rows = r["order"]
    start = next(i for i, row in enumerate(rows) if row["role"] == "collective")
    assert rows[start - 1]["role"] == "gradient"
    assert [row["op"] for row in rows[start + 1:start + 8]] == _BACKWARD
    assert all(row["role"] == "bwd kernel" for row in rows[start + 1:start + 8])
    assert [row["role"] for row in rows[start + 8:]] == [
        "collective done", "consumer", "other collective"]


def _with_vmem(text, sizes):
    """``text`` with a ``backend_config`` as the compiler prints it on each
    wrapped kernel of ``sizes``: the limit's window first, what the fusion
    really holds in ``used_scoped_memory_configs``."""
    out = []
    for line in text.splitlines():
        name = line.strip().split(" = ")[0].lstrip("%")
        if name in sizes:
            line += (', backend_config={"scoped_memory_configs":[{"memory_'
                     'space":"1","offset":"16777216","size":"50331648"}],'
                     '"used_scoped_memory_configs":[{"memory_space":"1",'
                     f'"offset":"0","size":"{sizes[name]}"}}]}}')
        out.append(line)
    return "\n".join(out)


@pytest.mark.parametrize("sizes,want", [
    ({}, 0),                                     # the trimmed fixture itself
    ({"conv2.5": 42053632, "fc.3": 25731072}, 42053632),
    # a kernel outside the pair does not count: it holds no collective
    ({"fc.2": 99999999, "bn2.fused.4": 19640320}, 19640320),
])
def test_issue_order_reads_the_vmem_the_wrapped_kernels_hold(sizes, want):
    with open(os.path.join(_FIXTURES, "dp4_step_largest_first.txt")) as f:
        r = issue_order_report(_with_vmem(f.read(), sizes))
    assert r["scoped_vmem_bytes_under_collective"] == want
    assert r["kernels_under_the_collective"] == 7


def test_issue_order_without_a_collective():
    assert issue_order_report(_LOOP_HLO)["collective"] is None


# ---------------------------------------------------------------------------
# aot_serve_step.count_page_copies: what moves a serving program's pages
# ---------------------------------------------------------------------------

# lines of gpt2m_serve_decode_replay's decode program as the v5e compiler
# wrote it before PR 42 (one stacked buffer for all layers; configs trimmed),
# and the same places of the program with a buffer a layer
_STACKED_PAGES_HLO = """\
ENTRY %main.209 (k_pages.1: bf16[24,4097,16,16,64]) -> (f32[64,50257]) {
  %k_pages.1 = bf16[24,4097,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} parameter(290), metadata={op_name="k_pages"}
  %copy.426 = bf16[24,4097,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%k_pages.1), sharding={replicated}, metadata={op_name="k_pages"}
  %bitcast.17 = bf16[24,65552,16,64]{3,2,1,0:T(8,128)(2,1)} bitcast(%copy.426)
  %fusion.51 = bf16[24,65552,16,64]{3,2,1,0:T(8,128)(2,1)} fusion(%bitcast.17, %p.1), kind=kLoop, calls=%fused_scatter
  %fusion.51.remat_compressed = bf16[24,65552,16,64]{1,3,2,0:T(8,128)(2,1)} copy(%fusion.51), backend_config={"flag_configs":[]}
  %fusion.51.remat_uncompressed = bf16[24,65552,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%fusion.51.remat_compressed), backend_config={"flag_configs":[]}
  %gather.8 = bf16[64,1024,16,64]{3,2,1,0:T(8,128)(2,1)} gather(%fusion.51.remat_uncompressed, %ctx.3), offset_dims={2,3}
  %tuple.9 = (f32[64,50257]{1,0:T(8,128)}, bf16[24,65552,16,64]{3,2,1,0:T(8,128)(2,1)}) tuple(%logits.2, %fusion.51.remat_uncompressed)
  ROOT %while.1 = (s32[]{:T(128)}, bf16[24,65552,16,64]{3,2,1,0:T(8,128)(2,1)}) while(%tuple.8), condition=%cond, body=%body
}
"""
_PAGES_A_LAYER_HLO = """\
  %k_pages_0_.1 = bf16[4097,16,1024]{2,1,0:T(8,128)(2,1)} parameter(290), metadata={op_name="k_pages[0]"}
  %bitcast.17 = bf16[65552,1024]{1,0:T(8,128)(2,1)} bitcast(%k_pages_0_.1)
  %scatter.1 = bf16[65552,1024]{1,0:T(8,128)(2,1)} scatter(%bitcast.17, %dest.2, %rows.5), to_apply=%assign
  %gather.8 = bf16[64,64,16,1024]{3,2,1,0:T(8,128)(2,1)} gather(%param_0.2, %transpose.373), offset_dims={2,3}
  %copy.12 = bf16[64,1024,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%bitcast.40)
"""


def test_page_copies_are_counted_by_name_and_by_the_results_size():
    from aot_serve_step import count_page_copies

    layer = 4097 * 16 * 16 * 64
    assert count_page_copies(_STACKED_PAGES_HLO, layer, 24 * layer) == {
        # the two halves by name; three copies of the pool (the argument's
        # and both halves); with the scatter's fusion four values of its
        # size -- the parameter, the bitcast, the tuple and the while pass
        # buffers on and are no values of their own
        "remat_compressed": 1, "remat_uncompressed": 1,
        "page_copies": 3, "pool_sized": 4}
    # in place: the scatter's result is one layer's pages, and counts as a
    # copy of them only if the compiler says so; the re-laid gathered
    # context (64 x 1024 rows) stays under a layer's 65,552 rows
    assert count_page_copies(_PAGES_A_LAYER_HLO, layer, 24 * layer) == {
        "remat_compressed": 0, "remat_uncompressed": 0,
        "page_copies": 0, "pool_sized": 0}
    # a copy of one layer's pages is found at a layer's size
    padded = _PAGES_A_LAYER_HLO + (
        "  %copy.292 = bf16[4097,16,16,64]{0,3,2,1:T(8,128)(2,1)} "
        "copy(%bitcast.17)\n")
    assert count_page_copies(padded, layer, 24 * layer)["page_copies"] == 1

"""Collective-bytes parser: all HLO shape formats the sweep encounters."""
import pytest

from repro.launch.hlo_analysis import parse_collectives


def test_scalar_and_simple_shapes():
    out = parse_collectives(
        "%ar = f32[] all-reduce(%x), replica_groups=[2,4]<=[8]\n"
        "%ag = bf16[16,4096]{1,0} all-gather(%h), replica_groups=[16,16]<=[256]\n")
    assert abs(out["all-reduce"] - 2 * 4 * 3 / 4) < 1e-6
    assert abs(out["all-gather"] - 16 * 4096 * 2 * 15 / 16) < 1e-6


def test_tuple_shapes_with_index_comments():
    out = parse_collectives(
        "%ar2 = (f32[64]{0}, f32[64,64]{1,0}, /*index=2*/f32[]) "
        "all-reduce(%a, %b, %c), replica_groups={{0,1,2,3}}\n")
    expect = (64 + 64 * 64 + 1) * 4 * 2 * 3 / 4
    assert abs(out["all-reduce"] - expect) < 1e-6


def test_get_tuple_element_not_counted():
    out = parse_collectives(
        "%gte = f32[1,1448,64]{2,1,0} get-tuple-element(%all-to-all), "
        "index=0\n")
    assert out["count"] == 0


def test_all_to_all_ring_factor():
    out = parse_collectives(
        "%a2a = (f32[1,8,4]{2,1,0}, f32[1,8,4]{2,1,0}) all-to-all(%p, %q), "
        "replica_groups=[1,256]<=[256]\n")
    assert abs(out["all-to-all"] - 2 * 8 * 4 * 4 * 255 / 256) < 1e-6


def test_collective_permute_no_group_discount():
    out = parse_collectives(
        "%cp = f32[8,128]{1,0} collective-permute(%y), "
        "source_target_pairs={{0,1}}\n")
    assert abs(out["collective-permute"] - 8 * 128 * 4) < 1e-6


def test_start_done_pairs_counted_once():
    out = parse_collectives(
        "%ars = f32[256]{0} all-reduce-start(%x), replica_groups=[1,8]<=[8]\n"
        "%ard = f32[256]{0} all-reduce-done(%ars)\n")
    assert out["count"] == 1


_LOOP_HLO = """
%fill (p0: s32[]) -> s32[1000] {
  %p0 = s32[] parameter(0)
  ROOT %b = s32[1000]{0} broadcast(%p0), dimensions={}
}

%dus (p0: s32[1000], p1: s32[1], p2: s32[]) -> s32[1000] {
  %p0 = s32[1000]{0} parameter(0)
  %p1 = s32[1]{0} parameter(1)
  %p2 = s32[] parameter(2)
  ROOT %d = s32[1000]{0} dynamic-update-slice(%p0, %p1, %p2)
}

%body (arg: (s32[], s32[1000])) -> (s32[], s32[1000]) {
  %arg = (s32[], s32[1000]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = s32[1000]{0} get-tuple-element(%arg), index=1
  %one = s32[1]{0} constant({1})
  %y = s32[1000]{0} fusion(%x, %one, %i), kind=kLoop, calls=%dus
  %z = s32[1000]{0} scatter(%y, %i, %one), to_apply=%add
  %w = (s32[1000]{0}, s32[1]{0}) custom-call(%one, %z), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{0}: (1, {})}
  %v = s32[1000]{0} get-tuple-element(%w), index=0
  %f = s32[1000]{0} fusion(%i), kind=kLoop, calls=%fill
  %c = s32[1000]{0} copy(%v)
  ROOT %t = (s32[], s32[1000]{0}) tuple(%i, %c)
}

ENTRY %main (a: s32[1000]) -> s32[1000] {
  %a = s32[1000]{0} parameter(0)
  %init = (s32[], s32[1000]{0}) tuple(%zero, %a)
  %loop = (s32[], s32[1000]{0}) while(%init), condition=%cond, body=%body
  %fill_outside = s32[1000]{0} broadcast(%zero), dimensions={}
  ROOT %r = s32[1000]{0} get-tuple-element(%loop), index=1
}
"""


@pytest.mark.parametrize("updates,flagged", [
    (("dynamic-update-slice", "scatter"), ["f", "c"]),
    ((), ["y", "z", "f", "c"]),
], ids=["in-place-updates-pass", "every-update-flagged"])
def test_loop_wide_ops(updates, flagged):
    """A fill and a copy of a loop-carried array are flagged, in-place
    updates (dynamic-update-slice, scatter, an aliasing TPU kernel) pass
    unless ``updates`` excludes them, and ops outside loops never count."""
    from repro.launch.hlo_analysis import loop_wide_ops
    found = loop_wide_ops(_LOOP_HLO, "s32[1000]", updates=updates)
    assert [op for _, op, _ in found] == flagged
    assert all(body == "body" for body, _, _ in found)
    assert loop_wide_ops(_LOOP_HLO, "s32[999]") == []

"""``tools/step_relayouts.relayouts`` on a compiled module's text written by
hand (no compile, no chip): what counts as a re-layout, where it is looked for
and how often a step runs it. ``tests/test_chip_compile.py`` runs the same
parser over the real programs."""

import importlib.util
import pathlib

import pytest

HLO = """HloModule jit_step

%fused_slice (p0: s8[4,2304,4096], p1: s32[]) -> s8[1,2304,4096] {
  %p0 = s8[4,2304,4096]{2,1,0:T(8,128)(4,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  ROOT %ds = s8[1,2304,4096]{2,1,0:T(8,128)(4,1)S(1)} dynamic-slice(%p0, %p1, %zero, %zero), dynamic_slice_sizes={1,2304,4096}
}

%fused_dot (p0: bf16[8,2304], p1: s8[4,2304,4096], p2: s32[]) -> bf16[8,4096] {
  %p0 = bf16[8,2304]{1,0} parameter(0)
  %p1 = s8[4,2304,4096]{2,1,0} parameter(1)
  %p2 = s32[]{:T(128)} parameter(2)
  %zero = s32[]{:T(128)} constant(0)
  %ds = s8[1,2304,4096]{2,1,0} dynamic-slice(%p1, %p2, %zero, %zero), dynamic_slice_sizes={1,2304,4096}
  %w = bf16[2304,4096]{1,0} convert(%ds)
  ROOT %dot = bf16[8,4096]{1,0} convolution(%p0, %w), dim_labels=bf_io->bf
}

%cond (arg: (s32[], bf16[8,2304], s8[4,2304,4096])) -> pred[] {
  %arg = (s32[]{:T(128)}, bf16[8,2304]{1,0:T(8,128)(2,1)}, s8[4,2304,4096]{2,1,0:T(8,128)(4,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %n = s32[]{:T(128)} constant(4)
  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT
}

%body (arg: (s32[], bf16[8,2304], s8[4,2304,4096])) -> (s32[], bf16[8,2304], s8[4,2304,4096]) {
  %arg = (s32[]{:T(128)}, bf16[8,2304]{1,0:T(8,128)(2,1)}, s8[4,2304,4096]{2,1,0:T(8,128)(4,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %x = bf16[8,2304]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %wq = s8[4,2304,4096]{2,1,0:T(8,128)(4,1)} get-tuple-element(%arg), index=2
  %slice_fusion.7 = s8[1,2304,4096]{2,1,0:T(8,128)(4,1)S(1)} fusion(%wq, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(step)/while/body/dynamic_slice" stack_frame_id=12}
  %copy.41 = s8[1,2304,4096]{1,2,0:T(8,128)(4,1)S(1)} copy(%slice_fusion.7), metadata={op_name="jit(step)/while/body/dynamic_slice" stack_frame_id=12}
  %copy.42 = bf16[8,2304]{0,1:T(8,128)(2,1)} copy(%x)
  %fusion.9 = bf16[8,4096]{1,0:T(8,128)(2,1)} fusion(%x, %wq, %i), kind=kOutput, calls=%fused_dot
  %one = s32[]{:T(128)} constant(1)
  %next = s32[]{:T(128)} add(%i, %one)
  ROOT %out = (s32[]{:T(128)}, bf16[8,2304]{1,0:T(8,128)(2,1)}, s8[4,2304,4096]{2,1,0:T(8,128)(4,1)}) tuple(%next, %x, %wq)
}

ENTRY %main (x: bf16[8,2304], wq: s8[4,2304,4096]) -> bf16[8,2304] {
  %x = bf16[8,2304]{1,0:T(8,128)(2,1)} parameter(0)
  %wq = s8[4,2304,4096]{2,1,0:T(8,128)(4,1)} parameter(1)
  %copy.1 = s8[4,2304,4096]{1,2,0:T(8,128)(4,1)} copy(%wq)
  %zero = s32[]{:T(128)} constant(0)
  %init = (s32[]{:T(128)}, bf16[8,2304]{1,0:T(8,128)(2,1)}, s8[4,2304,4096]{2,1,0:T(8,128)(4,1)}) tuple(%zero, %x, %wq)
  %while.5 = (s32[]{:T(128)}, bf16[8,2304]{1,0:T(8,128)(2,1)}, s8[4,2304,4096]{2,1,0:T(8,128)(4,1)}) while(%init), condition=%cond, body=%body
  ROOT %y = bf16[8,2304]{1,0:T(8,128)(2,1)} get-tuple-element(%while.5), index=1
}
"""


def load_tool():
    """``tools/step_relayouts.py`` as a module (``tools`` is no package)."""
    spec = importlib.util.spec_from_file_location(
        "step_relayouts", pathlib.Path(__file__).parents[1] / "tools" / "step_relayouts.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tool():
    return load_tool()


def test_a_slice_and_a_transposed_copy_in_the_scan_body_are_listed(tool):
    found = {op["name"]: op for op in tool.relayouts(HLO)}
    # The stand-alone slice and the transposed copy of it, four times a step (the scan's trip count);
    # not the small activation's copy, not the fusion that computes, not the copy outside the loop.
    assert sorted(found) == ["copy.41", "slice_fusion.7"]
    copy = found["copy.41"]
    assert (copy["dtype"], copy["shape"], copy["bytes"], copy["times"]) == ("s8", "s8[1,2304,4096]", 2304 * 4096, 4)
    assert copy["reads"] == ["s8[1,2304,4096]{2,1,0}"] and copy["writes"] == "{1,2,0}"
    assert copy["op_name"].endswith("dynamic_slice")
    assert found["slice_fusion.7"]["reads"] == ["s8[4,2304,4096]{2,1,0}"] and found["slice_fusion.7"]["opcode"] == "fusion"
    total = tool.summary(list(found.values()))
    assert total["relaid_bytes_per_step"] == total["s8_relaid_bytes_per_step"] == 2 * 4 * 2304 * 4096


def test_the_size_floor_and_a_loop_without_a_count(tool):
    assert {op["name"] for op in tool.relayouts(HLO, min_bytes=1)} == {"copy.41", "copy.42", "slice_fusion.7"}
    unknown = HLO.replace("direction=LT", "direction=NE")  # no scan: the text does not say how often
    assert {op["times"] for op in tool.relayouts(unknown)} == {1}

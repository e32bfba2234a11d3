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


# -- what the layer scan's body executes (``--count``, ISSUE 39) ------------------

COUNTED = """HloModule jit_step

%fused_scatter (p0: s32[8]) -> s32[4] {
  %p0 = s32[8]{0} parameter(0)
  %zeros = s32[4]{0} constant({0, 0, 0, 0})
  ROOT %scatter-add.3 = s32[4]{0} scatter(%zeros, %p0, %p0), to_apply=%sum
}

%fused_other_scatter (p0: s32[8]) -> s32[4] {
  %p0 = s32[8]{0} parameter(0)
  %zeros = s32[4]{0} constant({0, 0, 0, 0})
  ROOT %scatter.9 = s32[4]{0} scatter(%zeros, %p0, %p0), to_apply=%sum, metadata={op_name="jit(step)/while/body/attn/scatter"}
}

%inner_cond (arg: (s32[])) -> pred[] {
  %arg = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(2)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%inner_body (arg: (s32[])) -> (s32[]) {
  %arg = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %one = s32[] constant(1)
  %gathered = f32[8]{0} fusion(%i), kind=kLoop, calls=%fused_gather, metadata={op_name="jit(step)/while/body/mlp/while/body/gather"}
  %next = s32[] add(%i, %one), metadata={op_name="jit(step)/while/body/mlp/while/body/moe.combine/add"}
  ROOT %out = (s32[]) tuple(%next)
}

%fast_arm (arg: (f32[8])) -> f32[8] {
  %arg = (f32[8]) parameter(0)
  %x = f32[8]{0} get-tuple-element(%arg), index=0
  ROOT %kernel = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/mlp/cond/branch_1_fun/moe.experts_down/pallas_call"}
}

%slow_arm (arg: (f32[8])) -> f32[8] {
  %arg = (f32[8]) parameter(0)
  %x = f32[8]{0} get-tuple-element(%arg), index=0
  %zero = s32[] constant(0)
  %init = (s32[]) tuple(%zero)
  %while.9 = (s32[]) while(%init), condition=%inner_cond, body=%inner_body
  ROOT %kernel = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/mlp/cond/branch_0_fun/moe.experts_down/pallas_call"}
}

%cond (arg: (s32[], f32[8])) -> pred[] {
  %arg = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(39)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %q = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_q, metadata={op_name="jit(step)/while/body/attn/dot_general"}
  %seen = s32[4]{0} fusion(%i), kind=kLoop, calls=%fused_other_scatter, metadata={op_name="jit(step)/while/body/attn/scatter"}
  %sort.4 = (f32[8]{0}, s32[8]{0}) sort(%q, %q), dimensions={0}, to_apply=%less, metadata={op_name="jit(step)/while/body/mlp/moe.router/top_k"}
  %keys = s32[8]{0} get-tuple-element(%sort.4), index=1
  %sizes = s32[4]{0} fusion(%keys), kind=kLoop, calls=%fused_scatter, metadata={op_name="jit(step)/while/body/mlp/moe.router/scatter-add"}
  %cast = f32[8]{0} bitcast(%q)
  %zero = s32[] constant(0)
  %init = (s32[]) tuple(%zero)
  %while.7 = (s32[]) while(%init), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(step)/while/body/mlp/while"}
  %pick = s32[] get-tuple-element(%while.7), index=0
  %operand = (f32[8]) tuple(%cast)
  %routed = f32[8]{0} conditional(%pick, %operand, %operand), branch_computations={%slow_arm, %fast_arm}, metadata={op_name="jit(step)/while/body/mlp/cond"}
  %shared = f32[8]{0} fusion(%x), kind=kOutput, calls=%fused_shared, metadata={op_name="jit(step)/while/body/mlp/moe.shared/dot_general"}
  %y = f32[8]{0} add(%routed, %shared)
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %out = (s32[], f32[8]) tuple(%next, %y)
}

%sampler_body (arg: (s32[])) -> (s32[]) {
  %arg = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %out = (s32[]) tuple(%next)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]) tuple(%zero, %x)
  %while.5 = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  %small = (s32[]) tuple(%zero)
  %while.6 = (s32[]) while(%small), condition=%inner_cond, body=%sampler_body
  ROOT %y = f32[8]{0} get-tuple-element(%while.5), index=1
}
"""


def test_the_layer_bodys_instructions_are_counted_by_scope(tool):
    """The layer scan is the entry's loop with the longest body; a fusion, a
    sort, a loop, a conditional and a custom call count one each, a parameter,
    a constant, a tuple and a bitcast none; what a nested loop or a
    conditional's arms hold is counted apart, and an arm's own loop as the
    arm's."""
    counts = tool.body_counts(COUNTED)
    assert counts["body"] == 9  # q, seen, sort.4, sizes, while.7, routed, shared, y, next
    assert counts["nested"] == {"while": 2, "cond": 5}  # the loop's two; the arms' while.9 + its two + a kernel each
    assert counts["arms"] == [[4, 1]] and counts["executed"] == 9 + 2 + 4
    assert counts["by_scope"] == {"attn": 2, "mlp": 2, "mlp/while": 1, "moe.combine/while": 1, "moe.router": 2,
                                  "moe.shared": 1, "other": 2, "moe.experts/cond": 2, "mlp/cond": 1, "moe.combine/cond": 1,
                                  "other/cond": 1}
    assert counts["sorts"] == ["sort.4"]
    assert counts["moe_scatters"] == ["scatter-add.3"]  # the router's, inside its fusion; not the attention's
    assert counts["moving"] == {"copy": 0, "transpose": 0, "sort": 1, "gather": 0}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/while/body/closed_call/mlp/moe.router/top_k", "moe.router"),
    ("jit(step)/while/body/mlp.moe/while/body/moe.combine/scatter-add", "moe.combine"),
    ("jit(step)/while/body/mlp/cond/branch_1_fun/jit(expert_ffn_int8)/moe.experts_gate_up/pallas_call", "moe.experts"),
    ("jit(step)/while/body/mlp/moe.shared/dot_general", "moe.shared"),
    ("jit(step)/while/body/mlp.moe/moe.zero/mul", "moe.zero"),
    ("jit(step)/while/body/mlp/cond/branch_1_fun/moe.dispatch/dot_general", "moe.dispatch"),
    ("jit(step)/while/body/attn/mla.absorb/dot_general", "attn"),
    ("jit(step)/while/body/mlp/reshape", "mlp"),
    ("jit(step)/while/body/dynamic_slice", "other"),
    ("", "other"),
])
def test_an_instructions_scope_is_the_innermost_named_one(tool, op_name, scope):
    assert tool.scope_of(op_name) == scope


def test_a_program_without_a_loop_counts_nothing(tool):
    flat = "HloModule m\n\nENTRY %main (x: f32[8]) -> f32[8] {\n  %x = f32[8]{0} parameter(0)\n  ROOT %y = f32[8]{0} negate(%x)\n}\n"
    counts = tool.body_counts(flat)
    assert counts["body"] == counts["executed"] == 0 and not counts["sorts"] and not counts["arms"]


# -- the device's time operation by operation (``tools/step_ops_table.py``, ISSUE 39) ---------


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns, self.stats = name, start_ns, duration_ns, [("device_duration_ps", 1)]


class _Named:
    def __init__(self, name, **parts):
        self.name = name
        self.__dict__.update(parts)


def _fake_profile():
    """Two runs of one step program and one of another: a layer scan
    (``while.1``) that holds a fusion twice and a nested loop with one."""
    ops = [
        _Event("%while.1 = (s32[]) while(...)", 10, 900), _Event("%fusion.1 = f32[2] fusion(...)", 20, 100),
        _Event("%while.2 = (s32[]) while(...)", 200, 300), _Event("%fusion.2 = f32[] fusion(...)", 210, 100),
        _Event("%fusion.2 = f32[] fusion(...)", 350, 100), _Event("%fusion.1 = f32[2] fusion(...)", 600, 100),
        _Event("%head = f32[] fusion(...)", 950, 40),
        _Event("%while.1 = (s32[]) while(...)", 2010, 900), _Event("%fusion.1 = f32[2] fusion(...)", 2020, 100),
        _Event("%fusion.9 = f32[2] fusion(...)", 4020, 100),
    ]
    modules = [_Event("jit__step_packed(1)", 0, 1000), _Event("jit__step_packed(1)", 2000, 1000),
               _Event("jit__step_packed(2)", 4000, 500), _Event("jit_convert(3)", 5000, 10)]
    device = _Named("/device:TPU:0", lines=[_Named("XLA Modules", events=modules), _Named("XLA Ops", events=ops)])
    return _Named("profile", planes=[_Named("/host:CPU", lines=[]), device])


@pytest.fixture()
def ops_tool(monkeypatch):
    import jax

    spec = importlib.util.spec_from_file_location(
        "step_ops_table", pathlib.Path(__file__).parents[1] / "tools" / "step_ops_table.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module.glob, "glob", lambda pattern: ["trace.xplane.pb"])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", staticmethod(lambda path: _fake_profile()))
    return module


def test_an_operations_own_time_is_what_nothing_nested_in_it_covers(ops_tool):
    table = ops_tool.ops_table("anywhere")
    assert (table["module"], table["runs"], table["step_programs_in_trace"]) == ("jit__step_packed(1)", 2, 3)
    assert table["run_us"] == 1.0
    ops = {op["name"]: op for op in table["ops"]}
    assert list(ops) == ["while.1", "fusion.1", "while.2", "fusion.2", "head"]  # in the order a run meets them
    assert "fusion.9" not in ops  # the other program's
    # per run of the program: 900 + 900 ns whole; own = less its children (100 + 300 + 100, then 100)
    assert ops["while.1"]["whole_us_per_run"] == pytest.approx(0.9) and ops["while.1"]["own_us_per_run"] == pytest.approx(0.6)
    assert (ops["while.2"]["parent"], ops["while.2"]["depth"]) == ("while.1", 1)
    assert ops["while.2"]["own_us_per_run"] == pytest.approx(0.05)  # 300 - 2 x 100, in one of two runs
    assert (ops["fusion.2"]["parent"], ops["fusion.2"]["depth"], ops["fusion.2"]["calls_per_run"]) == ("while.2", 2, 1.0)
    assert ops["fusion.1"]["calls_per_run"] == 1.5 and ops["head"]["depth"] == 0
    assert ops["fusion.1"]["line"].startswith("%fusion.1 = f32[2]") and ops["fusion.1"]["stats"] == {"device_duration_ps": 1}


def test_a_program_can_be_named_by_a_part_of_its_modules_name(ops_tool):
    """``--program``: the commonest of the step programs whose module's name
    holds the word (a window with more mixed steps than decode steps still
    gives the decode step's table); a word no program has is an error that
    lists what the trace holds."""
    table = ops_tool.ops_table("anywhere", "packed(2)")
    assert (table["module"], table["runs"], table["step_programs_in_trace"]) == ("jit__step_packed(2)", 1, 3)
    assert [op["name"] for op in table["ops"]] == ["fusion.9"]
    assert ops_tool.ops_table("anywhere", "_step_packed")["module"] == "jit__step_packed(1)"
    with pytest.raises(ValueError, match=r"_step_split.*jit__step_packed\(1\)"):
        ops_tool.ops_table("anywhere", "_step_split")


def test_scopes_come_from_the_programs_text_by_instruction_name(ops_tool):
    text = ('%body (x: f32[2]) -> f32[2] {\n'
            '  %fusion.1 = f32[2]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/while/body/attn/dot_general"}\n'
            '  %fusion.2 = f32[]{:T(128)} fusion(%i), kind=kLoop, calls=%g, metadata={op_name="jit(step)/while/body/mlp/while/body/moe.combine/add"}\n'
            '}\n')
    got = ops_tool.scopes(ops_tool.ops_table("anywhere"), text)
    assert got["layers"] == 2  # the scan's commonest operation runs 1.5 times a run of the program: two layers, one run cut
    by = got["by_scope"]
    assert set(by) == {"outside the layer scan", "attn", "moe.combine/while", "a nested while's own time"}
    assert by["attn"]["us"] == pytest.approx(0.15) and by["attn"]["per_layer_us"] == pytest.approx(0.075)
    assert by["moe.combine/while"]["us"] == pytest.approx(0.1) and by["a nested while's own time"]["us"] == pytest.approx(0.05)
    assert by["outside the layer scan"]["ops"] == 2 and by["outside the layer scan"]["per_layer_us"] is None
    assert set(ops_tool.scopes(ops_tool.ops_table("anywhere"))["by_scope"]) == {
        "outside the layer scan", "other", "other/while", "a nested while's own time"}  # no text: nothing named


def test_the_served_programs_text_is_the_one_that_names_the_tables_operations(ops_tool):
    class Lowered:
        def __init__(self, text):
            self.text = text

        def lower(self, *args, **kwargs):
            return self

        def compile(self):
            return self

        def as_text(self):
            return self.text

    head = "ENTRY %main (x: f32[2]) -> f32[] {\n"
    right = head + "  %while.1 = () while()\n  %fusion.1 = f32[2] fusion()\n  %while.2 = () while()\n  %fusion.2 = f32[] fusion()\n  ROOT %head = f32[] fusion()\n}\n"
    wrong = head + "  %while.1 = () while()\n  %fusion.7 = f32[2] fusion()\n}\n"
    seen = {("_step_packed", (("b", 8),)): [Lowered(wrong), (), {}, 50], ("_step_packed", (("b", 64),)): [Lowered(right), (), {}, 9],
            ("_step_split", ()): [Lowered("never lowered: the module is not named after it"), (), {}, 99]}
    assert ops_tool.served_text(seen, ops_tool.ops_table("anywhere")) == right

"""Stage attribution on hand-built HLO text and events."""
import pytest

from bench import harness, scopes, trace

BODY = "jit(f)/while/body/closed_call/jit(fn)"
HLO = f"""
HloModule jit_step, entry_computation_layout={{(f32[18,18]{{1,0}})->f32[18,18]{{1,0}}}}

%fused_computation (param_0: f32[18,18]) -> f32[1,18,16] {{
  %param_0 = f32[18,18]{{1,0}} parameter(0)
  %slice.1 = f32[18,16]{{1,0}} slice(%param_0), slice={{[0:18], [1:17]}}, metadata={{op_name="{BODY}/emit.accumulate/slice"}}
  ROOT %bitcast.1 = f32[1,18,16]{{2,1,0}} bitcast(%slice.1), metadata={{op_name="{BODY}/emit.accumulate/emit.layout/kernel.prep/reshape"}}
}}

%fused_computation.2 (param_0.2: f32[16,16], param_1.2: f32[16,16]) -> f32[16,16] {{
  %param_0.2 = f32[16,16]{{1,0}} parameter(0)
  %param_1.2 = f32[16,16]{{1,0}} parameter(1)
  ROOT %add.2 = f32[16,16]{{1,0}} add(%param_0.2, %param_1.2), metadata={{op_name="{BODY}/emit.accumulate/add"}}
}}

%body (p: (s32[], f32[18,18])) -> (s32[], f32[18,18]) {{
  %p = (s32[], f32[18,18]{{0,1}}) parameter(0)
  %gte.1 = f32[18,18]{{0,1}} get-tuple-element(%p), index=1
  %copy.1 = f32[18,18]{{1,0}} copy(%gte.1)
  %fusion.1 = f32[1,18,16]{{2,1,0}} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={{op_name="{BODY}/emit.accumulate/emit.layout/kernel.prep/reshape"}}
  %pad.1 = f32[1,24,16]{{2,1,0}} pad(%fusion.1, %c.1), padding=0_0x0_6x0_0, metadata={{op_name="{BODY}/emit.accumulate/emit.layout/jit(_sptc_fused_jit)/kernel.prep/jit(_pad)/pad"}}
  %sptc_star.1 = f32[1,16,16]{{2,1,0}} custom-call(%pad.1), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/emit.accumulate/emit.layout/jit(_sptc_fused_jit)/sptc_star/pallas_call"}}
  %copy.2 = f32[1,16,16]{{1,2,0}} copy(%sptc_star.1), metadata={{op_name="{BODY}/emit.accumulate/emit.layout/jit(_sptc_fused_jit)/sptc_star/pallas_call"}}
  %fusion.2 = f32[16,16]{{1,0}} fusion(%copy.2, %copy.2), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{BODY}/emit.accumulate/add"}}
  %pad.2 = f32[18,18]{{0,1}} pad(%fusion.2, %c.1), padding=1_1x1_1, metadata={{op_name="jit(f)/while/body/closed_call/iterate.repad/jit(_pad)/pad"}}
  %collective-permute-start.1 = (f32[1,18]{{1,0}}, f32[1,18]{{1,0}}) collective-permute-start(%pad.2), source_target_pairs={{{{0,1}}}}, metadata={{op_name="jit(f)/while/body/halo.exchange/ppermute"}}
  %collective-permute-done.1 = f32[1,18]{{1,0}} collective-permute-done(%collective-permute-start.1), metadata={{op_name="jit(f)/while/body/halo.exchange/ppermute"}}
  %copy.4 = f32[18,18]{{0,1}} copy(%gte.1)
  %concatenate.1 = f32[36,18]{{0,1}} concatenate(%copy.4, %pad.2), dimensions={{0}}, metadata={{op_name="jit(f)/while/body/halo.stitch/concatenate"}}
  %copy-start.1 = (f32[18,18]{{0,1}}, f32[18,18]{{0,1}}, u32[]) copy-start(%pad.2)
  %copy-done.1 = f32[18,18]{{0,1}} copy-done(%copy-start.1)
  %add.9 = s32[] add(%gte.0, %c.2), metadata={{op_name="jit(f)/while/body/add"}}
  ROOT %tuple.9 = (s32[], f32[18,18]) tuple(%add.9, %copy-done.1)
}}

ENTRY %main (u: f32[18,18]) -> f32[18,18] {{
  %u = f32[18,18]{{1,0}} parameter(0)
  %copy.8 = f32[18,18]{{0,1}} copy(%u)
  %while = (s32[], f32[18,18]{{0,1}}) while(%tuple.1), condition=%cond, body=%body, metadata={{op_name="jit(f)/while"}}
  %gte.2 = f32[18,18]{{0,1}} get-tuple-element(%while), index=1, metadata={{op_name="jit(f)/while"}}
  ROOT %copy.9 = f32[18,18]{{1,0}} copy(%gte.2), metadata={{op_name="jit(f)/while"}}
}}
"""

NS = 1e9
#: one chip's ops in a 10 s window: 4 s kernel, 1 s collective, the rest
#: off-kernel and one stretch outside the window
DEV0 = [("fusion.1", -1 * NS, 1 * NS),         # 1 s inside the window
        ("pad.1", 1 * NS, 1.5 * NS),
        ("sptc_star.1", 1.5 * NS, 5.5 * NS),
        ("copy.2", 5.5 * NS, 6 * NS),
        ("fusion.2", 6 * NS, 6.75 * NS),
        ("pad.2", 6.75 * NS, 7 * NS),
        ("collective-permute-start.1", 7 * NS, 8 * NS),
        ("copy-done.1", 8 * NS, 8.25 * NS),
        ("copy.1", 8.25 * NS, 8.5 * NS),
        ("copy.8", 8.5 * NS, 8.625 * NS),
        ("add.9", 8.625 * NS, 8.75 * NS),
        ("copy.4", 8.75 * NS, 8.875 * NS),
        ("concatenate.1", 8.875 * NS, 9 * NS),
        ("copy.9", 9.5 * NS, 11 * NS)]         # 0.5 s inside the window
WINDOW = (0, 10 * NS)


@pytest.mark.parametrize("op_name,stage", [
    (f"{BODY}/emit.accumulate/emit.layout/jit(_sptc_fused_jit)/kernel.prep/jit(_pad)/pad",
     "kernel.prep"),
    (f"{BODY}/emit.accumulate/add", "emit.accumulate"),
    ("jit(f)/shard_map/while/body/halo.stitch/emit.accumulate/emit.layout/transpose",
     "emit.layout"),
    ("jit(f)/while/body/halo.exchange/slice;jit(f)/while/body/halo.stitch/concatenate",
     "halo.exchange"),
    ("jit(f)/while/body/add", None),
    ("", None),
    (None, None),
], ids=["innermost-prep", "accumulate", "innermost-layout", "merged-first", "none",
        "empty", "absent"])
def test_innermost_stage_wins(op_name, stage):
    assert scopes.stage_of_op_name(op_name) == stage


def test_parse_reads_opcodes_operands_and_fusions():
    ins = scopes.parse([HLO])
    assert ins["fusion.1"].opcode == "fusion" and ins["fusion.1"].calls == "fused_computation"
    assert ins["fusion.2"].operands == ["copy.2", "copy.2"]
    assert ins["collective-permute-start.1"].operands == ["pad.2"]
    assert ins["bitcast.1"].root and ins["bitcast.1"].comp == "fused_computation"
    assert ins["copy.9"].comp == "main" and ins["copy.9"].root
    assert ins["copy.1"].op_name is None
    assert (ins["copy.1"].layout, ins["gte.1"].layout, ins["u"].layout) == ("1,0", "0,1", "1,0")
    assert ins["collective-permute-start.1"].layout is None


def test_attribution_rules():
    at = scopes.attribute([HLO])
    # a fusion counts by its root, looked through the bitcast to the slice
    assert at["fusion.1"] == "emit.accumulate"
    assert at["fusion.2"] == "emit.accumulate"
    # innermost stage of the op's own name
    assert at["pad.1"] == "kernel.prep" and at["pad.2"] == "iterate.repad"
    # a layout copy carries its operand's metadata: the kernel call's
    assert at["copy.2"] == "emit.layout"
    # a copy into another dimension order naming no stage is a transpose:
    # in the loop, and on the state's way into and out of it
    assert at["copy.1"] == at["copy.8"] == at["copy.9"] == "emit.layout"
    # no metadata, same layout, operand without any: the first user's stage
    assert at["copy.4"] == "halo.stitch"
    # no metadata: through the metadata-less copy-start to its operand
    assert at["copy-start.1"] == "iterate.repad"
    assert at["copy-done.1"] == "iterate.repad"
    # the loop's own ops name no stage
    assert at["add.9"] == at["while"] == scopes.UNSCOPED


def test_a_program_without_stages_has_no_shares():
    bare = HLO
    for s in scopes.STAGES:
        bare = bare.replace(s + "/", "")
    assert set(scopes.attribute([bare]).values()) == {"emit.layout", scopes.UNSCOPED}
    assert scopes.stage_shares(scopes.op_seconds({0: DEV0}, WINDOW), 9.0, [bare]) == {}


def test_stage_names_are_the_programs():
    from repro.core import scopes as program_scopes
    assert scopes.STAGES == program_scopes.ALL


def test_op_seconds_is_the_reductions_per_op_sum():
    dev1 = [("sptc_star.1", 0, 2 * NS), ("pad.2", 2 * NS, 3 * NS)]
    events = {0: DEV0, 1: dev1}
    kernels, collectives = trace.classify_hlo([HLO])
    red = trace.reduce_events(events, [], kernels, collectives, WINDOW)
    op_s = scopes.op_seconds(events, WINDOW)
    assert op_s["fusion.1"] == pytest.approx(0.5)
    assert op_s["copy.9"] == pytest.approx(0.25)
    for key, sec in red.top_ops:
        name, kind = key.rsplit(" ", 1)
        assert op_s[name] == pytest.approx(sec), key
        assert kind == ("[kernel]" if name in kernels else
                        "[collective]" if name in collectives else "[op]")


def test_kernels_and_collectives_are_left_out():
    op_s = scopes.op_seconds({0: DEV0}, WINDOW)
    by_stage, unscoped = scopes.stage_seconds(op_s, [HLO])
    assert by_stage == pytest.approx({
        "emit.layout": 0.5 + 0.25 + 0.125 + 0.5, "emit.accumulate": 1 + 0.75,
        "kernel.prep": 0.5, "iterate.repad": 0.25 + 0.25,
        "halo.exchange": 0.0, "halo.stitch": 0.125 + 0.125, "unscoped": 0.125})
    assert unscoped == pytest.approx({"add.9": 0.125})


def test_stage_shares_add_up_to_the_offkernel_share():
    events = {0: DEV0, 1: [(n, s + 0.5 * NS, e + 0.5 * NS) for n, s, e in DEV0]}
    kernels, collectives = trace.classify_hlo([HLO])
    red = trace.reduce_events(events, [], kernels, collectives, WINDOW)
    shares = scopes.stage_shares(scopes.op_seconds(events, WINDOW), red.busy_s_mean, [HLO])
    offkernel = harness.load_reader("offkernel_busy_frac.iterate").read({"trace": red})
    assert sum(shares.values()) == pytest.approx(offkernel)
    # fusion.1 starts before the window: 1 s of it on chip 0, 1.5 s on chip 1
    assert shares["emit.accumulate"] == pytest.approx(100 * (1.75 + 2.25) / 2 / red.busy_s_mean)
    assert scopes.stage_shares({}, 0.0, [HLO]) == {}

"""The trace reduction on hand-built events and HLO text."""
import pytest

from bench import trace

HLO = """
HloModule jit_step, entry_computation_layout={(f32[16,16]{1,0})->f32[16,16]{1,0}}

%body (p: (s32[], f32[16,16])) -> (s32[], f32[16,16]) {
  %k.1 = f32[1,16,16]{2,1,0:T(8,128)} custom-call(%pad.3, %c.2), custom_call_target="tpu_custom_call", metadata={op_name="pallas"}
  %fusion.4 = f32[16,16]{1,0:T(8,128)} fusion(%k.1), kind=kLoop, calls=%fused_computation
  %collective-permute-start.1 = (f32[1,16]{1,0}, f32[1,16]{1,0}) collective-permute-start(%fusion.4), source_target_pairs={{0,1}}
  %collective-permute-done.1 = f32[1,16]{1,0} collective-permute-done(%collective-permute-start.1)
  ROOT %tuple.9 = (s32[], f32[16,16]) tuple(%add.1, %fusion.4)
}

ENTRY %main (u: f32[16,16]) -> f32[16,16] {
  %while = (s32[], f32[16,16]{0,1:T(8,128)}) while(%tuple.1), condition=%cond, body=%body
  ROOT %copy.1 = f32[16,16]{1,0:T(8,128)} copy(%gte.2)
}
"""


def test_classify_hlo_reads_kernels_and_collectives_from_program_text():
    kernels, collectives = trace.classify_hlo([HLO])
    assert kernels == {"k.1"}
    assert collectives == {"collective-permute-start.1", "collective-permute-done.1"}


@pytest.mark.parametrize("text,name,container", [
    ("%while = (s32[]{:T(128)}, f32[8,8]{0,1:T(8,128)}) while((s32[]{:T(128)} %t)", "while", True),
    ("%k.1 = f32[1,16,16]{2,1,0:T(8,128)} custom-call(f32[1,24,16]{2,1,0:T(8,128)} %p)", "k.1", False),
    ("%copy-start = (f32[4,4]{0,1:T(4,128)S(1)}, u32[]{:S(2)}) copy-start(f32[4,4] %c)",
     "copy-start", False),
    ("%call.3 = f32[8]{0} call(f32[8]{0} %x), to_apply=%f", "call.3", True),
])
def test_event_names_and_containers(text, name, container):
    assert trace.event_name(text) == name
    assert trace.is_container(text) is container


def test_union_measure_minus_gaps():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (10, 10), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert trace.measure(merged) == 7
    assert trace.minus(merged, [(1, 2), (4, 6), (8, 20)]) == pytest.approx(2 + 2)
    assert trace.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (9, 12)]


def test_reduce_events_busy_idle_split_and_exposed_collective():
    ns = 1e9
    dev0 = [("k.1", 0 * ns, 2 * ns),                # kernel 2 s
            ("fusion.4", 2 * ns, 3 * ns),           # other 1 s
            ("collective-permute-start.1", 2.5 * ns, 4 * ns),   # 1 s exposed
            ("k.1", 6 * ns, 7 * ns)]                # idle 4..6
    dev1 = [("k.1", 0, 1 * ns),
            ("collective-permute-done.1", 0.5 * ns, 1.2 * ns)]  # 0.2 s exposed
    spans = [("bench.window", 0, 10 * ns), ("bench.call", 0, 4.5 * ns),
             ("bench.wait", 4.5 * ns, 6 * ns)]
    red = trace.reduce_events({0: dev0, 1: dev1}, spans, {"k.1"},
                              {"collective-permute-start.1", "collective-permute-done.1"},
                              (0, 10 * ns))
    d0, d1 = red.devices[0], red.devices[1]
    assert red.window_s == pytest.approx(10)
    assert d0.busy_s == pytest.approx(5)
    assert d0.kernel_s == pytest.approx(3)
    assert d0.other_s == pytest.approx(1)
    assert d0.collective_s == pytest.approx(1.5)
    assert d0.exposed_collective_s == pytest.approx(1)
    assert d1.busy_s == pytest.approx(1.2)
    assert d1.exposed_collective_s == pytest.approx(0.2)
    assert red.busy_s_mean == pytest.approx(3.1)
    top = dict(red.top_ops)
    assert top["k.1 [kernel]"] == pytest.approx((3 + 1) / 2)
    assert top["fusion.4 [op]"] == pytest.approx(0.5)
    # device 0 idles 4..6 (midpoint in bench.wait) and 7..10 (no span)
    assert dict(red.idle_gaps) == {"bench.wait": pytest.approx(2.0),
                                   "no benchmark span": pytest.approx(3.0)}
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_events_clips_to_window():
    red = trace.reduce_events({0: [("a", -5, 5), ("b", 8, 20)]}, [], set(), set(), (0, 10))
    assert red.devices[0].busy_s == pytest.approx(7e-9)
    assert red.devices[0].other_s == pytest.approx(7e-9)


def test_readers_on_a_reduction():
    from bench import harness
    ns = 1e9
    red = trace.reduce_events({0: [("k", 0, 2 * ns), ("f", 2 * ns, 3 * ns)]}, [],
                              {"k"}, set(), (0, 4 * ns))
    facts = {"trace": red, "steps": 10, "t_min_step_s": 0.03}
    assert harness.load_reader("iterate_step_roofline").read(facts) == pytest.approx(10.0)
    assert harness.load_reader("offkernel_busy_frac.iterate").read(facts) == pytest.approx(100 / 3)
    assert harness.load_reader("device_idle_frac.iterate").read(facts) == pytest.approx(25.0)
    assert harness.load_reader("exposed_collective_frac").read(facts) is None
    assert harness.load_reader("iterate_step_roofline").read({}) is None


@pytest.mark.parametrize("spans,ok", [
    ([("bench.call", 1, 2), ("bench.window", 0, 10)], True),
    ([("bench.call", 1, 2)], False),
    ([("bench.window", 0, 10), ("bench.window", 20, 30)], False),
], ids=["one", "none", "two"])
def test_the_window_is_the_one_window_span(spans, ok):
    if ok:
        assert trace.window_of(spans) == (0, 10)
    else:
        with pytest.raises(ValueError):
            trace.window_of(spans)

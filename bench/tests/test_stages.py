"""Stage attribution, the idle split and gaps named by the program's
spans (``bench/stages.py``), on synthetic HLO text and intervals and on
the 200-cycle trace recorded on a TPU v5e (a program without scopes)."""
import glob
import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(BENCH, "stages.py"))
st = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(st)

OP = 'metadata={op_name="jit(_run)/while/body/closed_call/%s"}'
HLO = f"""HloModule jit__run, entry_computation_layout={{()->s32[8]{{0}}}}

%fused_computation.1 (param_0.1: s32[8]) -> s32[8] {{
  %param_0.1 = s32[8]{{0}} parameter(0)
  %add.1 = s32[8]{{0}} add(s32[8]{{0}} %param_0.1, s32[8]{{0}} %param_0.1), {OP % "sim.network/add"}
  ROOT %negate.1 = s32[8]{{0}} negate(s32[8]{{0}} %add.1), {OP % "sim.network/jit(_roll)/neg"}
}}

%fused_computation.2 (param_0.2: s32[8]) -> s32[8] {{
  %param_0.2 = s32[8]{{0}} parameter(0)
  %abs.2 = s32[8]{{0}} abs(s32[8]{{0}} %param_0.2), {OP % "sim.account/abs"}
  %sign.2 = s32[8]{{0}} sign(s32[8]{{0}} %abs.2), {OP % "sim.account/sign"}
  ROOT %not.2 = s32[8]{{0}} not(s32[8]{{0}} %sign.2), {OP % "sim.wake/not"}
}}

%region_0.3 (a.3: s32[], b.3: s32[]) -> s32[] {{
  %a.3 = s32[] parameter(0)
  %b.3 = s32[] parameter(1)
  ROOT %add.3 = s32[] add(s32[] %a.3, s32[] %b.3)
}}

%body.4 (arg.4: (s32[], s32[8])) -> (s32[], s32[8]) {{
  %arg.4 = (s32[], s32[8]{{0:T(256)}}) parameter(0)
  %gte.4 = s32[8]{{0:T(256)S(1)}} get-tuple-element((s32[], s32[8]{{0}}) %arg.4), index=1
  %fusion.1 = s32[8]{{0}} fusion(s32[8]{{0}} %gte.4), kind=kLoop, calls=%fused_computation.1
  %constant.4 = s32[] constant(0)
  %reduce-window.4 = s32[8]{{0}} reduce-window(s32[8]{{0}} %fusion.1, s32[] %constant.4), window={{size=8 pad=7_0}}, to_apply=%region_0.3, metadata={{op_name="reduce_window_sum"}}
  %engine_step.4 = s32[8]{{0}} custom-call(s32[8]{{0}} %reduce-window.4), custom_call_target="tpu_custom_call", {OP % "sim.arbitrate/engine_step"}
  %fusion.2 = s32[8]{{0}} fusion(s32[8]{{0}} %engine_step.4), kind=kLoop, calls=%fused_computation.2
  %gte.5 = s32[] get-tuple-element((s32[], s32[8]{{0}}) %arg.4), index=0
  %copy.4 = s32[] copy(s32[] %gte.5)
  %add.4 = s32[] add(s32[] %copy.4, s32[] %gte.5), {OP % "sim.issue/add"}
  ROOT %tuple.4 = (s32[], s32[8]{{0}}) tuple(s32[] %add.4, s32[8]{{0}} %fusion.2)
}}

%cond.5 (arg.5: (s32[], s32[8])) -> pred[] {{
  %arg.5 = (s32[], s32[8]{{0}}) parameter(0)
  %after-all.5 = token[] after-all()
  ROOT %constant.5 = pred[] constant(false)
}}

ENTRY %main.6 () -> s32[8] {{
  %constant.6 = (s32[], s32[8]{{0}}) constant({{0, {{0,0,0,0,0,0,0,0}}}})
  %while.6 = (s32[], s32[8]{{0}}) while((s32[], s32[8]{{0}}) %constant.6), condition=%cond.5, body=%body.4
  ROOT %gte.6 = s32[8]{{0}} get-tuple-element((s32[], s32[8]{{0}}) %while.6), index=1
}}
"""


def test_scope_of_takes_the_innermost_stage():
    assert st.scope_of("jit(f)/while/body/sim.network/sim.faults/x") == \
        "sim.faults"
    assert st.scope_of("jit(f)/while/body/sim.arbitrate") == "sim.arbitrate"
    assert st.scope_of("jit(f)/sim.networkx/sim.bogus/y") is None
    assert st.scope_of("reduce_window_sum") is None


def test_stage_map_rules_in_order():
    smap = st.stage_map(HLO)
    # 1. own op_name
    assert smap["engine_step.4"] == "sim.arbitrate"
    assert smap["add.4"] == "sim.issue"
    # 2. the most common stage of the called computation
    assert smap["fusion.1"] == "sim.network"
    assert smap["fusion.2"] == "sim.account"           # 2 account, 1 wake
    # 3. a bare op_name: the producer's stage, then the user's
    assert smap["reduce-window.4"] == "sim.network"     # from fusion.1
    assert smap["constant.4"] == "sim.network"          # its user's
    assert smap["gte.4"] == "sim.network"               # user fusion.1
    assert smap["copy.4"] == "sim.issue"                # via gte.5 <- add.4
    # 2. again, one level up: the while takes its body's majority
    assert smap["while.6"] == "sim.network"
    # 4. nothing scoped anywhere near
    assert smap["after-all.5"] == st.UNSCOPED
    assert smap["constant.5"] == st.UNSCOPED


def test_loop_body_and_opcodes():
    body = st.loop_body(HLO)
    assert [i.name for i in body][:3] == ["arg.4", "gte.4", "fusion.1"]
    ops = {i.name: i.opcode for i in body}
    assert ops["reduce-window.4"] == "reduce-window"
    assert ops["engine_step.4"] == "custom-call"
    assert ops["tuple.4"] == "tuple"
    with pytest.raises(ValueError):
        st.loop_body("HloModule m\n\nENTRY %e () -> s32[] {\n"
                     "  ROOT %c = s32[] constant(1)\n}\n")


def test_a_program_without_scopes_maps_to_unscoped():
    plain = HLO.replace("/sim.", "/x.")
    assert set(st.stage_map(plain).values()) == {st.UNSCOPED}


MOD = "/device:TPU:0"


def _raw():
    # a run executable from 1 to 9 with ops inside it and one outside
    ops = [(1.0, 2.0, "fusion.1", False), (2.5, 3.0, "engine_step.4", True),
           (3.0, 4.0, "reduce-window.4", False), (5.0, 6.0, "add.4", False),
           (6.0, 6.5, "fusion.2", False), (9.5, 10.0, "fusion.9", False)]
    modules = [(1.0, 9.0, st.RUN_MODULE), (9.4, 10.0, "jit_other")]
    spans = [(0.0, 0.0, "bench.trace_start"), (0.0, 9.2, "bench.sync_run"),
             (0.1, 9.1, "repro.run"), (0.2, 8.0, "repro.run.dispatch"),
             (8.0, 9.05, "repro.run.fetch"),
             (9.2, 11.0, "bench.sync_run"), (11.0, 11.0, "bench.trace_stop")]
    return {"devices": {MOD: ops}, "modules": {MOD: modules},
            "spans": spans}


def test_idle_split_inside_and_outside_the_run_executable():
    raw = _raw()
    win = st.marks(raw)
    assert win == (0.0, 11.0)
    s = st.idle_split(raw, win, st.RUN_MODULE)
    # busy 1+0.5+1+1+0.5+0.5 = 4.5 of 11; inside (1, 9): gaps
    # (2, 2.5) + (4, 5) + (6.5, 9) = 4; outside (0, 1) + (9, 9.5)
    # + (10, 11) = 2.5
    assert s["idle_s"] == pytest.approx(6.5)
    assert s["in_module_s"] == pytest.approx(4.0)
    assert s["outside_s"] == pytest.approx(2.5)
    assert s["in_module_s"] + s["outside_s"] == pytest.approx(s["idle_s"])


def test_gaps_named_by_program_spans_inside_bench_spans():
    # longest first, equal lengths latest first; each named by the
    # innermost span around its midpoint
    assert st.named_gaps(_raw(), (0.0, 11.0)) == [
        ["repro.run.fetch", pytest.approx(3.0)],        # (6.5, 9.5)
        ["bench.sync_run", pytest.approx(1.0)],         # (10, 11)
        ["repro.run.dispatch", pytest.approx(1.0)],     # (4, 5)
        ["repro.run.dispatch", pytest.approx(1.0)],     # (0, 1)
        ["repro.run.dispatch", pytest.approx(0.5)]]     # (2, 2.5)


def test_stage_times_leave_the_kernel_out_and_need_a_match():
    raw, smap = _raw(), st.stage_map(HLO)
    t = st.stage_times(raw, smap, (0.0, 11.0))
    assert t["matched_share"] == pytest.approx(1.0)
    assert t["busy_s"] == pytest.approx(4.0)            # fusion.9 is outside
    assert t["kernel_s"] == pytest.approx(0.5)
    g = t["stages"]
    assert g["sim.network"] == pytest.approx(2.0)       # fusion.1 + rw
    assert g["sim.issue"] == pytest.approx(1.0)
    assert g["sim.account"] == pytest.approx(0.5)
    assert g["sim.arbitrate"] == 0.0                    # the kernel only
    assert sum(g.values()) + t["kernel_s"] == pytest.approx(t["busy_s"])
    # an op the text does not hold: 1 of 5 s unmatched -> not read
    raw["devices"][MOD].append((7.0, 8.0, "fusion.77", False))
    t = st.stage_times(raw, smap, (0.0, 11.0))
    assert t["matched_share"] == pytest.approx(4.0 / 5.0)
    assert t["stages"] is None
    # a program without scopes: nothing to read
    t = st.stage_times(_raw(), st.stage_map(HLO.replace("/sim.", "/x.")),
                       (0.0, 11.0))
    assert t["matched_share"] == pytest.approx(1.0)
    assert t["stages"] is None


RECORDED = glob.glob(os.path.join(BENCH, "testdata", "*", "**",
                                  "*.xplane.pb"), recursive=True)


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
def test_recorded_trace_split_and_no_stages():
    """The 200-cycle hist1024 run recorded on the v5e (a program from
    before the scopes): the in-loop and host idle add up to the idle
    share the harness reads, and the stage times read None."""
    tr = st.tr
    raw = st.load(RECORDED[0])
    assert [m for _, _, m in raw["modules"][MOD]] == [st.RUN_MODULE]
    win = raw["spans"][0][:2]                           # the bench.run span
    r = tr.reduce(tr.load(RECORDED[0]), window=win)
    s = st.idle_split(raw, win, st.RUN_MODULE)
    idle_pct = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert 100.0 * (s["in_module_s"] + s["outside_s"]) / r["window_s"] == \
        pytest.approx(idle_pct)
    assert s["in_module_s"] > 0.0 and s["outside_s"] > 0.0
    assert st.stage_times(raw, {}, win)["stages"] is None
    # a map with scopes but of another compile: the names do not match
    t = st.stage_times(raw, st.stage_map(HLO), win)
    assert t["matched_share"] < st.MIN_MATCHED and t["stages"] is None


@pytest.mark.parametrize("name", ["terapool1024.rmw_hot4",
                                  "mempool256.grid50"])
def test_measure_rehearsed_on_the_cpu(name):
    """The on-chip measurement, on a tiny cell and the CPU (which has
    no device plane: nothing to attribute, nothing guessed)."""
    from test_rehearsal import no_chip_check, tiny
    out = st.measure(tiny(name), 2**31 + 7, 0.2, require=no_chip_check)
    assert len(out["rate_off"]) == len(out["rate_on"]) == 2
    assert out["window_s"] > 0
    spans = out["span_pct_off"][0]
    if name.endswith("rmw_hot4"):
        assert set(spans) == {"repro.run", "repro.run.dispatch",
                              "repro.run.fetch", "repro.run.metrics"}
        assert out["traced_cycles"] > 0
        assert out["stage_us_per_cycle"] is None
    else:
        assert set(spans) == {"repro.sweep.dispatch", "repro.sweep.drain",
                              "repro.sweep.metrics"}

"""The engine's own spans as the benchmark reads them (``pspans.py`` and
the readers built on it): programs found by their stable names, in-step
idle put down to the innermost ``serve.*`` span, the counting attributes
of the tracer's ``serve.step`` spans read per window step, and a whole
tiny run with the engine's tracer on."""

from __future__ import annotations

from pathlib import Path
import types

from chipbench import harness
from chipbench import loop
from chipbench import program
from chipbench import pspans
from chipbench import spec
from chipbench import traffic
from chipbench import xtrace
from chipbench.loop import Log
from chipbench.loop import Step
from chipbench.xtrace import Interval
import pytest

from repro.serve import ServeEngine
from repro.serve.tracing import Tracer

MS = 1e6
REPO = Path(__file__).resolve().parents[2]


def I(name, start, end):
    return Interval(name, start * MS, end * MS)


def _red():
    # two steps of one decode group each, with a prefill in the first
    spans = [I("chipbench.window", 0, 100), I("chipbench.step", 0, 40),
             I("chipbench.step", 50, 90), I("chipbench.idle", 40, 50)]
    modules = [I("jit_serve_prefill(1)", 2, 6), I("jit_serve_splice(2)", 6, 7),
               I("jit_serve_decode(3)", 10, 20),
               I("jit_serve_merge_slots(4)", 20, 24),
               I("jit_serve_decode(3)", 54.5, 64),
               I("jit_serve_merge_slots(4)", 64, 68),
               I("jit_serve_decode_other(5)", 70, 71)]
    return xtrace.build(modules, [], spans)


def _program_spans():
    return [I("serve.queue", -5, 1), I("serve.step", 0.5, 39.5),
            I("serve.admit", 1, 9), I("serve.prefill", 1, 2.5),
            I("serve.decode", 9.5, 11), I("serve.merge", 11, 12),
            I("serve.sample", 12, 30), I("serve.step", 50.5, 89.5),
            I("serve.decode", 55, 56), I("serve.merge", 56, 57),
            I("serve.sample", 57, 80)]


def _ctx(red, program=None):
    steps = [Step(0, 0.040, decode_ctx=[9], decode_calls=1),
             Step(0.050, 0.090, decode_ctx=[10], decode_calls=1),
             Step(0.100, 0.110, decode_ctx=[11], decode_calls=1)]
    log = Log(t0=0.0, seconds=0.1, served=[], steps=steps, t_end=0.095)
    return pspans.TracedContext(log, red, {}, None, None, 0, program)


def _read(name, ctx):
    return spec.metric_reader(REPO, name)(ctx)


def test_programs_are_found_by_name():
    red = _red()
    assert [m.start / MS for m in pspans.programs(red, "serve_decode")] \
        == [10, 54.5]
    assert [m.start / MS for m in pspans.programs(red, "serve_merge_slots")] \
        == [20, 64]
    assert _read("merge_ms_per_step", _ctx(red)) == pytest.approx(4.0)


def test_steps_without_device_programs_are_left_out():
    """A step span after the profiler's device events ran out holds no
    program: it counts in no denominator."""
    red = _red()
    red.steps.append(I("chipbench.step", 92, 99))
    assert pspans.decoding_steps(red) == [0, 1]
    rec = pspans.ProgramRecord(tracer=None, spans=_program_spans()
                               + [I("serve.sample", 93, 98)])
    ctx = _ctx(red, rec)
    assert _read("merge_ms_per_step", ctx) == pytest.approx(4.0)
    assert _read("sample_idle_ms_per_step", ctx) == \
        pytest.approx((6 + 2 + 9 + 0.5) / 2)


def test_without_named_programs_the_readers_report_nothing():
    red = xtrace.build([I("jit__lambda(1)", 10, 20),
                        I("jit__where(2)", 20, 24)], [],
                       [I("chipbench.step", 0, 40)])
    for name in ("merge_ms_per_step", "decode_groups_per_step",
                 "sample_idle_ms_per_step", "host_syncs_per_step",
                 "admit_wait_ms_p50"):
        assert _read(name, _ctx(red)) is None


def test_in_step_idle_is_what_the_idle_share_counts():
    red = _red()
    gaps = pspans.in_step_idle(red)
    assert [(lo / MS, hi / MS) for lo, hi in gaps] == \
        [(0, 2), (7, 10), (24, 40), (50, 54.5), (68, 70), (71, 90)]
    total = sum(s.dur for s in red.steps)
    assert sum(hi - lo for lo, hi in gaps) == pytest.approx(
        total - red.busy_within(red.steps))


def test_idle_goes_to_the_innermost_span_and_adds_up():
    red = _red()
    idle = pspans.idle_by_span(red, _program_spans())
    # (0, 2): none, step, prefill; (7, 10): admit, step, decode;
    # (24, 40): sample, step, none; (50, 54.5): none, step; (68, 70):
    # sample; (71, 90): sample, step, none.  The queue span is no work.
    assert {k: v / MS for k, v in idle.items()} == pytest.approx({
        "none": 2.0, "serve.step": 24.0, "serve.prefill": 1.0,
        "serve.admit": 2.0, "serve.decode": 0.5, "serve.sample": 17.0})
    in_step = sum(hi - lo for lo, hi in pspans.in_step_idle(red))
    assert sum(idle.values()) == pytest.approx(in_step)


def test_sample_idle_per_decoding_step():
    red = _red()
    rec = pspans.ProgramRecord(tracer=None, spans=_program_spans())
    # sample (12, 30) and (57, 80) against the idle (24, 40), (68, 70)
    # and (71, 90): 6 + 2 + 9 ms; decode (9.5, 11) against (7, 10): 0.5
    # ms; over two steps that decoded
    assert _read("sample_idle_ms_per_step", _ctx(red, rec)) == \
        pytest.approx((6 + 2 + 9 + 0.5) / 2)


def _span(i, name, start_ms, end_ms, uid=None, **attrs):
    """A span as the engine's tracer records it (times in ns)."""
    start, end = int(start_ms * MS), int(end_ms * MS)
    return types.SimpleNamespace(id=i, parent=None, name=name, uid=uid,
                                 start=start, end=end, dur=end - start,
                                 attrs=attrs)


def test_counter_readers_take_the_window_steps_that_decoded():
    tracer = types.SimpleNamespace(spans=[
        _span(0, "serve.step", -5, -1, groups=3, host_reads=9),
        _span(1, "serve.step", 0, 40, groups=1, host_reads=3),
        _span(2, "serve.step", 45, 48, groups=0, host_reads=0),
        _span(3, "serve.step", 50, 90, groups=2, host_reads=2),
        _span(4, "serve.step", 96, 99, groups=1, host_reads=1),
        _span(5, "serve.queue", -10, -2, uid=1),
        _span(6, "serve.queue", -1, 1, uid=2),
        _span(7, "serve.queue", 10, 50, uid=3),
        _span(8, "serve.queue", 60, 64, uid=4),
        _span(9, "serve.queue", 90, 100, uid=5)])
    ctx = _ctx(_red(), pspans.ProgramRecord(tracer, None))
    assert _read("host_syncs_per_step", ctx) == pytest.approx(2.5)
    assert _read("decode_groups_per_step", ctx) == pytest.approx(1.5)
    assert _read("admit_wait_ms_p50", ctx) == pytest.approx(4.0)
    assert _read("sample_idle_ms_per_step", ctx) is None


RECORDED = REPO / "chipbench" / "testdata" / "nemo_chat_window.xplane.pb"


EXPECTED_BUSY_NS = 696630187.0
EXPECTED_OPS = {
    "prefill:jit__lambda(4270914235806739647)": 0.039570273,
    "jit__pad(4487065862781160197)": 0.000324824,
    "jit_convert_element_type(15388027131515875373)": 1.7909e-05,
    "jit_dynamic_update_slice(13282254305726148895)": 0.004562554,
    "jit_dynamic_slice(1740229268208374480)": 2.656e-06,
    "jit_squeeze(18409712833349587380)": 7.7054e-05,
    "jit__argmax(15993869291948722370)": 3.4027e-05,
    "decode:jit__lambda(14877881568712217289)": 0.534829689,
    "jit_reshape(164188228491484405)": 2.1993e-05,
    "jit__where(2138782153612107596)": 0.117145444,
    "jit_dynamic_slice(7654916134543441206)": 9.6816e-05,
}
EXPECTED_GAPS = [("idle", 0.750746866), ("step", 0.002731198),
                 ("step", 0.002701282)]


def test_reduction_of_the_recorded_trace_is_unchanged():
    """The numbers the existing readers are built on, as first read from
    1.5 s of `nemo-chat` on a TPU v5e."""
    red = xtrace.reduce(xtrace.load(RECORDED))
    kinds = [c.kind for c in red.calls]
    assert (kinds.count("prefill"), kinds.count("decode")) == (1, 20)
    assert len(red.steps) == 20
    assert red.busy_ns == pytest.approx(EXPECTED_BUSY_NS, rel=1e-12)
    ops = xtrace.device_ops(red)
    assert sorted(ops) == sorted(EXPECTED_OPS)
    for k, v in EXPECTED_OPS.items():
        assert ops[k] == pytest.approx(v, rel=1e-9)
    assert [(lab, round(d, 9)) for lab, d in
            ((xtrace.host_label(red, (lo + hi) / 2), (hi - lo) * 1e-9)
             for lo, hi in sorted(xtrace.idle_gaps(red),
                                  key=lambda g: g[0] - g[1])[:3])] == \
        EXPECTED_GAPS


def test_tiny_run_with_the_engine_tracer(bench_root):
    """A whole tiny window on the CPU with the engine's tracer on and the
    open loop's wrapper counting: the engine counted as many decode groups
    as the wrapper counted decode calls, the readers of the tracer report,
    and nothing compiled in the window."""
    cell = harness.load_cell(bench_root, "td")
    with Tracer() as tracer:
        def make_engine(cfg, params, max_batch, max_seq):
            return ServeEngine(cfg, params, max_batch=max_batch,
                               max_seq=max_seq, tracer=tracer)
        prog = types.SimpleNamespace(
            arch=program.arch, request=program.request,
            warm_up=program.warm_up, engine_module=program.engine_module,
            make_engine=make_engine)
        cfg, params = harness.program_weights(cell, prog, 7)
        engine = harness.warm_engine(cell, prog, cfg, params)
        planned = traffic.generate(cell.mix, 2.0, 7, cell.ref.vocab(cell.c))
        warm = tracer.compiles
        log = loop.run_open_loop(engine, planned, prog.request, 2.0,
                                 spans=True,
                                 engine_module=prog.engine_module)
        assert tracer.compiles == warm
    ctx = pspans.TracedContext(log, None, cell.c, None, None, 0,
                               pspans.ProgramRecord(tracer, None))
    calls = _read("decode_calls_per_step", ctx)
    assert calls >= 1
    assert _read("decode_groups_per_step", ctx) == pytest.approx(calls)
    assert _read("host_syncs_per_step", ctx) >= 1
    assert _read("admit_wait_ms_p50", ctx) >= 0


SPANS_RECORDED = (REPO / "chipbench" / "testdata"
                  / "nemo_code_serve_spans.xplane.pb")


def test_recorded_trace_with_the_engine_spans():
    """1.6 s of `nemo-code` on a TPU v5e with the engine's tracer on (two
    prefills, 23 steps of one decode group): the programs named
    ``serve_decode`` and ``serve_prefill`` are the very ones the span-based
    selection of ``xtrace.build`` takes, and the in-step idle, put down to
    the engine's spans, adds up to what the idle share counts."""
    data = xtrace.load(SPANS_RECORDED)
    red, spans = xtrace.reduce(data), pspans.program_spans(data)
    for kind in ("decode", "prefill"):
        by_span = [c.program for c in red.calls if c.kind == kind]
        by_name = pspans.programs(red, "serve_" + kind)
        assert len(by_name) == len(by_span) > 0
        assert all(a is b for a, b in zip(by_span, by_name))
    assert {s.name for s in spans} == {
        "serve.queue", "serve.step", "serve.admit", "serve.prefill",
        "serve.splice", "serve.first_token", "serve.decode", "serve.merge",
        "serve.sample"}
    idle = pspans.idle_by_span(red, spans)
    in_step = sum(s.dur for s in red.steps) - red.busy_within(red.steps)
    assert sum(idle.values()) == pytest.approx(in_step)
    assert max(idle, key=idle.get) == "serve.sample"
    assert pspans.decoding_steps(red) == list(range(len(red.steps)))
    ctx = pspans.TracedContext(None, red, {}, None, None, 0)
    assert 5.0 < _read("merge_ms_per_step", ctx) < 7.0
    ops = xtrace.device_ops(red)
    assert max(ops, key=ops.get).startswith("decode:jit_serve_decode(")

"""Trace reduction: device busy time, the program each host call launched,
idle gaps labelled by the host span open at the time."""

from __future__ import annotations

from pathlib import Path

from chipbench import xtrace
from chipbench.xtrace import Interval
import pytest

MS = 1e6


def I(name, start, end):
    """An event from ``start`` to ``end`` milliseconds."""
    return Interval(name, start * MS, end * MS)


def _trace():
    # one window of 100 ms; one step span holding a prefill call and a
    # decode call; the decode program appears to start before its span
    # opens, as the clocks of a TPU trace allow; an eager op after each
    # program; an idle span after the step
    spans = [I("chipbench.window", 0, 100), I("chipbench.step", 5, 70),
             I("chipbench.prefill", 6, 8), I("chipbench.merge", 40, 41),
             I("chipbench.decode", 30, 32), I("chipbench.idle", 70, 100)]
    modules = [I("jit__lambda", 10, 25), I("jit_pad", 26, 28),
               I("jit__lambda", 29.5, 53), I("jit_where", 55, 60)]
    ops = [I("fusion.1", 10, 20), I("fusion.2", 20, 25), I("pad", 26, 28),
           I("fusion.3", 29.5, 53), I("select", 55, 60)]
    return xtrace.build(modules, ops, spans)


def test_busy_is_the_union_of_device_ops():
    red = _trace()
    assert red.busy == [(10 * MS, 25 * MS), (26 * MS, 28 * MS),
                        (29.5 * MS, 53 * MS), (55 * MS, 60 * MS)]
    assert red.busy_ns == pytest.approx((15 + 2 + 23.5 + 5) * MS)
    assert red.busy_within(red.steps) == pytest.approx(45.5 * MS)
    assert red.window.dur == 100 * MS


def test_each_call_finds_the_program_it_launched():
    red = _trace()
    kinds = [(c.kind, c.program.start / MS, c.program.dur / MS, c.step)
             for c in red.calls]
    assert kinds == [("prefill", 10, 15, 0), ("decode", 29.5, 23.5, 0)]
    ops = xtrace.device_ops(red)
    assert ops["prefill:jit__lambda"] == pytest.approx(15e-3)
    assert ops["decode:jit__lambda"] == pytest.approx(23.5e-3)
    assert ops["jit_where"] == pytest.approx(5e-3)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    red = _trace()
    gaps = xtrace.idle_gaps(red)
    assert [(lo / MS, hi / MS) for lo, hi in gaps] == \
        [(0, 10), (25, 26), (28, 29.5), (53, 55), (60, 100)]
    labels = [xtrace.host_label(red, (lo + hi) / 2) for lo, hi in gaps]
    assert labels == ["step", "step", "step", "step", "idle"]


def test_without_a_window_span_the_trace_extent_is_the_window():
    red = xtrace.build([I("m", 10, 20)], [], [I("chipbench.step", 5, 30)])
    assert (red.window.start, red.window.end) == (5 * MS, 30 * MS)
    assert red.busy == [(10 * MS, 20 * MS)]


RECORDED = (Path(__file__).resolve().parents[2] / "chipbench" / "testdata"
            / "nemo_chat_window.xplane.pb")


def test_reduction_of_a_trace_recorded_on_the_chip():
    """1.5 s of `nemo-chat` on a TPU v5e (one prefill, then decode steps
    of one position group each): every call finds its program, every
    decode call the same one, and the device is busy most of each step."""
    red = xtrace.reduce(xtrace.load(RECORDED))
    assert 1.4e9 < red.window.dur < 1.7e9
    decodes = [c for c in red.calls if c.kind == "decode"]
    prefills = [c for c in red.calls if c.kind == "prefill"]
    assert decodes and prefills
    assert all(c.step >= 0 for c in red.calls)
    assert len({c.program.name for c in decodes}) == 1
    assert all(c.program.dur > 5e6 for c in red.calls)     # > 5 ms
    assert 0 < red.busy_ns < red.window.dur
    assert red.busy_within(red.steps) > 0.5 * sum(s.dur for s in red.steps)
    ops = xtrace.device_ops(red)
    top = max(ops, key=ops.get)
    assert top.startswith("decode:")

"""The program's own spans, joined with the device trace.

The engine records ``serve.*`` spans, whose attributes count its work,
when it is given a tracer (``repro.serve.tracing``).  Each span lands twice: in the tracer's
memory, on the host's ``perf_counter`` clock (that of ``loop.Log``),
and on the profiler's host plane, on the device trace's clock.  The
readers of the program-side metrics take both from
``TracedContext.program``; a context without it (a run whose engine had
no tracer) gives them nothing to read.

Device programs are found by their stable names (``serve_decode``,
``serve_merge_slots``, ...), which needs no host span at all.
"""

from __future__ import annotations

import bisect
import itertools
from dataclasses import dataclass
from typing import Dict
from typing import List
from typing import Optional
from typing import Tuple

from chipbench import harness
from chipbench import xtrace
from chipbench.xtrace import CLOCK_SLACK_NS
from chipbench.xtrace import Interval

PREFIX = "serve."
# a request's wait spans steps: it says nothing of what the host is doing
NOT_HOST_WORK = ("serve.queue",)


@dataclass
class ProgramRecord:
    """What the engine recorded in one run: its tracer (``spans``), and
    its spans from the profiler's host plane (None without a profile)."""
    tracer: object
    spans: Optional[List[Interval]]


@dataclass
class TracedContext(harness.Context):
    program: Optional[ProgramRecord] = None


def record(ctx) -> Optional[ProgramRecord]:
    return getattr(ctx, "program", None)


def program_spans(profile) -> List[Interval]:
    """The ``serve.*`` events of the profiler's host plane, by start."""
    out: List[Interval] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(e for e in xtrace._events(line)
                           if e.name.startswith(PREFIX))
    return sorted(out, key=lambda s: s.start)


def programs(red: xtrace.Reduced, name: str) -> List[Interval]:
    """The window's device programs of the jitted function ``name``
    (XLA names the module ``jit_<name>(<fingerprint>)``)."""
    want = "jit_" + name
    return [m for m in red.modules if m.name.partition("(")[0] == want]


def by_step(red: xtrace.Reduced, name: str) -> List[List[Interval]]:
    """The programs of ``name`` that started in each step span of the
    window, searched from ``CLOCK_SLACK_NS`` before the span: the host
    waits for each step's last program, so none runs on into the next."""
    starts = [s.start for s in red.steps]
    out: List[List[Interval]] = [[] for _ in red.steps]
    for m in programs(red, name):
        j = bisect.bisect_right(starts, m.start + CLOCK_SLACK_NS) - 1
        if j >= 0 and m.start < red.steps[j].end:
            out[j].append(m)
    return out


def decoding_steps(red: xtrace.Reduced) -> List[int]:
    """The step spans in which a ``serve_decode`` program ran.  A step
    that decoded but holds no device program lies where the profiler
    recorded no device events (its buffer fills in a long window), and is
    left out, as ``readers.decode_steps`` leaves it out."""
    return [j for j, d in enumerate(by_step(red, "serve_decode")) if d]


def decoding_step_spans(ctx) -> Optional[List]:
    """The tracer's ``serve.step`` spans that started in the window of the
    open loop's log (both on the ``perf_counter`` clock) and decoded; None
    for a run whose engine had no tracer."""
    rec = record(ctx)
    if rec is None:
        return None
    lo, hi = ctx.log.t0 * 1e9, ctx.log.t_end * 1e9
    return [s for s in rec.tracer.spans if s.name == "serve.step"
            and lo <= s.start < hi and s.attrs["groups"]]


def in_step_idle(red: xtrace.Reduced,
                 steps: Optional[List[int]] = None) -> List[Tuple[float, float]]:
    """Every interval inside a step span (all, or those of ``steps``) in
    which no device op ran (ns): what ``device_idle_share`` counts as
    idle."""
    out = []
    for st in (red.steps if steps is None else [red.steps[j]
                                                for j in steps]):
        t = st.start
        i = max(bisect.bisect_right(red._busy_starts, st.start) - 1, 0)
        for s, e in red.busy[i:]:
            if s >= st.end:
                break
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < st.end:
            out.append((t, st.end))
    return out


def idle_by_span(red: xtrace.Reduced, spans: List[Interval],
                 steps: Optional[List[int]] = None) -> Dict[str, float]:
    """The in-step idle time (ns; all steps, or those of ``steps``) by the
    innermost program span open at each instant, ``"none"`` where none is;
    the values add up to the in-step idle.  The profiler puts host and device events on one clock;
    as in ``xtrace.host_label``, no further correction is made, so each
    boundary between spans is as exact as that alignment."""
    work = sorted((s for s in spans if s.name not in NOT_HOST_WORK),
                  key=lambda s: s.start)
    starts = [s.start for s in work]
    reach = list(itertools.accumulate((s.end for s in work), max))
    cuts = sorted(t for s in work for t in (s.start, s.end))
    out: Dict[str, float] = {}
    for lo, hi in in_step_idle(red, steps):
        edges = [lo] + cuts[bisect.bisect_right(cuts, lo):
                            bisect.bisect_left(cuts, hi)] + [hi]
        for a, b in zip(edges, edges[1:]):
            if b > a:
                name = _innermost(work, starts, reach, (a + b) / 2)
                out[name] = out.get(name, 0.0) + (b - a)
    return out


def _innermost(work: List[Interval], starts: List[float],
               reach: List[float], t: float) -> str:
    """The latest-started span open at ``t``; ``reach[i]`` is the latest
    end among ``work[:i + 1]``, so the search stops where none is open."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if reach[i] <= t:
            break
        if t < work[i].end:
            return work[i].name
    return "none"

"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
readers need: device busy time, the device program each host call
launched, and the idle gaps labelled by what the host was doing.

Host spans are the driver's ``chipbench.*`` annotations.  The engine waits
for the device after every prefill and decode call (it reads the sampled
token back), so the programs that run between one call and the next are
those the call launched: its own program, then the eager copies and the
sampling that follow it.  The call's own program is the longest of them.
The host and device clocks of a TPU trace disagree by up to about a
millisecond (programs appear to start up to 0.9 ms before the span that
launched them), so the search starts ``CLOCK_SLACK_NS`` early.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from dataclasses import field
from pathlib import Path
from typing import Dict
from typing import List
from typing import Optional
from typing import Tuple

SPAN = "chipbench."
CALLS = ("prefill", "decode")
CLOCK_SLACK_NS = 2e6


@dataclass
class Interval:
    name: str
    start: float        # ns
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Call:
    kind: str           # "prefill" | "decode"
    span: Interval
    program: Optional[Interval]     # device program it launched
    step: int           # index of the step span holding it, or -1


@dataclass
class Reduced:
    window: Interval
    busy: List[Tuple[float, float]]           # merged device intervals
    modules: List[Interval]
    spans: List[Interval]                     # host chipbench spans
    calls: List[Call] = field(default_factory=list)
    steps: List[Interval] = field(default_factory=list)

    def __post_init__(self):
        self._busy_starts = [s for s, _ in self.busy]
        self._span_starts = [s.start for s in self.spans]

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)

    def busy_within(self, spans: List[Interval]) -> float:
        """Device busy time inside the given host spans (ns)."""
        total = 0.0
        for sp in spans:
            i = bisect.bisect_right(self._busy_starts, sp.start) - 1
            for s, e in self.busy[max(i, 0):]:
                if s >= sp.end:
                    break
                total += max(0.0, min(e, sp.end) - max(s, sp.start))
        return total


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line):
    for e in line.events:
        yield Interval(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(profile) -> Reduced:
    """Device and host events of one traced window (the ``chipbench.window``
    span, or the whole trace)."""
    modules: List[Interval] = []
    ops: List[Interval] = []
    spans: List[Interval] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and \
                "SparseCore" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.extend(_events(line))
                elif line.name == "XLA Ops":
                    ops.extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith(SPAN))
    return build(modules, ops, spans)


def build(modules: List[Interval], ops: List[Interval],
          spans: List[Interval]) -> Reduced:
    spans.sort(key=lambda s: s.start)
    win = [s for s in spans if s.name == SPAN + "window"]
    if win:
        window = win[0]
    else:
        every = modules + ops + spans
        window = Interval("window", min(e.start for e in every),
                          max(e.end for e in every))
    modules = sorted((m for m in modules
                      if window.start <= m.start < window.end),
                     key=lambda m: m.start)
    busy = _merge([(max(o.start, window.start), min(o.end, window.end))
                   for o in (ops or modules)
                   if o.end > window.start and o.start < window.end])
    red = Reduced(window=window, busy=busy, modules=modules, spans=spans)
    red.steps = [s for s in spans if s.name == SPAN + "step"
                 and window.start <= s.start < window.end]
    step_starts = [s.start for s in red.steps]
    starts = [m.start for m in modules]
    calls = [s for s in spans if s.name[len(SPAN):] in CALLS
             and window.start <= s.start < window.end]
    for k, s in enumerate(calls):
        nxt = calls[k + 1].start if k + 1 < len(calls) else window.end
        lo = bisect.bisect_left(starts, s.start - CLOCK_SLACK_NS)
        hi = bisect.bisect_left(starts, nxt - CLOCK_SLACK_NS)
        prog = max(modules[lo:hi], key=lambda m: m.dur, default=None)
        j = bisect.bisect_right(step_starts, s.start) - 1
        in_step = j >= 0 and s.start <= red.steps[j].end
        red.calls.append(Call(s.name[len(SPAN):], s, prog,
                              j if in_step else -1))
    return red


def idle_gaps(red: Reduced) -> List[Tuple[float, float]]:
    """Every interval of the window in which no device op ran (ns)."""
    edges = [red.window.start]
    for s, e in red.busy:
        edges.extend([s, e])
    edges.append(red.window.end)
    return [(lo, hi) for lo, hi in zip(edges[::2], edges[1::2]) if hi > lo]


def host_label(red: Reduced, t: float) -> str:
    """The innermost host span open at ``t`` ("none" outside every span)."""
    for i in range(bisect.bisect_right(red._span_starts, t) - 1, -1, -1):
        s = red.spans[i]
        if t < s.end and s.name != SPAN + "window":
            return s.name[len(SPAN):]
    return "none"


def device_ops(red: Reduced) -> Dict[str, float]:
    """Device seconds per program, the engine's calls named by kind."""
    launched = {id(c.program): c.kind for c in red.calls if c.program}
    out: Dict[str, float] = {}
    for m in red.modules:
        name = m.name
        if id(m) in launched:
            name = f"{launched[id(m)]}:{name}"
        out[name] = out.get(name, 0.0) + m.dur * 1e-9
    return out

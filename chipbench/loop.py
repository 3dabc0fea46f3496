"""Open-loop driver: offers the planned requests to ``ServeEngine`` at their
due times and records when each token reached the caller.

The engine is synchronous, so one thread does everything: add every
request that is due, run one ``ServeEngine.step``, stamp the tokens that
step returned with its end time, and sleep only when the engine has no
work.  A request due during a step is added after it; its wait still
counts, because every latency is taken from the due time.

With ``spans=True`` (the traced run) the driver also wraps the engine's
prefill and decode calls, ``_splice`` and ``_merge_slots`` in profiler
annotations and counts the calls per step; the untraced run calls the
engine unwrapped.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from dataclasses import field
import time
from typing import Callable
from typing import List
from typing import Optional

import jax

SPAN = "chipbench."


@dataclass
class Served:
    """One request as the caller saw it (times from ``perf_counter``)."""
    uid: int
    due: float
    prompt_len: int
    req: object
    added: float = 0.0
    prefill_at: Optional[float] = None
    times: List[float] = field(default_factory=list)


@dataclass
class Step:
    start: float
    end: float
    prefill_lens: List[int] = field(default_factory=list)
    decode_ctx: List[int] = field(default_factory=list)   # keys attended
    decode_calls: int = 0
    first_decode_at: Optional[float] = None


@dataclass
class Log:
    t0: float                       # window opens
    seconds: float
    served: List[Served]
    steps: List[Step]
    t_end: float = 0.0              # window closes
    t_drained: float = 0.0          # drain over


class _Spans:
    """Wraps the engine's calls in annotations, counts them per step."""

    def __init__(self, engine, engine_module, log_steps, served):
        self.log_steps = log_steps
        self.order = served               # prefill calls follow add order
        self.undo = []
        for attr, kind in (("_prefill", "prefill"), ("_decode", "decode")):
            self._patch(engine, attr, kind)
        for attr, kind in (("_splice", "splice"), ("_merge_slots", "merge")):
            self._patch(engine_module, attr, kind)

    def _patch(self, owner, attr, kind):
        fn = getattr(owner, attr)

        def wrapped(*args, **kw):
            self._note(kind)
            with jax.profiler.TraceAnnotation(SPAN + kind):
                return fn(*args, **kw)
        setattr(owner, attr, wrapped)
        self.undo.append((owner, attr, fn))

    def _note(self, kind):
        now = time.perf_counter()
        step = self.log_steps[-1]
        if kind == "prefill":
            pending = [s for s in self.order if s.prefill_at is None]
            if pending:
                pending[0].prefill_at = now
        elif kind == "decode":
            step.decode_calls += 1
            if step.first_decode_at is None:
                step.first_decode_at = now

    def restore(self):
        for owner, attr, fn in reversed(self.undo):
            setattr(owner, attr, fn)


def _annotate(spans: bool, name: str):
    if spans:
        return jax.profiler.TraceAnnotation(SPAN + name)
    return contextlib.nullcontext()


def run_open_loop(engine, planned, make_request: Callable, seconds: float,
                  *, spans: bool = False, engine_module=None,
                  drain_s: float = 60.0,
                  on_close: Optional[Callable[[], None]] = None) -> Log:
    """Offer ``planned`` (sorted by due time) for ``seconds``, call
    ``on_close`` as the window closes, then keep stepping, with no new
    arrivals, until every request due in the window has its first token or
    ``drain_s`` has passed."""
    served: List[Served] = []
    steps: List[Step] = []
    hooks = _Spans(engine, engine_module, steps, served) if spans else None
    inflight: List[Served] = []
    sched = engine.sched
    nxt = 0
    t0 = time.perf_counter()
    log = Log(t0=t0, seconds=seconds, served=served, steps=steps)

    def add_due(now):
        nonlocal nxt
        while nxt < len(planned) and t0 + planned[nxt].due_s <= now:
            p = planned[nxt]
            s = Served(uid=p.uid, due=t0 + p.due_s,
                       prompt_len=len(p.prompt), req=make_request(p),
                       added=now)
            engine.add_request(s.req)
            served.append(s)
            inflight.append(s)
            nxt += 1

    def one_step():
        st = Step(start=time.perf_counter(), end=0.0)
        steps.append(st)
        with _annotate(spans, "step"):
            engine.step()
        st.end = time.perf_counter()
        for s in inflight:
            got = len(s.req.tokens_out)
            for j in range(len(s.times), got):
                if j == 0:
                    st.prefill_lens.append(s.prompt_len)
                else:
                    st.decode_ctx.append(s.prompt_len + j)
            s.times.extend([st.end] * (got - len(s.times)))
        inflight[:] = [s for s in inflight if not s.req.done]

    try:
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            add_due(now)
            if not sched.drained:
                one_step()
                continue
            wake = t0 + (planned[nxt].due_s if nxt < len(planned)
                         else seconds)
            with _annotate(spans, "idle"):
                time.sleep(max(0.0, min(wake, t0 + seconds)
                               - time.perf_counter()))
        log.t_end = time.perf_counter()
        if on_close is not None:
            on_close()
        add_due(float("inf"))       # due in the window, added after it
        while (any(not s.times for s in served)
               and time.perf_counter() - log.t_end < drain_s):
            one_step()
        log.t_drained = time.perf_counter()
    finally:
        if hooks is not None:
            hooks.restore()
    return log

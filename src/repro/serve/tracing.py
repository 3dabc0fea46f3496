"""Spans and counters of the serving engine.

A :class:`Tracer` records what :class:`~repro.serve.engine.ServeEngine`
does, in memory: spans (name, start and end on the ``perf_counter_ns``
clock, the enclosing span, the request uid, a few integer attributes that
count the span's work) in a bounded buffer.  Each span is also a
``jax.profiler.TraceAnnotation`` of the same name, so that a profiler
trace shows it on the host plane, on the device trace's clock.  While the
tracer is open it listens to JAX's compile events: it counts the programs
lowered (``compiles``) and adds each to the ``compiles`` attribute of the
innermost open span.

An engine without a tracer records nothing: each span site is one test of
``tracer`` against None, or the shared no-op context :data:`NO_SPAN`.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Deque
from typing import Dict
from typing import List
from typing import Optional

import jax

# JAX reports one such event for every program it lowers for a new shape,
# whether XLA then compiles it or the persistent cache supplies it.
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
NO_SPAN = contextlib.nullcontext()


class Span:
    """One timed interval of the engine's work (times in ns)."""

    __slots__ = ("id", "parent", "name", "uid", "start", "end", "attrs",
                 "_note")

    def __init__(self, id: int, parent: Optional[int], name: str,
                 uid: Optional[int], attrs: Dict[str, int]):
        self.id = id
        self.parent = parent
        self.name = name
        self.uid = uid
        self.attrs = attrs
        self.start = 0
        self.end = 0
        self._note = None

    @property
    def dur(self) -> int:
        return self.end - self.start

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"uid={self.uid}, dur={self.dur}, attrs={self.attrs})")


class Tracer:
    """The engine's spans and compile counter; see the module docstring.

    Use it as a context manager, or call :meth:`close`, so that the
    compile listener it registers is removed again."""

    def __init__(self, capacity: int = 1 << 16):
        self.spans: Deque[Span] = collections.deque(maxlen=capacity)
        self.compiles = 0
        self._open: List[Span] = []
        self._next_id = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- spans ----------------------------------------------------------
    def start(self, name: str, uid: Optional[int] = None,
              nested: bool = True, **attrs: int) -> Span:
        """Opens a span.  A nested span is a child of the innermost open
        one, takes its uid unless given one, and must be finished before
        it; a span with ``nested=False`` (a request's wait across steps)
        has no parent and may end at any time."""
        parent = self._open[-1] if nested and self._open else None
        if uid is None and parent is not None:
            uid = parent.uid
        sp = Span(self._next_id, parent.id if parent else None, name, uid,
                  attrs)
        self._next_id += 1
        sp._note = jax.profiler.TraceAnnotation(name)
        sp._note.__enter__()
        if nested:
            self._open.append(sp)
        sp.start = time.perf_counter_ns()
        return sp

    def finish(self, sp: Span) -> None:
        sp.end = time.perf_counter_ns()
        sp._note.__exit__(None, None, None)
        sp._note = None
        if self._open and self._open[-1] is sp:
            self._open.pop()
        self.spans.append(sp)

    @contextlib.contextmanager
    def span(self, name: str, uid: Optional[int] = None, **attrs: int):
        sp = self.start(name, uid, **attrs)
        try:
            yield sp
        finally:
            self.finish(sp)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event != LOWERED_EVENT:
            return
        self.compiles += 1
        if self._open:
            sp = self._open[-1]
            sp.attrs["compiles"] = sp.attrs.get("compiles", 0) + 1


__all__ = ["LOWERED_EVENT", "NO_SPAN", "Span", "Tracer"]

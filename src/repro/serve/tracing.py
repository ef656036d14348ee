"""Host spans of the serving scheduler, on and off with the JAX profiler.

``with span("step", step=n): ...`` marks one phase of the scheduler's host
work.  While no profiler session is active the call is one
``TraceAnnotation.is_enabled()`` check and returns a shared no-op context:
no object, no clock read.  Nothing else turns spans on: taking a profile
(``jax.profiler.trace`` / ``start_trace``) does.

While a session is active a span enters ``TraceAnnotation("sched.<name>")``,
so it shows in the profiler trace beside the device ops, and also keeps a
record in a bounded in-memory ring: ``(name, start, end, parent, ids)``,
times from ``time.perf_counter()``, ``parent`` the index of the enclosing
span (the scheduler runs on one thread, so a plain stack nests them), and
``ids`` the request id or step number the span is about.  :func:`spans`
returns the finished records; readers put them on the trace's clock
themselves.  The ring keeps the newest ``RING`` spans.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

RING = 2 ** 17

_enabled = TraceAnnotation.is_enabled
_ring: collections.deque = collections.deque(maxlen=RING)
_stack: List[int] = []
_started = 0                    # spans ever started: the next span's index


class Span(NamedTuple):
    index: int
    name: str
    start: float                # time.perf_counter() seconds
    end: float
    parent: Optional[int]       # index of the enclosing span
    ids: Dict


class _Off:
    """The context every span returns while no profile is being taken."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, et, ev, tb):
        return False


_OFF = _Off()


class _On:
    __slots__ = ("name", "ids", "rec", "ann")

    def __init__(self, name: str, ids: Dict):
        self.name = name
        self.ids = ids

    def __enter__(self) -> Dict:
        global _started
        self.ann = TraceAnnotation(f"sched.{self.name}")
        self.ann.__enter__()
        self.rec = [self.name, time.perf_counter(), None,
                    _stack[-1] if _stack else None, self.ids]
        _ring.append(self.rec)
        _stack.append(_started)
        _started += 1
        return self.ids

    def __exit__(self, et, ev, tb):
        self.rec[2] = time.perf_counter()
        _stack.pop()
        return self.ann.__exit__(et, ev, tb)


def span(name: str, request_id=None, step: Optional[int] = None):
    """A context manager around one scheduler phase.  Entered, it gives the
    span's ``ids`` dict (``None`` while no profile is taken), which the body
    may add to: ``with span("grow") as ids: ...; if ids is not None:
    ids["pages"] = n``.  The ids are named parameters, not ``**ids``, so that
    a call with the profiler off builds nothing."""
    if not _enabled():
        return _OFF
    ids = {}
    if request_id is not None:
        ids["request_id"] = request_id
    if step is not None:
        ids["step"] = step
    return _On(name, ids)


def spans() -> List[Span]:
    """The finished spans the ring holds, oldest first."""
    first = _started - len(_ring)
    return [Span(first + i, name, start, end, parent, ids)
            for i, (name, start, end, parent, ids) in enumerate(list(_ring))
            if end is not None]


def clear() -> None:
    """Forget every recorded span (tests)."""
    global _started
    _ring.clear()
    _stack.clear()
    _started = 0

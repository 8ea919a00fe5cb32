"""Spans and counters inside the serving path, off by default.

An operator turns them on around a serve and reads them after it:

    from repro.serving import trace
    trace.enable()
    srv.serve(requests)
    spans, counts = trace.spans(), trace.counters()

While off, `span()` returns one shared no-op context and `count()`
returns at once: a site costs a call and a flag check, with no object
made and no clock read.

While on, a span records its name, its start and end on
`time.monotonic()` (the base of the server's realtime clock,
`RealtimeEventLoop`), its thread, its ids (`rid`, `instance`, `dp`,
`step`, `rows`, `tokens`), its parent (the span enclosing it on the same
thread) and its cause (the span on another thread that handed it its
work, passed as `cause=`, read there with `current()`).  Each span also
enters a `jax.profiler.TraceAnnotation` of the same name and ids, so
inside a profiler window the spans are events on the trace's host
plane, on the device trace's own clock: any `jax.profiler` trace
(TensorBoard, Perfetto) shows them.

`count(name, n)` adds `n` to the process total and to every span open on
the calling thread, so a span carries the counts made inside it.  Spans
and counts stay in memory until `reset()`; the worker threads append to
them under one lock.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

# jax.profiler's, imported by the first enable(): importing this module
# imports no JAX, so the cost-model simulator stays free of it
TraceAnnotation = None
_on = False
_lock = threading.Lock()
_spans: List["Span"] = []
_counts: Dict[str, int] = {}
_ids = itertools.count(1)
_tls = threading.local()


def _stack() -> List["Span"]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class Span:
    """One recorded interval; `with` opens and closes it."""

    __slots__ = ("id", "name", "ids", "cause", "parent", "thread", "start",
                 "end", "counts", "_note")

    def __init__(self, name: str, ids: Dict[str, int], cause: Optional[int]):
        self.id = next(_ids)
        self.name = name
        self.ids = ids
        self.cause = cause
        self.parent: Optional[int] = None
        self.thread = threading.get_ident()
        self.start = self.end = float("nan")
        self.counts: Dict[str, int] = {}
        self._note = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self._note = TraceAnnotation(self.name, **self.ids)
        self._note.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.monotonic()
        self._note.__exit__(*exc)
        self._note = None
        _stack().pop()
        with _lock:
            _spans.append(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.ids}, {self.seconds * 1e3:.3f} "
                f"ms, parent={self.parent}, cause={self.cause})")


class _Off:
    """The shared context `span()` returns while tracing is off."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False


OFF = _Off()


def span(name: str, *, rid: Optional[int] = None,
         instance: Optional[int] = None, dp: Optional[int] = None,
         step: Optional[int] = None, rows: Optional[int] = None,
         tokens: Optional[int] = None, cause: Optional[int] = None):
    """A context that records `name` over its body while tracing is on,
    else `OFF`.  The ids are named parameters, not `**kwargs`, so a call
    while off builds no dict."""
    if not _on:
        return OFF
    ids = {k: v for k, v in (("rid", rid), ("instance", instance),
                             ("dp", dp), ("step", step), ("rows", rows),
                             ("tokens", tokens)) if v is not None}
    return Span(name, ids, cause)


def current() -> Optional[int]:
    """Id of the innermost span open on this thread (None while off or
    outside every span): the `cause` of work handed to another thread."""
    if not _on:
        return None
    stack = _stack()
    return stack[-1].id if stack else None


def count(name: str, n: int = 1) -> None:
    if not _on:
        return
    with _lock:
        _counts[name] = _counts.get(name, 0) + n
    for s in _stack():
        s.counts[name] = s.counts.get(name, 0) + n


def enable() -> None:
    global _on, TraceAnnotation
    if TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def reset() -> None:
    """Drop every recorded span and count (open spans still record when
    they close)."""
    with _lock:
        _spans.clear()
        _counts.clear()


def spans() -> List[Span]:
    """The closed spans, in the order they closed."""
    with _lock:
        return list(_spans)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counts)

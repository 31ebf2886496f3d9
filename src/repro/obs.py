"""Host spans of the served path, kept on one process-wide ring.

``span(name, **attrs)`` times a block of host work on
``time.perf_counter_ns()`` and appends a :class:`Span` to a bounded ring
(the oldest spans drop out first; ``dropped()`` counts them).  It also
opens a ``jax.profiler.TraceAnnotation`` of the same name, so that in a
profiler trace (TensorBoard, Perfetto) the span sits on its host thread
next to the device ops it caused.  ``interval`` records a span whose start
was known only later, such as a request's wait in the queue.  Counts of
work (``n``, ``bytes``) are attributes of the span that did the work, so
that a reader can cut them to any window of time.

JAX's compile events are recorded as intervals too: ``jax.trace`` (Python
to jaxpr), ``jax.lower`` (jaxpr to MLIR) and ``jax.compile`` (the backend
compile, which wraps the persistent-cache lookup: on a cache hit it is the
time to load the program), each with the function's name as ``fun_name``.

Recording is always on; a span costs a few microseconds.  Spans are
host-side only: none is opened inside a jitted or Pallas function.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

__all__ = ["Span", "RING_SIZE", "span", "interval", "spans", "dropped"]

RING_SIZE = 65536


class Span(NamedTuple):
    name: str
    t0_ns: int        # time.perf_counter_ns() at the start
    t1_ns: int        # ... and at the end
    thread: int       # threading.get_ident() of the recording thread
    id: int
    parent: int       # id of the enclosing span on that thread; 0 if none
    attrs: dict       # small counts: flush, req, n, bytes, head_work
    #                   (fun_name; head_windows, a tuple per stage)


_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_dropped = 0


def _open() -> list:
    """The calling thread's stack of open spans, as (id, attrs) pairs."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _record(name: str, t0_ns: int, t1_ns: int, sid: int, attrs: dict,
            stack: list) -> None:
    global _dropped
    parent, outer = stack[-1] if stack else (0, {})
    if "flush" in outer:            # a flush's work carries the flush's id
        attrs.setdefault("flush", outer["flush"])
    s = Span(name, t0_ns, t1_ns, threading.get_ident(), sid, parent, attrs)
    with _lock:
        _dropped += len(_ring) == RING_SIZE
        _ring.append(s)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the block as a span named ``name``; yields its ``attrs``, to
    which the block may add counts it learns on the way."""
    stack = _open()
    sid = next(_ids)
    t0 = time.perf_counter_ns()
    stack.append((sid, attrs))
    try:
        with jax.profiler.TraceAnnotation(name):
            yield attrs
    finally:
        t1 = time.perf_counter_ns()
        stack.pop()
        _record(name, t0, t1, sid, attrs, stack)


def interval(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Record a span that has already ended, on ``perf_counter_ns``, as a
    child of the span open on the calling thread; to the ring only."""
    _record(name, t0_ns, t1_ns, next(_ids), attrs, _open())


def spans() -> list[Span]:
    """A snapshot of the ring, oldest first."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """Spans pushed out of the full ring since the process started."""
    return _dropped


# JAX reports its compile events on time.time(); the ring's clock is
# perf_counter_ns, so map them by the offset between the two clocks.
_WALL_TO_PERF_NS = time.perf_counter_ns() - time.time_ns()
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


def _on_compile_event(event: str, start_s: float, end_s: float,
                      **kwargs) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is not None:
        interval(name, int(start_s * 1e9) + _WALL_TO_PERF_NS,
                 int(end_s * 1e9) + _WALL_TO_PERF_NS,
                 fun_name=str(kwargs.get("fun_name", "")))


jax.monitoring.register_event_time_span_listener(_on_compile_event)

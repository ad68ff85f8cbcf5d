"""Spans at the program's host boundaries, kept in memory and put on the
profiler's clock.

``span(name, **counts)`` times a block of host work. While the JAX profiler
records, the block is also a ``jax.profiler.TraceAnnotation`` of the same
name, so a trace shows it beside the device's ops and an idle gap of the
device can be put down to the span open at the time. Whether or not the
profiler records, the finished span goes into a bounded ring that
``spans()`` reads; the profiler being on or off is the only switch.

A record holds its name, start and end (``time.perf_counter_ns``), thread,
its own id, the id of the span open around it on the same thread
(``parent``), the span on another thread that asked for the work
(``cause``), the outermost span of that chain (``root``: a statement's
spans, and the page fetches it asked for, carry the statement's id), and
its counts as attributes, which the block may set (``rec.hits = n``).
Names are fixed strings and ids stay in the record, so spans of one name
add up across calls.

JAX's own compile-path durations become ring-only spans on the thread that
ran them (``jax.trace``, ``jax.lower``, ``jax.compile``, ``jax.cache_load``;
a cache load runs inside a compile).
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

import jax

RING_SPANS = 65_536

#: JAX monitoring events recorded as ring-only spans
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}

_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_ring_lock = threading.Lock()
_dropped_end_ns = 0  # end of the newest span the ring let go of
_ids = itertools.count(1)
_local = threading.local()


class Span:
    """One finished (or open) span; counts are extra attributes."""

    def __init__(self, name: str, parent, cause, counts: dict):
        self.name = name
        self.id = next(_ids)
        self.tid = threading.get_ident()
        self.parent = parent.id if parent is not None else 0
        self.cause = cause.id if cause is not None else 0
        origin = parent if parent is not None else cause
        self.root = origin.root if origin is not None else self.id
        self.t0 = self.t1 = 0
        self.__dict__.update(counts)

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keep(rec: Span) -> None:
    global _dropped_end_ns
    with _ring_lock:
        if len(_ring) == RING_SPANS:
            _dropped_end_ns = max(_dropped_end_ns, _ring[0].t1)
        _ring.append(rec)


class span:
    """``with span("pool.fetch", cause=c, pages=n) as rec:`` times the block
    as one record (see the module's docstring). ``cause`` is the span,
    usually on another thread, that asked for this work."""

    __slots__ = ("name", "cause", "counts", "rec", "_ann")

    def __init__(self, name: str, cause: Span | None = None, **counts):
        self.name, self.cause, self.counts = name, cause, counts

    def __enter__(self) -> Span:
        stack = _stack()
        rec = self.rec = Span(self.name, stack[-1] if stack else None,
                              self.cause, self.counts)
        stack.append(rec)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        rec.t0 = time.perf_counter_ns()
        return rec

    def __exit__(self, *exc) -> None:
        rec = self.rec
        rec.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        _keep(rec)


def current() -> Span | None:
    """The innermost span open on this thread, if any."""
    stack = _stack()
    return stack[-1] if stack else None


def spans() -> list[Span]:
    """A snapshot of the ring, oldest end first."""
    with _ring_lock:
        return list(_ring)


def oldest_ns() -> int:
    """The ring holds every span that ended after this time: 0 until it
    has let one go. A reader whose window starts before it has lost spans."""
    return _dropped_end_ns


def _on_jax_event(event: str, duration_secs: float, **_) -> None:
    name = JAX_EVENTS.get(event)
    if name is None:
        return
    rec = Span(name, current(), None, {})
    rec.t1 = time.perf_counter_ns()
    rec.t0 = rec.t1 - int(duration_secs * 1e9)
    _keep(rec)


jax.monitoring.register_event_duration_secs_listener(_on_jax_event)

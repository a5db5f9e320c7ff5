"""Host spans and the JIT counter of the real serving path.

:func:`span` marks one stretch of host work in ``serve_forever`` or
``Engine``. It always opens a ``jax.profiler.TraceAnnotation`` of the same
name, so the span lands on the profiler's host plane, on the device
trace's clock, whenever a profile is taken; it appends a row to the
recorder's ``host_spans`` only when a :class:`~repro.obs.FlightRecorder`
is armed (``rec`` not None). Unarmed, a site costs the annotation and one
branch.

:func:`jit_booking` books JAX's compile events to the innermost open span
of an armed recorder: tracing, lowering, backend compiles, and compiles
that were compile-cache loads, told apart by the cache hit JAX reports
inside them. ``jax.monitoring`` listeners are process-wide, so they are
registered once per process, on the first arm, and route each event to
the recorder armed last.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.obs.recorder import (JIT_COMPILE, JIT_LOWER, JIT_TRACE,
                                FlightRecorder)

_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": JIT_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": JIT_LOWER,
    "/jax/core/compile/backend_compile_duration": JIT_COMPILE,
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

# recorders booking JIT events, the one armed last at the end; the
# listeners below are process-wide, so this list is too
_sinks: List[FlightRecorder] = []
_listening = False


def _on_time_span(event: str, start: float, end: float, **_) -> None:
    if _sinks:
        cat = _JIT_EVENTS.get(event)
        if cat is not None:
            _sinks[-1].book_jit(cat, start, end)


def _on_event(event: str, **_) -> None:
    if _sinks and (event == _CACHE_HIT or event == _CACHE_MISS):
        _sinks[-1].book_cache(event == _CACHE_HIT)


@contextlib.contextmanager
def jit_booking(rec: Optional[FlightRecorder]):
    """Book JAX's compile events to ``rec`` while the block runs (nothing
    when ``rec`` is None)."""
    global _listening
    if rec is None:
        yield None
        return
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    _sinks.append(rec)
    try:
        yield rec
    finally:
        _sinks.pop()


class _RecordedSpan:
    __slots__ = ("_ann", "_rec", "_args", "_row")

    def __init__(self, name: str, rec: FlightRecorder, instance: int,
                 request: int, arg: int):
        self._ann = TraceAnnotation(name)
        self._rec = rec
        self._args = (rec.host_span_code(name), instance, request, arg)

    def __enter__(self):
        self._ann.__enter__()
        self._row = self._rec.open_host_span(*self._args)
        return self

    def __exit__(self, exc_type, exc, tb):
        self._rec.close_host_span(self._row)
        self._ann.__exit__(exc_type, exc, tb)
        return False


def span(name: str, rec: Optional[FlightRecorder], instance: int = -1,
         request: int = -1, arg: int = -1):
    """A context manager for one span of host work named ``name``: a
    profiler annotation always, a ``host_spans`` row of ``rec`` when it is
    armed. ``instance``, ``request`` (a ``req_id``) and ``arg`` go into
    the row."""
    if rec is None:
        return TraceAnnotation(name)
    return _RecordedSpan(name, rec, instance, request, arg)

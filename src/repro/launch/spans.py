"""Program spans and counters: the program's one tracing module.

``span(name, **attrs)`` marks a step of the program.  It always enters a
``jax.profiler.TraceAnnotation``, so the step lands in any profiler trace
on the host plane, on the clock of the device's operations, with its attrs
as the event's stats.  While a ``record()`` context is open, the span is
also kept in memory (``Span``): its parent, its host-clock start and end,
and what was counted while it was the innermost open span.

``count(name, n)`` adds to an always-on counter in ``counts``.  The
program counts ``campaigns``, ``host_transfers``, ``segment_builds`` (a
segment scan that missed the campaign driver's process cache: traced,
lowered and compiled or loaded), ``segment_hits`` (one that hit it: no
trace; the hit share is hits / (hits + builds)) and ``p2_bisect_steps``
(the bisection steps of a plan's P2 bandwidth solves).  One
``jax.monitoring`` listener, registered at import, counts the executables
JAX compiles (``executables_compiled``) and loads from its persistent cache
(``executables_loaded``); while recording it also gives the innermost open
span JAX's tracing, lowering and compile-or-load seconds (``trace_s``,
``lower_s``, ``compile_s``, and their union ``rebuild_s``).

An operator records a campaign with both::

    with jax.profiler.trace(log_dir), spans.record() as recorded:
        campaign.run_campaign(...)

The in-memory record serves durations and counts; the profiler trace places
the same spans against the device's operations.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax

# Every program span.  The roots open a campaign; a step that falls
# outside every other span is the root's own time.
ROOTS = ("run_campaign", "run_population_campaign")
NAMES = ROOTS + ("plan_schedule", "init_state", "segment", "checkpoint_save",
                 "host_fetch")

# JAX's compile events -> the seconds key each adds to the open span
JAX_SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
               "/jax/core/compile/backend_compile_duration": "compile_s"}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

counts: collections.Counter = collections.Counter()


@dataclass
class Span:
    """One recorded span.  ``start_ns``/``end_ns`` are on the host's
    ``perf_counter_ns`` clock, good for durations only: a profiler trace
    has its own origin, so relating a span to device time reads the span's
    ``TraceAnnotation`` from the trace instead."""
    id: int
    parent: Optional[int]
    name: str
    start_ns: int
    end_ns: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)
    counts: Dict[str, float] = field(default_factory=dict)
    jax_intervals: List[Tuple[float, float]] = field(default_factory=list,
                                                     repr=False)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_recorded: Optional[List[Span]] = None
_ids = itertools.count()
_local = threading.local()


def _stack() -> List[Span]:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def _innermost() -> Optional[Span]:
    if _recorded is None:
        return None
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def record() -> Iterator[List[Span]]:
    """Keep every span entered inside the block; yields the list, in the
    order the spans started."""
    global _recorded
    prev, _recorded = _recorded, []
    try:
        yield _recorded
    finally:
        _recorded = prev


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[None]:
    """A program step called ``name`` (one of ``NAMES``)."""
    if name not in NAMES:
        raise ValueError(f"{name!r} is not a program span; add it to "
                         f"spans.NAMES")
    with jax.profiler.TraceAnnotation(name, **attrs):
        rec = _recorded
        if rec is None:
            yield
            return
        stack = _stack()
        s = Span(id=next(_ids), parent=stack[-1].id if stack else None,
                 name=name, start_ns=time.perf_counter_ns(), attrs=attrs)
        rec.append(s)
        stack.append(s)
        try:
            yield
        finally:
            stack.pop()
            s.end_ns = time.perf_counter_ns()
            if s.jax_intervals:
                s.counts["rebuild_s"] = union_seconds(s.jax_intervals)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` and, while recording, to the
    innermost open span's."""
    counts[name] += n
    inner = _innermost()
    if inner is not None:
        inner.counts[name] = inner.counts.get(name, 0) + n


def union_seconds(intervals) -> float:
    """Seconds covered by ``(start, end)`` intervals, overlaps once: JAX's
    compile events nest (a trace holds the traces of the jits it calls)."""
    total, last = 0.0, float("-inf")
    for s, e in sorted(intervals):
        total += max(0.0, e - max(s, last))
        last = max(last, e)
    return total


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _local.cache_hit = True


def _on_span(event: str, start: float, end: float, **_) -> None:
    key = JAX_SECONDS.get(event)
    if key is None:
        return
    if event == _BACKEND_COMPILE:
        # a cache hit is recorded inside the compile-or-load it ends
        loaded = getattr(_local, "cache_hit", False)
        _local.cache_hit = False
        count("executables_loaded" if loaded else "executables_compiled")
    inner = _innermost()
    if inner is not None:
        inner.counts[key] = inner.counts.get(key, 0.0) + (end - start)
        inner.jax_intervals.append((start, end))


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_time_span_listener(_on_span)

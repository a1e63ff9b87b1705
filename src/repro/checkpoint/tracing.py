"""Spans and counters of one checkpoint event (docs/perf.md, "Save-path
spans").

``span(name, **attrs)`` times a stage of the save path with
``time.perf_counter_ns`` and, over the same interval, opens a
``jax.profiler.TraceAnnotation`` of the same name, so that a profiler
trace holds the stage on the device trace's clock beside the device
operations.  ``count(name, n)`` adds to a counter of the event.

Spans and counters land in the :class:`Event` active in the current
context (``active``): one per checkpoint event, kept in memory only until
the event commits, when the saver folds it into ``last_save_stats``
(``Event.fold``) and drops it.  A span opened with no active event opens
its annotation and records nothing.

The event and the enclosing span travel with a task to the transfer
pool's threads (``TransferPool.submit`` runs each task in a copy of the
submitter's context), so a writer-lane span belongs to the event that
queued it, whenever it runs.

The tracer adds no synchronisation: a span around an asynchronous
dispatch measures the dispatch, and the wait for the device shows in the
span of the call that blocks.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import threading
import time
from collections import Counter, defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

import jax

_EVENT: contextvars.ContextVar[Optional["Event"]] = contextvars.ContextVar(
    "ckpt_event", default=None)
_PARENT: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "ckpt_span", default=None)
_IDS = itertools.count(1)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent: Optional[int]       # id of the enclosing span, on any thread
    thread: int                 # ``threading.get_ident()`` of the host thread
    event: int                  # id of the event the span belongs to


class Event:
    """The spans and counters of one checkpoint event, from its first
    span until it is folded at the commit."""

    def __init__(self) -> None:
        self.id = next(_IDS)
        self.start_ns = time.perf_counter_ns()
        self.spans: List[Span] = []
        self.counters: Counter = Counter()
        self._lock = threading.Lock()

    def fold(self) -> Tuple[Dict[str, float], Counter]:
        """Seconds per span name summed over the event (a writer stage
        summed over the writer threads) and the counters; the spans are
        dropped."""
        with self._lock:
            spans, self.spans = self.spans, []
            counters, self.counters = self.counters, Counter()
        stages: Dict[str, float] = defaultdict(float)
        for s in spans:
            stages[s.name] += (s.end_ns - s.start_ns) / 1e9
        return dict(stages), counters

    def seconds(self) -> float:
        """Host seconds since the event was opened."""
        return (time.perf_counter_ns() - self.start_ns) / 1e9


@contextlib.contextmanager
def active(event: Event) -> Iterator[Event]:
    """Make ``event`` the one that spans and counters in this context
    land in (the overlapped saver re-enters its event on every tick)."""
    token = _EVENT.set(event)
    try:
        yield event
    finally:
        _EVENT.reset(token)


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[None]:
    event = _EVENT.get()
    parent = _PARENT.get()
    span_id = next(_IDS)
    token = _PARENT.set(span_id)
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield
    finally:
        end = time.perf_counter_ns()
        _PARENT.reset(token)
        if event is not None:
            with event._lock:
                event.spans.append(Span(name, start, end, span_id, parent,
                                        threading.get_ident(), event.id))


def count(name: str, n: int = 1) -> None:
    event = _EVENT.get()
    if event is not None:
        with event._lock:
            event.counters[name] += n

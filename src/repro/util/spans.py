"""Spans and counters inside the program, on the device trace's clock.

Off by default: :func:`span` then hands back one shared no-op object,
reads no clock and opens no profiler annotation, and :func:`count`
returns at once.  A caller turns recording on with :func:`enable`
(usually around a ``jax.profiler`` trace) and reads it with
:func:`collect`.  While on, each span

* opens ``jax.profiler.TraceAnnotation(name)``, so it lands on the
  profiler's host plane on the same clock as the device's ops;
* records ``(name, start_ns, end_ns, span_id, parent_id, attrs)`` from
  ``time.perf_counter_ns`` into a ring of :data:`RING` spans (the oldest
  are dropped beyond that, and counted);
* takes as parent the innermost span open in the same context.

Every span and counter the program emits is named ``repro.<part>.<what>``.
``span(name, clock=True)`` reads the clock even while recording is off,
for callers that report the durations themselves (``simulate_batch``'s
``timings``); such a span is recorded only while recording is on.
"""
from __future__ import annotations

import collections
import contextvars
import dataclasses
import functools
import itertools
import threading
import time
from typing import Dict, List, Optional

import jax

#: spans kept; older ones are dropped and counted in ``Collected.dropped``
RING = 1 << 16

_parent: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_span_parent", default=None)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    attrs: Dict

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Collected:
    """What :func:`collect` returns: spans oldest first, counter totals,
    and the number of spans the ring dropped."""
    spans: List[Span]
    counters: Dict[str, int]
    dropped: int

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def children(self, span: Span) -> List[Span]:
        return self._by_parent.get(span.span_id, [])

    @functools.cached_property
    def _by_parent(self) -> Dict[int, List[Span]]:
        out: Dict[int, List[Span]] = collections.defaultdict(list)
        for s in self.spans:
            out[s.parent_id].append(s)
        return out

    def self_time(self, span: Span) -> int:
        """``span``'s duration in ns less the part its children cover."""
        covered, end = 0, span.start_ns
        for c in sorted(self.children(span), key=lambda s: s.start_ns):
            lo, hi = max(c.start_ns, end), min(c.end_ns, span.end_ns)
            if hi > lo:
                covered += hi - lo
                end = hi
        return span.duration_ns - covered


class _Recorder:
    def __init__(self):
        self.on = False
        self.ring: collections.deque = collections.deque(maxlen=RING)
        self.dropped = 0
        self.counters: Dict[str, int] = collections.Counter()
        self.ids = itertools.count(1)
        self.lock = threading.Lock()

    def reset(self) -> None:
        with self.lock:
            self.ring.clear()
            self.dropped = 0
            self.counters.clear()

    def add(self, span: Span) -> None:
        with self.lock:
            if len(self.ring) == self.ring.maxlen:
                self.dropped += 1
            self.ring.append(span)


_REC = _Recorder()


class _Off:
    """The span handed out while recording is off: does nothing."""
    __slots__ = ()
    seconds = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Live:
    """A span that reads the clock, and records itself while on."""
    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_record",
                 "_annotation", "_span_id", "_parent_id", "_token")

    def __init__(self, name: str, attrs: Dict, record: bool):
        self.name, self.attrs, self._record = name, attrs, record
        self.start_ns = self.end_ns = 0

    def set(self, **attrs) -> None:
        """Attach attributes known only once the work has run."""
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __enter__(self):
        if self._record:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
            self._span_id = next(_REC.ids)
            self._parent_id = _parent.get()
            self._token = _parent.set(self._span_id)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        if self._record:
            _parent.reset(self._token)
            self._annotation.__exit__(*exc)
            _REC.add(Span(self.name, self.start_ns, self.end_ns,
                          self._span_id, self._parent_id, self.attrs))
        return False


def span(name: str, clock: bool = False, **attrs):
    """A context manager around one piece of work.  Off and without
    ``clock``, the shared no-op; otherwise a span whose ``seconds`` is
    its duration once closed."""
    if not (_REC.on or clock):
        return _OFF
    return _Live(name, attrs, _REC.on)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while recording is on."""
    if _REC.on:
        with _REC.lock:
            _REC.counters[name] += n


def enable() -> None:
    """Start recording afresh: the ring and counters are emptied."""
    _REC.reset()
    _REC.on = True


def disable() -> None:
    _REC.on = False


def collect() -> Collected:
    """A copy of what has been recorded since :func:`enable`."""
    with _REC.lock:
        return Collected(list(_REC.ring), dict(_REC.counters),
                         _REC.dropped)

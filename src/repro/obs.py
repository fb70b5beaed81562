"""The program's host spans and counters: one recorder, always on.

    from repro import obs

    with obs.span("stage"):           # a TraceAnnotation plus a ring record
        ...
    obs.count("stage_bytes", n)
    obs.records(lo, hi)               # spans that started in [lo, hi)
    obs.counters()                    # a snapshot

A span is a ``jax.profiler.TraceAnnotation`` of its name, so a profiled run
has it on the device trace's clock, and a ``Span`` record in a ring of the
last ``RING`` spans: its thread, its start and end on ``time.perf_counter``,
the innermost span open on the same thread when it opened (``parent``), and
a ``key`` that ties the spans of one training step together across threads
(the step's first round; a span given no key takes its parent's). Memory is
bounded by the ring; a span costs two clock reads, one annotation and one
append, a few microseconds. Nothing here waits for the device.

Compiles are counted from JAX's monitoring events: ``compiles`` and
``compile_s`` (backend compiles and their seconds; a persistent-cache
retrieval counts as its compile), ``compiles_under:<span>`` (charged to the
innermost span open on the compiling thread), and the persistent cache's
``compile_cache_hits`` and ``compile_cache_misses``.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

RING = 65_536

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache_hits",
                "/jax/compilation_cache/cache_misses": "compile_cache_misses"}


class Span(NamedTuple):
    name: str
    thread: str
    t0: float                   # time.perf_counter seconds
    t1: float
    parent: Optional[str]       # innermost open span on the same thread
    key: Optional[int]          # ties one step's spans across threads


_ring: collections.deque = collections.deque(maxlen=RING)  # Span fields
_ring_lock = threading.Lock()
_evicted_t0 = -math.inf         # latest start among spans the ring dropped
_counters: Dict[str, float] = collections.defaultdict(int)
_count_lock = threading.Lock()
_local = threading.local()


def _stack() -> list:
    """This thread's open spans, innermost last, as (name, key)."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _append(rec: tuple) -> None:
    """Ring a span's fields (a plain tuple: cheaper than a ``Span``)."""
    global _evicted_t0
    with _ring_lock:
        if len(_ring) == _ring.maxlen:
            _evicted_t0 = max(_evicted_t0, _ring[0][2])
        _ring.append(rec)


class span:
    """Context manager: one span named ``name`` around its body."""

    __slots__ = ("name", "key", "_parent", "_t0", "_annotation")

    def __init__(self, name: str, key: Optional[int] = None):
        self.name = name
        self.key = key

    def __enter__(self) -> "span":
        stack = _stack()
        if stack:
            self._parent, parent_key = stack[-1]
            if self.key is None:
                self.key = parent_key
        else:
            self._parent = None
        stack.append((self.name, self.key))
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        _stack().pop()
        _append((self.name, threading.current_thread().name, self._t0, t1,
                 self._parent, self.key))


def record(name: str, t0: float, t1: float,
           key: Optional[int] = None) -> None:
    """A span with an explicit start and end (``time.perf_counter``), for
    an interval no one thread holds open, such as a request's queue wait.
    It is not in the profiler's trace."""
    stack = _stack()
    parent = stack[-1][0] if stack else None
    _append((name, threading.current_thread().name, t0, t1, parent, key))


def count(name: str, n: float = 1) -> None:
    with _count_lock:
        _counters[name] += n


def counters() -> Dict[str, float]:
    with _count_lock:
        return dict(_counters)


def records(lo: float = -math.inf, hi: float = math.inf) -> List[Span]:
    """The ring's spans that started in [lo, hi), in the order they ended."""
    with _ring_lock:
        return [Span._make(s) for s in _ring if lo <= s[2] < hi]


def complete(lo: float) -> bool:
    """False when the ring dropped a span that started at or after ``lo``:
    ``records(lo, ...)`` then misses some."""
    with _ring_lock:
        return _evicted_t0 < lo


def reset() -> None:
    """Empty the ring and the counters (this thread's open spans stay)."""
    global _evicted_t0
    with _ring_lock:
        _ring.clear()
        _evicted_t0 = -math.inf
    with _count_lock:
        _counters.clear()


def _on_duration(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _stack()
    with _count_lock:
        _counters["compiles"] += 1
        _counters["compile_s"] += duration
        if stack:
            _counters["compiles_under:" + stack[-1][0]] += 1


def _on_event(event: str, **_) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        count(name)


# once per process, at first import: JAX keeps its listeners for good
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

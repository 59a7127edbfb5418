"""Spans and counts at the program's layer boundaries.

``span(name)`` records one phase of a call on the host: its name, an id,
the id of the span that opened it (``parent``) and of the outermost open
span (``root``, shared by every span of one front-door call), its start
and end from ``time.time_ns()`` (the wall clock the JAX profiler stamps
its events with) and a dict of counts. It also opens a
``jax.profiler.TraceAnnotation`` of the same name, so that a profiler
trace shows each phase on the host's timeline beside the device's
operations. ``count(key, n)`` adds to the innermost open span; a closing
span adds its counts to its parent's, so a root holds the whole call's.
``n`` may be an array, whose elements are summed; a JAX array is kept as
it is and summed when ``recent()`` is read, so counting a value the
device computes does not wait for the device.

Closed spans go into a bounded in-memory record that ``recent()`` returns,
oldest first. Nothing is written anywhere: the profiler trace is the
exporter. A span costs a few microseconds, and the front doors open a
handful per call, so recording is always on.

From the first span on, two ``jax.monitoring`` events are counted under
the innermost open span of the thread that raises them:
``xla.programs``, each program XLA is asked to build for a call (compiled,
or loaded from the persistent compilation cache), and
``xla.cache_loads``, those the persistent cache served.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

RECENT_MAX = 4096
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass(frozen=True)
class _Deferred:
    """A count that holds JAX arrays: ``base`` plus the sum of their
    elements, resolved when the record is read."""

    base: int
    arrays: Tuple[jax.Array, ...]

    def resolve(self) -> int:
        return self.base + sum(int(np.asarray(a).sum())
                               for a in self.arrays)


def _sum(old, n):
    """``old + n`` for counts: ints, arrays, or deferred sums."""
    if isinstance(n, jax.Array):
        n = _Deferred(0, (n,))
    if isinstance(old, _Deferred) or isinstance(n, _Deferred):
        a, b = (x if isinstance(x, _Deferred) else _Deferred(x, ())
                for x in (old, n))
        return _Deferred(a.base + b.base, a.arrays + b.arrays)
    return old + int(np.sum(n))


@dataclass
class Span:
    name: str
    id: int
    parent: Optional[int]
    root: int
    start_ns: int
    end_ns: int = 0
    counts: Dict[str, int] = field(default_factory=dict)


_recent: Deque[Span] = collections.deque(maxlen=RECENT_MAX)
_ids = itertools.count(1)
_local = threading.local()
_listening = False
_listen_lock = threading.Lock()


def _open() -> List[Span]:
    """This thread's open spans, outermost first."""
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def count(key: str, n: int = 1) -> None:
    """Add ``n`` to ``key`` in the innermost open span; outside any span,
    nothing is recorded."""
    stack = _open()
    if stack:
        c = stack[-1].counts
        c[key] = _sum(c.get(key, 0), n)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE:
        count("xla.programs")


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        count("xla.cache_loads")


def _listen() -> None:
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


@contextlib.contextmanager
def span(name: str) -> Iterator[Span]:
    """Record the enclosed block as the span ``name``. A block left by an
    exception still closes its span, with the time it ran."""
    if not _listening:
        _listen()
    stack = _open()
    parent = stack[-1] if stack else None
    sid = next(_ids)
    s = Span(name, sid, None if parent is None else parent.id,
             sid if parent is None else parent.root, time.time_ns())
    stack.append(s)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield s
    finally:
        s.end_ns = time.time_ns()
        stack.pop()
        if parent is not None:
            for k, v in s.counts.items():
                parent.counts[k] = _sum(parent.counts.get(k, 0), v)
        _recent.append(s)


def recent() -> Deque[Span]:
    """The last ``RECENT_MAX`` closed spans, in the order they closed,
    every count resolved to an int."""
    for s in _recent:
        for k, v in s.counts.items():
            if isinstance(v, _Deferred):
                s.counts[k] = v.resolve()
    return _recent

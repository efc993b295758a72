"""In-program spans and counters of the DSE hot path.

``span(name)`` times a block on ``time.perf_counter`` (the clock the chip
benchmark's window uses) and opens a ``jax.profiler.TraceAnnotation`` of
the same name, so that under a profiler the span sits on the trace's clock
beside the device operations.  Spans nest through a context variable: each
records its parent, and the outermost span of a call gives its id as
``job_id`` to every span opened inside it.  ``count(name, n)`` records a
timestamped counter event and adds ``n`` to the counter's running total.
``fetch(name, *arrays)`` is the one way the co-design path copies device
arrays to the host: a span around the wait, and one ``dse.syncs`` count.
``Program(name, fn)`` jits ``fn`` as the program ``jit_<name>`` (its HLO
module and device-trace name) and counts ``jit.trace.<name>`` and
``jit.call.<name>``.

Recording is always on.  Events go into one bounded ring; readers take the
events that start in a window (:func:`spans_between`,
:func:`counts_between`), which is None when the ring has already dropped
events that ended inside it.  Program span names start with ``dse.``.
"""

from __future__ import annotations

import collections
import contextvars
import functools
import inspect
import itertools
import threading
import time
from typing import Callable, List, NamedTuple, Optional, Sequence

import jax
import numpy as np

#: Events the ring keeps: a 30-s benchmark window holds about 60 co-design
#: jobs of about 75 events each.
RING = 1 << 16


class Span(NamedTuple):
    id: int
    parent_id: Optional[int]
    job_id: int
    name: str
    t0: float
    t1: float


class Count(NamedTuple):
    name: str
    t: float
    n: int


_ring: collections.deque = collections.deque(maxlen=RING)
_totals: collections.Counter = collections.Counter()
_lock = threading.Lock()
_horizon = float("-inf")        # end of the last event the ring dropped
_ids = itertools.count(1)
_open: contextvars.ContextVar = contextvars.ContextVar("obs_open", default=())


def _record(ev) -> None:
    global _horizon
    with _lock:
        if len(_ring) == _ring.maxlen:
            old = _ring[0]
            _horizon = max(_horizon,
                           old.t1 if isinstance(old, Span) else old.t)
        _ring.append(ev)
        if isinstance(ev, Count):
            _totals[ev.name] += ev.n


class span:
    """Context manager: record one span around the block (also when the
    block raises)."""

    __slots__ = ("name", "_sid", "_parent", "_job", "_token", "_note", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._sid = sid = next(_ids)
        stack = _open.get()
        self._parent, self._job = stack[-1] if stack else (None, sid)
        self._token = _open.set(stack + ((sid, self._job),))
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        _open.reset(self._token)
        _record(Span(self._sid, self._parent, self._job, self.name,
                     self._t0, t1))
        return False


def traced(name: str) -> Callable:
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def count(name: str, n: int = 1) -> None:
    _record(Count(name, time.perf_counter(), n))


def total(name: str) -> int:
    """Everything counted under ``name`` since the process started."""
    return _totals[name]


def fetch(name: str, *arrays) -> tuple:
    """Wait for ``arrays`` inside ``span(name)`` and return them as numpy
    arrays; counts one ``dse.syncs``."""
    with span(name):
        jax.block_until_ready(arrays)
        out = tuple(np.asarray(a) for a in arrays)
    count("dse.syncs")
    return out


class Program:
    """``jax.jit(fn, **jit_kwargs)`` under the stable name ``jit_<name>``;
    counts ``jit.trace.<name>`` while tracing and ``jit.call.<name>`` on
    each dispatch."""

    def __init__(self, name: str, fn: Callable, **jit_kwargs):
        def body(*args, **kwargs):
            count(f"jit.trace.{name}")
            return fn(*args, **kwargs)

        body.__name__ = body.__qualname__ = name
        body.__signature__ = inspect.signature(fn)
        self.name = name
        self.jitted = jax.jit(body, **jit_kwargs)
        self._call = f"jit.call.{name}"

    def __call__(self, *args, **kwargs):
        count(self._call)
        return self.jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)


def oldest() -> float:
    """The time from which every event is still in the ring (-inf until the
    ring first overflows)."""
    return _horizon


def _between(kind, t0: float, t1: float, start) -> Optional[List]:
    with _lock:
        if t0 <= _horizon:
            return None
        events = list(_ring)
    return [e for e in events if isinstance(e, kind) and t0 <= start(e) <= t1]


def spans_between(t0: float, t1: float) -> Optional[List[Span]]:
    """Spans that started in [t0, t1]; None where the ring lost some."""
    return _between(Span, t0, t1, lambda s: s.t0)


def counts_between(t0: float, t1: float) -> Optional[List[Count]]:
    """Counter events in [t0, t1]; None where the ring lost some."""
    return _between(Count, t0, t1, lambda c: c.t)


def self_time(spans: Sequence[Span], name: str) -> float:
    """Summed seconds of the spans called ``name`` less those of their
    direct children among ``spans``: the time spent in the layer's own
    code."""
    ids = {s.id for s in spans if s.name == name}
    own = sum(s.t1 - s.t0 for s in spans if s.id in ids)
    return own - sum(s.t1 - s.t0 for s in spans if s.parent_id in ids)

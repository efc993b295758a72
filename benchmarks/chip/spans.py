"""Host spans and compile events, recorded from the benchmark's side.

:class:`Recorder` wraps, at call time, the module-level functions a
generator names, so every call leaves a span (name, start, end on the host's
``perf_counter``) and, while a profiler trace runs, a
``TraceAnnotation`` of the same name on the trace's own clock.  It also
counts JAX's backend-compile events (a compile or a load from the
persistent cache), each with its time and program name.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import time
from typing import List, Tuple

#: JAX's event for one backend compile (or persistent-cache load).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Recorder:
    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.compiles: List[Tuple[float, str]] = []
        self._undo = []

    @contextlib.contextmanager
    def span(self, name: str):
        """Record one span and annotate the trace with it."""
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def wrap(self, module: str, attr: str, name: str) -> None:
        """Wrap ``module.attr``; ``attr`` may name a method (``Cls.fn``)."""
        mod = importlib.import_module(module)
        *owner, attr = attr.split(".")
        for o in owner:
            mod = getattr(mod, o)
        orig = getattr(mod, attr)

        @functools.wraps(orig)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return orig(*args, **kwargs)

        setattr(mod, attr, wrapped)
        self._undo.append((mod, attr, orig))

    def listen_compiles(self) -> None:
        import jax

        def on_event(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.compiles.append((time.perf_counter(),
                                      str(kw.get("fun_name", "?"))))

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def restore(self) -> None:
        for mod, attr, orig in reversed(self._undo):
            setattr(mod, attr, orig)
        self._undo.clear()

    def in_window(self, t0: float, t1: float):
        """Spans that started inside [t0, t1]."""
        return [s for s in self.spans if t0 <= s[1] <= t1]


def per_job(ctx, *names, minus=()):
    """Summed seconds of the spans ``names`` that started in the measured
    window, less those of ``minus``, over the jobs completed in it; None
    where no such span ran."""
    lo, hi = ctx.window
    spans = ctx.recorder.in_window(lo, hi)
    got = [b - a for n, a, b in spans if n in names]
    if not got or not ctx.result["jobs"]:
        return None
    less = sum(b - a for n, a, b in spans if n in minus)
    return (sum(got) - less) / ctx.result["jobs"]

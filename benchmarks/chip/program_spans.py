"""Readers of the program's own spans and counters (``repro.core.obs``).

The program records them whatever the run; the harness reads those of the
measured window.  Each function returns None where there is nothing to
read: a program without ``repro.core.obs``, no such span or counter in
the window, or a ring that no longer holds the whole window.
"""

from __future__ import annotations


def _events(ctx, kind: str):
    try:
        from repro.core import obs
    except ImportError:
        return None, None
    return obs, getattr(obs, f"{kind}_between")(*ctx.window)


def per_job(ctx, name: str, self_time: bool = False):
    """Seconds per completed job in the spans ``name`` that started in the
    window; with ``self_time``, less the time of their child spans."""
    obs, spans = _events(ctx, "spans")
    jobs = ctx.result["jobs"]
    if not spans or not jobs or all(s.name != name for s in spans):
        return None
    if self_time:
        return obs.self_time(spans, name) / jobs
    return sum(s.t1 - s.t0 for s in spans if s.name == name) / jobs


def counted(ctx, name: str):
    """Sum of the counter ``name`` over the window."""
    _, counts = _events(ctx, "counts")
    got = [c.n for c in counts or () if c.name == name]
    return sum(got) if got else None

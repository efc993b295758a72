"""Busy time of each device in a traced window.

``trace_reduce.reduce`` reports the devices' mean busy time.  A cell over
several chips also needs each device's own, for ``device_balance``:
:func:`report_per_device` wraps ``trace_reduce.reduce`` so that its next
call adds ``busy_per_device_s`` (seconds, one per device used, in device
order) to what it returns, and then removes the wrap.  The busy time is
taken as ``trace_reduce`` takes it: the union of a device's ``XLA Ops``
intervals, clipped to the ``bench.window`` annotation.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import trace_reduce


def per_device(data, n_devices: int) -> Optional[List[float]]:
    """Busy seconds of each of the first ``n_devices`` devices in the
    trace's window; None without a window or a device."""
    window = None
    devices = {}
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m is not None:
            devices[int(m.group(1))] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == trace_reduce.OPS_LINE
                for ev in line.events]
            continue
        for line in plane.lines:
            for ev in line.events:
                if window is None and ev.name == trace_reduce.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None or not devices:
        return None
    lo, hi = window
    out = []
    for d in sorted(devices)[:n_devices]:
        u = trace_reduce.union((max(a, lo), min(b, hi))
                               for a, b in devices[d] if b > lo and a < hi)
        out.append(sum(b - a for a, b in u) / 1e9)
    return out


def report_per_device() -> None:
    """Make the next ``trace_reduce.reduce`` also report each device's
    busy seconds, as ``busy_per_device_s`` (once, however often this is
    called before it)."""
    orig = trace_reduce.reduce
    if hasattr(orig, "__wrapped__"):
        return

    @functools.wraps(orig)
    def reduce(data, span_names, n_devices=1, top=10):
        trace_reduce.reduce = orig
        out = orig(data, span_names, n_devices=n_devices, top=top)
        if out is not None:
            out["busy_per_device_s"] = per_device(data, n_devices)
        return out

    trace_reduce.reduce = reduce

"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an XLA operation ran on a
  device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), clipped to
  the traced window and averaged over the devices used;
* window: the host annotation ``bench.window`` the harness puts around
  the measured window;
* top device operations by summed duration, under the trace's names:
  the program (line ``XLA Modules``: name and fingerprint) and the HLO
  instruction, without its text;
* idle gaps: the stretches of the window in which the device ran
  nothing, each labelled with the innermost benchmark span open on the
  host at the gap's middle (``none`` where no span was open), summed per
  label.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def op_name(text: str) -> str:
    """An XLA op's name without its HLO text: ``%while.5 = (...) ...`` ->
    ``%while.5``."""
    return text.split(" = ", 1)[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def load(path: str):
    """``path``: an ``.xplane.pb`` file, or a directory holding one."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return ProfileData.from_file(path)


def reduce(data, span_names: Sequence[str], n_devices: int = 1,
           top: int = 10) -> Optional[Dict]:
    """Device busy/idle, top ops and labelled idle gaps of one trace.

    Returns None where the trace holds no window annotation or no device
    operation inside it."""
    host: List[Tuple[str, float, float]] = []
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    names = set(span_names) | {WINDOW_SPAN}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is not None:
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.name)
                          for ev in lines.get(MODULES_LINE, []))
            starts = [a for a, _ in mods]
            dev = device_ops.setdefault(int(m.group(1)), [])
            for ev in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[i][1] + "/" if i >= 0 else ""
                dev.append((mod + op_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    windows = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    used = sorted(device_ops)[:n_devices]
    busy_ns, per_op = [], collections.Counter()
    first_busy = None
    for d in used:
        evs = [(n, max(a, lo), min(b, hi)) for n, a, b in device_ops[d]
               if b > lo and a < hi]
        for n, a, b in evs:
            per_op[n] += (b - a) / 1e9
        u = union((a, b) for _, a, b in evs)
        busy_ns.append(sum(b - a for a, b in u))
        if first_busy is None:
            first_busy = u
    if not used or sum(busy_ns) == 0:
        return None
    spans = [(n, a, b) for n, a, b in host if n != WINDOW_SPAN]
    idle = collections.Counter()
    for a, b in gaps(first_busy, lo, hi):
        mid = 0.5 * (a + b)
        open_ = [(b2 - a2, n) for n, a2, b2 in spans if a2 <= mid <= b2]
        idle[min(open_)[1] if open_ else "none"] += (b - a) / 1e9
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    return dict(
        window_s=window_s, busy_s=busy_s, idle_share=1.0 - busy_s / window_s,
        device_ops=[[n, s] for n, s in per_op.most_common(top)],
        idle_gaps=[[n, s] for n, s in idle.most_common(top)])

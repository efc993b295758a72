"""The comparison that decides ``correct`` for a co-design answer.

``rel_gap`` is the widest relative gap between what the timed path
produced and the plain reference, over every value of the answer:

* the pool: the reference's own pool must be the same grid points in the
  same order (else the gap is infinite);
* the engine: per-layer energy and latency of every pool entry, every
  network and layer;
* the fold: every network's minimum energy, latency and EDP over the grid;
* the solver and the scoring: every candidate chip's score;
* the answer: the chosen chip's per-network scheduled energy and
  bottleneck and its score, and how far its score lies above the
  reference's best chip.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def rel(a, b) -> float:
    """Largest |a - b| / |b| (0 where both are 0, inf where only b is)."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.shape != b.shape:
        return float("inf")
    diff = np.abs(a - b)
    den = np.abs(b)
    out = np.where(diff == 0, 0.0,
                   np.where(den > 0, diff / np.where(den > 0, den, 1.0),
                            np.inf))
    return float(out.max()) if out.size else 0.0


def rel_gap(prog: dict, ref: dict) -> Tuple[float, Dict[str, float]]:
    """``prog``: the job record of the timed path; ``ref``: the
    reference's co-design of the same grid and energy table."""
    d: Dict[str, float] = {}
    if list(prog["pool"]) != list(ref["pool"]):
        d["pool"] = float("inf")
        return float("inf"), d
    d["layer"] = max(rel(prog["e_layer"], ref["e_layer"]),
                     rel(prog["t_layer"], ref["t_layer"]))
    d["minima"] = max(rel(prog["min_energy"], ref["min_energy"]),
                      rel(prog["min_latency"], ref["min_latency"]),
                      rel(prog["min_edp"], ref["min_edp"]))
    keys = [(tuple(t), tuple(c)) for t, c in ref["chips"]]
    pos = {k: i for i, k in enumerate(keys)}
    pk = [(tuple(t), tuple(c)) for t, c in prog["chips"]]
    if sorted(pk) != sorted(keys):
        d["chips"] = float("inf")
        return float("inf"), d
    order = [pos[k] for k in pk]
    rs = np.asarray(ref["chip_scores"])[order]
    d["chip_scores"] = rel(prog["chip_scores"], rs)
    ci = int(np.argmin(prog["chip_scores"]))
    ri = order[ci]
    d["answer"] = max(rel(prog["energy"], ref["energy"][ri]),
                      rel(prog["latency"], ref["latency"][ri]),
                      rel(prog["score"], ref["chip_scores"][ri]))
    best = float(np.min(ref["chip_scores"]))
    d["optimality"] = (float(ref["chip_scores"][ri]) - best) / best
    return max(d.values()), d

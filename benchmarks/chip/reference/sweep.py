"""Plain reference of the streamed per-layer sweep, and its comparison.

Given every grid point's whole-network energy and latency (``tool``):
per network, the EDP minimum (and the energy and latency minima), the
``topk`` points of least EDP (the lower grid index first on a tie) with
their per-layer energy and latency, and the boundary set: every point
within ``bound`` of the minimum, in (EDP, grid index) order.

``rel_gap`` decides ``correct`` for one job.  The timed path and the
reference round differently (the program sums each network's layers on
the device, the reference sums count terms first), so points whose EDP
ties or nearly ties may trade places; the comparison is built so that
such a swap costs only its rounding:

* ``topk``: the EDP of rank r against the reference's EDP of rank r;
* ``points``: the EDP the program gives each point it names against the
  reference's EDP of that same point;
* ``layer``: the per-layer energy and latency of every named point
  against the reference's per-layer values of that point;
* ``minima``: every network's minimum energy, latency and EDP, and the
  reference's EDP at the program's arg-min against the minimum;
* ``boundary``: for points in both sets, energy and latency against the
  reference's; a point in one set only counts as the relative distance
  of its reference EDP from the set's bound (0 would be on the bound).

Nothing here imports the system under test.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from . import tool
from .compare import rel


def sweep(fields: Dict[str, np.ndarray], networks: Dict[str, list],
          topk: int, bound: float, sums: "tool.NetworkSums") -> dict:
    """The reference's answer for one grid (energy table included in
    ``fields``), in the arithmetic of ``sums.dtype``."""
    E, T = sums.evaluate(fields)
    edp = E * T
    idx = np.arange(edp.shape[0])
    mins = edp.min(axis=0)
    top = np.stack([np.lexsort((idx, edp[:, j]))[:topk]
                    for j in range(edp.shape[1])], axis=1)
    b_idx, b_e, b_t = {}, {}, {}
    for j, name in enumerate(networks):
        sel = np.flatnonzero(edp[:, j] <= mins[j] * (1.0 + bound))
        sel = sel[np.lexsort((sel, edp[sel, j]))]
        b_idx[name], b_e[name], b_t[name] = sel, E[sel, j], T[sel, j]
    return dict(E=E, T=T, edp=edp, topk_idx=top,
                topk_metric=np.take_along_axis(edp, top, axis=0),
                min_energy=E.min(axis=0), min_latency=T.min(axis=0),
                min_edp=mins, bound=bound, boundary_idx=b_idx,
                boundary_energy=b_e, boundary_latency=b_t)


def rel_gap(prog: dict, ref: dict, fields: Dict[str, np.ndarray],
            networks: Dict[str, list]) -> Tuple[float, Dict[str, float]]:
    """``prog``: the job record of the timed path; ``ref``: :func:`sweep`
    of the same grid and energy table (float64)."""
    d: Dict[str, float] = {}
    edp = ref["edp"]
    n = edp.shape[0]
    top_i = np.asarray(prog["topk_idx"])
    argm = np.asarray(prog["argmin"])
    named = [top_i[:, j] for j in range(top_i.shape[1])]
    named += [np.asarray(prog["boundary_idx"][name]) for name in networks]
    if (top_i.shape != ref["topk_idx"].shape or argm.min() < 0
            or argm.max() >= n or any(
                i.size and (i.min() < 0 or i.max() >= n
                            or np.unique(i).size != i.size) for i in named)):
        d["indices"] = float("inf")        # a point named twice or no point
        return float("inf"), d
    d["topk"] = rel(prog["topk_metric"], ref["topk_metric"])
    d["points"] = rel(prog["topk_metric"],
                      np.take_along_axis(edp, top_i, axis=0))
    points = np.unique(top_i)
    e_l, t_l = tool.per_layer(fields, points, networks)
    pos = np.searchsorted(points, top_i)              # [k, n_net]
    cols = np.arange(top_i.shape[1])[None, :]
    d["layer"] = max(rel(prog["layer_energy"], e_l[pos, cols]),
                     rel(prog["layer_latency"], t_l[pos, cols]))
    d["minima"] = max(
        rel(prog["min_energy"], ref["min_energy"]),
        rel(prog["min_latency"], ref["min_latency"]),
        rel(prog["min_edp"], ref["min_edp"]),
        rel(prog["min_metric"], ref["min_edp"]),
        rel(edp[argm, np.arange(argm.size)], ref["min_edp"]))
    gaps = [0.0]
    for j, name in enumerate(networks):
        p_idx = np.asarray(prog["boundary_idx"][name])
        r_idx = ref["boundary_idx"][name]
        both, pi, ri = np.intersect1d(p_idx, r_idx, return_indices=True)
        gaps.append(rel(prog["boundary_energy"][name][pi],
                        ref["boundary_energy"][name][ri]))
        gaps.append(rel(prog["boundary_latency"][name][pi],
                        ref["boundary_latency"][name][ri]))
        only = np.setxor1d(p_idx, r_idx)
        if only.size:
            thr = ref["min_edp"][j] * (1.0 + ref["bound"])
            gaps.append(float(np.max(np.abs(edp[only, j] - thr)) / thr))
    d["boundary"] = max(gaps)
    return max(d.values()), d

"""Plain reference of the heterogeneous chip co-design (sec. IV-V).

Given every grid point's whole-network energy and latency (``tool``):

1. per network, the EDP minimum and the boundary set of points within
   ``bound`` of it;
2. the candidate pool: greedy set cover of the boundary sets (most
   networks covered, then the lower sum of metric over minimum, then the
   lower grid index), topped up in (least metric over minimum across
   networks, grid index) order, skipping points whose grid row repeats
   one already pooled;
3. every candidate chip: up to ``max_types`` pool entries with a
   composition of ``m_cores`` cores over them;
4. every (chip, network) schedule: each layer on its fastest core type
   (the lower type first on a tie), each type's layers split contiguously
   over its cores at the least bottleneck (exact min-max partition);
5. every chip's score: the mean over networks of scheduled EDP over the
   network's EDP minimum.

Nothing here imports the system under test.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

import numpy as np

from . import tool


def compositions(n: int, k: int):
    """Ordered positive k-tuples summing to n, smallest first part first."""
    if k == 1:
        yield (n,)
        return
    for first in range(1, n - k + 2):
        for rest in compositions(n - first, k - 1):
            yield (first,) + rest


def chips(pool_size: int, max_types: int, m_cores: int
          ) -> List[Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    out = []
    for k in range(1, min(max_types, m_cores, pool_size) + 1):
        for combo in itertools.combinations(range(pool_size), k):
            for comp in compositions(m_cores, k):
                out.append((combo, comp))
    return out


def minmax_split(x: np.ndarray, k_max: int) -> np.ndarray:
    """Least bottleneck of splitting ``x`` into at most k contiguous
    segments, for k = 1 .. k_max (index k - 1)."""
    n = x.size
    P = np.concatenate([np.zeros(1, x.dtype), np.cumsum(x)])
    best = np.empty(k_max, dtype=x.dtype)
    f = P.copy()                       # f[j]: first j items, one segment
    best[0] = f[n]
    seg = P[None, :] - P[:, None]      # seg[i, j] = sum of items i .. j-1
    lower = np.tril(np.ones((n + 1, n + 1), dtype=bool))
    for k in range(2, k_max + 1):
        if k >= n:
            best[k - 1] = x.max() if n else 0.0
            continue
        cand = np.maximum(f[:, None], seg)
        cand[lower] = np.inf           # cut i must lie before j
        f = np.concatenate([[0.0], cand.min(axis=0)[1:]]).astype(x.dtype)
        best[k - 1] = f[n]
    return best


def pool(edp: np.ndarray, fields: Dict[str, np.ndarray], bound: float,
         pool_size: int) -> List[int]:
    """Steps 1-2 on the dense [n, n_net] EDP matrix."""
    mins = edp.min(axis=0)
    cand = (edp <= mins[None, :] * (1.0 + bound)).T
    rel = (edp / mins[None, :]).T
    keys = np.stack([fields[k] for k in tool.GRID_COLUMNS], axis=1)
    chosen: List[int] = []
    seen = set()

    def add(c):
        key = keys[c].tobytes()
        if key not in seen:
            seen.add(key)
            chosen.append(int(c))

    uncovered = np.ones(cand.shape[0], dtype=bool)
    rounds = 0
    while uncovered.any() and rounds < pool_size:
        counts = cand[uncovered].sum(axis=0)
        top = counts.max()
        if top == 0:
            break
        rel_sum = np.where(cand[uncovered], rel[uncovered], 0.0).sum(axis=0)
        tied = np.flatnonzero(counts == top)
        col = int(tied[np.argmin(rel_sum[tied])])
        rounds += 1
        add(col)
        uncovered &= ~cand[:, col]
    if len(chosen) < pool_size:
        for c in np.lexsort((np.arange(edp.shape[0]), rel.min(axis=0))):
            add(c)
            if len(chosen) == pool_size:
                break
    return chosen


def schedule(e_l: np.ndarray, t_l: np.ndarray, lens, chip_list):
    """Steps 4-5 inputs: scheduled energy and bottleneck of every
    (chip, network), [n_chips, n_net]."""
    n_net = e_l.shape[1]
    energy = np.zeros((len(chip_list), n_net), dtype=e_l.dtype)
    bott = np.zeros_like(energy)
    by_types: Dict[tuple, list] = {}
    for ci, (ty, cn) in enumerate(chip_list):
        by_types.setdefault(ty, []).append((ci, cn))
    for ty, members in by_types.items():
        k_max = max(max(cn) for _, cn in members)
        for j in range(n_net):
            L = int(lens[j])
            lat = t_l[list(ty), j, :L]
            en = e_l[list(ty), j, :L]
            tt = np.argmin(lat, axis=0)
            e_sum = en[tt, np.arange(L)].sum()
            splits = [minmax_split(lat[t, tt == t], k_max)
                      if (tt == t).any() else None for t in range(len(ty))]
            for ci, cn in members:
                b = 0.0
                for t, s in enumerate(splits):
                    if s is not None:
                        b = max(b, s[cn[t] - 1])
                energy[ci, j] = e_sum
                bott[ci, j] = b
    return energy, bott


def codesign(fields: Dict[str, np.ndarray], networks: Dict[str, list],
             params: dict, sums: "tool.NetworkSums") -> dict:
    """The whole co-design of one grid (energy table included in
    ``fields``), in the arithmetic of ``sums.dtype``."""
    dt = sums.dtype
    E, T = sums.evaluate(fields)
    edp = E * T
    pl = pool(edp, fields, params["bound"], params["pool_size"])
    e_l, t_l = tool.per_layer(fields, pl, networks, dt)
    chip_list = chips(len(pl), params["max_types"], params["m_cores"])
    lens = tool.layer_counts(networks)
    energy, bott = schedule(e_l, t_l, lens, chip_list)
    min_edp = edp.min(axis=0)
    scores = (energy * bott / min_edp[None, :]).mean(axis=1)
    best = int(np.argmin(scores))
    return dict(pool=pl, min_energy=E.min(axis=0), min_latency=T.min(axis=0),
                min_edp=min_edp, e_layer=e_l, t_layer=t_l, chips=chip_list,
                chip_scores=scores, best=best, energy=energy, latency=bott)

"""Plain reference of the DSE service's answers (best-config, best-chip,
Pareto) over one design space.

The co-design problem set is the one of ``codesign``: the EDP pool, every
chip of up to ``max_types`` pool types over ``m_cores`` cores, every
(chip, network) latency schedule.  On top of it, for a relative deadline
``d`` (a network's bound is ``d`` times its least single-core latency
over the grid):

* a chip's score is the mean over networks of scheduled energy over the
  network's least energy, +inf unless every network meets its bound; the
  best chip has the least score;
* a network's front is the chips not weakly dominated in (energy over
  least energy, bottleneck), fastest first;
* the energy-aware slack schedule starts from the latency schedule and,
  where the bound leaves slack, moves layers to their least-energy type,
  largest saving first (lower layer first on a tie), keeping each move
  whose greedy covering of every type's layers still fits that type's
  cores within the bound; its bottleneck is the least covering threshold
  (56 bisection steps from [0, min(bound, largest type total)]), its
  energy the sequential sum over layers;
* the slack front of a network at one deadline is the non-dominated set
  of the latency points and that deadline's slack points.

Nothing here imports the system under test.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import codesign, tool

BISECT_ITERS = 56


def _scan(lat, tt, thr, n_l, dt):
    n_types = lat.shape[0]
    run = np.zeros(n_types, dtype=dt)
    segs = np.ones(n_types, dtype=np.int64)
    viol = np.zeros(n_types, dtype=bool)
    peak = np.zeros(n_types, dtype=dt)
    for l in range(n_l):
        t = tt[l]
        x = lat[t, l]
        nxt = run[t] + x
        if nxt > thr:
            if x > thr:
                viol[t] = True
            segs[t] += 1
            peak[t] = max(peak[t], run[t])
            run[t] = x
        else:
            run[t] = nxt
    return run, segs, viol, np.maximum(peak, run)


def slack(lat, en, counts, tt0, t_star, deadline):
    """(bottleneck, energy, n_moves, feasible) of one slack schedule."""
    dt = lat.dtype
    n_l = lat.shape[1]
    kk = np.maximum(np.asarray(counts), 1)

    def energy(tt):
        e = dt.type(0.0)
        for l in range(n_l):
            e = e + en[tt[l], l]
        return e

    if not deadline > t_star:
        return t_star, energy(tt0), 0, bool(t_star <= deadline)
    te = np.argmin(en, axis=0)
    d_e = en[tt0, np.arange(n_l)] - en[te, np.arange(n_l)]
    cand = (te != tt0) & (d_e > 0)
    order = np.lexsort((np.arange(n_l), np.where(cand, -d_e, np.inf)))
    tt = tt0.copy()
    moves = 0
    for l in order[:int(cand.sum())]:
        trial = tt.copy()
        trial[l] = te[l]
        _, segs, viol, _ = _scan(lat, trial, deadline, n_l, dt)
        if ((segs <= kk) & ~viol).all():
            tt = trial
            moves += 1
    if moves == 0:
        return t_star, energy(tt0), 0, True
    totals = _scan(lat, tt, dt.type(np.inf), n_l, dt)[0]
    lo, hi = dt.type(0.0), min(deadline, totals.max())
    for _ in range(BISECT_ITERS):
        mid = dt.type(0.5) * (lo + hi)
        _, segs, viol, _ = _scan(lat, tt, mid, n_l, dt)
        if ((segs <= kk) & ~viol).all():
            hi = mid
        else:
            lo = mid
    return _scan(lat, tt, hi, n_l, dt)[3].max(), energy(tt), moves, True


def _front(value, latency) -> np.ndarray:
    e1, e2 = value[:, None], value[None, :]
    l1, l2 = latency[:, None], latency[None, :]
    dom = (e2 <= e1) & (l2 <= l1) & ((e2 < e1) | (l2 < l1))
    return ~dom.any(axis=1)


class Service:
    """Every answer of the service over ``fields`` (energy table
    included), in the arithmetic of ``dtype``."""

    def __init__(self, fields, networks: Dict[str, list], params: dict,
                 dtype=np.float64):
        self.names = list(networks)
        sums = tool.NetworkSums(fields, networks, dtype)
        E, T = sums.evaluate(fields)
        edp = E * T
        self.edp = edp
        self.argmin = np.argmin(edp, axis=0)
        self.min_edp, self.min_e, self.min_t = (edp.min(axis=0),
                                                E.min(axis=0), T.min(axis=0))
        self.pool = codesign.pool(edp, fields, params["metric_bound"],
                                  params["pool_size"])
        self.e_l, self.t_l = tool.per_layer(fields, self.pool, networks,
                                            dtype)
        self.chips = codesign.chips(len(self.pool), params["max_types"],
                                    params["m_cores"])
        self.lens = tool.layer_counts(networks)
        self.energy, self.bott = codesign.schedule(self.e_l, self.t_l,
                                                   self.lens, self.chips)
        self._slack: Dict[float, tuple] = {}

    def chip_index(self, types, counts):
        """Index of the chip with grid rows ``types`` x ``counts``."""
        try:
            key = (tuple(self.pool.index(int(t)) for t in types),
                   tuple(int(c) for c in counts))
            return self.chips.index(key)
        except ValueError:
            return None

    def best_config(self) -> dict:
        return {nm: dict(idx=int(self.argmin[j]),
                         metric=float(self.min_edp[j]),
                         energy=float(self.min_e[j]),
                         latency=float(self.min_t[j]))
                for j, nm in enumerate(self.names)}

    def _slack_at(self, d: float):
        if d in self._slack:
            return self._slack[d]
        C, N = self.bott.shape
        s_e = np.zeros((C, N), dtype=self.bott.dtype)
        s_l = np.zeros_like(s_e)
        moves = np.zeros((C, N), dtype=np.int64)
        feas = np.zeros((C, N), dtype=bool)
        for ci, (ty, cn) in enumerate(self.chips):
            for j in range(N):
                L = self.lens[j]
                lat = self.t_l[list(ty), j, :L]
                en = self.e_l[list(ty), j, :L]
                tt0 = np.argmin(lat, axis=0)
                dl = self.min_t[j] * self.bott.dtype.type(d)
                s_l[ci, j], s_e[ci, j], moves[ci, j], feas[ci, j] = slack(
                    lat, en, cn, tt0, self.bott[ci, j], dl)
        self._slack[d] = (s_e, s_l, moves, feas)
        return self._slack[d]

    def scores(self, d: float):
        dl = self.min_t * self.bott.dtype.type(d)
        norm = self.energy / self.min_e[None, :]
        masked = np.where(self.bott <= dl[None, :], norm, np.inf)
        return masked.sum(axis=1) / self.bott.dtype.type(len(self.names))

    def best_chip(self, d: float) -> dict:
        sc = self.scores(d)
        if not np.isfinite(sc).any():
            return dict(feasible=False, deadline=float(d))
        ci = int(np.argmin(sc))
        s_e, s_l, moves, feas = self._slack_at(d)
        norm = s_e / self.min_e[None, :]
        s_sc = np.where(feas.all(axis=1), norm.mean(axis=1), np.inf)
        cs = int(np.argmin(s_sc))
        return dict(
            feasible=True, deadline=float(d),
            chip_types=[self.pool[p] for p in self.chips[ci][0]],
            chip_counts=list(self.chips[ci][1]), score=float(sc[ci]),
            slack=dict(chip_types=[self.pool[p] for p in self.chips[cs][0]],
                       chip_counts=list(self.chips[cs][1]),
                       score=float(s_sc[cs]), moves=int(moves[cs].sum()),
                       energy_saved_pct=float(
                           (1.0 - s_sc[cs] / sc[cs]) * 100.0)))

    def pareto(self, network: str, d: float) -> dict:
        j = self.names.index(network)
        norm = self.energy[:, j] / self.min_e[j]
        idx = np.flatnonzero(_front(norm, self.bott[:, j]))
        order = np.lexsort((self.energy[idx, j], self.bott[idx, j]))
        frontier = [(int(c), float(self.bott[c, j]), float(self.energy[c, j]))
                    for c in idx[order]]
        s_e, s_l, _, _ = self._slack_at(d)
        C = len(self.chips)
        lat = np.concatenate([self.bott[:, j], s_l[:, j]])
        en = np.concatenate([self.energy[:, j], s_e[:, j]])
        chip = np.concatenate([np.arange(C), np.arange(C)])
        ok = np.isfinite(lat) & np.isfinite(en)
        lat, en, chip = lat[ok], en[ok], chip[ok]
        keep = np.flatnonzero(_front(en, lat))
        pts = sorted({(float(lat[i]), float(en[i]), int(chip[i]))
                      for i in keep})
        return dict(network=network, frontier=frontier,
                    slack_frontier=[(c, l, e) for l, e, c in pts],
                    pool=list(self.pool))

    def answer(self, kind: str, network, d: float) -> dict:
        if kind == "best_config":
            full = self.best_config()
            return full if network is None else full[network]
        if kind == "best_chip":
            return self.best_chip(d)
        return self.pareto(network, d)


def rel(a, b) -> float:
    a, b = float(a), float(b)
    if a == b:
        return 0.0
    return abs(a - b) / abs(b) if b != 0 else float("inf")


def front_gap(got, want, cands) -> float:
    """How far a front ``got`` of (chip, latency, energy) points is from
    the reference front ``want``, over the reference's candidate points
    ``cands`` (chip -> its points).  The widest of: each point of ``got``
    against the nearest reference point of the chip it names; by how
    much any reference candidate beats a point of ``got`` in both
    latency and energy at once; by how much the nearest point of ``got``
    exceeds a point of ``want`` in latency or energy.  Membership alone
    is not compared: where two chips' latencies are equal but for
    rounding, either may dominate the other, on any backend."""
    inf = float("inf")
    if not got or not want:
        return 0.0 if not got and not want else inf
    pts = [p for ps in cands.values() for p in ps]
    out = 0.0
    for c, lat, en in got:
        if c not in cands:
            return inf
        out = max(out, min(max(rel(lat, l), rel(en, e))
                           for l, e in cands[c]),
                  max(min((lat - l) / l, (en - e) / e) for l, e in pts))
    for _, lat, en in want:
        out = max(out, min(max(0.0, (l - lat) / lat, (e - en) / en)
                           for _, l, e in got))
    return float(out)


def compare(ref: "Service", kind: str, network, d: float, got: dict
            ) -> float:
    """Widest relative gap between the service's answer ``got`` and the
    reference, judged by what the answer says: each value relative to the
    reference's, a chosen configuration or chip by how far the
    reference's metric of it lies above the reference's best, fronts by
    :func:`front_gap`, the pool and the slack moves exactly (else inf)."""
    inf = float("inf")
    if kind == "best_config":
        full = ref.best_config()
        want = full if network is None else {network: full[network]}
        got = got if network is None else {network: got}
        if set(got) != set(want):
            return inf
        out = 0.0
        for nm, w in want.items():
            g, j = got[nm], ref.names.index(nm)
            edp_at = ref.edp[int(g["idx"]), j]
            out = max(out, (edp_at - ref.min_edp[j]) / ref.min_edp[j],
                      rel(g["metric"], w["metric"]),
                      rel(g["energy"], w["energy"]),
                      rel(g["latency"], w["latency"]))
        return float(out)
    if kind == "pareto":
        if [int(x) for x in got["pool"]] != list(ref.pool):
            return inf
        want = ref.pareto(network, d)
        j = ref.names.index(network)
        s_e, s_l, _, _ = ref._slack_at(d)
        base = {c: [(ref.bott[c, j], ref.energy[c, j])]
                for c in range(len(ref.chips))}
        both = {c: v + [(s_l[c, j], s_e[c, j])] for c, v in base.items()}
        return max(front_gap(got["frontier"], want["frontier"], base),
                   front_gap(got["slack_frontier"], want["slack_frontier"],
                             both))
    want = ref.best_chip(d)
    if bool(got["feasible"]) != want["feasible"]:
        return inf
    if not want["feasible"]:
        return 0.0
    sc = ref.scores(d)
    s_e, _, moves, feas = ref._slack_at(d)
    s_sc = np.where(feas.all(axis=1), (s_e / ref.min_e[None, :]).mean(axis=1),
                    np.inf)
    ci = ref.chip_index(got["chip_types"], got["chip_counts"])
    cs = ref.chip_index(got["slack"]["chip_types"],
                        got["slack"]["chip_counts"])
    if ci is None or cs is None or int(got["slack"]["moves"]) != int(
            moves[cs].sum()):
        return inf
    return float(max(
        rel(got["score"], sc[ci]), (sc[ci] - sc.min()) / sc.min(),
        rel(got["slack"]["score"], s_sc[cs]),
        (s_sc[cs] - s_sc.min()) / s_sc.min(),
        abs(got["slack"]["energy_saved_pct"]
            - (1.0 - s_sc[cs] / sc[cs]) * 100.0) / 100.0))

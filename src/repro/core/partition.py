"""Model parallelism on homogeneous cores (§IV.B, Algorithm II).

A network's layers are distributed *contiguously* over k identical cores
forming a processing pipeline through off-chip DRAM (Fig. 11).  The pipeline
latency is the maximum per-core latency; the speedup of eq. (6) is

    speedup = sum(latencies) / max(core latency).

``bb_partition`` is the paper's branch-and-bound: walk layers accumulating
latency until the running sum crosses the balanced average, branch on
including/excluding the crossing layer, and bound any branch whose current
core latency already exceeds the best pipeline latency found so far.

``dp_partition`` is an exact oracle (classic linear-partition DP) and
``brute_force_partition`` enumerates all splits — both used by the tests to
verify the B&B lands on (near-)optimal pipelines, and by the TPU adaptation
(`parallel/pipeline.py`) to place transformer layers on pipeline stages.

``batch_partition`` is the production hot path: a vectorized parametric
search that solves ALL (network × core-count) splits in one call — binary
search on the bottleneck latency T, with a ``searchsorted``-style greedy
feasibility check over prefix sums, batched over every (network, k) pair.
Segment sums are evaluated as prefix differences, the same arithmetic
``dp_partition`` uses, so the two agree exactly.

Array-shape conventions: per-network layer latencies arrive as 1-D
``[n_layers]`` vectors (``NetworkReport.layer_latencies`` from
:mod:`repro.core.energymodel`, in ns); the batch solver pads them to one
``[n_networks, n_pad]`` matrix (bucketed like the DSE engine's layer
axis, so repeated zoo-sized calls share one trace) with a validity mask,
and broadcasts the bisection over a ``[n_networks, n_k]`` problem grid.
A :class:`Partition` stores ``boundaries`` as the k+1 split indices into
the layer axis (``boundaries[0] == 0``, contiguous, monotone) and
``loads`` as the per-core latency sums — ``pipeline_latency =
max(loads)`` and eq. (6)'s ``speedup = sum / max``.

``batch_schedule_hetero`` generalises the solver beyond same-type cores
(the heterogeneous-chip co-design of :func:`repro.core.hetero.co_design`):
each problem is a (chip, network) pair with per-layer latencies on every
core TYPE (``[n_types, n_layers]``, from the DSE engine's
``per_layer=True`` path) and a core count per type.  The schedule is
defined in two exact stages — (1) every layer goes to the available type
that runs it fastest (per-layer argmin, ties → lower type index); (2)
each type's layer subsequence is split contiguously over that type's
cores, all types balanced against ONE shared pipeline bottleneck.
Feasibility of a bottleneck T is the conjunction of the per-type greedy
coverings (each monotone in T), so a single bisection per problem drives
every (problem × type) greedy row at once, and the optimum is exactly
``max over types of dp_partition(type's subsequence, type's cores)`` —
the oracle :func:`schedule_hetero_oracle` the tests compare against.
With one type and count k this degenerates to ``batch_partition``.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import obs
from .energymodel import _bucketed, jax_available, x64


@dataclasses.dataclass(frozen=True)
class Partition:
    """Contiguous layer → core assignment."""

    boundaries: Tuple[int, ...]   # start index of each core's slice
    loads: Tuple[float, ...]      # per-core total latency
    pipeline_latency: float       # max(loads)
    speedup: float                # eq. (6)
    n_layers: int = 0

    @property
    def n_cores(self) -> int:
        return len(self.loads)

    def table_row(self) -> List[Tuple[int, int]]:
        """(l_initial, n_C) tuples, 1-indexed like Tables 7–8."""
        bounds = list(self.boundaries) + [self.n_layers]
        return [(bounds[i] + 1, bounds[i + 1] - bounds[i])
                for i in range(len(self.boundaries))]


def _mk_partition(lat: Sequence[float], bounds: Sequence[int]) -> Partition:
    lat = np.asarray(lat, dtype=np.float64)
    prefix = np.concatenate([[0.0], np.cumsum(lat)])
    starts = np.asarray(bounds, dtype=np.intp)
    ends = np.concatenate([starts[1:], [lat.size]])
    loads = prefix[ends] - prefix[starts]        # O(k), not O(k·n)
    total = float(prefix[-1])
    pipe = float(loads.max())
    return Partition(boundaries=tuple(int(b) for b in starts),
                     loads=tuple(float(x) for x in loads),
                     pipeline_latency=pipe,
                     speedup=total / pipe if pipe > 0 else float("inf"),
                     n_layers=int(lat.size))


def bb_partition(latencies: Sequence[float], n_cores: int) -> Partition:
    """Algorithm II: branch-and-bound layer distribution."""
    lat = [float(x) for x in latencies]
    n = len(lat)
    if n_cores <= 1 or n <= n_cores:
        bounds = list(range(min(n, n_cores)))
        return _mk_partition(lat, bounds)

    total = sum(lat)
    avg = total / n_cores
    suffix = np.concatenate([np.cumsum(lat[::-1])[::-1], [0.0]])

    best = {"pipe": float("inf"), "bounds": None}

    def rec(i: int, cores_left: int, cur_max: float, bounds: List[int]):
        # Assign layers [i:] to the remaining cores; bounds holds the start
        # index of every core opened so far.
        if cur_max >= best["pipe"]:
            return                      # bound condition
        if cores_left == 1:
            seg = float(suffix[i])
            pipe = max(cur_max, seg)
            if pipe < best["pipe"]:
                best["pipe"] = pipe
                best["bounds"] = bounds + [i]
            return
        # accumulate from layer i until the running sum crosses the average
        s = 0.0
        j = i
        while j < n - (cores_left - 1) and s + lat[j] < avg:
            s += lat[j]
            j += 1
        j = min(j, n - (cores_left - 1))
        # branch 1: include the crossing layer (segment sum ≥ avg)
        hi = min(j + 1, n - (cores_left - 1))
        s_hi = float(sum(lat[i:hi]))
        rec(hi, cores_left - 1, max(cur_max, s_hi), bounds + [i])
        # branch 2: exclude it (segment sum < avg)
        if j > i and j != hi:
            s_lo = float(sum(lat[i:j]))
            rec(j, cores_left - 1, max(cur_max, s_lo), bounds + [i])

    rec(0, n_cores, 0.0, [])
    assert best["bounds"] is not None
    return _mk_partition(lat, best["bounds"])


def dp_partition(latencies: Sequence[float], n_cores: int) -> Partition:
    """Exact minimal-bottleneck contiguous partition (DP oracle)."""
    lat = [float(x) for x in latencies]
    n = len(lat)
    k = min(n_cores, n) if n else 1
    prefix = np.concatenate([[0.0], np.cumsum(lat)])

    # dp[c][i] = minimal pipeline latency splitting lat[:i] into c cores.
    # The inner minimisation over the cut point j is vectorised with numpy
    # over prefix sums (argmin keeps the first minimum, matching the
    # original scalar loop's strict-improvement tie-breaking).
    NEG = float("inf")
    dp = np.full((k + 1, n + 1), NEG)
    cut = np.zeros((k + 1, n + 1), dtype=int)
    dp[0][0] = 0.0
    for c in range(1, k + 1):
        prev = dp[c - 1]
        for i in range(c, n + 1):
            j0 = c - 1
            cand = np.maximum(prev[j0:i], prefix[i] - prefix[j0:i])
            bj = int(np.argmin(cand))
            dp[c][i] = cand[bj]
            cut[c][i] = j0 + bj
    bounds: List[int] = []
    i = n
    for c in range(k, 0, -1):
        j = int(cut[c][i])
        bounds.append(j)
        i = j
    bounds.reverse()
    return _mk_partition(lat, bounds)


def brute_force_partition(latencies: Sequence[float], n_cores: int
                          ) -> Partition:
    """Enumerate every contiguous split (tests only; exponential)."""
    lat = [float(x) for x in latencies]
    n = len(lat)
    k = min(n_cores, n)
    best = None
    for cuts in itertools.combinations(range(1, n), k - 1):
        bounds = [0] + list(cuts)
        p = _mk_partition(lat, bounds)
        if best is None or p.pipeline_latency < best.pipeline_latency:
            best = p
    return best if best is not None else _mk_partition(lat, [0])


# ---------------------------------------------------------------------------
# Batched parametric search: all (network × k) splits in one vectorized call.
#
# Feasibility of a bottleneck T is monotone (feasible ⟺ T ≥ T*), so a
# bisection on T converges to the optimum; every bisection step runs ONE
# greedy maximal-jump segmentation for ALL (network, k) pairs at once, each
# jump a vectorized binary search over the per-network prefix-sum rows.
# _BISECT_ITERS halvings shrink the bracket below one ulp of T* (see the
# constant's note), and segment sums are prefix DIFFERENCES throughout
# (never ``prefix + T`` sums), so the final bottleneck is bit-identical to
# ``dp_partition``'s.
# ---------------------------------------------------------------------------

#: Bisection steps: the initial bracket is at most ~one bottleneck wide
#: (see the lb/hi seeding in batch_partition), so 56 halvings push the
#: bracket below one ulp of the optimum — the greedy segmentation at the
#: upper end then lands on it exactly.
_BISECT_ITERS = 56

#: Static-shape buckets for the jitted solver: padding the prefix axis and
#: the (network × k) row axis to these multiples keeps the module-level
#: compile cache warm across calls with nearby problem sizes.
_N_BUCKET = 64
_ROW_BUCKET = 32
_K_MAX = 8


def _row_searchsorted(P: np.ndarray, net: np.ndarray, pos: np.ndarray,
                      thr: np.ndarray) -> np.ndarray:
    """Per-row maximal jump: largest j with P[net, j] − P[net, pos] ≤ thr.

    ``P`` rows are non-decreasing (prefix sums padded with +inf), so the
    predicate is monotone in j and a batched binary search finds the last
    true position.  Comparisons subtract prefixes — the exact arithmetic of
    the DP oracle — rather than pre-adding ``thr`` to the base (which would
    round and admit off-by-one-ulp jumps)."""
    base = P[net, pos]
    lo = pos.copy()                       # predicate holds at pos (0 ≤ thr)
    hi = np.full_like(pos, P.shape[1] - 1)
    steps = int(np.ceil(np.log2(P.shape[1]))) + 1
    for _ in range(steps):
        mid = (lo + hi + 1) >> 1
        ok = P[net, mid] - base <= thr
        lo = np.where(ok, mid, lo)
        hi = np.where(ok, hi, mid - 1)
    return lo


def _batch_greedy(P: np.ndarray, net: np.ndarray, n_arr: np.ndarray,
                  thr: np.ndarray, kk: np.ndarray, k_max: int,
                  exact: bool):
    """Greedy maximal-jump segmentation at threshold ``thr`` for every row.

    ``exact=False``: feasibility — True where ≤ kk segments cover all
    layers with every segment sum ≤ thr.  ``exact=True``: returns the
    [rows, k_max] start indices of an exactly-kk segmentation (each of the
    remaining segments is guaranteed ≥ 1 layer), valid when thr ≥ T*.
    """
    rows = net.shape[0]
    pos = np.zeros(rows, dtype=np.intp)
    viol = np.zeros(rows, dtype=bool)
    starts = np.full((rows, k_max), 0, dtype=np.intp) if exact else None
    for s in range(k_max):
        active = (s < kk) & (pos < n_arr)
        j = _row_searchsorted(P, net, pos, thr)
        if exact:
            rem = kk - s                      # segments still to open
            j = np.minimum(j, n_arr - np.maximum(rem - 1, 0))
        j = np.maximum(j, pos + 1)            # force progress …
        j = np.minimum(j, n_arr)              # … but stay in bounds
        viol |= active & (P[net, j] - P[net, pos] > thr)
        if exact:
            starts[:, s] = np.where(s < kk, np.minimum(pos, n_arr), n_arr)
        pos = np.where(active, j, pos)
    if exact:
        return starts
    return (pos >= n_arr) & ~viol


_jitted_solver = None          # built lazily on first jax dispatch


def _jax_solver():
    """One fused XLA program for the whole parametric search: the bisection
    on the bottleneck latency (each step one greedy maximal-jump
    feasibility over all (network, k) rows) plus the final exact-k
    segmentation.  The inner binary search and the greedy segment loop are
    UNROLLED (static bs_steps / _K_MAX) so each bisection step is one
    straight-line fused body; only the bisection itself is a sequential
    device loop.  Jitted at module level, so the all-pairs solve is ONE
    device dispatch instead of thousands of tiny numpy ops."""
    global _jitted_solver
    if _jitted_solver is None:
        import jax.numpy as jnp
        from jax import lax

        def solve(P, net, n_arr, kk, lo, hi, k_max, bs_steps):
            def rowsearch(pos, thr):
                base = P[net, pos]
                blo = pos
                bhi = jnp.full_like(pos, P.shape[1] - 1)
                for _ in range(bs_steps):
                    mid = (blo + bhi + 1) >> 1
                    ok = P[net, mid] - base <= thr
                    blo = jnp.where(ok, mid, blo)
                    bhi = jnp.where(ok, bhi, mid - 1)
                return blo

            def feasible(thr):
                pos = jnp.zeros_like(net)
                viol = jnp.zeros(net.shape, bool)
                for s in range(k_max):
                    active = (s < kk) & (pos < n_arr)
                    j = rowsearch(pos, thr)
                    j = jnp.minimum(jnp.maximum(j, pos + 1), n_arr)
                    viol = viol | (active & (P[net, j] - P[net, pos] > thr))
                    pos = jnp.where(active, j, pos)
                return (pos >= n_arr) & ~viol

            def bisect(_, lh):
                blo, bhi = lh
                mid = 0.5 * (blo + bhi)
                feas = feasible(mid)
                return (jnp.where(feas, blo, mid),
                        jnp.where(feas, mid, bhi))
            lo_f, hi_f = lax.fori_loop(0, _BISECT_ITERS, bisect, (lo, hi))

            starts = []
            pos = jnp.zeros_like(net)
            for s in range(k_max):            # static unroll; kk masks
                starts.append(jnp.where(s < kk,
                                        jnp.minimum(pos, n_arr), n_arr))
                j = rowsearch(pos, hi_f)
                j = jnp.minimum(j, n_arr - jnp.maximum(kk - s - 1, 0))
                j = jnp.minimum(jnp.maximum(j, pos + 1), n_arr)
                pos = jnp.where((s < kk) & (pos < n_arr), j, pos)
            return jnp.stack(starts, axis=1)

        _jitted_solver = obs.Program("partition_solve", solve,
                                     static_argnums=(6, 7))
    return _jitted_solver


def batch_partition(latencies: Sequence[Sequence[float]],
                    n_cores: Sequence[int] | int,
                    use_jax: bool | None = None,
                    ) -> List[Dict[int, Partition]]:
    """Solve every (network, k) minimal-bottleneck split in one call.

    ``latencies`` is a sequence of per-network layer-latency sequences and
    ``n_cores`` an int or sequence of core counts; returns one
    ``{k: Partition}`` dict per network.  Pipeline latencies are exactly
    ``dp_partition``'s (same prefix-difference arithmetic): the
    ``_BISECT_ITERS``-step bisection shrinks the bracket below one ulp of
    the optimum, so the greedy segmentation at the upper bracket lands on
    it exactly.  With
    jax available the whole search is one jitted dispatch; the numpy body
    is the reference fallback.
    """
    lats = [np.asarray(l, dtype=np.float64) for l in latencies]
    ks = ((int(n_cores),) if isinstance(n_cores, (int, np.integer))
          else tuple(int(k) for k in n_cores))
    if not lats or not ks:
        return [dict() for _ in lats]
    if max(ks) > _K_MAX and use_jax is not False:
        use_jax = False                    # solver unrolls _K_MAX segments
    use_jax = (jax_available() if use_jax is None else use_jax)
    n_lens = np.array([l.size for l in lats], dtype=np.int64)
    n_max = int(n_lens.max())
    n_net = len(lats)

    n_pad = _bucketed(n_max, _N_BUCKET) if use_jax else n_max
    P = np.full((n_net, n_pad + 1), np.inf)
    mx = np.zeros(n_net)
    for i, l in enumerate(lats):
        P[i, 0] = 0.0
        P[i, 1:l.size + 1] = np.cumsum(l)
        mx[i] = l.max() if l.size else 0.0

    # one row per (network, requested k), clamped like dp_partition
    net = np.repeat(np.arange(n_net, dtype=np.int64), len(ks))
    k_req = np.tile(np.asarray(ks, dtype=np.int64), n_net)
    kk = np.minimum(np.maximum(k_req, 1), np.maximum(n_lens[net], 1))
    k_max = int(kk.max())
    n_arr = n_lens[net]
    n_rows = net.size

    total = P[net, n_arr]
    # Tight initial bracket: any bottleneck is ≥ max(max layer, total/k),
    # and the greedy bound gives T* ≤ total/k + max layer.  The tiny
    # relative slack absorbs the rounding of the bound itself; the
    # bisection count then only has to cover the ~2^53 floats inside.
    lb = np.maximum(mx[net], total / np.maximum(kk, 1))
    lo = np.nextafter(lb, -np.inf)
    hi = np.minimum(total, (total / np.maximum(kk, 1) + mx[net])
                    * (1.0 + 1e-12))

    if use_jax:
        r_pad = _bucketed(n_rows, _ROW_BUCKET)
        pad = r_pad - n_rows
        netp = np.concatenate([net, np.zeros(pad, np.int64)])
        n_ap = np.concatenate([n_arr, np.full(pad, n_lens[0], np.int64)])
        kkp = np.concatenate([kk, np.ones(pad, np.int64)])
        lop = np.concatenate([lo, np.full(pad, lo[0] if n_rows else 0.0)])
        hip = np.concatenate([hi, np.full(pad, hi[0] if n_rows else 1.0)])
        with x64():
            bs_steps = int(np.ceil(np.log2(n_pad + 1))) + 1
            starts = np.asarray(_jax_solver()(
                P, netp, n_ap, kkp, lop, hip, _K_MAX, bs_steps))[:n_rows]
    else:
        for _ in range(_BISECT_ITERS):
            mid = 0.5 * (lo + hi)
            feas = _batch_greedy(P, net, n_arr, mid, kk, k_max,
                                 exact=False)
            hi = np.where(feas, mid, hi)
            lo = np.where(feas, lo, mid)
        starts = _batch_greedy(P, net, n_arr, hi, kk, k_max, exact=True)

    # Vectorised load extraction, then plain-Python object construction
    # (no per-row numpy calls — they would dominate at 126 rows).
    ends = np.concatenate([starts[:, 1:],
                           np.full((n_rows, 1), 0, np.int64)], axis=1)
    ends[:, -1] = n_arr
    ends = np.minimum(np.maximum(ends, starts), n_arr[:, None])
    loads_all = (P[net[:, None], ends] - P[net[:, None], starts]).tolist()
    starts_l = starts.tolist()
    totals = total.tolist()
    out: List[Dict[int, Partition]] = [dict() for _ in lats]
    for r in range(n_rows):
        i, k, kr = int(net[r]), int(k_req[r]), int(kk[r])
        loads = loads_all[r][:kr]
        pipe = max(loads)
        out[i][k] = Partition(
            boundaries=tuple(starts_l[r][:kr]), loads=tuple(loads),
            pipeline_latency=pipe,
            speedup=totals[r] / pipe if pipe > 0 else float("inf"),
            n_layers=int(n_lens[i]))
    return out


# ---------------------------------------------------------------------------
# Latency-bound Pareto scoring: batch_schedule_hetero's chip scoring
# vectorised over a deadline axis.  A solved problem set gives every
# (chip, network) pair a scheduled (energy, latency) point; under a latency
# bound the score of a chip is its energy *subject to* the pipeline
# bottleneck meeting the deadline — infeasible schedules mask to +inf, so
# per-deadline argmins and the whole (chips × networks × deadlines) score
# block come out of ONE compiled call, with no python loop over deadlines.
# The (energy, latency) dominance masks (the Pareto fronts) ride along in
# the same program.
# ---------------------------------------------------------------------------


def _pareto_body(xp, value, latency, norm_latency, deadlines, n_net):
    """Traced body shared by the numpy and jitted paths.

    ``value``/``latency``/``norm_latency``: [C, N] per-(chip, network)
    score (normalised energy by convention), raw pipeline bottleneck, and
    normalised bottleneck; ``deadlines``: [N, D] absolute per-network
    latency bounds; ``n_net``: N as a float64 operand.  Means over the
    network axis divide by it rather than by a trace-time constant, which
    XLA:CPU turns into a multiply by the rounded reciprocal, one ulp off
    numpy's division.  Returns

    * ``masked``  [C, N, D] — ``value`` where the schedule meets the
      deadline, +inf where it misses,
    * ``scores``  [C, D]   — per-chip mean over networks (one infeasible
      network poisons the chip: +inf propagates through the mean),
    * ``best``    [D]      — argmin chip per deadline (-1: none feasible),
    * ``best_net`` [N, D]  — per-network argmin chip per deadline,
    * ``net_front`` [C, N] — non-dominated (value, latency) chips per
      network (weak dominance: a point falls only to another that is ≤ in
      both coordinates and < in at least one),
    * ``chip_front`` [C]   — non-dominated chips on the network-mean
      (value, norm_latency) plane."""
    feas = latency[:, :, None] <= deadlines[None, :, :]
    masked = xp.where(feas, value[:, :, None], np.inf)
    scores = masked.sum(axis=1) / n_net                       # [C, D]
    best = xp.where(xp.isfinite(scores).any(axis=0),
                    xp.argmin(scores, axis=0), -1)
    best_net = xp.where(xp.isfinite(masked).any(axis=0),
                        xp.argmin(masked, axis=0), -1)        # [N, D]

    e1, e2 = value[:, None, :], value[None, :, :]
    l1, l2 = latency[:, None, :], latency[None, :, :]
    dom = (e2 <= e1) & (l2 <= l1) & ((e2 < e1) | (l2 < l1))
    net_front = ~dom.any(axis=1)                              # [C, N]

    mv = value.sum(axis=1) / n_net
    ml = norm_latency.sum(axis=1) / n_net
    domc = ((mv[None, :] <= mv[:, None]) & (ml[None, :] <= ml[:, None])
            & ((mv[None, :] < mv[:, None]) | (ml[None, :] < ml[:, None])))
    chip_front = ~domc.any(axis=1)                            # [C]
    return masked, scores, best, best_net, net_front, chip_front


_jitted_pareto = None


def _jax_pareto():
    global _jitted_pareto
    if _jitted_pareto is None:
        import jax.numpy as jnp

        def kernel(value, latency, norm_latency, deadlines, n_net):
            return _pareto_body(jnp, value, latency, norm_latency,
                                deadlines, n_net)

        _jitted_pareto = obs.Program("pareto_scores", kernel)
    return _jitted_pareto


def batch_pareto_scores(value, latency, deadlines,
                        norm_latency=None,
                        use_jax: bool | None = None):
    """Score a solved (chip × network) block against ALL deadlines at once.

    ``value``/``latency`` are [C, N] (scheduled score — normalised energy
    by convention — and pipeline bottleneck); ``deadlines`` is [N, D]
    absolute per-network bounds or [D] (broadcast to every network);
    ``norm_latency`` defaults to ``latency`` and only feeds the
    network-mean chip front.  Returns the 6-tuple of
    :func:`_pareto_body` as numpy arrays.  With jax available the whole
    block — masking, per-deadline argmins, both dominance fronts — is ONE
    jitted dispatch; the numpy body is the reference fallback."""
    value = np.asarray(value, dtype=np.float64)
    latency = np.asarray(latency, dtype=np.float64)
    deadlines = np.asarray(deadlines, dtype=np.float64)
    if deadlines.ndim == 1:
        deadlines = np.broadcast_to(deadlines[None, :],
                                    (value.shape[1], deadlines.shape[0]))
    norm_latency = (latency if norm_latency is None
                    else np.asarray(norm_latency, dtype=np.float64))
    n_net = np.float64(value.shape[1])
    use_jax = jax_available() if use_jax is None else use_jax
    if use_jax:
        with x64():
            out = _jax_pareto()(value, latency, norm_latency, deadlines,
                                n_net)
        return tuple(np.asarray(o) for o in out)
    return _pareto_body(np, value, latency, norm_latency, deadlines, n_net)


def partition_network(report, n_cores: int, method: str = "bb") -> Partition:
    """Distribute a simulated network (NetworkReport) across cores."""
    lat = report.layer_latencies
    fn = {"bb": bb_partition, "dp": dp_partition,
          "brute": brute_force_partition}[method]
    return fn(lat, n_cores)


# ---------------------------------------------------------------------------
# Heterogeneous layer→core scheduling: batch_partition generalised beyond
# same-type cores.  A problem is a (chip, network) pair — per-layer
# latencies on every core TYPE plus a core count per type.  The schedule:
#
# 1. **per-layer argmin** — each layer runs on the available type that
#    executes it fastest (ties → lower type index);
# 2. **per-core-count balancing** — each type's layer subsequence is split
#    contiguously over that type's cores; the pipeline bottleneck is the
#    max core load across ALL types, so feasibility of a bottleneck T is
#    the AND of the per-type greedy coverings and ONE bisection per
#    problem drives every (problem × type) greedy row at once.
#
# Masked prefix sums make stage 2 exact: a type's costs are written onto
# the FULL layer axis (other types' slots are 0.0 — adding zero is exact
# in fp), so segment sums are the same prefix differences dp_partition
# computes on the compacted subsequence, and the final bottleneck is
# bit-identical to max_t dp_partition(subseq_t, counts_t) — the oracle.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeteroSchedule:
    """One network's layer→core schedule on a heterogeneous chip."""

    types: Tuple[int, ...]        # core → type index (type-major order)
    layer_type: Tuple[int, ...]   # layer → type index (per-layer argmin)
    layer_core: Tuple[int, ...]   # layer → global core id
    loads: Tuple[float, ...]      # per-core latency sums (idle cores 0.0)
    bottleneck: float             # pipeline latency = max(loads)
    speedup: float                # Σ assigned layer latency / bottleneck
    n_layers: int

    @property
    def n_cores(self) -> int:
        return len(self.types)


@dataclasses.dataclass(frozen=True)
class BatchHeteroResult:
    """Array-level output of :func:`batch_schedule_hetero` (B problems).

    Kept as arrays so mega-batch co-design sweeps never pay per-problem
    Python object construction for schedules nobody reads —
    :meth:`schedule` materialises a :class:`HeteroSchedule` on demand.
    """

    counts: np.ndarray            # [B, T] cores per type (as requested)
    n_layers: np.ndarray          # [B]
    layer_type: np.ndarray        # [B, L_pad] per-layer argmin type
    starts: np.ndarray            # [B, T, k_max] full-axis segment starts
    seg_counts: np.ndarray        # [B, T] segments actually opened
    loads: np.ndarray             # [B, T, k_max] per-segment latency sums
    bottleneck: np.ndarray        # [B] (+inf: infeasible, strict=False)
    total: np.ndarray             # [B] Σ assigned layer latency
    feasible: np.ndarray | None = None   # [B] False → no core available
    labels: Tuple[str, ...] | None = None   # per-problem names for errors

    def __len__(self) -> int:
        return int(self.bottleneck.shape[0])

    @property
    def speedup(self) -> np.ndarray:
        with np.errstate(divide="ignore"):
            return np.where(self.bottleneck > 0,
                            self.total / self.bottleneck, np.inf)

    def schedule(self, i: int) -> HeteroSchedule:
        if self.feasible is not None and not self.feasible[i]:
            lab = (self.labels[i] if self.labels is not None
                   else f"problem {i}")
            raise ValueError(
                f"{lab}: infeasible — every core type has count 0 (the "
                "fault scenario killed the whole chip); bottleneck is "
                "+inf and no schedule exists")
        n_t = self.counts.shape[1]
        L = int(self.n_layers[i])
        tt = self.layer_type[i, :L]
        counts = self.counts[i]
        core_off = np.concatenate([[0], np.cumsum(counts)])
        types = tuple(int(t) for t in np.repeat(np.arange(n_t), counts))
        loads = np.zeros(int(core_off[-1]))
        layer_core = np.zeros(L, dtype=np.intp)
        for t in range(n_t):
            if counts[t] == 0:
                continue
            kk = int(self.seg_counts[i, t])
            st = self.starts[i, t, :kk]
            ends = np.concatenate([st[1:], [L]])
            lt = np.flatnonzero(tt == t)
            if lt.size:
                layer_core[lt] = core_off[t] + np.searchsorted(
                    ends, lt, side="right")
            loads[core_off[t]:core_off[t] + kk] = self.loads[i, t, :kk]
        bott = float(self.bottleneck[i])
        total = float(self.total[i])
        return HeteroSchedule(
            types=types, layer_type=tuple(int(t) for t in tt),
            layer_core=tuple(int(c) for c in layer_core),
            loads=tuple(float(x) for x in loads),
            bottleneck=bott,
            speedup=total / bott if bott > 0 else float("inf"),
            n_layers=L)

    def schedules(self) -> List[HeteroSchedule]:
        return [self.schedule(i) for i in range(len(self))]


def schedule_hetero_oracle(latencies, counts) -> Dict[str, Any]:
    """Scalar reference for ONE (chip, network) problem.

    ``latencies``: [n_types, n_layers] per-layer latency on each core
    type; ``counts``: [n_types] cores per type.  Per-layer argmin over
    the available types, then ``dp_partition`` per type's subsequence —
    the exact semantics ``batch_schedule_hetero`` batches."""
    lat = np.asarray(latencies, dtype=np.float64)
    counts = np.asarray(counts, dtype=np.int64)
    n_types, n_layers = lat.shape
    if counts.shape[0] > n_types:        # zero-padded type slots are fine
        if (counts[n_types:] > 0).any():
            raise ValueError("counts for more types than latency rows")
        counts = counts[:n_types]
    if n_layers == 0:
        raise ValueError("schedule_hetero_oracle needs ≥ 1 layer")
    if not (counts > 0).any():
        raise ValueError("schedule_hetero_oracle needs ≥ 1 core")
    cost = np.where((counts > 0)[:, None], lat, np.inf)
    tt = np.argmin(cost, axis=0)
    bottleneck = 0.0
    for t in range(n_types):
        sub = lat[t, tt == t]
        if counts[t] <= 0 or sub.size == 0:
            continue
        p = dp_partition(sub, int(counts[t]))
        bottleneck = max(bottleneck, p.pipeline_latency)
    total = float(lat[tt, np.arange(n_layers)].sum())
    return dict(bottleneck=bottleneck, layer_type=tt, total=total,
                speedup=total / bottleneck if bottleneck > 0
                else float("inf"))


_B_BUCKET = 32     # problem-axis bucket of the jitted hetero solver

_jitted_hetero_stage1 = None


def _jax_hetero_stage1():
    """Fused stage 1 of the hetero solver: per-layer argmin assignment +
    masked per-type prefix sums + the per-type reductions (layer counts,
    max, total), one XLA program instead of ~6 full-tensor numpy passes
    over the [B, T, L] block.  Bit-identical to the numpy body (same
    first-minimum argmin, same cumsum order; adding exact zeros)."""
    global _jitted_hetero_stage1
    if _jitted_hetero_stage1 is None:
        import jax.numpy as jnp

        def stage1(lat, avail, n_lens):
            n_types = lat.shape[1]
            l_idx = jnp.arange(lat.shape[2])
            valid = l_idx[None, :] < n_lens[:, None]          # [B, L]
            cost = jnp.where(avail[:, :, None], lat, jnp.inf)
            tt = jnp.argmin(cost, axis=1)                     # [B, L]
            tmask = ((tt[:, None, :] == jnp.arange(n_types)[None, :, None])
                     & valid[:, None, :])                     # [B, T, L]
            masked = jnp.where(tmask, lat, 0.0)
            # NOTE no cumsum here: XLA's scan is not bit-identical to
            # numpy's sequential one, and the solver's exactness-vs-dp
            # contract rides on identical prefix arithmetic — the prefix
            # sums stay on the host.
            return (masked, jnp.where(valid, tt, 0),
                    tmask.sum(axis=-1), masked.max(axis=-1))

        _jitted_hetero_stage1 = obs.Program("schedule_stage1", stage1)
    return _jitted_hetero_stage1


@obs.traced("dse.schedule")
def batch_schedule_hetero(latencies, counts,
                          n_layers=None,
                          use_jax: bool | None = None,
                          *,
                          strict: bool = True,
                          labels=None,
                          ) -> BatchHeteroResult:
    """Solve every heterogeneous (chip, network) schedule in one call.

    ``latencies``: one ``[n_types, n_layers]`` per-layer latency matrix
    per problem — a sequence of such, or ONE dense ``[B, T, L]`` float64
    array (the DSE engine's ``per_layer=True`` tensors gathered per
    chip; the fast path — no per-problem Python work).  ``counts``: the
    matching per-type core counts (``[T]`` per problem, or ``[B, T]``).
    With a dense array, ``n_layers`` gives each problem's true layer
    count (default: the full ``L``) — entries past it are ignored.
    Types with count 0 (padding slots) never receive layers.  Returns a
    :class:`BatchHeteroResult`; bottlenecks are exactly
    :func:`schedule_hetero_oracle`'s (same prefix-difference arithmetic,
    ulp-tight bisection).  With jax available the bisection +
    segmentation run as ONE jitted dispatch over all (problem × type)
    rows; the numpy body is the reference fallback.

    **Fault-scenario axis.**  A dense ``[B, S, T, L]`` array adds a
    scenario axis (per-problem perturbed latencies — e.g. degraded PE
    arrays swap in slower type rows): scenarios are just more problem
    rows, flattened scenario-minor to ``B·S`` problems solved in the
    same single call.  ``counts`` may then be ``[B, S, T]`` (scenarios
    with killed cores), ``[B, T]`` (same counts every scenario) or
    ``[T]``; ``n_layers`` ``[B]`` or ``[B, S]``.  Problem ``b``'s
    scenario ``s`` is flat row ``b·S + s`` of the result.

    **Infeasibility.**  ``strict=True`` (default) raises when any
    problem's counts are all zero.  ``strict=False`` reports such
    problems (a scenario that killed every core) per-problem instead:
    ``bottleneck`` is +inf, ``feasible`` is False, and
    :meth:`BatchHeteroResult.schedule` raises naming the problem via
    ``labels`` (one string per flattened problem row).
    """
    if isinstance(latencies, np.ndarray) and latencies.ndim == 4:
        b0, n_s = latencies.shape[:2]
        latencies = latencies.reshape(b0 * n_s, *latencies.shape[2:])
        cnts_in = np.asarray(counts)
        if cnts_in.ndim == 3:
            counts = cnts_in.reshape(b0 * n_s, cnts_in.shape[2])
        elif cnts_in.ndim == 2:
            counts = np.repeat(cnts_in, n_s, axis=0)
        if n_layers is not None:
            nl = np.asarray(n_layers, dtype=np.int64)
            n_layers = (np.repeat(nl, n_s) if nl.ndim == 1
                        else nl.reshape(b0 * n_s))
    dense = isinstance(latencies, np.ndarray) and latencies.ndim == 3
    if dense:
        n_b, in_types, n_max = latencies.shape
        n_lens = (np.full(n_b, n_max, dtype=np.int64) if n_layers is None
                  else np.asarray(n_layers, dtype=np.int64))
    else:
        lats = [np.asarray(l, dtype=np.float64) for l in latencies]
        n_b = len(lats)
        in_types = max((l.shape[0] for l in lats), default=0)
        n_lens = np.array([l.shape[1] for l in lats], dtype=np.int64)
        n_max = int(n_lens.max()) if n_b else 0
    cnts = np.asarray(counts)
    if cnts.ndim == 1:
        cnts = np.broadcast_to(cnts, (n_b, cnts.shape[0]))
    cnts = cnts.astype(np.int64)
    if n_b == 0:
        return BatchHeteroResult(
            counts=np.zeros((0, 0), np.int64), n_layers=np.zeros(0, np.int64),
            layer_type=np.zeros((0, 0), np.int64),
            starts=np.zeros((0, 0, _K_MAX), np.int64),
            seg_counts=np.zeros((0, 0), np.int64),
            loads=np.zeros((0, 0, _K_MAX)), bottleneck=np.zeros(0),
            total=np.zeros(0), feasible=np.zeros(0, bool))
    if cnts.shape[0] != n_b:
        raise ValueError(f"counts rows {cnts.shape[0]} != problems {n_b}")
    if labels is not None:
        labels = tuple(str(x) for x in labels)
        if len(labels) != n_b:
            raise ValueError(
                f"labels has {len(labels)} entries for {n_b} problems")
    n_types = max(in_types, cnts.shape[1])
    if (n_lens == 0).any():
        raise ValueError("every problem needs ≥ 1 layer")
    # a positive count for a type slot beyond a problem's latency rows
    # would hand every layer to a phantom zero-latency type — reject it,
    # exactly like schedule_hetero_oracle does
    prob_types = (np.asarray([l.shape[0] for l in lats], dtype=np.int64)
                  if not dense else np.full(n_b, in_types, np.int64))
    ghost = np.arange(cnts.shape[1])[None, :] >= prob_types[:, None]
    if (cnts * ghost).any():
        raise ValueError("counts for more types than latency rows")

    if max(int(c) for c in cnts.max(axis=0)) > _K_MAX and use_jax is not False:
        use_jax = False                    # solver unrolls _K_MAX segments
    use_jax = (jax_available() if use_jax is None else use_jax)

    n_pad = _bucketed(n_max, _N_BUCKET) if use_jax else n_max
    b_pad = _bucketed(n_b, _B_BUCKET) if use_jax else n_b

    lat = np.zeros((b_pad, n_types, n_pad))
    if dense:
        lat[:n_b, :in_types, :n_max] = latencies
    else:
        for i, l in enumerate(lats):
            lat[i, :l.shape[0], :l.shape[1]] = l
    counts_p = np.ones((b_pad, n_types), dtype=np.int64)  # benign pad rows
    counts_p[:n_b] = 0
    counts_p[:n_b, :cnts.shape[1]] = cnts
    avail = counts_p > 0
    feas_b = avail[:n_b].any(axis=1)
    if not feas_b.all():
        if strict:
            raise ValueError(
                "every problem needs ≥ 1 core (counts all zero); pass "
                "strict=False to report per-problem infeasibility instead")
        # all-types-dead problems (a scenario that killed every core)
        # solve as benign single-core rows like the padding, then report
        # +inf below — the rest of the batch is unaffected
        avail[np.flatnonzero(~feas_b), 0] = True
    avail[n_b:] = False
    avail[n_b:, 0] = True                  # padded problems: 1 trivial core
    n_lens_p = np.concatenate([n_lens, np.ones(b_pad - n_b, np.int64)])

    # stage 1: per-layer argmin over the available types + masked per-type
    # prefix sums (fused on-device when jax runs the search below)
    l_idx = np.arange(n_pad)
    valid_l = l_idx[None, :] < n_lens_p[:, None]              # [B, L]
    if use_jax:
        with x64():
            masked, tt, n_t, mx = obs.fetch(
                "dse.schedule.wait",
                *_jax_hetero_stage1()(lat, avail, n_lens_p))
    else:
        cost = np.where(avail[:, :, None], lat, np.inf)
        tt = np.argmin(cost, axis=1)                          # [B, L]
        tt = np.where(valid_l, tt, 0)
        tmask = ((tt[:, None, :] == np.arange(n_types)[None, :, None])
                 & valid_l[:, None, :])
        masked = np.where(tmask, lat, 0.0)
        n_t = tmask.sum(axis=-1)                              # layers/type
        mx = masked.max(axis=-1)                              # [B, T]
    # prefix sums on the HOST: numpy's sequential cumsum is the exact
    # arithmetic of the dp oracle (see _jax_hetero_stage1's note)
    cum = np.cumsum(masked, axis=-1)                          # [B, T, L]
    pref = np.where(valid_l[:, None, :], cum, np.inf)
    P = np.full((b_pad * n_types, n_pad + 1), np.inf)
    P[:, 0] = 0.0
    P[:, 1:] = pref.reshape(b_pad * n_types, n_pad)
    kk = np.where(n_t > 0, np.minimum(counts_p, np.maximum(n_t, 1)), 1)
    kk = np.maximum(kk, 1)
    total_t = P[np.arange(b_pad * n_types),
                np.repeat(n_lens_p, n_types)].reshape(b_pad, n_types)

    # Per-(problem, type) solves: the global bottleneck is simply the MAX
    # of the independent per-type optima (feasibility decomposes over
    # types), so every row runs its OWN parametric search — the exact
    # machinery (and jit cache) of batch_partition, one row per
    # (problem, type).  Two row classes are CLOSED FORM and skip the
    # bisection entirely (in chip co-design sweeps they are the
    # majority — core counts are small):
    #   kk == 1     → one segment: T* = total_t, starts = [0, …]
    #   kk == n_t   → one layer per segment: T* = mx_t, starts = the
    #                 type's layer positions on the full axis
    # (kk = min(counts, n_t) never exceeds n_t, so these two plus the
    # bisected 2 ≤ kk < n_t rows are exhaustive.)
    rows = b_pad * n_types
    net_r = np.arange(rows, dtype=np.int64)
    n_arr_r = np.repeat(n_lens_p, n_types)
    kk_r = kk.reshape(rows)
    n_t_r = n_t.reshape(rows)
    k_out = max(_K_MAX, int(kk_r.max()))
    starts_r = np.broadcast_to(n_arr_r[:, None],
                               (rows, k_out)).copy()

    single = kk_r == 1
    starts_r[single, 0] = 0

    per_layer_rows = (~single) & (kk_r == n_t_r)
    if per_layer_rows.any():
        type_mask = ((tt[:, None, :] == np.arange(n_types)[None, :, None])
                     & valid_l[:, None, :]).reshape(rows, n_pad)
        sub = type_mask[per_layer_rows]
        occ = np.cumsum(sub, axis=1)
        for s in range(int(kk_r[per_layer_rows].max())):
            hit = sub & (occ == s + 1)
            pos = np.argmax(hit, axis=1)
            has = hit.any(axis=1)
            starts_r[np.flatnonzero(per_layer_rows)[has], s] = pos[has]

    # kk == 2 is closed form too: with A_j = P[j] (non-decreasing) and
    # B_j = P[n] − P[j] (non-increasing), T* = min_j max(A_j, B_j) sits at
    # the predicate crossing A_j ≤ B_j — one vectorised binary search per
    # row, then the two candidate cuts around it.  Same prefix-difference
    # arithmetic as the dp oracle, so still exact.
    halves = np.flatnonzero(~single & ~per_layer_rows & (kk_r == 2))
    if halves.size:
        net_h, n_h = net_r[halves], n_arr_r[halves]
        tot_h = P[net_h, n_h]
        lo_j = np.ones(halves.size, dtype=np.int64)
        hi_j = np.maximum(n_h - 1, 1)
        steps = int(np.ceil(np.log2(P.shape[1]))) + 1
        for _ in range(steps):
            mid = (lo_j + hi_j + 1) >> 1
            ok = P[net_h, mid] <= tot_h - P[net_h, mid]
            lo_j = np.where(ok, mid, lo_j)
            hi_j = np.where(ok, hi_j, mid - 1)
        j0 = np.clip(lo_j, 1, np.maximum(n_h - 1, 1))
        j1 = np.clip(lo_j + 1, 1, np.maximum(n_h - 1, 1))
        m0 = np.maximum(P[net_h, j0], tot_h - P[net_h, j0])
        m1 = np.maximum(P[net_h, j1], tot_h - P[net_h, j1])
        cut = np.where(m0 <= m1, j0, j1)
        starts_r[halves, 0] = 0
        starts_r[halves, 1] = cut

    need = np.flatnonzero(~single & ~per_layer_rows & (kk_r > 2))
    if need.size:
        lb = np.maximum(mx, total_t / kk).reshape(-1)[need]
        lo_n = np.nextafter(lb, -np.inf)
        hi_n = ((total_t / kk + mx).reshape(-1)[need]) * (1.0 + 1e-12)
        net_n, n_arr_n, kk_n = net_r[need], n_arr_r[need], kk_r[need]
        k_mx = int(kk_n.max())
        if use_jax:
            r_pad = _bucketed(need.size, _ROW_BUCKET)
            pad = r_pad - need.size
            netp = np.concatenate([net_n, np.zeros(pad, np.int64)])
            n_ap = np.concatenate([n_arr_n,
                                   np.full(pad, n_arr_r[0], np.int64)])
            kkp = np.concatenate([kk_n, np.ones(pad, np.int64)])
            lop = np.concatenate([lo_n, np.zeros(pad)])
            hip = np.concatenate([hi_n, np.ones(pad)])
            with x64():
                bs_steps = int(np.ceil(np.log2(n_pad + 1))) + 1
                starts_r[need, :k_mx] = obs.fetch(
                    "dse.schedule.wait", _jax_solver()(
                        P, netp, n_ap, kkp, lop, hip, k_mx,
                        bs_steps))[0][:need.size]
        else:
            lo_b, hi_b = lo_n.copy(), hi_n.copy()
            for _ in range(_BISECT_ITERS):
                mid = 0.5 * (lo_b + hi_b)
                feas = _batch_greedy(P, net_n, n_arr_n, mid, kk_n, k_mx,
                                     exact=False)
                hi_b = np.where(feas, mid, hi_b)
                lo_b = np.where(feas, lo_b, mid)
            st = _batch_greedy(P, net_n, n_arr_n, hi_b, kk_n, k_mx,
                               exact=True)
            starts_r[need, :st.shape[1]] = st

    k_out = starts_r.shape[1]
    ends_r = np.concatenate(
        [starts_r[:, 1:], np.zeros((rows, 1), starts_r.dtype)], axis=1)
    ends_r[:, -1] = n_arr_r
    ends_r = np.minimum(np.maximum(ends_r, starts_r), n_arr_r[:, None])
    loads_r = P[net_r[:, None], ends_r] - P[net_r[:, None], starts_r]
    loads_r = np.where(np.isfinite(loads_r), loads_r, 0.0)

    loads = loads_r.reshape(b_pad, n_types, k_out)[:n_b]
    bottleneck = loads.max(axis=(1, 2))
    if not feas_b.all():
        loads = np.where(feas_b[:, None, None], loads, 0.0)
        bottleneck = np.where(feas_b, bottleneck, np.inf)
    return BatchHeteroResult(
        counts=np.asarray(cnts), n_layers=n_lens,
        layer_type=tt[:n_b], starts=starts_r.reshape(
            b_pad, n_types, k_out)[:n_b],
        seg_counts=kk[:n_b], loads=loads,
        bottleneck=bottleneck, total=total_t[:n_b].sum(axis=1),
        feasible=feas_b.copy(), labels=labels)


# ---------------------------------------------------------------------------
# Energy-aware deadline-slack scheduling.
#
# Stage 1 of batch_schedule_hetero is latency-argmin only, so every
# frontier built on it is latency-optimal.  The slack pass starts from
# that schedule and greedily moves layers to LOWER-ENERGY types (largest
# energy saving first) while the pipeline still meets a deadline.
# Feasibility of a candidate assignment at a threshold is decided by a
# sequential greedy-covering SCAN over the layer axis (open a new
# segment when the running sum would exceed the threshold) — the same
# arithmetic in the scalar oracle, the numpy batch kernel and the jitted
# jax kernel, so the three stay bit-identical:
#
#     x    = lat[t, l] if tt[l] == t else 0.0     (exact zero-padding)
#     nxt  = run + x                              (computed ONCE, reused)
#     over = nxt > thr
#     viol |= over & (x > thr)
#     segs += over;  run = over ? x : nxt
#
# A type is coverable iff segs <= max(count, 1) and never viol.  After
# the greedy move loop the true bottleneck of the final assignment is
# recovered by bisecting the threshold (56 iterations, lo = 0, hi =
# min(deadline, per-type scan totals max) — both endpoints verified
# feasible, and hi is only ever replaced by a TESTED-feasible midpoint,
# so extraction at hi always succeeds and bottleneck <= deadline holds
# at the bit level).  Energy totals are summed by a SEQUENTIAL per-layer
# loop in both paths (np.sum's pairwise tree would differ between the
# oracle's [n_l] vector and the batch's padded rows).
# ---------------------------------------------------------------------------


def _oracle_slack_scan(lat, tt, thr, n_l):
    """Scalar greedy-covering scan for ONE problem (python loop).

    Returns (run [T] final running sums, segs [T], viol [T], peak [T]
    max completed-segment sum incl. the final running one)."""
    n_types = lat.shape[0]
    run = np.zeros(n_types)
    segs = np.ones(n_types, dtype=np.int64)
    viol = np.zeros(n_types, dtype=bool)
    peak = np.zeros(n_types)
    for l in range(n_l):
        t = int(tt[l])
        x = float(lat[t, l])
        nxt = run[t] + x
        if nxt > thr:
            if x > thr:
                viol[t] = True
            segs[t] += 1
            peak[t] = max(peak[t], run[t])
            run[t] = x
        else:
            run[t] = nxt
    peak = np.maximum(peak, run)
    return run, segs, viol, peak


def slack_schedule_oracle(latencies, energies, counts, deadline
                          ) -> Dict[str, Any]:
    """Scalar reference for ONE energy-aware slack schedule.

    ``latencies``/``energies``: [n_types, n_layers]; ``counts``:
    [n_types] cores per type; ``deadline``: absolute pipeline-latency
    budget.  Starts from :func:`schedule_hetero_oracle`'s latency-argmin
    schedule; when ``deadline`` leaves slack (deadline > T*), greedily
    re-assigns layers to the energy-argmin type (largest per-layer
    saving first, ties -> lower layer index), accepting each move iff
    the greedy-covering scan still fits every type's cores within the
    deadline.  Returns dict(bottleneck, layer_type, energy, n_moves,
    feasible) — the exact semantics :func:`batch_slack_schedule`
    batches (bit-identical arithmetic)."""
    lat = np.asarray(latencies, dtype=np.float64)
    en = np.asarray(energies, dtype=np.float64)
    base = schedule_hetero_oracle(lat, counts)
    n_types, n_l = lat.shape
    if en.shape != lat.shape:
        raise ValueError(
            f"energies shape {en.shape} != latencies shape {lat.shape}")
    cnt = np.asarray(counts, dtype=np.int64)[:n_types]
    deadline = float(deadline)
    tt0 = np.asarray(base["layer_type"], dtype=np.int64)
    t_star = float(base["bottleneck"])

    def _energy(tt):
        eng = 0.0                       # sequential: matches batch path
        for l in range(n_l):
            eng += en[tt[l], l]
        return eng

    def _base_copy():
        return dict(bottleneck=t_star, layer_type=tt0.copy(),
                    energy=_energy(tt0), n_moves=0,
                    feasible=bool(t_star <= deadline))

    if not (deadline > t_star):        # no slack (or infeasible): base
        return _base_copy()

    avail = cnt > 0
    te = np.argmin(np.where(avail[:, None], en, np.inf), axis=0)
    d_e = en[tt0, np.arange(n_l)] - en[te, np.arange(n_l)]
    cand = (te != tt0) & (d_e > 0)
    order = np.lexsort((np.arange(n_l), np.where(cand, -d_e, np.inf)))
    moves = order[:int(cand.sum())]

    kk = np.maximum(cnt, 1)
    tt = tt0.copy()
    n_moves = 0
    for l in moves:
        tt_try = tt.copy()
        tt_try[l] = te[l]
        _, segs, viol, _ = _oracle_slack_scan(lat, tt_try, deadline, n_l)
        if ((segs <= kk) & ~viol).all():
            tt = tt_try
            n_moves += 1
    if n_moves == 0:                   # ulp guard: keep the dp-exact T*
        return _base_copy()

    totals, _, _, _ = _oracle_slack_scan(lat, tt, np.inf, n_l)
    lo, hi = 0.0, min(deadline, float(totals.max()))
    for _ in range(_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        _, segs, viol, _ = _oracle_slack_scan(lat, tt, mid, n_l)
        if ((segs <= kk) & ~viol).all():
            hi = mid
        else:
            lo = mid
    _, _, _, peak = _oracle_slack_scan(lat, tt, hi, n_l)
    return dict(bottleneck=float(peak.max()), layer_type=tt,
                energy=_energy(tt), n_moves=n_moves, feasible=True)


def _slack_x_rows(lat, tt):
    """Materialise every per-step scan input in ONE op: ``x_all[l]`` is
    exactly the ``x`` the reference scan builds at step ``l`` (the
    latency of layer ``l`` on its assigned type, 0.0 elsewhere).  Shape
    [L, B, D, T] so each step reads a contiguous slice."""
    t_ar = np.arange(lat.shape[1])
    return np.where(np.transpose(tt, (2, 0, 1))[..., None] == t_ar,
                    np.transpose(lat, (2, 0, 1))[:, :, None, :], 0.0)


def _slack_scan_rows(lat, tt, kk, thr, x_all=None, x_max=None):
    """Vectorised greedy-covering scan (numpy batch reference).

    ``lat`` [B, T, L]; ``tt`` [B, D, L]; ``kk`` [B, T]; ``thr`` [B, D].
    Returns (run [B, D, T] final running sums, feas [B, D]).  Element-
    wise arithmetic identical to :func:`_oracle_slack_scan` (types other
    than tt[l] add an exact 0.0; `over` can only fire for them once viol
    is already set, which never changes the feasibility verdict).
    ``x_all`` lets callers reuse :func:`_slack_x_rows` across scans that
    share the same assignment (the bisection re-scans the SAME ``tt``
    dozens of times with different thresholds)."""
    n_b, n_d, n_pad = tt.shape
    n_types = lat.shape[1]
    if x_all is None:
        x_all = _slack_x_rows(lat, tt)
    if x_max is None:
        x_max = x_all.max(axis=0)
    run = np.zeros((n_b, n_d, n_types))
    segs = np.ones((n_b, n_d, n_types), dtype=np.int64)
    # x > th forces `over` at that step (run >= 0), so viol — "a single
    # layer exceeds the threshold" — needs no scan state: it is just
    # max_l(x_l) > th, and the max is threshold-independent (callers
    # bisecting over thresholds pass it in once)
    viol = x_max > thr[:, :, None]
    th = np.broadcast_to(thr[:, :, None], run.shape)
    over = np.empty(run.shape, dtype=bool)
    for l in range(n_pad):
        x = x_all[l]
        np.add(run, x, out=run)
        np.greater(run, th, out=over)
        segs += over
        np.copyto(run, x, where=over)
    feas = ((segs <= kk[:, None, :]) & ~viol).all(axis=-1)
    return run, feas


def _np_slack_kernel(lat, tt0, kk, mv_layer, mv_to, mv_valid, gate, dl,
                     n_lens, k_out):
    """Numpy slack solver: greedy move loop + bisection + extraction.

    Shapes: lat [B, T, L]; tt0 [B, L]; kk [B, T]; mv_layer/mv_to/
    mv_valid [B, M]; gate/dl [B, D]; n_lens [B]; k_out static.  Returns
    (tt [B, D, L], n_moves [B, D], starts [B, D, T, k_out], loads
    [B, D, T, k_out], seg_counts [B, D, T], bottleneck [B, D]).

    Rows are independent, so the batch is split into depth buckets
    (power-of-two layer counts) and each bucket scans only its own
    depth — padding columns past a problem's true layer count are exact
    scan no-ops, so an 11-layer problem need not ride along through a
    126-step loop sized by the deepest problem in the batch.  The move
    loop also shrinks per bucket (shallow problems have few candidate
    moves)."""
    n_b, n_types, n_pad = lat.shape
    n_d = dl.shape[1]
    depth = np.maximum(n_lens, 1)
    if np.unique(depth).size <= 8:     # few distinct depths: exact cut
        buckets = depth
    else:
        buckets = 1 << np.ceil(np.log2(depth)).astype(np.int64)
        buckets = np.minimum(np.maximum(buckets, 8), n_pad)
    if n_b and buckets.min() < n_pad:
        tt = np.broadcast_to(tt0[:, None, :], (n_b, n_d, n_pad)).copy()
        n_moves = np.zeros((n_b, n_d), dtype=np.int64)
        starts = np.broadcast_to(
            n_lens[:, None, None, None],
            (n_b, n_d, n_types, k_out)).copy()
        starts[:, :, :, 0] = 0
        loads = np.zeros((n_b, n_d, n_types, k_out))
        segc = np.ones((n_b, n_d, n_types), dtype=np.int64)
        bott = np.full((n_b, n_d), np.inf)
        for bk in np.unique(buckets):
            idx = np.flatnonzero(buckets == bk)
            mv_v = mv_valid[idx]
            m_hi = int(mv_v.sum(axis=1).max(initial=0))
            out = _np_slack_rows(
                np.ascontiguousarray(lat[idx, :, :bk]), tt0[idx, :bk],
                kk[idx], mv_layer[idx, :m_hi], mv_to[idx, :m_hi],
                mv_v[:, :m_hi], gate[idx], dl[idx], n_lens[idx], k_out)
            tt[idx, :, :bk] = out[0]
            n_moves[idx] = out[1]
            starts[idx] = out[2]
            loads[idx] = out[3]
            segc[idx] = out[4]
            bott[idx] = out[5]
        return tt, n_moves, starts, loads, segc, bott
    return _np_slack_rows(lat, tt0, kk, mv_layer, mv_to, mv_valid, gate,
                          dl, n_lens, k_out)


def _np_slack_rows(lat, tt0, kk, mv_layer, mv_to, mv_valid, gate, dl,
                   n_lens, k_out):
    """One depth bucket of :func:`_np_slack_kernel` (same contract; the
    layer axis is the bucket depth, ``n_lens`` may be shorter)."""
    n_b, n_types, n_pad = lat.shape
    n_d = dl.shape[1]
    n_m = mv_layer.shape[1]
    tt = np.broadcast_to(tt0[:, None, :], (n_b, n_d, n_pad)).copy()
    n_moves = np.zeros((n_b, n_d), dtype=np.int64)
    x_cur = None
    if n_m:
        # The tentative scan per candidate runs on the DESTINATION lane
        # only.  On gated cells (dl > base bottleneck) the current
        # accepted assignment is always scan-feasible at the deadline
        # with no layer exceeding it: the base split's bottleneck
        # certifies it (greedy segment count is monotone in the
        # threshold), and every accepted move preserves it by
        # construction.  Greedy segment count is also monotone in the
        # element values, so zeroing the moved layer can never break
        # its OLD lane — both monotonicities hold exactly in float
        # arithmetic (sequential nonnegative adds are order-preserving),
        # so the full-assignment verdict the oracle computes reduces to
        # [new-lane scan feasible] AND [lat_new <= dl].  Candidates are
        # one per layer (its energy-argmin type), so a candidate layer
        # still sits on its base type when tried.  The new-lane
        # sequence is where-built per candidate in layer-major layout
        # from the cell-major tt/lat (sources keep the layer axis
        # contiguous, so the build streams), while x_cur [L, B, D, T]
        # is maintained by tiny accept-scatters purely for the bisect
        # stage below.  Every value written is a lat[] element or an
        # exact 0.0, so downstream scan arithmetic is bit-identical to
        # rebuilding x from the assignment.
        x_cur = _slack_x_rows(lat, tt)
        d_ar = np.arange(n_d)
        # the move axis is padded to the WORST problem's candidate
        # count — rows without move j (or without slack at all) are
        # excluded, keeping tt/n_moves unchanged, exactly as the dense
        # formulation would leave them
        live = gate.any(axis=1)
        for j in range(n_m):
            sel = np.flatnonzero(live & mv_valid[:, j])
            s = sel.size
            if s == 0:
                continue
            r_ix = sel[:, None]
            lyr = mv_layer[sel, j]
            l_ix = lyr[:, None]
            s_ar = np.arange(s)
            nt = mv_to[sel, j]                                # [s]
            nt_b = nt[:, None]                                # [s, 1]
            ot1 = tt0[sel, lyr]                               # [s]
            ot = ot1[:, None]
            lat_new = lat[sel, nt, lyr][:, None]              # [s, 1]
            x_old = lat[sel, ot1, lyr][:, None]               # [s, 1]
            dl_s = dl[sel]                                    # [s, D]
            cond = tt[sel] == nt[:, None, None]               # [s, D, L]
            xs = np.where(cond.transpose(2, 0, 1),
                          lat[sel, nt].T[:, :, None], 0.0)    # [L, s, D]
            xs[lyr, s_ar, :] = lat_new
            kk_nt = kk[sel, nt]                               # [s]
            run = np.zeros((s, n_d))
            segs = np.ones((s, n_d), dtype=np.int64)
            over = np.empty(run.shape, dtype=bool)
            for l in range(n_pad):
                x = xs[l]
                np.add(run, x, out=run)
                np.greater(run, dl_s, out=over)
                segs += over
                np.copyto(run, x, where=over)
            acc = ((segs <= kk_nt[:, None]) & (lat_new <= dl_s)
                   & gate[sel])                               # [s, D]
            x_cur[l_ix, r_ix, d_ar, ot] = np.where(acc, 0.0, x_old)
            x_cur[l_ix, r_ix, d_ar, nt_b] = np.where(
                acc, np.broadcast_to(lat_new, acc.shape), 0.0)
            tt[r_ix, d_ar, l_ix] = np.where(acc, nt_b, ot)
            n_moves[sel] += acc

    # rows with zero accepted moves carry the base schedule through
    # combine(), so bisection + extraction run on the moved rows only;
    # untouched rows get inert placeholders (overridden downstream)
    starts = np.broadcast_to(
        n_lens[:, None, None, None], (n_b, n_d, n_types, k_out)).copy()
    starts[:, :, :, 0] = 0
    loads = np.zeros((n_b, n_d, n_types, k_out))
    segc = np.ones((n_b, n_d, n_types), dtype=np.int64)
    bott = np.full((n_b, n_d), np.inf)
    rsel = np.flatnonzero((n_moves > 0).any(axis=1))
    if rsel.size == 0:
        return tt, n_moves, starts, loads, segc, bott
    lat_r, tt_r, kk_r, dl_r = lat[rsel], tt[rsel], kk[rsel], dl[rsel]
    # tt fixed from here on: one x tensor is shared by all scans (the
    # move loop left x_cur holding exactly _slack_x_rows(lat, tt))
    x_all = (x_cur[:, rsel] if x_cur is not None
             else _slack_x_rows(lat_r, tt_r))
    x_max = x_all.max(axis=0)
    totals, _ = _slack_scan_rows(lat_r, tt_r, kk_r,
                                 np.full_like(dl_r, np.inf), x_all, x_max)
    hi = np.minimum(dl_r, totals.max(axis=-1))
    lo = np.zeros_like(hi)
    for _ in range(_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        _, feas = _slack_scan_rows(lat_r, tt_r, kk_r, mid, x_all, x_max)
        lo = np.where(feas, lo, mid)
        hi = np.where(feas, mid, hi)

    n_r = rsel.size
    k_ar = np.arange(k_out)
    run = np.zeros((n_r, n_d, n_types))
    seg = np.zeros((n_r, n_d, n_types), dtype=np.int64)
    starts_r = np.broadcast_to(
        n_lens[rsel][:, None, None, None],
        (n_r, n_d, n_types, k_out)).copy()
    starts_r[:, :, :, 0] = 0
    loads_r = np.zeros((n_r, n_d, n_types, k_out))
    th = hi[:, :, None]
    for l in range(n_pad):
        x = x_all[l]
        nxt = run + x
        over = nxt > th
        starts_r = np.where(
            over[..., None] & (k_ar == (seg + 1)[..., None]), l, starts_r)
        loads_r = np.where(over[..., None] & (k_ar == seg[..., None]),
                           run[..., None], loads_r)
        seg = seg + over
        run = np.where(over, x, nxt)
    loads_r = np.where(k_ar == seg[..., None], run[..., None], loads_r)
    starts[rsel] = starts_r
    loads[rsel] = loads_r
    segc[rsel] = seg + 1
    bott[rsel] = loads_r.max(axis=(-1, -2))
    return tt, n_moves, starts, loads, segc, bott


_jitted_slack = None


def _jax_slack_solver():
    """Jitted twin of :func:`_np_slack_kernel`: the greedy move loop,
    bisection and segment extraction run as ONE XLA program over every
    (problem x deadline) cell.  Same elementwise arithmetic (fori_loop
    bodies mirror the numpy loops statement for statement), so results
    are bit-identical to the numpy kernel and the scalar oracle."""
    global _jitted_slack
    if _jitted_slack is None:
        import jax
        import jax.numpy as jnp

        def scan_rows(lat, tt, kk, thr):
            n_b, n_d, n_pad = tt.shape
            n_types = lat.shape[1]
            t_ar = jnp.arange(n_types)
            th = thr[:, :, None]

            def body(l, st):
                run, segs, viol = st
                x = jnp.where(tt[:, :, l][:, :, None] == t_ar,
                              lat[:, None, :, l], 0.0)
                nxt = run + x
                over = nxt > th
                viol = viol | (over & (x > th))
                segs = segs + over
                run = jnp.where(over, x, nxt)
                return run, segs, viol

            run, segs, viol = jax.lax.fori_loop(
                0, n_pad, body,
                (jnp.zeros((n_b, n_d, n_types)),
                 jnp.ones((n_b, n_d, n_types), jnp.int64),
                 jnp.zeros((n_b, n_d, n_types), bool)))
            feas = ((segs <= kk[:, None, :]) & ~viol).all(axis=-1)
            return run, feas

        def solve(lat, tt0, kk, mv_layer, mv_to, mv_valid, gate, dl,
                  n_lens, k_out):
            n_b, n_types, n_pad = lat.shape
            n_d = dl.shape[1]
            n_m = mv_layer.shape[1]
            l_ar = jnp.arange(n_pad)
            tt = jnp.broadcast_to(tt0[:, None, :], (n_b, n_d, n_pad))
            n_moves = jnp.zeros((n_b, n_d), jnp.int64)

            def mv_body(j, st):
                tt, n_moves = st
                onehot = l_ar[None, :] == mv_layer[:, j][:, None]
                tt_new = jnp.where(onehot[:, None, :],
                                   mv_to[:, j][:, None, None], tt)
                _, feas = scan_rows(lat, tt_new, kk, dl)
                acc = feas & gate & mv_valid[:, j][:, None]
                tt = jnp.where(acc[:, :, None], tt_new, tt)
                return tt, n_moves + acc

            tt, n_moves = jax.lax.fori_loop(0, n_m, mv_body,
                                            (tt, n_moves))

            totals, _ = scan_rows(lat, tt, kk,
                                  jnp.full_like(dl, jnp.inf))
            hi = jnp.minimum(dl, totals.max(axis=-1))
            lo = jnp.zeros_like(hi)

            def bs_body(_, st):
                lo, hi = st
                mid = 0.5 * (lo + hi)
                _, feas = scan_rows(lat, tt, kk, mid)
                return jnp.where(feas, lo, mid), jnp.where(feas, mid, hi)

            lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, bs_body,
                                       (lo, hi))

            t_ar = jnp.arange(n_types)
            k_ar = jnp.arange(k_out)
            th = hi[:, :, None]
            starts0 = jnp.where(
                k_ar == 0, 0,
                jnp.broadcast_to(n_lens[:, None, None, None],
                                 (n_b, n_d, n_types, k_out)))

            def ex_body(l, st):
                run, seg, starts, loads = st
                x = jnp.where(tt[:, :, l][:, :, None] == t_ar,
                              lat[:, None, :, l], 0.0)
                nxt = run + x
                over = nxt > th
                starts = jnp.where(
                    over[..., None] & (k_ar == (seg + 1)[..., None]),
                    l, starts)
                loads = jnp.where(
                    over[..., None] & (k_ar == seg[..., None]),
                    run[..., None], loads)
                seg = seg + over
                run = jnp.where(over, x, nxt)
                return run, seg, starts, loads

            run, seg, starts, loads = jax.lax.fori_loop(
                0, n_pad, ex_body,
                (jnp.zeros((n_b, n_d, n_types)),
                 jnp.zeros((n_b, n_d, n_types), jnp.int64),
                 starts0, jnp.zeros((n_b, n_d, n_types, k_out))))
            loads = jnp.where(k_ar == seg[..., None],
                              run[..., None], loads)
            bott = loads.max(axis=(-1, -2))
            return tt, n_moves, starts, loads, seg + 1, bott

        _jitted_slack = obs.Program("slack_solve", solve,
                                    static_argnums=(9,))
    return _jitted_slack


@dataclasses.dataclass(frozen=True)
class BatchSlackResult:
    """Array-level output of :func:`batch_slack_schedule`.

    Every field with a leading ``[B, D]`` is indexed (problem,
    deadline); ``base`` is the latency-only :class:`BatchHeteroResult`
    the slack pass started from.  Cells without slack (deadline <= the
    latency-optimal bottleneck, or no accepted move) carry the base
    schedule unchanged, so the slack result weakly dominates the base
    everywhere by construction."""

    base: BatchHeteroResult
    deadlines: np.ndarray         # [B, D] absolute deadlines
    layer_type: np.ndarray        # [B, D, L_pad]
    starts: np.ndarray            # [B, D, T, k_out] full-axis starts
    seg_counts: np.ndarray        # [B, D, T]
    loads: np.ndarray             # [B, D, T, k_out]
    bottleneck: np.ndarray        # [B, D] (<= deadline wherever slack)
    total: np.ndarray             # [B, D] sum of assigned layer latency
    energy: np.ndarray            # [B, D] sum of assigned layer energy
    n_moves: np.ndarray           # [B, D] accepted energy moves
    feasible: np.ndarray          # [B, D] bottleneck <= deadline

    def __len__(self) -> int:
        return int(self.bottleneck.shape[0])

    @property
    def n_deadlines(self) -> int:
        return int(self.bottleneck.shape[1])

    def schedule(self, i: int, d: int = 0) -> HeteroSchedule:
        if not self.feasible[i, d]:
            lab = (self.base.labels[i] if self.base.labels is not None
                   else f"problem {i}")
            raise ValueError(
                f"{lab}: infeasible at deadline {self.deadlines[i, d]} "
                f"(latency-optimal bottleneck "
                f"{float(self.base.bottleneck[i])}) — no schedule meets "
                "the deadline")
        n_t = self.base.counts.shape[1]
        n_l = int(self.base.n_layers[i])
        tt = self.layer_type[i, d, :n_l]
        counts = self.base.counts[i]
        core_off = np.concatenate([[0], np.cumsum(counts)])
        types = tuple(int(t) for t in np.repeat(np.arange(n_t), counts))
        loads = np.zeros(int(core_off[-1]))
        layer_core = np.zeros(n_l, dtype=np.intp)
        for t in range(n_t):
            if counts[t] == 0:
                continue
            kk = int(self.seg_counts[i, d, t])
            st = self.starts[i, d, t, :kk]
            ends = np.concatenate([st[1:], [n_l]])
            lt = np.flatnonzero(tt == t)
            if lt.size:
                layer_core[lt] = core_off[t] + np.searchsorted(
                    ends, lt, side="right")
            loads[core_off[t]:core_off[t] + kk] = self.loads[i, d, t, :kk]
        bott = float(self.bottleneck[i, d])
        total = float(self.total[i, d])
        return HeteroSchedule(
            types=types, layer_type=tuple(int(t) for t in tt),
            layer_core=tuple(int(c) for c in layer_core),
            loads=tuple(float(x) for x in loads),
            bottleneck=bott,
            speedup=total / bott if bott > 0 else float("inf"),
            n_layers=n_l)


def batch_slack_schedule(latencies, energies, counts, deadlines,
                         n_layers=None,
                         use_jax: bool | None = None,
                         *,
                         strict: bool = True,
                         labels=None,
                         base: BatchHeteroResult | None = None,
                         ) -> BatchSlackResult:
    """Solve every energy-aware slack schedule in ONE call.

    ``latencies``/``energies``: per-problem ``[n_types, n_layers]``
    matrices — a sequence of such, or dense ``[B, T, L]`` (or
    ``[B, S, T, L]`` with a fault-scenario axis, flattened
    scenario-minor exactly like :func:`batch_schedule_hetero`).
    ``deadlines``: absolute pipeline-latency budgets — a scalar, a
    ``[D]`` vector shared by every problem, or ``[B, D]`` per-problem
    rows.  For each (problem, deadline) cell the latency-argmin
    schedule is computed first (``base``, reusable across calls), then
    layers are greedily moved to lower-energy types while the greedy-
    covering scan keeps the pipeline within the deadline — all cells in
    one jitted dispatch.  Bit-exact against
    :func:`slack_schedule_oracle` per cell.  ``strict``/``labels``
    follow :func:`batch_schedule_hetero` (used only when ``base`` is
    not supplied)."""
    lat_in, en_in = latencies, energies
    if isinstance(lat_in, np.ndarray) and lat_in.ndim == 4:
        en_in = np.asarray(en_in, dtype=np.float64)
        if en_in.shape != lat_in.shape:
            raise ValueError(
                f"energies shape {en_in.shape} != latencies shape "
                f"{lat_in.shape}")
        b0, n_s = lat_in.shape[:2]
        lat_in = lat_in.reshape(b0 * n_s, *lat_in.shape[2:])
        en_in = en_in.reshape(b0 * n_s, *en_in.shape[2:])
        cnts_in = np.asarray(counts)
        if cnts_in.ndim == 3:
            counts = cnts_in.reshape(b0 * n_s, cnts_in.shape[2])
        elif cnts_in.ndim == 2:
            counts = np.repeat(cnts_in, n_s, axis=0)
        if n_layers is not None:
            nl = np.asarray(n_layers, dtype=np.int64)
            n_layers = (np.repeat(nl, n_s) if nl.ndim == 1
                        else nl.reshape(b0 * n_s))
    dense = isinstance(lat_in, np.ndarray) and lat_in.ndim == 3
    if dense:
        n_b, in_types, n_max = lat_in.shape
        n_lens = (np.full(n_b, n_max, dtype=np.int64) if n_layers is None
                  else np.asarray(n_layers, dtype=np.int64))
        prob_types = np.full(n_b, in_types, np.int64)
    else:
        lats = [np.asarray(l, dtype=np.float64) for l in lat_in]
        ens = [np.asarray(e, dtype=np.float64) for e in en_in]
        if len(ens) != len(lats):
            raise ValueError(
                f"{len(ens)} energy matrices for {len(lats)} problems")
        for l, e in zip(lats, ens):
            if e.shape != l.shape:
                raise ValueError(
                    f"energies shape {e.shape} != latencies {l.shape}")
        n_b = len(lats)
        in_types = max((l.shape[0] for l in lats), default=0)
        n_lens = np.array([l.shape[1] for l in lats], dtype=np.int64)
        n_max = int(n_lens.max()) if n_b else 0
        prob_types = np.asarray([l.shape[0] for l in lats],
                                dtype=np.int64)
    cnts = np.asarray(counts)
    if cnts.ndim == 1:
        cnts = np.broadcast_to(cnts, (n_b, cnts.shape[0]))
    cnts = cnts.astype(np.int64)

    dl = np.asarray(deadlines, dtype=np.float64)
    if dl.ndim == 0:
        dl = dl.reshape(1)
    if dl.ndim == 1:
        dl = np.broadcast_to(dl, (max(n_b, 1), dl.shape[0]))
    if dl.ndim != 2 or (n_b and dl.shape[0] != n_b):
        raise ValueError(
            f"deadlines shape {np.asarray(deadlines).shape} is not "
            f"scalar, [D], or [B={n_b}, D]")
    n_d = dl.shape[1]

    if n_b == 0:
        empty_base = batch_schedule_hetero(
            np.zeros((0, 0, 0)), np.zeros((0, 0), np.int64))
        z = np.zeros((0, n_d))
        return BatchSlackResult(
            base=empty_base, deadlines=np.zeros((0, n_d)),
            layer_type=np.zeros((0, n_d, 0), np.int64),
            starts=np.zeros((0, n_d, 0, _K_MAX), np.int64),
            seg_counts=np.zeros((0, n_d, 0), np.int64),
            loads=np.zeros((0, n_d, 0, _K_MAX)),
            bottleneck=z.copy(), total=z.copy(), energy=z.copy(),
            n_moves=np.zeros((0, n_d), np.int64),
            feasible=np.zeros((0, n_d), bool))

    if cnts.shape[0] != n_b:
        raise ValueError(f"counts rows {cnts.shape[0]} != problems {n_b}")
    # counts on type slots past a problem's latency rows would hand
    # layers to a phantom zero-latency/zero-energy type once densified
    ghost = np.arange(cnts.shape[1])[None, :] >= prob_types[:, None]
    if (cnts * ghost).any():
        raise ValueError("counts for more types than latency rows")

    n_types = max(in_types, cnts.shape[1])
    counts2 = np.zeros((n_b, n_types), dtype=np.int64)
    counts2[:, :cnts.shape[1]] = cnts
    lat_d = np.zeros((n_b, n_types, n_max))
    en_d = np.zeros((n_b, n_types, n_max))
    if dense:
        lat_d[:, :in_types, :] = lat_in
        en_src = np.asarray(en_in, dtype=np.float64)
        if en_src.shape != np.asarray(lat_in).shape:
            raise ValueError(
                f"energies shape {en_src.shape} != latencies shape "
                f"{np.asarray(lat_in).shape}")
        en_d[:, :in_types, :] = en_src
    else:
        for i, (l, e) in enumerate(zip(lats, ens)):
            lat_d[i, :l.shape[0], :l.shape[1]] = l
            en_d[i, :e.shape[0], :e.shape[1]] = e
    # the scan and the sequential energy/total sums rely on EXACT zeros
    # past each problem's true layer count — scrub dense garbage columns
    valid_cols = np.arange(n_max)[None, :] < n_lens[:, None]
    lat_d = np.where(valid_cols[:, None, :], lat_d, 0.0)
    en_d = np.where(valid_cols[:, None, :], en_d, 0.0)

    if base is None:
        base = batch_schedule_hetero(lat_d, counts2, n_lens, use_jax,
                                     strict=strict, labels=labels)
    elif len(base) != n_b:
        raise ValueError(
            f"base has {len(base)} problems, inputs have {n_b}")

    use_jax = (jax_available() if use_jax is None else use_jax)

    # host precompute: energy argmin targets + move order per problem
    tt0 = base.layer_type[:, :n_max].astype(np.int64)
    avail = counts2 > 0
    te = np.argmin(np.where(avail[:, :, None], en_d, np.inf), axis=1)
    l_idx = np.arange(n_max)
    valid_l = l_idx[None, :] < n_lens[:, None]
    e_cur = np.take_along_axis(en_d, tt0[:, None, :], axis=1)[:, 0, :]
    e_new = np.take_along_axis(en_d, te[:, None, :], axis=1)[:, 0, :]
    d_e = e_cur - e_new
    cand = (te != tt0) & (d_e > 0) & valid_l
    key = np.where(cand, -d_e, np.inf)
    order = np.lexsort(
        (np.broadcast_to(l_idx, key.shape), key), axis=-1)
    n_mv = cand.sum(axis=1)
    n_m = int(n_mv.max()) if n_b else 0
    mv_layer = order[:, :n_m]
    mv_valid = np.arange(n_m)[None, :] < n_mv[:, None]
    mv_to = np.take_along_axis(te, mv_layer, axis=1) if n_m else \
        np.zeros((n_b, 0), np.int64)
    with np.errstate(invalid="ignore"):
        gate = dl > base.bottleneck[:, None]       # inf-bottleneck safe
    kk = np.maximum(counts2, 1)
    k_out = max(base.starts.shape[2],
                max(1, min(int(counts2.max(initial=1)), n_max)))

    if use_jax:
        b_pad = _bucketed(n_b, _ROW_BUCKET)
        l_pad = _bucketed(n_max, _N_BUCKET)
        m_pad = _bucketed(max(n_m, 1), 8)   # fori body traced even at 0
        lat_p = np.zeros((b_pad, n_types, l_pad))
        lat_p[:n_b, :, :n_max] = lat_d
        tt_p = np.zeros((b_pad, l_pad), np.int64)
        tt_p[:n_b, :n_max] = tt0
        kk_p = np.ones((b_pad, n_types), np.int64)
        kk_p[:n_b] = kk
        mvl_p = np.zeros((b_pad, m_pad), np.int64)
        mvl_p[:n_b, :n_m] = mv_layer
        mvt_p = np.zeros((b_pad, m_pad), np.int64)
        mvt_p[:n_b, :n_m] = mv_to
        mvv_p = np.zeros((b_pad, m_pad), bool)
        mvv_p[:n_b, :n_m] = mv_valid
        gate_p = np.zeros((b_pad, n_d), bool)
        gate_p[:n_b] = gate
        dl_p = np.ones((b_pad, n_d))
        dl_p[:n_b] = dl
        nl_p = np.ones(b_pad, np.int64)
        nl_p[:n_b] = n_lens
        with x64():
            out = _jax_slack_solver()(
                lat_p, tt_p, kk_p, mvl_p, mvt_p, mvv_p, gate_p, dl_p,
                nl_p, k_out)
        tt_s, n_moves, starts_s, loads_s, segc_s, bott_s = (
            np.asarray(o)[:n_b] for o in out)
        tt_s, starts_s, loads_s = (tt_s[:, :, :n_max],
                                   starts_s, loads_s)
    else:
        tt_s, n_moves, starts_s, loads_s, segc_s, bott_s = \
            _np_slack_kernel(lat_d, tt0, kk, mv_layer, mv_to, mv_valid,
                             gate, dl, n_lens, k_out)

    # combine: cells without slack (or with zero accepted moves) carry
    # the base schedule unchanged — weak dominance by construction
    use = gate & (n_moves > 0)
    layer_type = np.where(use[:, :, None], tt_s, tt0[:, None, :])
    k_b = base.starts.shape[2]
    base_starts = base.starts
    base_loads = base.loads
    if k_out > k_b:
        base_starts = np.concatenate(
            [base_starts, np.broadcast_to(
                n_lens[:, None, None],
                (n_b, base_starts.shape[1], k_out - k_b))], axis=2)
        base_loads = np.concatenate(
            [base_loads, np.zeros(
                (n_b, base_loads.shape[1], k_out - k_b))], axis=2)
    starts = np.where(use[:, :, None, None], starts_s,
                      base_starts[:, None])
    loads = np.where(use[:, :, None, None], loads_s,
                     base_loads[:, None])
    seg_counts = np.where(use[:, :, None], segc_s,
                          base.seg_counts[:, None])
    bottleneck = np.where(use, bott_s, base.bottleneck[:, None])
    n_moves = np.where(use, n_moves, 0)
    with np.errstate(invalid="ignore"):
        feasible = ((base.feasible[:, None]
                     if base.feasible is not None else True)
                    & (bottleneck <= dl))

    # totals + energies of the COMBINED assignment: sequential per-layer
    # loops (padded cells gather type 0 whose padding is exact 0.0)
    l_sel = np.take_along_axis(
        np.broadcast_to(lat_d[:, None], (n_b, n_d) + lat_d.shape[1:]),
        layer_type[:, :, None, :], axis=2)[:, :, 0, :]
    e_sel = np.take_along_axis(
        np.broadcast_to(en_d[:, None], (n_b, n_d) + en_d.shape[1:]),
        layer_type[:, :, None, :], axis=2)[:, :, 0, :]
    total = np.zeros((n_b, n_d))
    energy = np.zeros((n_b, n_d))
    for l in range(n_max):
        total = total + l_sel[:, :, l]
        energy = energy + e_sel[:, :, l]
    # base cells keep base.total bit-for-bit (its per-type prefix-sum
    # order differs from the sequential re-gather by ulps)
    total = np.where(use, total, base.total[:, None])

    return BatchSlackResult(
        base=base, deadlines=np.ascontiguousarray(dl),
        layer_type=layer_type, starts=starts, seg_counts=seg_counts,
        loads=loads, bottleneck=bottleneck, total=total, energy=energy,
        n_moves=n_moves, feasible=feasible)

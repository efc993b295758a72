"""Shared differential-test harness: ONE seeded instance generator and
the brute-force oracles that were previously copy-pasted across
`test_partition.py`, `test_schedule_hetero.py` and
`test_pareto_codesign.py` (and now also back the energy-aware slack
suite in `test_slack_schedule.py`).

Everything here is deliberately SLOW and OBVIOUS — python loops,
exhaustive enumeration, no vectorisation — so the production solvers
have an independent reference to be bit-exact (or approx-equal, where
the test says so) against.
"""

import numpy as np

from repro.core import partition


# ---------------------------------------------------------------------------
# Seeded instance generators (the non-hypothesis twins always run)
# ---------------------------------------------------------------------------


def seeded_hetero_instances(seed, n, *, max_types=3, max_layers=8,
                            max_count=3, lat_range=(0.01, 100.0)):
    """``n`` random (lat [T, L], counts [T]) heterogeneous-schedule
    instances from one seed; at least one core is always available."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = int(rng.integers(1, max_types + 1))
        L = int(rng.integers(1, max_layers + 1))
        lat = rng.uniform(*lat_range, size=(t, L))
        counts = rng.integers(0, max_count + 1, size=t)
        if counts.sum() == 0:
            counts[int(rng.integers(t))] = 1
        out.append((lat, counts))
    return out


def seeded_slack_instances(seed, n, *, max_types=3, max_layers=10,
                           max_count=3, tie_values=(0.5, 1.0, 1.5, 2.0,
                                                    3.0)):
    """``n`` random (lat, energy, counts) slack-schedule instances.
    Values are drawn from a SMALL set so exact ties (the hardest case
    for deterministic tie-breaking) occur constantly."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = int(rng.integers(1, max_types + 1))
        L = int(rng.integers(1, max_layers + 1))
        lat = rng.choice(tie_values, size=(t, L))
        en = rng.choice(tie_values, size=(t, L))
        counts = rng.integers(0, max_count + 1, size=t)
        if counts.sum() == 0:
            counts[int(rng.integers(t))] = 1
        out.append((lat, en, counts))
    return out


# ---------------------------------------------------------------------------
# Oracle 1: the per-(network, k) dp partition loop
# ---------------------------------------------------------------------------


def dp_partition_loop(lat_groups, ks):
    """Python-loop twin of `partition.batch_partition`: one
    `dp_partition` call per (network, k) pair.  Returns
    ``{(i, k): Partition}``."""
    return {(i, k): partition.dp_partition(lat, k)
            for i, lat in enumerate(lat_groups) for k in ks}


# ---------------------------------------------------------------------------
# Oracle 2: brute-force heterogeneous schedule (argmin + enumeration)
# ---------------------------------------------------------------------------


def brute_force_hetero(lat, counts):
    """Brute-force oracle within the solver's semantics: per-layer argmin
    type assignment, then EVERY contiguous segmentation of each type's
    subsequence enumerated (`brute_force_partition`), bottleneck = max
    over types.  <=8 layers / <=3 types keeps this trivial."""
    lat = np.asarray(lat, dtype=np.float64)
    counts = np.asarray(counts, dtype=np.int64)
    cost = np.where((counts > 0)[:, None], lat, np.inf)
    tt = np.argmin(cost, axis=0)
    bottleneck = 0.0
    for t in range(lat.shape[0]):
        sub = lat[t, tt == t]
        if counts[t] <= 0 or sub.size == 0:
            continue
        p = partition.brute_force_partition(sub, int(counts[t]))
        bottleneck = max(bottleneck, p.pipeline_latency)
    return bottleneck


def assert_schedule_valid(s, lat, counts):
    """A HeteroSchedule is internally consistent: per-type core budgets,
    load recompute, bottleneck = max load, per-core contiguity."""
    import pytest
    lat = np.asarray(lat, dtype=np.float64)
    counts = np.asarray(counts, dtype=np.int64)
    assert s.n_cores == counts.sum()
    assert len(s.layer_type) == len(s.layer_core) == lat.shape[1]
    used = {}
    for ty, co in zip(s.layer_type, s.layer_core):
        assert counts[ty] > 0
        assert s.types[co] == ty
        used.setdefault(ty, set()).add(co)
    for ty, cores in used.items():
        assert len(cores) <= counts[ty]
    loads = np.zeros(len(s.types))
    for l in range(lat.shape[1]):
        loads[s.layer_core[l]] += lat[s.layer_type[l], l]
    np.testing.assert_allclose(loads, s.loads, rtol=1e-12, atol=1e-12)
    assert s.bottleneck == pytest.approx(max(s.loads))
    for ty, cores in used.items():
        seq = [s.layer_core[l] for l in range(lat.shape[1])
               if s.layer_type[l] == ty]
        assert seq == sorted(seq)


# ---------------------------------------------------------------------------
# Oracle 3: per-deadline pareto scoring loop + dominance filter
# ---------------------------------------------------------------------------


def brute_frontier(value, latency):
    """O(C^2) dominance filter: point c survives unless some other point
    is <= in both coordinates and < in at least one."""
    C = value.shape[0]
    keep = np.ones(C, dtype=bool)
    for c in range(C):
        for o in range(C):
            if (value[o] <= value[c] and latency[o] <= latency[c]
                    and (value[o] < value[c] or latency[o] < latency[c])):
                keep[c] = False
                break
    return keep


def loop_pareto_scores(value, latency, deadlines):
    """Per-deadline python loop twin of `partition.batch_pareto_scores`:
    returns (best [D], best_net [N, D])."""
    C, N = value.shape
    D = deadlines.shape[1]
    best = np.full(D, -1, dtype=np.int64)
    best_net = np.full((N, D), -1, dtype=np.int64)
    for d in range(D):
        best_s = np.inf
        net_s = np.full(N, np.inf)
        for c in range(C):
            feas = latency[c] <= deadlines[:, d]
            if feas.all() and value[c].mean() < best_s:
                best_s, best[d] = value[c].mean(), c
            for j in np.flatnonzero(feas):
                if value[c, j] < net_s[j]:
                    net_s[j], best_net[j, d] = value[c, j], c
    return best, best_net


# ---------------------------------------------------------------------------
# The engine's heavy stage without the two-level dedup
# ---------------------------------------------------------------------------


def count_term_sums_ref(cfg_u, lay, segments):
    """Dedup-free oracle of ``energymodel._term_sums_body``: the RS mapping
    runs directly on the count-unique rows (no mapping-level dedup and
    gather) and the layer axis is collapsed with static per-network
    segment slices.  cfg_u: dict of [n_u, 1] count columns; lay: dict of
    [1, L] layer columns; segments: ((start, stop), ...) per network.
    Returns the stacked [14, n_u, n_net] partial sums (config-independent
    terms broadcast along the unique axis)."""
    import jax.numpy as jnp

    from repro.core import energymodel
    terms = energymodel._count_terms(jnp, cfg_u, lay)
    n_u = cfg_u[next(iter(cfg_u))].shape[0]
    return jnp.stack([
        jnp.broadcast_to(
            jnp.stack([t[..., a:b].sum(-1) for a, b in segments], axis=-1),
            (n_u, len(segments)))
        for t in terms])


def count_term_layers_ref(cfg_u, lay):
    """Per-layer twin of :func:`count_term_sums_ref` (of
    ``energymodel._term_layers_body``): the raw [14, n_u, L] term stack,
    config-independent terms broadcast along the unique axis."""
    import jax.numpy as jnp

    from repro.core import energymodel
    terms = energymodel._count_terms(jnp, cfg_u, lay)
    n_u = cfg_u[next(iter(cfg_u))].shape[0]
    l_tot = lay[next(iter(lay))].shape[-1]
    return jnp.stack([jnp.broadcast_to(t, (n_u, l_tot)) for t in terms])

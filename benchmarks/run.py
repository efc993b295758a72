"""Benchmark harness: one function per paper table/figure + the TPU
roofline/autoshard analyses.  Prints ``name,us_per_call,derived`` CSV rows
and writes the full tables to experiments/tables/*.csv.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import resource
import sys
import time
from pathlib import Path

# One XLA host device per CPU core (capped), BEFORE anything imports jax —
# the backend locks the device count on first init (same pattern as
# repro/launch/dryrun.py).  This gives the sharded engine paths a device
# axis to spread the config dimension over.
_N_DEV = max(1, min(os.cpu_count() or 1, 8))
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_N_DEV}")

import numpy as np

from repro.core import (accelerator, dse, energymodel, hetero, partition,
                        rs_mapping, topology)
from repro.core import autoshard
from repro.core.tpu_costmodel import ShardingPolicy, step_time
from repro.launch.compile_cache import enable_compile_cache


OUT = Path("experiments/tables")
BENCH_DSE_JSON = Path("BENCH_dse.json")
BENCH_DSE_QUICK_JSON = Path("BENCH_dse.quick.json")

#: Chunk size of the streaming/mega paths: multiples of the mega grid's
#: noc-innermost axis keep per-chunk dedup aligned with the global dedup.
MEGA_CHUNK = 9800

PAPER_NETS = list(topology.NETWORKS)
QUICK_NETS = ["AlexNet", "VGG16", "GoogleNet", "ResNet50", "MobileNetV2",
              "Xception"]


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def _write(name, header, rows):
    OUT.mkdir(parents=True, exist_ok=True)
    with open(OUT / f"{name}.csv", "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)


def _sweeps(nets):
    # one batched jit call: every network × the whole grid
    return dse.sweep_networks({n: topology.get_network(n) for n in nets})


# ---------------------------------------------------------------------------
# DSE engine scaling: numpy-per-config (the seed implementation) vs the
# batched jit engine, at 150 / 1,350 / 5,400 grid points.  Results land in
# BENCH_dse.json (machine-readable) so future PRs can track the trajectory.
# ---------------------------------------------------------------------------

def _seed_numpy_sweep(layers, configs):
    """The seed's design-space loop, verbatim: one AcceleratorConfig object
    per grid point, per-config numpy struct rows, full [n_cfg, n_layer]
    energy math summed at the end.  Kept here as the reference baseline the
    batched engine is measured (and parity-checked) against."""
    compute = [l for l in layers if l.kind != "input"]
    lay = rs_mapping.layer_struct(np, compute)
    lay = {k: np.asarray(v, dtype=np.float64)[None, :]
           for k, v in lay.items()}
    cfg_rows = [energymodel._cfg_struct(np, c) for c in configs]
    cfgs = {k: np.stack([np.float64(c[k]) for c in cfg_rows])[:, None]
            for k in cfg_rows[0]}
    ct = energymodel._counts(np, cfgs, lay)
    el = energymodel._energy_latency(np, cfgs, lay, ct)
    return el["energy"].sum(-1), el["latency"].sum(-1)


def _dse_scale_levels(quick: bool):
    paper = dict(arrays=accelerator.ARRAY_SIZES,
                 gb_psum_kb=accelerator.GB_SIZES_KB,
                 gb_ifmap_kb=accelerator.GB_SIZES_KB)
    levels = [("paper_150", accelerator.ConfigGrid.product(**paper))]
    if not quick:        # quick: one smoke level, no extra cold compiles
        levels += [
            ("extended_1350", accelerator.ConfigGrid.product(
                **paper, rf_psum_words=accelerator.RF_PSUM_SIZES,
                noc_words_per_cycle=accelerator.NOC_WIDTHS)),
            ("extended_5400", accelerator.extended_grid()),
        ]
    return levels


def _warm_min(fn, reps: int = 3) -> float:
    """Minimum wall time over ``reps`` runs, after ONE untimed pre-warm
    call: the pre-warm absorbs trace/dispatch-cache population, so the
    timed passes measure the steady state (the seed mixed the first
    dispatch-cache miss into its warm number)."""
    fn()
    return min(_timed(fn)[1] / 1e6 for _ in range(reps))


def _rss_peak_mb() -> float:
    """Process-lifetime RSS high-water mark (includes earlier levels —
    a conservative upper bound on the chunked path's footprint)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _rss_now_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except OSError:                                    # pragma: no cover
        pass
    return float("nan")                                # pragma: no cover


def bench_dse_scale(quick: bool = False) -> list:
    nets = {n: topology.get_network(n) for n in topology.NETWORKS}
    use_jax = dse._use_jax_default()
    results = []
    for name, grid in _dse_scale_levels(quick):
        # seed path: per-network numpy loop over per-point config objects.
        # (Objects built once per level — the seed rebuilt them per network,
        # so this baseline is conservative.)
        configs = [grid.config_at(i) for i in range(grid.n)]
        t0 = time.perf_counter()
        e_np = np.empty((grid.n, len(nets)))
        t_np = np.empty((grid.n, len(nets)))
        for j, layers in enumerate(nets.values()):
            e_np[:, j], t_np[:, j] = _seed_numpy_sweep(layers, configs)
        numpy_s = time.perf_counter() - t0

        # batched jit engine: "cold" is the first call at this level
        # (jit_cold_cache_hit records whether an earlier same-shape call
        # had already compiled it); the warm passes run behind an untimed
        # pre-warm, so jit_precached is True by construction and
        # jit_warm_s has no dispatch-cache misses mixed in.
        traces_before = energymodel.jit_cache_stats()["traces"]
        t0 = time.perf_counter()
        e_j, t_j = energymodel.evaluate_networks(grid, nets, use_jax=use_jax)
        cold_s = time.perf_counter() - t0
        cold_hit = (use_jax and
                    energymodel.jit_cache_stats()["traces"] == traces_before)
        warm_s = _warm_min(
            lambda: energymodel.evaluate_networks(grid, nets,
                                                  use_jax=use_jax))

        err_e = float(np.max(np.abs(e_j - e_np) / e_np))
        err_t = float(np.max(np.abs(t_j - t_np) / t_np))
        _, inv = energymodel._dedup_count_rows(
            energymodel._cfg_struct_from_grid(np, grid))
        level = dict(
            name=name, points=grid.n, networks=len(nets),
            unique_count_rows=int(inv.max()) + 1,
            chunked=False,
            numpy_per_config_s=round(numpy_s, 4),
            jit_cold_s=round(cold_s, 4), jit_cold_cache_hit=cold_hit,
            jit_precached=True, jit_warm_s=round(warm_s, 4),
            speedup_warm=round(numpy_s / warm_s, 2),
            max_rel_err_energy=err_e, max_rel_err_latency=err_t)
        results.append(level)
        _emit(f"dse_scale_{name}", numpy_s * 1e6,
              f"{grid.n} pts: numpy {numpy_s:.2f}s vs jit {warm_s:.2f}s "
              f"warm → {numpy_s / warm_s:.1f}x, "
              f"err<={max(err_e, err_t):.1e}")

    results.append(_bench_mega_level(nets, use_jax, quick))
    return results


def _bench_mega_level(nets, use_jax: bool, quick: bool) -> dict:
    """Chunked + sharded streaming at mega scale (a reduced grid in quick
    mode, so CI still covers the whole path).  The full [n_cfg, n_net]
    result of the chunked pass is kept (tiny — the savings are in the
    per-chunk intermediates) to cross-check the stream reductions; the
    unchunked reference runs on a subsampled slice only."""
    if quick:
        grid, chunk, name = (accelerator.ConfigGrid.product(
            rf_psum_words=accelerator.RF_PSUM_SIZES,
            noc_words_per_cycle=accelerator.NOC_WIDTHS), 512,
            "mega_quick_1350")
    else:
        grid, chunk, name = accelerator.mega_grid(), MEGA_CHUNK, "mega_49000"
    n_dev = energymodel.host_device_count()

    t0 = time.perf_counter()
    e_c, t_c = energymodel.evaluate_networks(grid, nets, use_jax=use_jax,
                                             chunk_size=chunk)
    cold_s = time.perf_counter() - t0
    warm_s = _warm_min(lambda: energymodel.evaluate_networks(
        grid, nets, use_jax=use_jax, chunk_size=chunk), reps=2)
    sharded_s = _warm_min(lambda: energymodel.evaluate_networks(
        grid, nets, use_jax=use_jax, chunk_size=chunk, shard=True),
        reps=2)

    sr = energymodel.stream_networks(grid, nets, chunk_size=chunk,
                                     use_jax=use_jax, shard=True)
    stream_s = _timed(lambda: energymodel.stream_networks(
        grid, nets, chunk_size=chunk, use_jax=use_jax, shard=True))[1] / 1e6
    edp = e_c * t_c
    stream_ok = (np.allclose(sr.min_metric, edp.min(axis=0), rtol=1e-9)
                 and np.array_equal(sr.argmin, edp.argmin(axis=0)))

    # unchunked reference on a subsampled slice (the full unchunked mega
    # run is exactly what chunking exists to avoid)
    sub = np.arange(0, grid.n, 97)
    e_r, t_r = energymodel.evaluate_networks(grid.take(sub), nets,
                                             use_jax=use_jax)
    err_e = float(np.max(np.abs(e_c[sub] - e_r) / e_r))
    err_t = float(np.max(np.abs(t_c[sub] - t_r) / t_r))

    level = dict(
        name=name, points=grid.n, networks=len(nets),
        chunked=True, chunk_size=chunk, n_devices=n_dev,
        jit_cold_s=round(cold_s, 4), jit_precached=True,
        jit_warm_s=round(warm_s, 4),
        sharded_warm_s=round(sharded_s, 4),
        shard_speedup=round(warm_s / sharded_s, 3),
        stream_s=round(stream_s, 4), stream_consistent=bool(stream_ok),
        max_rel_err_energy=err_e, max_rel_err_latency=err_t,
        subsample_stride=97,
        rss_now_mb=round(_rss_now_mb(), 1),
        rss_peak_process_mb=round(_rss_peak_mb(), 1))
    _emit(f"dse_scale_{name}", warm_s * 1e6,
          f"{grid.n} pts chunked({chunk}): {warm_s:.2f}s, sharded "
          f"{sharded_s:.2f}s ({n_dev} dev), stream {stream_s:.2f}s, "
          f"err<={max(err_e, err_t):.1e}, "
          f"rss {level['rss_peak_process_mb']:.0f}MB peak")
    return level


def _median_s(fn, reps: int = 3) -> float:
    """Median wall time over ``reps`` runs after ONE untimed pre-warm —
    the amortised treatment every baseline loop gets (PR 2 timed the bb
    loop once, cold, which made `speedup_vs_bb` swing run to run)."""
    fn()
    return float(np.median([_timed(fn)[1] / 1e6 for _ in range(reps)]))


def _warm_stat(fn, quick: bool, reps: int = 3) -> float:
    """Floors-relevant warm timing: full runs keep the min-of-reps
    steady-state number; ``--quick`` runs (small problems on noisy
    shared CI runners) take the median-of-3 instead, which one
    descheduled rep cannot drag around."""
    return _median_s(fn, reps=reps) if quick else _warm_min(fn, reps=reps)


def bench_partition_batch(nets) -> dict:
    """All (network × k∈2..8) pipeline splits: the looped bb/dp hot path
    that bench_table7_8 used per pair, vs ONE batch_partition call.

    Both baselines are pre-warmed and median-of-reps (see `_median_s`);
    the honest perf claim is `speedup_vs_bb_dp_loop` — the batch solver
    REPLACED the bb+dp pair loop, so that is the guardrailed ratio.
    `speedup_vs_bb` (batch vs the inexact bb heuristic alone) stays as an
    informational column; the PR 2 50×-vs-bb target was re-scoped after
    amortised re-measurement still put it at single digits on this host
    (docs/bench_schema.md#known-caveats)."""
    ks = tuple(range(2, 9))
    cfg = accelerator.AcceleratorConfig()
    lats = [energymodel.simulate_network(
        cfg, topology.get_network(n), n).layer_latencies for n in nets]

    def loop_bb():
        for lat in lats:
            for k in ks:
                partition.bb_partition(lat, k)

    def loop_dp():
        return [{k: partition.dp_partition(lat, k) for k in ks}
                for lat in lats]

    loop_bb_s = _median_s(loop_bb)
    loop_dp_s = _median_s(loop_dp)
    dp = loop_dp()

    batch_s = _warm_min(lambda: partition.batch_partition(lats, ks))
    res = partition.batch_partition(lats, ks)
    diffs = [abs(res[i][k].pipeline_latency - dp[i][k].pipeline_latency)
             / dp[i][k].pipeline_latency
             for i in range(len(lats)) for k in ks]
    out = dict(
        pairs=len(lats) * len(ks), networks=len(lats), k_range=[2, 8],
        loop_bb_s=round(loop_bb_s, 4), loop_dp_s=round(loop_dp_s, 4),
        baseline_reps=3, baseline_prewarmed=True,
        partition_batch_s=round(batch_s, 5),
        speedup_vs_bb=round(loop_bb_s / batch_s, 1),
        speedup_vs_bb_dp_loop=round((loop_bb_s + loop_dp_s) / batch_s, 1),
        max_rel_diff_vs_dp=float(max(diffs)),
        exact_vs_dp=bool(max(diffs) == 0.0))
    _emit("partition_batch", batch_s * 1e6,
          f"{out['pairs']} pairs: batch {batch_s * 1e3:.1f}ms vs loops "
          f"bb {loop_bb_s * 1e3:.0f}ms + dp {loop_dp_s * 1e3:.0f}ms → "
          f"{out['speedup_vs_bb_dp_loop']:.0f}x (bb only "
          f"{out['speedup_vs_bb']:.0f}x), exact={out['exact_vs_dp']}")
    return out


# ---------------------------------------------------------------------------
# Co-design level (schema v4): the batched heterogeneous layer→core
# schedule search vs the per-(chip, network) python loop it replaces,
# plus per-layer-path parity across every engine backend.
# ---------------------------------------------------------------------------


def _per_layer_parity(grid, nets) -> dict:
    """`per_layer=True` parity across jax / chunked / sharded against the
    numpy per-layer reference (all ≤1e-6 guardrailed)."""
    def err(a, b):
        d = np.abs(a - b)
        with np.errstate(invalid="ignore", divide="ignore"):
            r = np.where(b != 0, d / np.abs(b), d)
        return float(r.max())

    e_n, t_n = energymodel.evaluate_networks(grid, nets, backend="numpy",
                                             per_layer=True)
    e_j, t_j = energymodel.evaluate_networks(grid, nets, backend="jax",
                                             per_layer=True)
    e_c, t_c = energymodel.evaluate_networks(grid, nets, backend="jax",
                                             per_layer=True, chunk_size=64)
    e_s, t_s = energymodel.evaluate_networks(grid, nets, backend="jax",
                                             per_layer=True, shard=True)
    return dict(
        max_rel_err_per_layer_jax=max(err(e_j, e_n), err(t_j, t_n)),
        max_rel_err_per_layer_chunked=max(err(e_c, e_j), err(t_c, t_j)),
        max_rel_err_per_layer_sharded=max(err(e_s, e_j), err(t_s, t_j)))


def bench_codesign(nets, quick: bool) -> dict:
    """Schema-v4 `codesign` level: every (chip candidate × network)
    heterogeneous layer→core schedule in ONE batch_schedule_hetero call,
    timed against the per-(chip, network) `schedule_hetero_oracle` loop
    it replaces (pre-warmed, median-of-reps), with exactness and
    per-layer-path parity guardrails."""
    networks = {n: topology.get_network(n) for n in nets}
    grid = accelerator.ConfigGrid.product()
    # quick keeps the full chip-enumeration shape (the batch solver's win
    # is amortising fixed dispatch over many problems — too few problems
    # and the bench measures overhead, not the solver)
    pool_size, m_cores, max_types = (5, 4, 3) if quick else (6, 4, 3)

    probs = hetero.codesign_problems(grid, networks, m_cores,
                                     max_types=max_types,
                                     pool_size=pool_size)

    lats = probs.lats                      # per-problem views, built once

    def loop_oracle():
        return [partition.schedule_hetero_oracle(lats[i], probs.counts[i])
                for i in range(probs.n_problems)]

    loop_s = _median_s(loop_oracle, reps=2 if quick else 3)
    oracle = loop_oracle()

    def batch():
        return partition.batch_schedule_hetero(
            probs.lat_dense, probs.counts, n_layers=probs.n_layers_b)

    batch_s = _warm_stat(batch, quick, reps=2 if quick else 3)
    res = batch()

    diffs = [abs(res.bottleneck[i] - oracle[i]["bottleneck"])
             / max(oracle[i]["bottleneck"], 1e-300)
             for i in range(probs.n_problems)]

    t0 = time.perf_counter()
    cd = hetero.co_design(grid, networks, m_cores, max_types=max_types,
                          pool_size=pool_size)
    codesign_s = time.perf_counter() - t0

    out = dict(
        name="codesign", points=grid.n, networks=len(networks),
        pool_size=pool_size, m_cores=m_cores, max_types=max_types,
        n_chips=len(probs.chips), problems=probs.n_problems,
        loop_oracle_s=round(loop_s, 4),
        schedule_batch_s=round(batch_s, 5),
        speedup_warm=round(loop_s / batch_s, 2),
        max_rel_diff_vs_oracle=float(max(diffs)),
        exact_vs_oracle=bool(max(diffs) == 0.0),
        codesign_end_to_end_s=round(codesign_s, 4),
        chip=dict(core_types=[grid.config_at(c).label()
                              for c in cd.core_types],
                  core_counts=cd.core_counts,
                  score=round(cd.score, 6),
                  homogeneous_score=round(cd.homogeneous_score, 6)))
    out.update(_per_layer_parity(grid, networks))
    _emit("codesign", batch_s * 1e6,
          f"{probs.n_problems} (chip,net) schedules: batch "
          f"{batch_s * 1e3:.1f}ms vs oracle loop {loop_s:.2f}s → "
          f"{out['speedup_warm']:.0f}x, exact={out['exact_vs_oracle']}, "
          f"chip {'+'.join(str(c) for c in cd.core_counts)} cores, "
          f"hetero/homog score {cd.score:.3f}/{cd.homogeneous_score:.3f}")
    return out


#: Warm-speedup floor of the batched co-design solver vs the oracle loop
#: (ISSUE 4 acceptance: ≥ 20× on full runs; quick runs solve a much
#: smaller problem set where fixed dispatch overhead dominates, so the
#: floor is relaxed there — benchmarks/floors.json keeps CI's copy).
CODESIGN_SPEEDUP_FLOOR = 20.0
CODESIGN_SPEEDUP_FLOOR_QUICK = 3.0

#: Speedup floor of the batched latency-bound Pareto sweep vs the
#: per-deadline python-loop rescoring it replaces (ISSUE 5 acceptance:
#: ≥ 10× on full runs; quick shares the chip-enumeration shape, so the
#: floor only relaxes for runner noise — benchmarks/floors.json again
#: keeps CI's copy).
PARETO_SPEEDUP_FLOOR = 10.0
PARETO_SPEEDUP_FLOOR_QUICK = 3.0


# ---------------------------------------------------------------------------
# codesign_mega level (schema v5): streamed candidate pool from the mega
# grid (one chunked stream_layer_topk pass — boundary sets + top-k +
# running minima, no [n_cfg, n_net(, n_layer)] matrices) + the batched
# latency-bound Pareto sweep vs the per-deadline python loop it replaces.
# ---------------------------------------------------------------------------


def _pareto_loop_baseline(norm_e, lat, norm_l, dl_abs):
    """The per-deadline python-loop rescoring `pareto_codesign` replaces:
    per (deadline × chip) feasibility + score in python, per-network
    per-deadline argmins, and O(n_chips²) dominance filters per network
    and on the network-mean plane.  Produces exactly the batched sweep's
    outputs, so exactness is asserted alongside the timing."""
    n_chips, n_net = norm_e.shape
    n_d = dl_abs.shape[1]
    best = np.full(n_d, -1, dtype=np.int64)
    best_net = np.full((n_net, n_d), -1, dtype=np.int64)
    for d in range(n_d):
        best_s = np.inf
        net_s = np.full(n_net, np.inf)
        for c in range(n_chips):
            feas = lat[c] <= dl_abs[:, d]
            if feas.all():
                s = norm_e[c].mean()
                if s < best_s:
                    best_s, best[d] = s, c
            for j in np.flatnonzero(feas):
                if norm_e[c, j] < net_s[j]:
                    net_s[j], best_net[j, d] = norm_e[c, j], c
    net_front = np.ones((n_chips, n_net), dtype=bool)
    for j in range(n_net):
        for c in range(n_chips):
            for o in range(n_chips):
                if (norm_e[o, j] <= norm_e[c, j] and lat[o, j] <= lat[c, j]
                        and (norm_e[o, j] < norm_e[c, j]
                             or lat[o, j] < lat[c, j])):
                    net_front[c, j] = False
                    break
    me, ml = norm_e.mean(axis=1), norm_l.mean(axis=1)
    chip_front = np.ones(n_chips, dtype=bool)
    for c in range(n_chips):
        for o in range(n_chips):
            if (me[o] <= me[c] and ml[o] <= ml[c]
                    and (me[o] < me[c] or ml[o] < ml[c])):
                chip_front[c] = False
                break
    return best, best_net, net_front, chip_front


def _pareto_matches(pc, loop_out, dl_abs) -> bool:
    """Exactness gate for the batched sweep vs the loop baseline.

    Feasibility masks, per-network argmins, and the per-network fronts
    involve only comparisons/selections on identical float inputs, so
    they must match BIT-EXACTLY.  The per-deadline best chip and the
    mean-plane chip front go through a mean reduction, which XLA and
    numpy may sum in different orders — a last-ulp difference between
    two near-tied chips can flip an argmin/dominance there, so those two
    accept index disagreements only between value-tied (≤1e-9 rel)
    picks."""
    l_best, l_best_net, l_front, l_chip_front = loop_out
    if not (np.array_equal(l_best_net, pc.best_chip_net)
            and np.array_equal(l_front, pc.net_frontier)):
        return False
    feas = pc.latency[:, :, None] <= dl_abs[None, :, :]
    loop_scores = np.where(feas, pc.norm_energy[:, :, None],
                           np.inf).mean(axis=1)
    for d in range(dl_abs.shape[1]):
        a, b = int(pc.best_chip[d]), int(l_best[d])
        if a == b:
            continue
        if a < 0 or b < 0:
            return False
        if not np.isclose(loop_scores[a, d], loop_scores[b, d],
                          rtol=1e-9, atol=0.0):
            return False
    if not np.array_equal(l_chip_front, pc.chip_frontier):
        me = pc.norm_energy.mean(axis=1)
        ml = pc.norm_latency.mean(axis=1)
        for c in np.flatnonzero(l_chip_front != pc.chip_frontier):
            tied = ((np.abs(me - me[c]) <= 1e-9 * np.abs(me[c]))
                    & (np.abs(ml - ml[c]) <= 1e-9 * np.abs(ml[c])))
            if tied.sum() < 2:
                return False
    return True


def bench_codesign_mega(nets, quick: bool) -> dict:
    """Schema-v5 `codesign_mega` level: mega-grid streaming co-design
    (the candidate pool streamed chunk by chunk, never a dense sweep)
    plus the batched Pareto sweep over ≥ 8 deadlines in ONE compiled
    call, timed against the per-deadline python-loop baseline."""
    networks = {n: topology.get_network(n) for n in nets}
    if quick:
        grid, chunk, name = (accelerator.ConfigGrid.product(
            rf_psum_words=accelerator.RF_PSUM_SIZES,
            noc_words_per_cycle=accelerator.NOC_WIDTHS), 256,
            "codesign_mega_quick_1350")
    else:
        grid, chunk, name = accelerator.mega_grid(), 2048, \
            "codesign_mega_49000"
    pool_size, m_cores, max_types = 6, 4, 3

    t0 = time.perf_counter()
    probs = hetero.codesign_problems_streaming(
        grid, networks, m_cores, max_types=max_types, pool_size=pool_size,
        chunk_size=chunk)
    stream_pool_s = time.perf_counter() - t0
    rss_after_stream = _rss_now_mb()

    # streamed pool == dense pool (quick grids only: a dense mega sweep is
    # exactly what the streaming path exists to avoid)
    pool_matches_dense = None
    if quick:
        dense = hetero.codesign_problems(grid, networks, m_cores,
                                         max_types=max_types,
                                         pool_size=pool_size)
        pool_matches_dense = bool(dense.pool == probs.pool)

    res = partition.batch_schedule_hetero(probs.lat_dense, probs.counts,
                                          n_layers=probs.n_layers_b)
    t0 = time.perf_counter()
    pc = hetero.pareto_codesign(probs, res, n_deadlines=12)
    build_s = time.perf_counter() - t0
    deadlines = pc.deadlines

    # the sweep re-run (new deadline grid, solved points reused) — the
    # apples-to-apples twin of the loop baseline below, which consumes
    # the same precomputed (energy, latency) points
    points = (pc.energy, pc.latency)
    pareto_s = _warm_stat(
        lambda: hetero.pareto_codesign(probs, deadlines=deadlines,
                                       points=points),
        quick, reps=2 if quick else 3)

    dl_abs = probs.min_latency[:, None] * deadlines[None, :]
    loop_s = _median_s(
        lambda: _pareto_loop_baseline(pc.norm_energy, pc.latency,
                                      pc.norm_latency, dl_abs),
        reps=2 if quick else 3)
    l_base = _pareto_loop_baseline(pc.norm_energy, pc.latency,
                                   pc.norm_latency, dl_abs)
    pareto_exact = _pareto_matches(pc, l_base, dl_abs)

    out = dict(
        name=name, points=grid.n, networks=len(networks),
        chunk_size=chunk, pool_size=pool_size, m_cores=m_cores,
        max_types=max_types, pool=[int(p) for p in probs.pool],
        n_chips=pc.n_chips, problems=probs.n_problems,
        n_deadlines=int(deadlines.size),
        deadline_lo=round(float(deadlines[0]), 6),
        deadline_hi=round(float(deadlines[-1]), 6),
        stream_pool_s=round(stream_pool_s, 4),
        pool_matches_dense=pool_matches_dense,
        pareto_build_s=round(build_s, 4),
        pareto_sweep_s=round(pareto_s, 5),
        pareto_loop_s=round(loop_s, 4),
        pareto_speedup=round(loop_s / pareto_s, 2),
        pareto_exact=pareto_exact,
        best_chip_by_deadline=[int(c) for c in pc.best_chip],
        frontier_sizes=[int(s) for s in pc.net_frontier.sum(axis=0)],
        rss_after_stream_mb=round(rss_after_stream, 1),
        rss_now_mb=round(_rss_now_mb(), 1),
        rss_peak_process_mb=round(_rss_peak_mb(), 1))
    _emit("codesign_mega", pareto_s * 1e6,
          f"{grid.n} pts streamed pool in {stream_pool_s:.1f}s "
          f"(rss {rss_after_stream:.0f}MB), pareto x{deadlines.size} "
          f"deadlines: {pareto_s * 1e3:.1f}ms vs loop {loop_s * 1e3:.0f}ms"
          f" → {out['pareto_speedup']:.0f}x, exact={pareto_exact}")
    return out


# ---------------------------------------------------------------------------
# slack level (schema v6): the energy-aware deadline-slack pass — every
# (chip candidate × network × deadline) cell re-scheduled toward cheaper
# core types in ONE batch_slack_schedule call, vs the per-cell
# slack_schedule_oracle loop it replaces.
# ---------------------------------------------------------------------------

#: Relative deadline grid (× the per-network single-config minimum
#: latency) — the tightest column leaves real-but-thin slack, the widest
#: is effectively energy-argmin.
SLACK_DEADLINES = (1.05, 1.25, 2.0, 4.0)

#: Warm-speedup floor of the batched slack solver vs the per-cell oracle
#: loop (ISSUE 8 acceptance: ≥ 10× on full runs; quick runs solve a far
#: smaller enumeration where fixed dispatch overhead dominates the
#: batch kernel — benchmarks/floors.json keeps CI's copy).
SLACK_SPEEDUP_FLOOR = 10.0
SLACK_SPEEDUP_FLOOR_QUICK = 2.0


def bench_slack(nets, quick: bool) -> dict:
    """Schema-v6 `slack` level: every (chip, network, deadline) energy-
    aware slack schedule in ONE batch_slack_schedule call, timed against
    the per-cell `slack_schedule_oracle` loop, with bit-exactness, weak
    energy-dominance and deadline-feasibility guardrails.

    The full run enumerates a LARGER chip pool than the `codesign` level
    (pool_size 8 vs 6): the depth-bucketed numpy kernel works on
    [rows, deadlines, types] slices whose per-op cost is dispatch-bound
    on small batches, so the solver's advantage is only honest at the
    enumeration scale the DSE service actually sweeps."""
    networks = {n: topology.get_network(n) for n in nets}
    grid = accelerator.ConfigGrid.product()
    pool_size, m_cores, max_types = (5, 4, 3) if quick else (8, 4, 3)
    probs = hetero.codesign_problems(grid, networks, m_cores,
                                     max_types=max_types,
                                     pool_size=pool_size)
    n_net = len(networks)
    n_chips = probs.n_problems // n_net
    t_max = probs.counts.shape[1]
    en = hetero._expand_pool_tensor(probs.e_layer, probs.chips, n_net,
                                    t_max)
    rel = np.asarray(SLACK_DEADLINES)
    dl = np.tile(probs.min_latency[:, None] * rel[None, :], (n_chips, 1))

    base = partition.batch_schedule_hetero(
        probs.lat_dense, probs.counts, n_layers=probs.n_layers_b)

    def batch():
        return partition.batch_slack_schedule(
            probs.lat_dense, en, probs.counts, dl,
            n_layers=probs.n_layers_b, use_jax=False, base=base)

    batch_s = _warm_stat(batch, quick)
    sl = batch()

    def loop_oracle():
        out = []
        for i in range(probs.n_problems):
            nl_i = int(probs.n_layers_b[i])
            lat_i = probs.lat_dense[i, :, :nl_i]
            e_i = en[i, :, :nl_i]
            cnt_i = probs.counts[i]
            for d in range(rel.size):
                out.append(partition.slack_schedule_oracle(
                    lat_i, e_i, cnt_i, dl[i, d]))
        return out

    # the oracle loop is timed ONCE — a median-of-reps treatment would
    # quadruple a baseline already tens of seconds long for a ratio this
    # lopsided; the timed run's outputs double as the parity reference
    oracle, loop_us = _timed(loop_oracle)
    loop_s = loop_us / 1e6

    shape = (probs.n_problems, rel.size)
    o_bott = np.array([o["bottleneck"] for o in oracle]).reshape(shape)
    o_energy = np.array([o["energy"] for o in oracle]).reshape(shape)
    o_moves = np.array([o["n_moves"] for o in oracle]).reshape(shape)
    o_feas = np.array([o["feasible"] for o in oracle]).reshape(shape)
    exact = (np.array_equal(sl.bottleneck, o_bott)
             and np.array_equal(sl.energy, o_energy)
             and np.array_equal(sl.n_moves, o_moves)
             and np.array_equal(sl.feasible, o_feas))

    def rel_diff(a, b):
        fin = np.isfinite(b)
        if not fin.any():
            return 0.0
        d = np.abs(a[fin] - b[fin])
        return float((d / np.maximum(np.abs(b[fin]), 1e-300)).max(
            initial=0.0))

    max_rel = max(rel_diff(sl.bottleneck, o_bott),
                  rel_diff(sl.energy, o_energy))

    # energy of the UNmoved base assignment per problem: a deadline equal
    # to the base bottleneck leaves zero slack, so the solver returns the
    # base schedule (and its sequentially-summed energy) verbatim
    base_e = partition.batch_slack_schedule(
        probs.lat_dense, en, probs.counts, base.bottleneck[:, None],
        n_layers=probs.n_layers_b, use_jax=False, base=base).energy[:, 0]
    with np.errstate(invalid="ignore"):
        saved_pct = 100.0 * (base_e[:, None] - sl.energy) / base_e[:, None]
    dominance_ok = bool(
        (sl.energy <= base_e[:, None] * (1.0 + 1e-9)).all())
    # a weak chip candidate's latency-argmin bottleneck can genuinely
    # exceed the tightest budget (deadlines are relative to the grid-wide
    # single-config minimum), so infeasible cells are allowed — the
    # guardrail is CONSISTENCY: the flag matches bottleneck <= deadline
    # exactly, and every feasible cell's schedule fits its budget
    deadline_met_ok = bool(
        (sl.feasible == (sl.bottleneck <= dl)).all()
        and (sl.bottleneck[sl.feasible] <= dl[sl.feasible]).all())

    out = dict(
        name="slack", points=grid.n, networks=len(networks),
        pool_size=pool_size, m_cores=m_cores, max_types=max_types,
        n_chips=n_chips, problems=probs.n_problems,
        n_deadlines=int(rel.size),
        deadlines_rel=[float(r) for r in rel],
        slack_batch_s=round(batch_s, 4),
        oracle_loop_s=round(loop_s, 3), baseline_reps=1,
        speedup_warm=round(loop_s / batch_s, 2),
        max_rel_diff_vs_oracle=max_rel,
        exact_vs_oracle=bool(exact),
        moves_total=int(sl.n_moves.sum()),
        moved_cells_pct=round(
            100.0 * float((sl.n_moves > 0).mean()), 2),
        feasible_cells_pct=round(
            100.0 * float(sl.feasible.mean()), 2),
        energy_saved_mean_pct=round(float(saved_pct.mean()), 3),
        energy_saved_max_pct=round(float(saved_pct.max()), 3),
        dominance_ok=dominance_ok,
        deadline_met_ok=deadline_met_ok)
    _emit("slack", batch_s * 1e6,
          f"{probs.n_problems}x{rel.size} (chip,net,deadline) cells: "
          f"batch {batch_s * 1e3:.0f}ms vs oracle loop {loop_s:.1f}s → "
          f"{out['speedup_warm']:.0f}x, exact={out['exact_vs_oracle']}, "
          f"{out['moves_total']} moves save "
          f"{out['energy_saved_mean_pct']:.1f}% energy on average")
    return out


def _check_bench_payload(payload: dict, quick: bool = False) -> list:
    """Schema/parity guardrails — CI fails on regressions here (documented
    in docs/bench_schema.md; keep the two in sync)."""
    problems = []
    for key in ("schema", "cpu_count", "n_devices", "backends", "levels",
                "partition", "codesign", "codesign_mega", "slack",
                "persistent_cache"):
        if key not in payload:
            problems.append(f"missing payload key {key!r}")
    if payload.get("schema") != "bench_dse/v6":
        problems.append(f"unexpected schema {payload.get('schema')!r}")
    for lv in payload.get("levels", []):
        for key in ("max_rel_err_energy", "max_rel_err_latency"):
            if key not in lv:
                problems.append(f"level {lv.get('name')}: missing {key!r}")
            elif lv[key] is not None and lv[key] > 1e-6:
                problems.append(
                    f"level {lv.get('name')}: {key}={lv.get(key):.2e}")
        if lv.get("chunked") and not lv.get("stream_consistent", True):
            problems.append(
                f"level {lv.get('name')}: stream reductions diverged")
    part = payload.get("partition", {})
    if part.get("max_rel_diff_vs_dp", 1.0) > 1e-12:
        problems.append(
            f"batch_partition vs dp: {part.get('max_rel_diff_vs_dp'):.2e}")
    cod = payload.get("codesign", {})
    if cod:
        if cod.get("max_rel_diff_vs_oracle", 1.0) > 1e-6:
            problems.append(
                "codesign: max_rel_diff_vs_oracle "
                f"{cod.get('max_rel_diff_vs_oracle'):.2e}")
        floor = (CODESIGN_SPEEDUP_FLOOR_QUICK if quick
                 else CODESIGN_SPEEDUP_FLOOR)
        if cod.get("speedup_warm", 0.0) < floor:
            problems.append(
                f"codesign: speedup_warm {cod.get('speedup_warm')} < "
                f"{floor}x floor")
        for key in ("max_rel_err_per_layer_jax",
                    "max_rel_err_per_layer_chunked",
                    "max_rel_err_per_layer_sharded"):
            if key not in cod:
                problems.append(f"codesign: missing {key!r}")
            elif cod[key] is not None and cod[key] > 1e-6:
                problems.append(f"codesign: {key}={cod.get(key):.2e}")
    mega = payload.get("codesign_mega", {})
    if mega:
        floor = (PARETO_SPEEDUP_FLOOR_QUICK if quick
                 else PARETO_SPEEDUP_FLOOR)
        if mega.get("pareto_speedup", 0.0) < floor:
            problems.append(
                f"codesign_mega: pareto_speedup "
                f"{mega.get('pareto_speedup')} < {floor}x floor")
        if not mega.get("pareto_exact", False):
            problems.append(
                "codesign_mega: batched pareto sweep diverged from the "
                "per-deadline loop baseline")
        if mega.get("pool_matches_dense") is False:
            problems.append(
                "codesign_mega: streamed pool != dense pool")
    sla = payload.get("slack", {})
    if sla:
        if sla.get("max_rel_diff_vs_oracle", 1.0) > 1e-6:
            problems.append(
                "slack: max_rel_diff_vs_oracle "
                f"{sla.get('max_rel_diff_vs_oracle'):.2e}")
        floor = (SLACK_SPEEDUP_FLOOR_QUICK if quick
                 else SLACK_SPEEDUP_FLOOR)
        if sla.get("speedup_warm", 0.0) < floor:
            problems.append(
                f"slack: speedup_warm {sla.get('speedup_warm')} < "
                f"{floor}x floor")
        if not sla.get("dominance_ok", False):
            problems.append(
                "slack: an energy-aware schedule costs MORE energy than "
                "its latency-argmin base (weak dominance broken)")
        if not sla.get("deadline_met_ok", False):
            problems.append(
                "slack: a cell misses its deadline (infeasible or "
                "bottleneck above the budget)")
    return problems


def _bench_warnings(payload: dict) -> list:
    """Non-fatal perf-target checks (ISSUE 2 acceptance asked for sharded
    ≥1.3x; on hosts where XLA's single-device inter-op parallelism
    already saturates the cores this is not reachable — surface the
    shortfall without failing CI).  The PR 2 ``speedup_vs_bb ≥ 50×``
    target was RE-SCOPED in ISSUE 4: the amortised (pre-warmed,
    median-of-reps) re-measurement still lands single-digit vs the
    inexact bb heuristic alone, so the guardrailed ratio is now the
    honest one — batch vs the bb+dp pair loop it actually replaced."""
    warns = []
    for lv in payload.get("levels", []):
        if lv.get("chunked") and lv.get("shard_speedup", 9.9) < 1.3:
            warns.append(
                f"level {lv.get('name')}: shard_speedup "
                f"{lv.get('shard_speedup')} < 1.3 target "
                f"({lv.get('n_devices')} devices)")
        peak = lv.get("rss_peak_process_mb", 0.0)
        if peak > 8192:
            warns.append(
                f"level {lv.get('name')}: process peak RSS {peak:.0f}MB "
                "> 8GB budget")
    mega = payload.get("codesign_mega", {})
    if mega.get("rss_after_stream_mb", 0.0) > 1536:
        warns.append(
            f"codesign_mega: rss_after_stream_mb "
            f"{mega.get('rss_after_stream_mb'):.0f}MB > ~1.5GB budget "
            "for the streamed mega pool")
    part = payload.get("partition", {})
    # only meaningful at full problem size — quick's 42-pair problem is
    # dominated by fixed dispatch and would always "warn"
    if (part.get("pairs", 0) >= 100
            and part.get("speedup_vs_bb_dp_loop", 99.0) < 50.0):
        warns.append(
            f"partition: speedup_vs_bb_dp_loop "
            f"{part.get('speedup_vs_bb_dp_loop')} < 50x target (vs bb "
            f"alone: {part.get('speedup_vs_bb')}x, informational)")
    return warns


def write_bench_json(levels: list, part: dict, codesign: dict,
                     codesign_mega: dict, slack: dict, cache_info: dict,
                     quick: bool) -> None:
    use_jax = dse._use_jax_default()
    payload = dict(
        schema="bench_dse/v6",
        cpu_count=os.cpu_count(),
        n_devices=energymodel.host_device_count(),
        backends=dict(jax=use_jax),
        persistent_cache=cache_info,
        jit_cache=energymodel.jit_cache_stats(),
        levels=levels,
        partition=part,
        codesign=codesign,
        codesign_mega=codesign_mega,
        slack=slack)
    if use_jax:
        import jax
        payload["jax"] = jax.__version__
    else:                                              # pragma: no cover
        payload["jax"] = None                          # numpy-only fallback
    # quick runs use reduced grids — record them beside, never clobber,
    # the full-run trajectory file
    path = BENCH_DSE_QUICK_JSON if quick else BENCH_DSE_JSON
    path.write_text(json.dumps(payload, indent=2) + "\n")
    _emit("bench_dse_json", 0.0, f"wrote {path}")

    for w in _bench_warnings(payload):
        print(f"BENCH WARN: {w}", file=sys.stderr)
    problems = _check_bench_payload(payload, quick=quick)
    if problems:
        for p in problems:
            print(f"BENCH CHECK FAILED: {p}", file=sys.stderr)
        raise SystemExit(1)
    _emit("bench_dse_check", 0.0, "schema/parity guardrails passed")


def bench_table1_2(sweeps):
    """Tables 1–2: μ^p_min / δ^max_min per array, ifmap- and psum-swept."""
    def run():
        rows = []
        for net, sw in sweeps.items():
            t1 = dse.mu_delta(sw, swept="ifmap")
            t2 = dse.mu_delta(sw, swept="psum")
            for arr in sw.arrays:
                rows.append([net, f"{arr[0]}x{arr[1]}",
                             f"{t1[arr][0]:.2f}", f"{t1[arr][1]:.2f}",
                             f"{t2[arr][0]:.2f}", f"{t2[arr][1]:.2f}"])
        return rows

    rows, us = _timed(run)
    _write("table1_2_mu_delta", ["network", "array", "mu_ifmap",
                                 "delta_ifmap", "mu_psum", "delta_psum"],
           rows)
    d16 = [float(r[5]) for r in rows if r[1] == "16x16"]
    _emit("table1_2_mu_delta", us,
          f"psum delta@[16x16] mean={np.mean(d16):.1f}% (paper 4.6-112%)")


def bench_table3(sweeps):
    """Table 3: Δ^max_min over the 25-point space per array."""
    def run():
        rows = []
        for net, sw in sweeps.items():
            d = dse.delta_whole_space(sw)
            rows.append([net] + [f"{d[a]:.2f}" for a in sw.arrays])
        return rows

    rows, us = _timed(run)
    arrays = next(iter(sweeps.values())).arrays
    _write("table3_delta", ["network"] + [f"{a[0]}x{a[1]}" for a in arrays],
           rows)
    vals = [float(v) for r in rows for v in r[1:]]
    _emit("table3_delta", us,
          f"range {min(vals):.0f}-{max(vals):.0f}% (paper 12-114%)")


def bench_table4(sweeps):
    """Table 4: EDP mean/max spread over the whole space."""
    def run():
        return [[net, f"{m:.1f}", f"{mx:.1f}"]
                for net, (m, mx) in
                ((n, dse.edp_spread(sw)) for n, sw in sweeps.items())]

    rows, us = _timed(run)
    _write("table4_edp_spread", ["network", "mean_pct", "max_pct"], rows)
    means = [float(r[1]) for r in rows]
    _emit("table4_edp_spread", us,
          f"mean spread {min(means):.0f}-{max(means):.0f}% (paper 17-130%)")


def bench_table5(sweeps):
    """Table 5: per-network 5%-boundary configurations + chip design."""
    def run():
        rows = []
        for net, sw in sweeps.items():
            cells = dse.boundary_configs(sw, bound=0.05)
            rows.append([net, len(cells),
                         " | ".join(sw.cell_label(c) for c in cells[:6])])
        chip = hetero.design_chip(sweeps, bound=0.05, max_cores=3)
        return rows, chip

    (rows, chip), us = _timed(run)
    _write("table5_boundary_configs", ["network", "n_configs",
                                       "configs(first 6)"], rows)
    _emit("table5_boundary_configs", us,
          f"core types={len(chip.core_types)}: "
          + "; ".join(chip.core_label(i)
                      for i in range(len(chip.core_types))))
    return chip


def bench_table6(sweeps, chip):
    """Table 6: Δ_E/Δ_D/Δ_EDP on non-corresponding cores + savings."""
    def run():
        rows = []
        for net in sorted(chip.assignment):
            own = chip.assignment[net]
            worst = dict(dE=0.0, dD=0.0, dEDP=0.0)
            for other in range(len(chip.core_types)):
                if other == own:
                    continue
                pen = hetero.cross_penalty(chip, net, other)
                if pen["dEDP"] > worst["dEDP"]:
                    worst = pen
            rows.append([net, f"{worst['dE']:.2f}", f"{worst['dD']:.2f}",
                         f"{worst['dEDP']:.2f}"])
        sav = hetero.savings_summary(chip)
        return rows, sav

    (rows, sav), us = _timed(run)
    _write("table6_cross_penalty", ["network", "dE_pct", "dD_pct",
                                    "dEDP_pct"], rows)
    es = max(v["energy_saved"] for v in sav.values())
    ed = max(v["edp_saved"] for v in sav.values())
    _emit("table6_cross_penalty", us,
          f"max saved: energy {es:.0f}% / EDP {ed:.0f}% (paper 36%/67%)")


def bench_table7_8(nets):
    """Tables 7–8: Alg. II distribution on the paper's two core configs.

    The optimal column comes from ONE ``batch_partition`` call over every
    (network, k) pair — the per-pair dp loop this replaces dominated the
    seed's table time; bb stays as the paper's per-network algorithm."""
    cfg3 = accelerator.AcceleratorConfig(array_rows=32, array_cols=32,
                                         gb_psum_kb=54, gb_ifmap_kb=54)
    cfg4 = accelerator.AcceleratorConfig(array_rows=12, array_cols=14,
                                         gb_psum_kb=216, gb_ifmap_kb=54)

    def run():
        lats, klist = [], []
        for net in nets:
            layers = topology.get_network(net)
            cat1 = net in topology.CATEGORY_1
            cfg, k = (cfg3, 3) if cat1 else (cfg4, 4)
            rep = energymodel.simulate_network(cfg, layers, net)
            lats.append(rep.layer_latencies)
            klist.append(k)
        batch = partition.batch_partition(lats, (3, 4))
        rows = []
        for i, net in enumerate(nets):
            k = klist[i]
            bb = partition.bb_partition(lats[i], k)
            opt = batch[i][k]
            rows.append([net, k,
                         " ".join(f"({a},{b})" for a, b in bb.table_row()),
                         f"{bb.speedup:.2f}", f"{opt.speedup:.2f}"])
        return rows

    rows, us = _timed(run)
    _write("table7_8_distribution", ["network", "cores", "(l_init,n_C)",
                                     "speedup_bb", "speedup_optimal"], rows)
    s = [float(r[3]) for r in rows]
    _emit("table7_8_distribution", us,
          f"speedups {min(s):.2f}-{max(s):.2f} (paper 2.01-3.92)")


def bench_autoshard():
    """TPU adaptation: sharding-policy DSE + fleet design (Table-5 analogue)."""
    from repro.configs import ARCHS

    def run():
        rows = []
        for name, cfg in ARCHS.items():
            scored = autoshard.sweep(cfg, n_chips=256, seq_len=4096,
                                     global_batch=256)
            best, s = scored[0]
            rows.append([name, best.name, f"{s * 1e3:.2f}"])
        fleet = autoshard.design_fleet(
            {n: c for n, c in ARCHS.items()}, n_chips=256, seq_len=4096,
            global_batch=256, max_policies=3)
        return rows, fleet

    (rows, fleet), us = _timed(run)
    _write("autoshard_policies", ["arch", "best_policy", "step_ms"], rows)
    _emit("autoshard_fleet", us,
          f"{len(fleet['policies'])} fleet policies cover all 10 archs: "
          + ", ".join(fleet["policies"]))


def bench_pipeline_stages():
    """B&B pipeline staging from the TPU cost model (Alg. II, TPU edition)."""
    from repro.configs import ARCHS
    from repro.core.tpu_costmodel import layer_costs

    def run():
        rows = []
        for name in ("qwen2.5-32b", "qwen2-vl-72b", "recurrentgemma-9b",
                     "arctic-480b"):
            cfg = ARCHS[name]
            costs = layer_costs(cfg, ShardingPolicy("p", dp=64, tp=4),
                                seq_len=4096, global_batch=256)
            lat = [c.time_s for c in costs]
            for k in (2, 4):
                p = partition.bb_partition(lat, k)
                rows.append([name, k, f"{p.speedup:.2f}",
                             f"{p.pipeline_latency * 1e3:.2f}"])
        return rows

    rows, us = _timed(run)
    _write("pipeline_stages", ["arch", "stages", "speedup",
                               "stage_ms"], rows)
    s = [float(r[2]) for r in rows if r[1] == 4]
    _emit("pipeline_stages", us,
          f"4-stage speedups {min(s):.2f}-{max(s):.2f}")


def bench_fig5_6_7(sweeps):
    """Fig. 5/6/7: energy & latency curves vs GB sizes per array (CSV)."""
    def run():
        rows = []
        for net in ("VGG16", "ResNet50"):
            sw = sweeps.get(net)
            if sw is None:
                return []
            for a, arr in enumerate(sw.arrays):
                for pi, ps in enumerate(sw.psum_kb):
                    for ii, ifm in enumerate(sw.ifmap_kb):
                        rows.append([net, f"{arr[0]}x{arr[1]}", ps, ifm,
                                     f"{sw.energy[a, pi, ii]:.6e}",
                                     f"{sw.latency[a, pi, ii]:.6e}"])
        return rows

    rows, us = _timed(run)
    if rows:
        _write("fig5_6_7_curves", ["network", "array", "gb_psum_kb",
                                   "gb_ifmap_kb", "energy_pj",
                                   "latency_ns"], rows)
        _emit("fig5_6_7_curves", us, f"{len(rows)} curve points")


def bench_roofline_table():
    """§Roofline: aggregate the dry-run JSON cells into the report table."""
    import json

    def run():
        rows = []
        for f in sorted(Path("experiments/dryrun").glob("*__single.json")):
            r = json.loads(f.read_text())
            if r.get("status") != "ok":
                continue
            rl = r["roofline"]
            rows.append([
                r["arch"], r["shape"], f"{r['per_device_gib']:.2f}",
                f"{rl['compute_s']:.4f}", f"{rl['memory_s']:.4f}",
                f"{rl['collective_s']:.4f}", rl["bottleneck"],
                f"{rl['useful_flops_ratio']:.3f}", f"{rl['mfu']:.4f}"])
        return rows

    rows, us = _timed(run)
    if rows:
        _write("roofline_single_pod", ["arch", "shape", "gib_per_dev",
                                       "compute_s", "memory_s",
                                       "collective_s", "bottleneck",
                                       "useful_flops", "mfu"], rows)
        bn = [r[6] for r in rows]
        _emit("roofline_single_pod", us,
              f"{len(rows)} cells; bottlenecks: "
              f"compute={bn.count('compute')} memory={bn.count('memory')} "
              f"collective={bn.count('collective')}")
    else:
        _emit("roofline_single_pod", us, "no dry-run cells found (run "
              "python -m repro.launch.dryrun first)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    nets = QUICK_NETS if args.quick else PAPER_NETS
    cache_info = dict(enabled=True, dir=enable_compile_cache())

    print("name,us_per_call,derived")
    _emit("persistent_cache", 0.0, f"dir={cache_info['dir']}")
    sweeps, us = _timed(lambda: _sweeps(nets))
    _emit("dse_sweep_all", us, f"{len(nets)} networks x 150 configs")
    levels = bench_dse_scale(quick=args.quick)
    part = bench_partition_batch(nets)
    codesign = bench_codesign(nets, quick=args.quick)
    codesign_mega = bench_codesign_mega(nets, quick=args.quick)
    slack = bench_slack(nets, quick=args.quick)
    bench_table1_2(sweeps)
    bench_table3(sweeps)
    bench_table4(sweeps)
    chip = bench_table5(sweeps)
    bench_table6(sweeps, chip)
    bench_table7_8(nets)
    bench_fig5_6_7(sweeps)
    bench_autoshard()
    bench_pipeline_stages()
    bench_roofline_table()
    write_bench_json(levels, part, codesign, codesign_mega, slack,
                     cache_info, quick=args.quick)


if __name__ == "__main__":
    main()

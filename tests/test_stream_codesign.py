"""Streaming co-design path: `stream_layer_topk`'s full reduction set
(top-k + minima + per-layer minima + ≤bound boundary sets), its
chunk-size-invariant index tie-breaking (regression: duplicated config
rows), and `co_design_streaming == co_design` parity on small grids
(every backend × chunked × sharded) and on the extended 5,400-point
space."""

import dataclasses

import numpy as np
import pytest

from repro.core import accelerator, energymodel, hetero, partition, \
    topology

NETS = ("AlexNet", "VGG16", "MobileNet")


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in NETS}


@pytest.fixture(scope="module")
def grid():
    return accelerator.ConfigGrid.product(
        arrays=((16, 16), (32, 32), (64, 64)), gb_psum_kb=(13, 54, 216),
        gb_ifmap_kb=(27, 108))


@pytest.fixture(scope="module")
def dense(networks, grid):
    el, tl = energymodel.evaluate_networks(grid, networks, use_jax=False,
                                           per_layer=True)
    return el, tl


def _dup_grid(grid):
    """Grid with every row duplicated (dup of row i at index n + i):
    every metric value ties exactly with its twin."""
    n = grid.n
    idx = np.concatenate([np.arange(n), np.arange(n)])
    return accelerator.ConfigGrid(
        {k: v[idx] for k, v in grid.fields.items()}), n


def test_stream_layer_topk_tie_regression(networks, grid):
    """Duplicated latency/energy rows: the top-k must keep the LOWER
    flat index of each tied pair, identically at every chunk size."""
    dgrid, n = _dup_grid(grid)
    k = 6
    ref = None
    for use_jax in (False, True):
        for chunk in (3, 7, 16, dgrid.n):
            lt = energymodel.stream_layer_topk(
                dgrid, networks, topk=k, chunk_size=chunk,
                use_jax=use_jax)
            if ref is None:
                ref, ref_v = lt.topk_idx, lt.topk_metric
            np.testing.assert_array_equal(
                lt.topk_idx, ref, err_msg=f"jax={use_jax} chunk={chunk}")
    # ties (every value has an exact twin) order by ascending index: the
    # best entry is always a low twin, and each tied run is idx-sorted
    assert (ref[0] < n).all()
    tied = ref_v[:-1] == ref_v[1:]
    assert (ref[:-1][tied] < ref[1:][tied]).all()


def test_stream_networks_topk_tie_regression(networks, grid):
    """Same regression through stream_networks' aggregate top-k."""
    dgrid, n = _dup_grid(grid)
    ref = None
    for use_jax in (False, True):
        for chunk in (5, 11, dgrid.n):
            sr = energymodel.stream_networks(
                dgrid, networks, topk=5, chunk_size=chunk,
                use_jax=use_jax)
            if ref is None:
                ref, ref_v = sr.topk_idx, sr.topk_metric
            np.testing.assert_array_equal(
                sr.topk_idx, ref, err_msg=f"jax={use_jax} chunk={chunk}")
    assert (ref[0] < n).all()
    tied = ref_v[:-1] == ref_v[1:]
    assert (ref[:-1][tied] < ref[1:][tied]).all()


def test_stream_layer_reductions_match_dense(networks, grid, dense):
    """Minima, argmins, per-layer minima, and boundary sets all equal the
    dense per-layer reference, for every chunk size and backend."""
    el, tl = dense
    es, ts = el.sum(-1), tl.sum(-1)
    edp = es * ts
    lens = energymodel.network_layer_counts(networks)
    bound = 0.10
    for kw in (dict(use_jax=False), dict(use_jax=True),
               dict(use_jax=True, shard=True)):
        for chunk in (7, grid.n):
            lt = energymodel.stream_layer_topk(
                grid, networks, topk=4, chunk_size=chunk, bound=bound,
                **kw)
            np.testing.assert_allclose(lt.min_energy, es.min(0),
                                       rtol=1e-9)
            np.testing.assert_allclose(lt.min_latency, ts.min(0),
                                       rtol=1e-9)
            np.testing.assert_allclose(lt.min_edp, edp.min(0), rtol=1e-9)
            np.testing.assert_allclose(lt.min_metric, edp.min(0),
                                       rtol=1e-9)
            np.testing.assert_array_equal(lt.argmin, edp.argmin(0))
            for j, nm in enumerate(networks):
                L = lens[j]
                lm = el[:, j, :L] * tl[:, j, :L]
                np.testing.assert_allclose(
                    lt.layer_min_metric[j, :L], lm.min(0), rtol=1e-9)
                np.testing.assert_array_equal(
                    lt.layer_argmin[j, :L], lm.argmin(0))
                # padded layer tail: +inf metric, -1 argmin
                assert np.all(np.isinf(lt.layer_min_metric[j, L:]))
                assert np.all(lt.layer_argmin[j, L:] == -1)
                # boundary set == dense threshold set, metric-sorted
                want = np.flatnonzero(edp[:, j]
                                      <= edp[:, j].min() * (1 + bound))
                assert set(lt.boundary_idx[nm]) == set(want), (kw, chunk)
                v = lt.boundary_metric(nm)
                assert (np.diff(v) >= 0).all()
                np.testing.assert_allclose(
                    v, edp[lt.boundary_idx[nm], j], rtol=1e-9)


def test_stream_layer_topk_without_bound(networks, grid):
    lt = energymodel.stream_layer_topk(grid, networks, topk=3,
                                       chunk_size=8, use_jax=False)
    assert lt.bound is None and lt.boundary_idx is None
    assert lt.min_energy is not None          # minima always maintained


def test_codesign_problems_streaming_parity(networks, grid):
    """Streamed problem sets equal dense ones — pool, solver tensors, and
    scoring references — for every backend, chunked and sharded."""
    dense_p = hetero.codesign_problems(grid, networks, 3, max_types=2,
                                       pool_size=4)
    combos = [dict(use_jax=False), dict(use_jax=True),
              dict(use_jax=True, shard=True)]
    for kw in combos:
        for chunk in (7, grid.n):
            sp = hetero.codesign_problems_streaming(
                grid, networks, 3, max_types=2, pool_size=4,
                chunk_size=chunk, **kw)
            assert sp.pool == dense_p.pool, (kw, chunk)
            assert sp.chips == dense_p.chips
            np.testing.assert_allclose(sp.lat_dense, dense_p.lat_dense,
                                       rtol=1e-9)
            np.testing.assert_allclose(sp.min_energy, dense_p.min_energy,
                                       rtol=1e-9)
            np.testing.assert_allclose(sp.min_latency,
                                       dense_p.min_latency, rtol=1e-9)
            np.testing.assert_allclose(sp.min_edp, dense_p.min_edp,
                                       rtol=1e-9)


def test_codesign_problems_streaming_reuses_stream(networks, grid):
    lt = energymodel.stream_layer_topk(grid, networks, topk=4, bound=0.05,
                                       chunk_size=16, use_jax=False)
    sp = hetero.codesign_problems_streaming(
        grid, networks, 3, max_types=2, pool_size=4, stream=lt,
        use_jax=False)
    dense_p = hetero.codesign_problems(grid, networks, 3, max_types=2,
                                       pool_size=4, use_jax=False)
    assert sp.pool == dense_p.pool
    # a stream without boundary sets is rejected
    bare = energymodel.stream_layer_topk(grid, networks, topk=4,
                                         chunk_size=16, use_jax=False)
    with pytest.raises(ValueError, match="boundary"):
        hetero.codesign_problems_streaming(grid, networks, 3, stream=bare)
    # a stream with too small a top-k is rejected
    small = energymodel.stream_layer_topk(grid, networks, topk=2,
                                          bound=0.05, chunk_size=16,
                                          use_jax=False)
    with pytest.raises(ValueError, match="top-k too small"):
        hetero.codesign_problems_streaming(grid, networks, 3,
                                           pool_size=4, stream=small)


def test_candidate_pool_dedups_identical_rows(networks, grid):
    """A duplicated grid row can never occupy two pool slots — and the
    pool of the duplicated grid maps 1:1 onto the original's (low-index
    twins), streamed and dense alike."""
    dgrid, n = _dup_grid(grid)
    base = hetero.codesign_problems(grid, networks, 3, max_types=2,
                                    pool_size=4, use_jax=False)
    dup = hetero.codesign_problems(dgrid, networks, 3, max_types=2,
                                   pool_size=4, use_jax=False)
    assert [p % n for p in dup.pool] == base.pool
    assert all(p < n for p in dup.pool)        # low twins win ties
    sdup = hetero.codesign_problems_streaming(
        dgrid, networks, 3, max_types=2, pool_size=4, chunk_size=13,
        use_jax=False)
    assert sdup.pool == dup.pool


def test_streaming_topk_saturation_warns_and_topk_recovers(networks,
                                                           grid):
    """A grid whose rows are duplicated 5× can saturate the per-network
    top-k with copies of one row, hiding distinct rows the dense top-up
    would reach: the streamed builder must WARN about the short pool,
    and a larger topk= must restore dense-pool equivalence."""
    import warnings as _warnings
    n = grid.n
    idx = np.concatenate([np.arange(n)] * 5)
    dgrid = accelerator.ConfigGrid(
        {k: v[idx] for k, v in grid.fields.items()})
    dense_p = hetero.codesign_problems(dgrid, networks, 3, max_types=2,
                                       pool_size=4, bound=1e-9,
                                       use_jax=False)
    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter("always")
        hetero.codesign_problems_streaming(
            dgrid, networks, 3, max_types=2, pool_size=4, bound=1e-9,
            chunk_size=13, use_jax=False)
    # the saturation precondition (a top-k with < pool_size distinct
    # rows) holds here whatever the pool length came out as — it MUST
    # have been flagged
    assert any("saturate" in str(w.message) for w in rec)
    # remedy: a top-k deep enough to see past the copies — no warning
    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter("always")
        full = hetero.codesign_problems_streaming(
            dgrid, networks, 3, max_types=2, pool_size=4, bound=1e-9,
            chunk_size=13, use_jax=False, topk=4 * 5)
    assert not any("saturate" in str(w.message) for w in rec)
    assert full.pool == dense_p.pool


def test_streaming_rejects_mismatched_stream(networks, grid):
    lt = energymodel.stream_layer_topk(grid, networks, topk=4, bound=0.05,
                                       chunk_size=16, use_jax=False)
    other = accelerator.ConfigGrid(
        {k: np.concatenate([v, v]) for k, v in grid.fields.items()})
    with pytest.raises(ValueError, match="wrong grid"):
        hetero.codesign_problems_streaming(other, networks, 3,
                                           pool_size=4, stream=lt)
    with pytest.raises(ValueError, match="bound, metric"):
        hetero.codesign_problems_streaming(grid, networks, 3, pool_size=4,
                                           bound=0.10, stream=lt)


def test_co_design_streaming_matches_dense_small(networks, grid):
    cd = hetero.co_design(grid, networks, m_cores=3, max_types=2,
                          pool_size=4)
    cs = hetero.co_design_streaming(grid, networks, m_cores=3,
                                    max_types=2, pool_size=4,
                                    chunk_size=11)
    assert cs.pool == cd.pool
    assert cs.core_types == cd.core_types
    assert cs.core_counts == cd.core_counts
    assert cs.schedules == cd.schedules
    assert cs.energy == cd.energy and cs.latency == cd.latency
    assert cs.score == pytest.approx(cd.score, rel=1e-9)
    assert cs.homogeneous_score == pytest.approx(cd.homogeneous_score,
                                                 rel=1e-9)


@pytest.mark.slow
def test_co_design_streaming_extended_grid_parity(networks):
    """ISSUE 5 acceptance: streamed co-design reproduces the dense path
    on the extended 5,400-point space — every backend, chunked and
    chunked+sharded.  Steps 2–4 are shared code, so pool equality makes
    the winning chip and every schedule bit-identical."""
    egrid = accelerator.extended_grid()
    cd = hetero.co_design(egrid, networks, m_cores=4, max_types=3,
                          pool_size=6)
    combos = [dict(use_jax=False), dict(use_jax=True),
              dict(use_jax=True, shard=True)]
    for kw in combos:
        cs = hetero.co_design_streaming(egrid, networks, m_cores=4,
                                        max_types=3, pool_size=6,
                                        chunk_size=1024, **kw)
        assert cs.pool == cd.pool, kw
        assert cs.core_types == cd.core_types, kw
        assert cs.core_counts == cd.core_counts, kw
        assert cs.schedules == cd.schedules, kw
        assert cs.energy == cd.energy, kw
        assert cs.score == pytest.approx(cd.score, rel=1e-9)


@pytest.mark.slow
def test_pareto_codesign_streaming_vs_dense_problems(networks, grid):
    """The Pareto sweep is agnostic to how the problem set was built:
    streamed and dense problems give identical frontiers and winners."""
    dp = hetero.codesign_problems(grid, networks, 3, max_types=2,
                                  pool_size=4)
    sp = hetero.codesign_problems_streaming(grid, networks, 3,
                                            max_types=2, pool_size=4,
                                            chunk_size=9)
    res_d = partition.batch_schedule_hetero(dp.lat_dense, dp.counts,
                                            n_layers=dp.n_layers_b)
    res_s = partition.batch_schedule_hetero(sp.lat_dense, sp.counts,
                                            n_layers=sp.n_layers_b)
    deadlines = np.linspace(0.3, 1.2, 8)
    pd_ = hetero.pareto_codesign(dp, res_d, deadlines=deadlines)
    ps = hetero.pareto_codesign(sp, res_s, deadlines=deadlines)
    np.testing.assert_array_equal(pd_.best_chip, ps.best_chip)
    np.testing.assert_array_equal(pd_.net_frontier, ps.net_frontier)
    np.testing.assert_allclose(pd_.scores, ps.scores, rtol=1e-9)


def test_pool_ignores_fold_rounding_of_the_minima():
    """On a TPU the fold's ``min_metric``/``topk_metric`` come from
    emulated float64, a rounding step away from the host's ``e * t`` of
    the same boundary rows.  Every network's argmin ties at relative
    metric 1.0 in the pool top-up, so the pool must not depend on which
    way each minimum rounded."""
    egrid = accelerator.extended_grid()
    nets = {n: topology.get_network(n) for n in topology.NETWORKS}
    st = energymodel.stream_layer_topk(egrid, nets, topk=6, bound=0.05,
                                       metric="edp", chunk_size=2048,
                                       backend="numpy")
    ref = hetero.codesign_problems_streaming(
        egrid, nets, 4, max_types=3, pool_size=6, stream=st).pool
    j = np.arange(len(nets))
    step = 1.0 + np.where(j % 2, 1, -1) * (j + 1) * 2.0 ** -52
    rounded = dataclasses.replace(st, min_metric=st.min_metric * step,
                                  topk_metric=st.topk_metric * step)
    assert not np.array_equal(rounded.min_metric, st.min_metric)
    got = hetero.codesign_problems_streaming(
        egrid, nets, 4, max_types=3, pool_size=6, stream=rounded).pool
    assert got == ref

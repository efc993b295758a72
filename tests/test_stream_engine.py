"""Device-sharded, chunked streaming DSE engine: chunk/shard/stream parity
with the one-call engine, streaming chip design equivalence, and the
batched (networks × cores) partition solver vs the DP oracle."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (accelerator, dse, energymodel, hetero, partition,
                        topology)

NETS = ("AlexNet", "VGG16", "MobileNet")


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in NETS}


@pytest.fixture(scope="module")
def grid150():
    return accelerator.ConfigGrid.product()


@pytest.fixture(scope="module")
def full150(networks, grid150):
    return energymodel.evaluate_networks(grid150, networks, use_jax=False)


# ---------------------------------------------------------------------------
# chunked evaluation
# ---------------------------------------------------------------------------

def test_chunked_matches_one_call_numpy(networks, grid150, full150):
    """Per-chunk dedup + bucket padding is invisible: bit-identical to the
    unchunked numpy engine (same per-row arithmetic)."""
    e0, t0 = full150
    for chunk in (32, 64, 150):
        e1, t1 = energymodel.evaluate_networks(
            grid150, networks, use_jax=False, chunk_size=chunk)
        np.testing.assert_allclose(e1, e0, rtol=1e-12)
        np.testing.assert_allclose(t1, t0, rtol=1e-12)


def test_chunked_matches_one_call_jax(networks, grid150, full150):
    e0, t0 = full150
    e1, t1 = energymodel.evaluate_networks(grid150, networks, use_jax=True,
                                           chunk_size=64)
    np.testing.assert_allclose(e1, e0, rtol=1e-12)
    np.testing.assert_allclose(t1, t0, rtol=1e-12)


def test_grid_take_and_slice(grid150):
    idx = np.array([3, 17, 149, 0])
    sub = grid150.take(idx)
    assert sub.n == 4
    for k, v in sub.fields.items():
        np.testing.assert_array_equal(v, grid150.fields[k][idx])
    sl = grid150.slice_rows(10, 20)
    assert sl.n == 10
    assert sl.config_at(0).label() == grid150.config_at(10).label()


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------

def test_sharded_matches_unsharded(networks, grid150, full150):
    """shard=True must agree with the numpy reference for any device count
    (a 1-device mesh degenerates to the plain kernel)."""
    e0, t0 = full150
    e1, t1 = energymodel.evaluate_networks(grid150, networks, use_jax=True,
                                           shard=True)
    np.testing.assert_allclose(e1, e0, rtol=1e-12)
    np.testing.assert_allclose(t1, t0, rtol=1e-12)
    e2, t2 = energymodel.evaluate_networks(grid150, networks, use_jax=True,
                                           shard=True, chunk_size=64)
    np.testing.assert_allclose(e2, e0, rtol=1e-12)
    np.testing.assert_allclose(t2, t0, rtol=1e-12)


@pytest.mark.slow
def test_sharded_multi_device_subprocess():
    """Real multi-device parity: a fresh process forced to 4 host devices
    must reproduce the numpy reference through both sharded paths."""
    script = textwrap.dedent("""
        import numpy as np
        from repro.core import accelerator, energymodel, topology
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        nets = {n: topology.get_network(n) for n in ("AlexNet", "VGG16")}
        grid = accelerator.ConfigGrid.product(
            rf_psum_words=accelerator.RF_PSUM_SIZES)
        e0, t0 = energymodel.evaluate_networks(grid, nets, use_jax=False)
        e1, t1 = energymodel.evaluate_networks(grid, nets, use_jax=True,
                                               shard=True)
        np.testing.assert_allclose(e1, e0, rtol=1e-9)
        np.testing.assert_allclose(t1, t0, rtol=1e-9)
        e2, t2 = energymodel.evaluate_networks(grid, nets, use_jax=True,
                                               shard=True, chunk_size=128)
        np.testing.assert_allclose(e2, e0, rtol=1e-9)
        sr = energymodel.stream_networks(grid, nets, chunk_size=128,
                                         use_jax=True, shard=True)
        edp = e0 * t0
        np.testing.assert_allclose(sr.min_metric, edp.min(0), rtol=1e-9)
        assert np.array_equal(sr.argmin, edp.argmin(0))
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = (os.path.abspath("src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_request_host_devices_after_jax_import():
    """jax is already initialised in-process: the helper must refuse (the
    flag can no longer take effect) and leave XLA_FLAGS untouched."""
    import jax                                          # noqa: F401
    before = os.environ.get("XLA_FLAGS")
    assert energymodel.request_host_devices(4) is False
    assert os.environ.get("XLA_FLAGS") == before


# ---------------------------------------------------------------------------
# streaming reductions
# ---------------------------------------------------------------------------

def _check_stream_against_full(sr, e0, t0, metric="edp", bound=0.05):
    val = energymodel._metric_of(metric, e0, t0)
    np.testing.assert_allclose(sr.min_energy, e0.min(0), rtol=1e-12)
    np.testing.assert_allclose(sr.min_latency, t0.min(0), rtol=1e-12)
    np.testing.assert_allclose(sr.min_metric, val.min(0), rtol=1e-12)
    assert np.array_equal(sr.argmin, val.argmin(0))
    for j, nm in enumerate(sr.networks):
        mn = val[:, j].min()
        want = np.flatnonzero(val[:, j] <= mn * (1.0 + bound))
        assert np.array_equal(np.sort(sr.boundary_idx[nm]), want)
        # boundary arrays are metric-sorted, best cell first
        bm = sr.boundary_metric(nm)
        assert np.all(np.diff(bm) >= 0)
        assert sr.boundary_idx[nm][0] == val[:, j].argmin()
        # top-k values equal the k smallest of the full column
        k = sr.topk_metric.shape[0]
        want_top = np.sort(val[:, j])[:k]
        np.testing.assert_allclose(sr.topk_metric[:, j], want_top,
                                   rtol=1e-12)


def test_stream_matches_full_numpy(networks, grid150, full150):
    e0, t0 = full150
    sr = energymodel.stream_networks(grid150, networks, chunk_size=32,
                                     use_jax=False)
    assert sr.n_cfg == grid150.n
    _check_stream_against_full(sr, e0, t0)


def test_stream_matches_full_jax(networks, grid150, full150):
    e0, t0 = full150
    sr = energymodel.stream_networks(grid150, networks, chunk_size=64,
                                     use_jax=True)
    _check_stream_against_full(sr, e0, t0)


def test_stream_other_metric(networks, grid150, full150):
    e0, t0 = full150
    sr = energymodel.stream_networks(grid150, networks, chunk_size=64,
                                     use_jax=False, metric="energy")
    assert np.array_equal(sr.argmin, e0.argmin(0))
    np.testing.assert_allclose(sr.min_metric, e0.min(0), rtol=1e-12)


# ---------------------------------------------------------------------------
# streaming chip design ≡ full design_chip
# ---------------------------------------------------------------------------

def test_design_chip_streaming_equivalence():
    names = ("VGG16", "GoogleNet", "ResNet50", "MobileNet", "AlexNet",
             "Xception")
    nets = {n: topology.get_network(n) for n in names}
    sweeps = dse.sweep_networks(nets, use_jax=False)
    grid = accelerator.ConfigGrid.product()
    shape = next(iter(sweeps.values())).edp.shape

    for max_cores in (2, 3):
        chip = hetero.design_chip(sweeps, bound=0.05, max_cores=max_cores)
        sr = dse.stream_grid(nets, grid, chunk_size=50, use_jax=False,
                             bound=0.05)
        schip = hetero.design_chip_streaming(sr, grid, nets,
                                             max_cores=max_cores,
                                             use_jax=False)
        assert schip.core_cells(shape) == chip.core_types
        assert schip.assignment == chip.assignment
        for nm in names:
            want = [int(np.ravel_multi_index(c, shape))
                    for c in chip.candidate_sets[nm]]
            assert schip.candidate_sets[nm] == want


# ---------------------------------------------------------------------------
# batched partition solver vs the DP oracle (non-hypothesis path; the
# property test lives in test_partition.py)
# ---------------------------------------------------------------------------

def test_batch_partition_matches_dp_on_zoo():
    """All (18 networks × k∈2..8) pairs, both solver backends: pipeline
    latencies identical to dp_partition."""
    cfg = accelerator.AcceleratorConfig()
    lats = [energymodel.simulate_network(
        cfg, topology.get_network(n), n).layer_latencies
        for n in topology.NETWORKS]
    ks = tuple(range(2, 9))
    dp = [{k: partition.dp_partition(lat, k) for k in ks} for lat in lats]
    for use_jax in (False, True):
        res = partition.batch_partition(lats, ks, use_jax=use_jax)
        for i in range(len(lats)):
            for k in ks:
                got, want = res[i][k], dp[i][k]
                assert got.pipeline_latency == want.pipeline_latency, (
                    topology.NETWORKS[i], k, use_jax)
                # a valid contiguous partition of everything
                assert got.boundaries[0] == 0
                assert list(got.boundaries) == sorted(set(got.boundaries))
                assert sum(got.loads) == pytest.approx(sum(lats[i]))
                assert got.speedup == pytest.approx(
                    sum(lats[i]) / got.pipeline_latency)


def test_batch_partition_edges():
    res = partition.batch_partition([[5.0]], [1, 3], use_jax=False)[0]
    assert res[1].loads == (5.0,) and res[3].loads == (5.0,)
    res = partition.batch_partition([[1.0, 2.0, 3.0]], [2, 7])[0]
    assert res[2].pipeline_latency == pytest.approx(3.0)
    assert res[7].n_cores == 3          # clamped to n_layers
    lat = np.arange(1.0, 11.0)
    got = partition.batch_partition([lat], [4])[0][4]
    assert got.pipeline_latency == partition.dp_partition(lat, 4).pipeline_latency

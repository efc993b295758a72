"""The per-layer metric path (`evaluate_networks(per_layer=True)`): parity
with the scalar per-layer reports and the aggregate path, across every
engine variant (numpy/jax × chunked × sharded), the streaming
per-layer top-k, and the warn-once backend-fallback contract."""

import warnings

import numpy as np
import pytest

from repro.core import accelerator, dse, energymodel, topology

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
def per_layer_np(networks, grid):
    return energymodel.evaluate_networks(grid, networks, use_jax=False,
                                         per_layer=True)


def test_shape_and_zero_padding(networks, grid, per_layer_np):
    el, tl = per_layer_np
    lens = energymodel.network_layer_counts(networks)
    assert el.shape == tl.shape == (grid.n, len(networks), lens.max())
    for j, nm in enumerate(networks):
        assert np.all(el[:, j, lens[j]:] == 0.0), nm
        assert np.all(tl[:, j, lens[j]:] == 0.0), nm
        assert np.all(el[:, j, :lens[j]] > 0.0), nm


def test_matches_scalar_layer_reports(networks, grid, per_layer_np):
    """Per-layer rows ≡ simulate_network's LayerReport values (the scalar
    §II.B.2 path), config by config."""
    el, tl = per_layer_np
    for i in (0, grid.n - 1):
        for j, (nm, layers) in enumerate(networks.items()):
            rep = energymodel.simulate_network(grid.config_at(i), layers,
                                               nm)
            np.testing.assert_allclose(
                el[i, j, :len(rep.layers)],
                [l.energy for l in rep.layers], rtol=1e-12)
            np.testing.assert_allclose(
                tl[i, j, :len(rep.layers)],
                [l.latency for l in rep.layers], rtol=1e-12)


def test_layer_sums_reproduce_aggregate_path(networks, grid, per_layer_np):
    """Summing the layer axis reproduces the default early-reduction path
    exactly — the two paths differ only in WHEN the sum happens."""
    el, tl = per_layer_np
    e0, t0 = energymodel.evaluate_networks(grid, networks, use_jax=False)
    np.testing.assert_allclose(el.sum(-1), e0, rtol=1e-12)
    np.testing.assert_allclose(tl.sum(-1), t0, rtol=1e-12)


def test_jax_chunked_sharded_parity(networks, grid, per_layer_np):
    """per_layer=True through the jitted, chunked, sharded, and
    chunked+sharded paths all agree with the numpy reference."""
    el, tl = per_layer_np
    for kw in (dict(), dict(chunk_size=7), dict(shard=True),
               dict(shard=True, chunk_size=7)):
        e1, t1 = energymodel.evaluate_networks(grid, networks,
                                               use_jax=True,
                                               per_layer=True, **kw)
        np.testing.assert_allclose(e1, el, rtol=1e-9, err_msg=str(kw))
        np.testing.assert_allclose(t1, tl, rtol=1e-9, err_msg=str(kw))


def test_dse_layer_metrics_wrapper(networks, grid, per_layer_np):
    el, tl = per_layer_np
    e1, t1 = dse.layer_metrics(networks, grid, use_jax=False)
    np.testing.assert_array_equal(e1, el)
    np.testing.assert_array_equal(t1, tl)


def test_stream_layer_topk_matches_dense(networks, grid, per_layer_np):
    """The streaming top-k keeps exactly the k best configs' per-layer
    rows, for every chunk size and backend."""
    el, tl = per_layer_np
    edp = el.sum(-1) * tl.sum(-1)
    k = 4
    for kw in (dict(use_jax=False), dict(use_jax=True),
               dict(use_jax=True, shard=True)):
        for chunk in (5, 16, grid.n):
            lt = energymodel.stream_layer_topk(grid, networks, topk=k,
                                               chunk_size=chunk, **kw)
            assert lt.n_cfg == grid.n
            for j, nm in enumerate(networks):
                want = np.argsort(edp[:, j], kind="stable")[:k]
                assert np.array_equal(lt.topk_idx[:, j], want), (kw, chunk)
                np.testing.assert_allclose(lt.layer_energy[:, j],
                                           el[want, j], rtol=1e-9)
                np.testing.assert_allclose(lt.layer_latency[:, j],
                                           tl[want, j], rtol=1e-9)
                np.testing.assert_allclose(
                    lt.topk_metric[:, j], edp[want, j], rtol=1e-9)


def test_aggregate_trace_sharing_unaffected(networks):
    """The default path still shares one trace across single-network
    sweeps (per_layer uses its own cache key and true segment lengths)."""
    grid = accelerator.ConfigGrid.product()
    dse.sweep_network(networks["AlexNet"], "AlexNet", use_jax=True)
    before = energymodel.jit_cache_stats()["traces"]
    dse.sweep_network(networks["VGG16"], "VGG16", use_jax=True)
    assert energymodel.jit_cache_stats()["traces"] == before


# ---------------------------------------------------------------------------
# warn-once auto-fallback + last_backend under forced fallback
# ---------------------------------------------------------------------------


def test_fallback_warns_exactly_once_per_process(networks, monkeypatch):
    """A degraded explicit backend warns ONCE per process per edge — not
    per call — last_backend() reports what actually ran, and the
    auto-selection (nothing requested) never warns."""
    monkeypatch.setattr(energymodel, "jax_available", lambda: False)
    monkeypatch.setattr(energymodel, "_FALLBACK_WARNED", set())
    grid = accelerator.ConfigGrid.product(arrays=((16, 16),),
                                          gb_psum_kb=(54,),
                                          gb_ifmap_kb=(54,))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        energymodel.evaluate_networks(grid, networks, backend="jax")
        assert energymodel.last_backend() == "numpy"
        energymodel.evaluate_networks(grid, networks, backend="jax")
        energymodel.stream_networks(grid, networks, backend="jax",
                                    chunk_size=8)
        assert energymodel.resolve_backend(None) == "numpy"
        energymodel.evaluate_networks(grid, networks)
    ours = [w for w in rec if issubclass(w.category, RuntimeWarning)
            and "falling back" in str(w.message)]
    assert len(ours) == 1, [str(w.message) for w in rec]
    assert "'jax'" in str(ours[0].message)
    assert energymodel.last_backend() == "numpy"

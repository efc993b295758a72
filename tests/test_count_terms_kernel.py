"""Fused Pallas count-terms kernel: parity against the pure-jnp oracle
(`ref.py`), the existing jax engine, and the numpy reference — plus the
backend auto-fallback contract and a hypothesis property sweep over random
layer/config rows."""

import numpy as np
import pytest

from repro.core import accelerator, energymodel, topology
from repro.kernels.count_terms import (count_term_layers,
                                       count_term_layers_ref,
                                       count_term_sums, count_term_sums_ref)
from repro.kernels.count_terms.kernel import CFG_COLUMNS, LAYER_FIELDS

NETS = ("AlexNet", "VGG16", "MobileNet")


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in NETS}


def _kernel_inputs(grid, networks):
    """Grid + networks → the engine operands the kernel consumes."""
    lay, segments = energymodel._stack_networks(networks)
    lay = {k: v[None, :] for k, v in lay.items()}
    cfgs = energymodel._cfg_struct_from_grid(np, grid)
    cfg_u, _ = energymodel._dedup_count_rows(cfgs)
    cfg_u = {k: v[:, None] for k, v in cfg_u.items()}
    return cfg_u, lay, segments


def _pallas_vs_ref(cfg_u, lay, segments, rtol=1e-12):
    with energymodel.x64():
        ref = np.asarray(count_term_sums_ref(cfg_u, lay, segments))
        out = np.stack([np.asarray(o)
                        for o in count_term_sums(cfg_u, lay, segments)])
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=rtol, atol=0.0)


def test_pallas_matches_ref_paper_grid(networks):
    """Interpret-mode kernel ≡ the pure-jnp oracle on the 150-pt space."""
    _pallas_vs_ref(*_kernel_inputs(accelerator.ConfigGrid.product(),
                                   networks))


def test_pallas_matches_ref_odd_blocks(networks):
    """Unique-row counts that don't divide the block sizes exercise the
    edge-padding path (row-0 repeats + zero segment columns)."""
    grid = accelerator.ConfigGrid.product(
        arrays=((12, 14), (16, 16), (64, 64)), gb_psum_kb=(13, 54, 216),
        gb_ifmap_kb=(27,))
    _pallas_vs_ref(*_kernel_inputs(grid, networks))


def test_per_layer_kernel_matches_ref(networks):
    """The segment-matmul-free per-layer variant ≡ the raw [14, n_u, L]
    term stack, and summing its segments reproduces count_term_sums."""
    cfg_u, lay, segments = _kernel_inputs(
        accelerator.ConfigGrid.product(
            arrays=((12, 14), (16, 16), (64, 64)),
            gb_psum_kb=(13, 54, 216), gb_ifmap_kb=(27,)), networks)
    with energymodel.x64():
        ref = np.asarray(count_term_layers_ref(cfg_u, lay))
        out = np.stack([np.asarray(o)
                        for o in count_term_layers(cfg_u, lay)])
        sums = np.stack([np.asarray(o)
                         for o in count_term_sums(cfg_u, lay, segments)])
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=1e-12, atol=0.0)
    seg_sums = np.stack([out[..., a:b].sum(-1) for a, b in segments],
                        axis=-1)
    np.testing.assert_allclose(seg_sums, sums, rtol=1e-12)


def test_per_layer_kernel_odd_blocks(networks):
    """Layer/row paddings of the per-layer kernel slice off cleanly."""
    grid = accelerator.ConfigGrid.product(
        arrays=((16, 16),), gb_psum_kb=(13, 27, 54), gb_ifmap_kb=(27, 54))
    cfg_u, lay, _ = _kernel_inputs(grid, {"AlexNet":
                                          networks["AlexNet"]})
    with energymodel.x64():
        ref = np.asarray(count_term_layers_ref(cfg_u, lay))
        out = np.stack([np.asarray(o)
                        for o in count_term_layers(cfg_u, lay,
                                                   block_u=4, block_l=8)])
    np.testing.assert_allclose(out, ref, rtol=1e-12, atol=0.0)


def test_pallas_backend_matches_jax_engine_5400_subsample(networks):
    """End-to-end backend parity on a subsample of the extended 5,400-pt
    space: pallas vs jax vs numpy, all within the bench guardrail (1e-6 —
    observed: machine eps)."""
    grid = accelerator.extended_grid().take(np.arange(0, 5400, 37))
    e_p, t_p = energymodel.evaluate_networks(grid, networks,
                                             backend="pallas")
    e_j, t_j = energymodel.evaluate_networks(grid, networks, backend="jax")
    e_n, t_n = energymodel.evaluate_networks(grid, networks,
                                             backend="numpy")
    np.testing.assert_allclose(e_p, e_j, rtol=1e-9)
    np.testing.assert_allclose(t_p, t_j, rtol=1e-9)
    np.testing.assert_allclose(e_p, e_n, rtol=1e-6)
    np.testing.assert_allclose(t_p, t_n, rtol=1e-6)


def test_pallas_routes_through_chunked_sharded_stream(networks):
    """backend="pallas" must flow through every engine path: chunked,
    sharded (1-device mesh degenerates), and streaming reductions."""
    grid = accelerator.ConfigGrid.product()
    e0, t0 = energymodel.evaluate_networks(grid, networks, use_jax=False)
    for kw in (dict(chunk_size=64), dict(shard=True),
               dict(shard=True, chunk_size=64)):
        e1, t1 = energymodel.evaluate_networks(grid, networks,
                                               backend="pallas", **kw)
        np.testing.assert_allclose(e1, e0, rtol=1e-9)
        np.testing.assert_allclose(t1, t0, rtol=1e-9)
        assert energymodel.last_backend() == "pallas"
    sr = energymodel.stream_networks(grid, networks, chunk_size=64,
                                     backend="pallas")
    edp = e0 * t0
    np.testing.assert_allclose(sr.min_metric, edp.min(0), rtol=1e-9)
    assert np.array_equal(sr.argmin, edp.argmin(0))


def test_backend_resolution_and_fallback(monkeypatch):
    assert energymodel.resolve_backend("pallas") == "pallas"
    assert energymodel.resolve_backend("numpy") == "numpy"
    assert energymodel.resolve_backend(None, True) == "jax"
    assert energymodel.resolve_backend(None, False) == "numpy"
    with pytest.raises(ValueError):
        energymodel.resolve_backend("tpu")
    monkeypatch.setattr(energymodel, "pallas_available", lambda: False)
    assert energymodel.resolve_backend("pallas") == "jax"
    monkeypatch.setattr(energymodel, "jax_available", lambda: False)
    assert energymodel.resolve_backend("pallas") == "numpy"
    assert energymodel.resolve_backend(None) == "numpy"


def test_tpu_platform_refuses_pallas_and_never_interprets(networks,
                                                         monkeypatch):
    """On a TPU the device path is jax: the Pallas kernel is not reported
    runnable, an explicit request raises (naming the 64-bit reason)
    before any kernel is traced or dispatched, and nothing interprets."""
    from repro.kernels import default_interpret
    assert default_interpret() is True                 # the CPU here
    monkeypatch.setattr(energymodel, "platform", lambda: "tpu")
    assert default_interpret() is False
    assert not energymodel.pallas_available()
    assert energymodel.resolve_backend(None) == "jax"
    assert energymodel.resolve_backend("jax") == "jax"
    before = energymodel.jit_cache_stats()
    grid = accelerator.ConfigGrid.product(arrays=((16, 16),),
                                          gb_psum_kb=(13,),
                                          gb_ifmap_kb=(27,))
    for call in (
            lambda: energymodel.evaluate_networks(grid, networks,
                                                  backend="pallas"),
            lambda: energymodel.stream_layer_topk(grid, networks,
                                                  backend="pallas")):
        with pytest.raises(energymodel.BackendUnavailable,
                           match="64-bit"):
            call()
    assert energymodel.jit_cache_stats() == before


def test_kernel_column_orders_match_engine():
    """The kernel's stacked operand orders must track the engine structs —
    a silent reorder would compute valid-looking garbage."""
    assert CFG_COLUMNS == energymodel._COUNT_COLUMNS
    from repro.core import rs_mapping
    lay = rs_mapping.layer_struct(
        np, [l for l in topology.get_network("AlexNet")
             if l.kind != "input"])
    assert tuple(lay.keys()) == LAYER_FIELDS


# ---------------------------------------------------------------------------
# hypothesis property sweep: random layer and config rows.  Guarded per-test
# (not module-level importorskip) so the parity tests above always run.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    _HAS_HYPOTHESIS = False


def _random_layer_rows(draw, n_lay):
    dims = st.integers(min_value=1, max_value=96)
    rows = {k: [] for k in LAYER_FIELDS}
    for _ in range(n_lay):
        c, m, k, s = (draw(dims), draw(dims),
                      draw(st.sampled_from([1, 3, 5, 7, 11])),
                      draw(st.sampled_from([1, 2])))
        ox = oy = max(1, draw(dims) // s)
        ix, iy = (ox - 1) * s + k, (oy - 1) * s + k
        kind = draw(st.sampled_from(["conv", "dw", "pool", "fc"]))
        is_acc = kind in ("conv", "fc")
        c_out = m if is_acc else c
        row = dict(
            c_ch=c, m=c_out, ky=k, kx=k, stride=s, ix=ix, iy=iy,
            oy=oy, ox=ox,
            macs=float(c * c_out * k * k * ox * oy),
            weight_words=float(c * c_out * k * k),
            ifmap_words=float(c * ix * iy),
            ofmap_words=float(c_out * ox * oy),
            is_acc=float(is_acc), is_dw=float(kind == "dw"),
            is_pool=float(kind == "pool"))
        for kk, v in row.items():
            rows[kk].append(float(v))
    return {k: np.asarray(v, dtype=np.float64)[None, :]
            for k, v in rows.items()}


if _HAS_HYPOTHESIS:
    def _property(f):
        return settings(max_examples=20, deadline=None)(
            given(st.data())(f))
else:                                                  # pragma: no cover
    _property = pytest.mark.skip(
        reason="property test needs hypothesis "
        "(pip install -r requirements-dev.txt)")


@_property
def test_pallas_property_random_rows(data):
    """Random (config rows × layer rows × segment splits): the fused
    kernel agrees with the oracle wherever the oracle is finite."""
    draw = data.draw
    n_u = draw(st.integers(min_value=1, max_value=9))
    n_lay = draw(st.integers(min_value=1, max_value=12))
    lay = _random_layer_rows(draw, n_lay)

    word_sizes = st.sampled_from([16.0, 64.0, 512.0, 4096.0, 110592.0])
    cfg_u = {
        "rows": st.sampled_from([8.0, 12.0, 16.0, 32.0, 64.0]),
        "cols": st.sampled_from([8.0, 14.0, 16.0, 32.0, 64.0]),
        "gb_ifmap_words": word_sizes, "gb_psum_words": word_sizes,
        "rf_ifmap_words": st.just(12.0),
        "rf_weight_words": st.sampled_from([96.0, 224.0]),
        "rf_psum_words": st.sampled_from([16.0, 24.0]),
    }
    cfg_u = {k: np.asarray([draw(s) for _ in range(n_u)],
                           dtype=np.float64)[:, None]
             for k, s in cfg_u.items()}

    cut = draw(st.integers(min_value=0, max_value=n_lay))
    segments = ((0, cut), (cut, n_lay)) if 0 < cut < n_lay \
        else ((0, n_lay),)
    _pallas_vs_ref(cfg_u, lay, segments, rtol=1e-10)

"""The engine's heavy stage — the 14 count terms of the grid kernel —
against a dedup-free oracle (`oracles.count_term_sums_ref`): the mapping
computed on the mapping-unique rows and gathered back to the count-unique
rows must equal the mapping computed on every count-unique row.  Plus the
jax backend against the numpy reference, the backend resolution
contract, and a hypothesis property sweep over random layer/config
rows."""

import jax.numpy as jnp
import numpy as np
import pytest

import oracles
from repro.core import accelerator, energymodel, topology

NETS = ("AlexNet", "VGG16", "MobileNet")


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in NETS}


def _heavy_inputs(grid, networks):
    """Grid + networks → the count-unique rows, the layer struct and the
    segments of the engine's heavy stage."""
    lay, segments = energymodel._stack_networks(networks)
    lay = {k: v[None, :] for k, v in lay.items()}
    cfgs = energymodel._cfg_struct_from_grid(np, grid)
    cfg_u, _ = energymodel._dedup_count_rows(cfgs)
    cfg_u = {k: v[:, None] for k, v in cfg_u.items()}
    return cfg_u, lay, segments


def _mapping_rows(cfg_u):
    """The engine's mapping level of the two-level dedup: the
    mapping-unique rows ([n_m, 1] columns) and the [n_u] gather index."""
    cfg_m, inv_m = energymodel._dedup_rows(
        {k: v[:, 0] for k, v in cfg_u.items()}, energymodel._MAPPING_COLUMNS)
    return {k: v[:, None] for k, v in cfg_m.items()}, inv_m


def _sums_vs_oracle(cfg_u, lay, segments, rtol=1e-12):
    """The heavy stage's per-network partial sums ≡ the oracle's; returns
    (mapping-unique rows, count-unique rows)."""
    cfg_m, inv_m = _mapping_rows(cfg_u)
    with energymodel.x64():
        ref = np.asarray(oracles.count_term_sums_ref(cfg_u, lay, segments))
        out = np.stack([
            np.broadcast_to(np.asarray(o), ref.shape[1:])
            for o in energymodel._term_sums_body(
                jnp, segments, cfg_m, cfg_u, lay, inv_m)])
    np.testing.assert_allclose(out, ref, rtol=rtol, atol=0.0)
    return inv_m.max() + 1, inv_m.shape[0]


def _layers_vs_oracle(cfg_u, lay):
    """The per-layer heavy stage ≡ the oracle's raw [14, n_u, L] stack;
    returns it."""
    cfg_m, inv_m = _mapping_rows(cfg_u)
    with energymodel.x64():
        ref = np.asarray(oracles.count_term_layers_ref(cfg_u, lay))
        out = np.stack([
            np.broadcast_to(np.asarray(o), ref.shape[1:])
            for o in energymodel._term_layers_body(jnp, cfg_m, cfg_u, lay,
                                                   inv_m)])
    np.testing.assert_allclose(out, ref, rtol=1e-12, atol=0.0)
    return out


def test_heavy_stage_matches_oracle_paper_grid(networks):
    """On the 150-point space, where the mapping runs on fewer rows than
    the counts (GB_psum only enters the counts), so the gather is real."""
    n_m, n_u = _sums_vs_oracle(*_heavy_inputs(
        accelerator.ConfigGrid.product(), networks))
    assert n_m < n_u


def test_heavy_stage_matches_oracle_odd_blocks(networks):
    """Odd row counts (irregular arrays, three GB_psum sizes) and a
    mapping row shared by an uneven number of count rows."""
    grid = accelerator.ConfigGrid.product(
        arrays=((12, 14), (16, 16), (64, 64)), gb_psum_kb=(13, 54, 216),
        gb_ifmap_kb=(27,))
    n_m, n_u = _sums_vs_oracle(*_heavy_inputs(grid, networks))
    assert n_m < n_u


def test_per_layer_kernel_matches_ref(networks):
    """The segment-reduction-free per-layer stage ≡ the raw [14, n_u, L]
    oracle stack, and summing its segments reproduces the aggregate
    stage's partial sums."""
    cfg_u, lay, segments = _heavy_inputs(
        accelerator.ConfigGrid.product(
            arrays=((12, 14), (16, 16), (64, 64)),
            gb_psum_kb=(13, 54, 216), gb_ifmap_kb=(27,)), networks)
    out = _layers_vs_oracle(cfg_u, lay)
    cfg_m, inv_m = _mapping_rows(cfg_u)
    with energymodel.x64():
        sums = np.stack([
            np.broadcast_to(np.asarray(o), out.shape[1:2] + (len(segments),))
            for o in energymodel._term_sums_body(
                jnp, segments, cfg_m, cfg_u, lay, inv_m)])
    seg_sums = np.stack([out[..., a:b].sum(-1) for a, b in segments],
                        axis=-1)
    np.testing.assert_allclose(seg_sums, sums, rtol=1e-12)


def test_per_layer_kernel_odd_blocks(networks):
    """One network and one array shape: every count row shares one of
    two mapping rows."""
    grid = accelerator.ConfigGrid.product(
        arrays=((16, 16),), gb_psum_kb=(13, 27, 54), gb_ifmap_kb=(27, 54))
    cfg_u, lay, _ = _heavy_inputs(grid, {"AlexNet": networks["AlexNet"]})
    _layers_vs_oracle(cfg_u, lay)


def test_jax_backend_matches_numpy_5400_subsample(networks):
    """End-to-end backend parity on a subsample of the extended 5,400-pt
    space: the jax device path against the numpy reference."""
    grid = accelerator.extended_grid().take(np.arange(0, 5400, 37))
    e_j, t_j = energymodel.evaluate_networks(grid, networks, backend="jax")
    assert energymodel.last_backend() == "jax"
    e_n, t_n = energymodel.evaluate_networks(grid, networks,
                                             backend="numpy")
    np.testing.assert_allclose(e_j, e_n, rtol=1e-9)
    np.testing.assert_allclose(t_j, t_n, rtol=1e-9)


def test_backend_resolution_and_fallback(monkeypatch):
    assert energymodel.BACKENDS == ("jax", "numpy")
    assert energymodel.resolve_backend("jax") == "jax"
    assert energymodel.resolve_backend("numpy") == "numpy"
    assert energymodel.resolve_backend(None, True) == "jax"
    assert energymodel.resolve_backend(None, False) == "numpy"
    for name in ("pallas", "tpu"):
        with pytest.raises(ValueError, match="backend must be one of"):
            energymodel.resolve_backend(name)
    monkeypatch.setattr(energymodel, "jax_available", lambda: False)
    monkeypatch.setattr(energymodel, "_FALLBACK_WARNED", set())
    with pytest.warns(RuntimeWarning, match="falling back to 'numpy'"):
        assert energymodel.resolve_backend("jax") == "numpy"
    assert energymodel.resolve_backend(None) == "numpy"


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
    rows: dict = {}
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
            rows.setdefault(kk, []).append(float(v))
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
def test_heavy_stage_property_random_rows(data):
    """Random (config rows × layer rows × segment splits), duplicate
    mapping rows included: the heavy stage agrees with the oracle."""
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
    _sums_vs_oracle(cfg_u, lay, segments, rtol=1e-10)

"""The engine's row dedup (``energymodel._dedup_rows``) gives exactly
``np.unique(axis=0)``'s answer: the same unique rows in the same
lexicographic order and the same inverse, on the mega grid's stream
chunks at both dedup levels, on heavily duplicated random grids, on
inputs whose key needs compaction, and on the pool's tiny inputs."""

import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import energymodel, obs

MEGA_CONFIG = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
               / "configs" / "mega49k-cnn18.json")
CHUNK = 9800


def _reference_dedup(cfgs, columns):
    """The dedup as ``np.unique`` over whole rows computes it."""
    key = np.stack([cfgs[k] for k in columns], axis=1)
    uniq, inv = np.unique(key, axis=0, return_inverse=True)
    return dict(zip(columns, uniq.T.copy())), inv.astype(np.int32)


def _mega_fields():
    """The benchmark configuration's grid, outer to inner axis as its file
    states, every other column at its ``base`` value."""
    g = json.loads(MEGA_CONFIG.read_text())["grid"]
    arrays = np.asarray(g["arrays"], np.float64)
    axes = (np.arange(len(arrays)), g["gb_psum_kb"], g["gb_ifmap_kb"],
            g["rf_psum_words"], g["noc_wpc"])
    ai, ps, ifm, rf, nw = (np.asarray(a, np.float64).ravel() for a in
                           np.meshgrid(*axes, indexing="ij"))
    out = {k: np.full(ai.size, float(v)) for k, v in g["base"].items()}
    out.update(rows=arrays[ai.astype(np.intp), 0],
               cols=arrays[ai.astype(np.intp), 1],
               gb_psum_kb=ps, gb_ifmap_kb=ifm, rf_psum_words=rf,
               noc_wpc=nw)
    return out


def _mega_chunk(c):
    f = _mega_fields()
    return {k: v[c * CHUNK:(c + 1) * CHUNK] for k, v in f.items()}


def _mega_case(c, level):
    cfgs = energymodel._cfg_struct_from_grid(np, _mega_chunk(c))
    if level == "count":
        return cfgs, energymodel._COUNT_COLUMNS
    cfg_u, _ = _reference_dedup(cfgs, energymodel._COUNT_COLUMNS)
    return cfg_u, energymodel._MAPPING_COLUMNS


def _random_case(seed, n, n_cols, n_vals):
    """Integer-valued columns drawn from a few values each: heavy
    duplication, every column's ranks interleaved."""
    g = np.random.default_rng(seed)
    cols = tuple(f"c{j}" for j in range(n_cols))
    return ({k: g.integers(0, n_vals, n).astype(np.float64) * 3.5 - 7.0
             for k in cols}, cols)


def _distinct_case(n=CHUNK, n_cols=7):
    """Every column all-distinct: the radix product 9,800**7 passes 2**62,
    so the partial key has to be compacted on the way."""
    g = np.random.default_rng(7)
    cols = tuple(f"c{j}" for j in range(n_cols))
    return {k: g.permutation(n).astype(np.float64) for k in cols}, cols


CASES = (
    [pytest.param(lambda c=c, lv=lv: _mega_case(c, lv), False,
                  id=f"mega-chunk{c}-{lv}")
     for c in range(5) for lv in ("count", "mapping")]
    + [pytest.param(lambda: _random_case(1, 5000, 7, 4), False,
                    id="random-5000x7-4vals"),
       pytest.param(lambda: _random_case(2, 20000, 6, 11), False,
                    id="random-20000x6-11vals"),
       pytest.param(lambda: _random_case(3, 3000, 1, 3), False,
                    id="random-3000x1-3vals"),
       pytest.param(_distinct_case, True, id="distinct-9800x7-rekey"),
       pytest.param(lambda: _random_case(4, 1, 7, 4), False, id="1-row"),
       pytest.param(lambda: _random_case(5, 6, 7, 2), False, id="6-rows")])


@pytest.mark.parametrize("make, rekeys", CASES)
def test_dedup_rows_matches_unique_axis0(make, rekeys):
    cfgs, columns = make()
    before = obs.total("dse.dedup.rekeys")
    got_u, got_inv = energymodel._dedup_rows(cfgs, columns)
    added = obs.total("dse.dedup.rekeys") - before
    want_u, want_inv = _reference_dedup(cfgs, columns)
    assert list(got_u) == list(columns)
    for k in columns:
        assert got_u[k].dtype == want_u[k].dtype
        np.testing.assert_array_equal(got_u[k], want_u[k])
    assert got_inv.dtype == np.int32
    np.testing.assert_array_equal(got_inv, want_inv)
    assert (added > 0) == rekeys


def test_prepare_fields_unchanged_on_mega_chunk(monkeypatch):
    """The stream's kernel inputs for a mega chunk, bucket padding
    included, equal those the whole-row ``np.unique`` dedup gives."""
    fc = _mega_chunk(2)
    args = (fc, energymodel._UNIQUE_BUCKET, energymodel._MAPPING_BUCKET)
    got = energymodel._prepare_fields(*args)
    monkeypatch.setattr(energymodel, "_dedup_rows", _reference_dedup)
    want = energymodel._prepare_fields(*args)
    assert got[1]["rows"].shape == (2048, 1)      # 1,960 rows, padded
    for g, w in zip(got, want):
        if isinstance(g, dict):
            assert list(g) == list(w)
            g, w = list(g.values()), list(w.values())
        else:
            g, w = [g], [w]
        for a, b in zip(g, w):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)

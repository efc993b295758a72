"""Silent-data-corruption defense: verifier invariants + scrubber.

Each fold invariant is individually violated against a hand-built
corrupted state and must be individually caught, with chunk / network /
row provenance asserted — plus the shadow-recompute mismatch path, the
at-rest payload checks, and the store scrubber's quarantine/recompute
loop (:mod:`repro.ft.verify`, ISSUE 10)."""

import json
import os

import numpy as np
import pytest

from repro.core import energymodel, topology
from repro.core.accelerator import ConfigGrid
from repro.ft.verify import (SHADOW_RTOL, FoldInvariantError,
                             ShadowMismatchError, StreamVerifier,
                             VerifyConfig, check_layer_topk_result,
                             scrub_layer_topk)
from repro.serving import store as store_mod

NAMES = ("NetA", "NetB")


def _verifier(kind="layer_topk", **kw):
    v = StreamVerifier(verify_fraction=0.0, **kw)
    v.bind(kind=kind, names=NAMES, metric="edp", topk=2, bound=0.1,
           backend="numpy")
    return v


def _layer_state():
    """A small SELF-CONSISTENT layer_topk fold state (2 nets, 2 layers,
    k=2): per-layer rows sum to the aggregates the rows were ranked by,
    top-k is lex-sorted, minima agree with the best top-k value."""
    top_e = np.array([[[1.0, 1.0], [1.5, 0.5]],
                      [[2.0, 1.0], [2.0, 2.0]]])     # [k, net, layer]
    top_t = np.array([[[1.0, 1.0], [1.0, 1.0]],
                      [[1.0, 1.0], [1.0, 1.0]]])
    es = top_e.sum(-1)                               # [k, net]
    ts = top_t.sum(-1)
    top_v = es * ts                                  # edp: [[4, 4], [6, 8]]
    top_i = np.array([[0, 5], [3, 7]])
    min_e = es.min(0)
    min_t = ts.min(0)
    min_edp = top_v.min(0)
    min_m = top_v[0].copy()
    argm = top_i[0].copy()
    lmin = np.array([[0.9, 0.9], [1.4, 0.4]])        # [net, layer]
    larg = np.array([[0, 0], [5, 5]])
    return [top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m,
            argm, lmin, larg]


def _networks_state():
    top_v = np.array([[4.0, 4.0], [6.0, 8.0]])
    top_i = np.array([[0, 5], [3, 7]])
    min_e = np.array([2.0, 2.0])
    min_t = np.array([2.0, 2.0])
    min_m = top_v[0].copy()
    argm = top_i[0].copy()
    return [min_e, min_t, min_m, argm, top_v, top_i]


def _fold(v, prev, new, **kw):
    v.check_fold(3, 15, 20, prev, new, **kw)


# -- each invariant individually violated → individually caught ------------

def test_clean_states_pass():
    v = _verifier()
    _fold(v, _layer_state(), _layer_state())
    vn = _verifier(kind="networks")
    _fold(vn, _networks_state(), _networks_state())
    assert v.stats["invariant_violations"] == 0
    assert vn.stats["invariant_violations"] == 0
    assert v.stats["invariant_checks"] == 1


@pytest.mark.parametrize("slot,label", ((4, "min_energy"),
                                        (5, "min_latency"),
                                        (6, "min_edp"),
                                        (9, "layer_min_metric")))
def test_monotone_minima_caught(slot, label):
    v = _verifier()
    new = _layer_state()
    new[slot] = np.asarray(new[slot]) + 0.5       # a running min went UP
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _layer_state(), new)
    err = ei.value
    assert err.invariant == "monotone_min"
    assert err.chunk == 3 and (err.start, err.stop) == (15, 20)
    assert err.network in NAMES
    assert label in str(err)
    assert v.stats["invariant_violations"] == 1


def test_monotone_min_metric_caught_networks_kind():
    v = _verifier(kind="networks")
    new = _networks_state()
    new[2] = new[2] + 1.0
    new[4] = new[4] + 1.0                 # keep min == top_v[0] consistent
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _networks_state(), new)
    assert ei.value.invariant == "monotone_min"
    assert ei.value.network == "NetA"


def test_topk_sort_violation_caught():
    v = _verifier()
    new = _layer_state()
    new[0] = np.array([[4.0, 4.0], [3.0, 8.0]])   # NetA rank-1 beats rank-0
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _layer_state(), new)
    assert ei.value.invariant == "topk_sorted"
    assert ei.value.network == "NetA"
    assert ei.value.row == 3                      # the out-of-order row


def test_topk_lex_tiebreak_violation_caught():
    """Equal values must still be index-sorted (the fold's lexsort)."""
    v = _verifier()
    new = _layer_state()
    new[0] = np.array([[4.0, 4.0], [4.0, 8.0]])   # tie on value ...
    new[1] = np.array([[3, 5], [0, 7]])           # ... but indices reversed
    new[7] = new[0][0].copy()
    new[8] = new[1][0].copy()
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _layer_state(), new)
    assert ei.value.invariant == "topk_sorted"


def test_topk_duplicate_index_caught():
    v = _verifier()
    new = _layer_state()
    new[1] = np.array([[0, 5], [0, 7]])           # grid row 0 twice in NetA
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _layer_state(), new)
    assert ei.value.invariant == "topk_unique"
    assert ei.value.network == "NetA"
    assert ei.value.row == 0


def test_unfilled_sentinel_slots_allowed():
    """-1 index sentinels carry +inf and may repeat — not duplicates."""
    v = _verifier()
    st = _layer_state()
    st[0] = np.array([[4.0, 4.0], [np.inf, np.inf]])
    st[1] = np.array([[0, 5], [-1, -1]])
    _fold(v, st, [np.array(a, copy=True) for a in st])
    assert v.stats["invariant_violations"] == 0


def test_min_not_equal_top_caught():
    v = _verifier()
    new = _layer_state()
    new[7] = new[7] * 0.5                 # min_m drifted from top_v[0]
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _layer_state(), new)
    assert ei.value.invariant == "min_equals_top"


def test_layer_sum_aggregate_mismatch_caught():
    """A corrupted per-layer row no longer reproduces the aggregate the
    fold ranked that config by — the invariant that catches finite
    corruption of the CARRIED top-k payload."""
    v = _verifier()
    new = _layer_state()
    new[2] = np.array(new[2], copy=True)
    new[2][1, 0, 0] *= 1.001              # NetA's rank-1 energy row
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, _layer_state(), new)
    err = ei.value
    assert err.invariant == "layer_sum_aggregate"
    assert err.network == "NetA"
    assert err.row == 3                   # flat grid row of the bad config


def test_boundary_hit_outside_bound_caught():
    v = _verifier()
    st = _layer_state()
    es = np.array([[10.0, 2.0]])          # NetA row metric 10*1=10 > 4*1.1
    ts = np.array([[1.0, 1.0]])
    mask = np.array([[True, False]])
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, st, [np.array(a, copy=True) for a in st],
              es=es, ts=ts, mask=mask)
    err = ei.value
    assert err.invariant == "boundary_bound"
    assert err.network == "NetA"
    assert err.row == 15                  # start + local row 0


def test_boundary_hit_below_min_caught():
    """A hit BELOW the running minimum means the min fold missed it."""
    v = _verifier()
    st = _layer_state()
    es = np.array([[1.0, 2.0]])           # metric 1 < min_m 4
    ts = np.array([[1.0, 1.0]])
    mask = np.array([[True, False]])
    with pytest.raises(FoldInvariantError) as ei:
        _fold(v, st, [np.array(a, copy=True) for a in st],
              es=es, ts=ts, mask=mask)
    assert ei.value.invariant == "boundary_bound"


@pytest.mark.parametrize("backend,rel,caught", [
    ("numpy", 1e-15, True),     # same arithmetic: exact bound
    ("jax", 1e-15, False),      # device rounding of e*t: tolerated
    ("jax", 1e-9, True),        # a real miss: caught on every backend
])
def test_boundary_below_min_tolerance_follows_backend(backend, rel, caught):
    """The host re-derives a hit's metric in IEEE float64; the fold's
    minimum came from the backend's own arithmetic (emulated float64 on
    a TPU).  A hit a rounding step below the minimum is that rounding on
    a device backend, and a missed update on the numpy one."""
    v = StreamVerifier(verify_fraction=0.0)
    v.bind(kind="layer_topk", names=NAMES, metric="edp", topk=2,
           bound=0.1, backend=backend)
    st = _layer_state()
    es = np.array([[4.0 * (1.0 - rel), 2.0]])     # min_m is 4 for NetA
    ts = np.array([[1.0, 1.0]])
    mask = np.array([[True, False]])
    cand = {"NetA": [(np.array([2]), es[0, :1], ts[0, :1])], "NetB": []}
    checks = (lambda: _fold(v, st, [np.array(a, copy=True) for a in st],
                            es=es, ts=ts, mask=mask),
              lambda: v.check_resume(st, cand))
    for check in checks:
        if caught:
            with pytest.raises(FoldInvariantError) as ei:
                check()
            assert ei.value.invariant == "boundary_bound"
        else:
            check()


def test_resume_state_nan_caught():
    v = _verifier()
    st = _layer_state()
    st[2][0, 0, 0] = np.nan
    with pytest.raises(FoldInvariantError) as ei:
        v.check_resume(st, {nm: [] for nm in NAMES})
    assert ei.value.invariant == "state_finite"
    assert ei.value.chunk is None         # resume provenance, not a chunk


def test_resume_candidate_below_min_caught():
    v = _verifier()
    cand = {"NetA": [(np.array([2]), np.array([1.0]), np.array([1.0]))],
            "NetB": []}
    with pytest.raises(FoldInvariantError) as ei:
        v.check_resume(_layer_state(), cand)
    err = ei.value
    assert err.invariant == "boundary_bound"
    assert err.network == "NetA" and err.row == 2


def test_invariants_opt_out():
    v = StreamVerifier(VerifyConfig(invariants=False, verify_fraction=0.0))
    v.bind(kind="layer_topk", names=NAMES, metric="edp", topk=2,
           bound=0.1, backend="numpy")
    bad = _layer_state()
    bad[7] = bad[7] * 0.5
    _fold(v, _layer_state(), bad)         # does not raise
    assert v.stats["invariant_checks"] == 0


# -- shadow recompute ------------------------------------------------------

def _shadow_verifier(ref_eval, **kw):
    v = StreamVerifier(verify_fraction=1.0, **kw)
    v.bind(kind="layer_topk", names=NAMES, metric="edp", topk=2,
           bound=0.1, backend="numpy", ref_eval=ref_eval)
    return v


def test_shadow_mismatch_provenance():
    e = np.ones((3, 2, 2))
    t = np.ones((3, 2, 2))
    e_ref = np.array(e, copy=True)
    e_ref[1, 0, 1] *= 1.0 + 1e-9          # fast path diverges there
    v = _shadow_verifier(lambda fc: (e_ref, t))
    with pytest.raises(ShadowMismatchError) as ei:
        v.check_chunk(2, 10, 13, None, e, t)
    err = ei.value
    assert err.chunk == 2 and (err.start, err.stop) == (10, 13)
    assert err.mismatches == [dict(row=11, network="NetA",
                                   term="energy[layer 1]",
                                   got=1.0, want=1.0 + 1e-9)]
    assert v.stats["shadow_mismatches"] == 1


def test_shadow_bitexact_on_numpy_cross_rtol_on_jax():
    """backend="numpy" compares bit-exactly; jax within SHADOW_RTOL, so
    ulp-level cross-backend noise never false-positives."""
    e = np.ones((2, 2, 2))
    t = np.ones((2, 2, 2))
    e_ref = e * (1.0 + 1e-15)             # one ulp-ish off
    v_np = _shadow_verifier(lambda fc: (e_ref, t))
    with pytest.raises(ShadowMismatchError):
        v_np.check_chunk(0, 0, 2, None, e, t)
    v_jax = StreamVerifier(verify_fraction=1.0)
    v_jax.bind(kind="layer_topk", names=NAMES, metric="edp", topk=2,
               bound=0.1, backend="jax", ref_eval=lambda fc: (e_ref, t))
    v_jax.check_chunk(0, 0, 2, None, e, t)        # within SHADOW_RTOL
    assert v_jax.stats["shadow_mismatches"] == 0
    assert v_jax._rtol == SHADOW_RTOL and v_np._rtol == 0.0


def test_shadow_catches_padding_row_corruption():
    """Padded rows are deterministic duplicates of the chunk's first row;
    corruption landing there is compared (and flagged) too."""
    e = np.ones((4, 2, 2))
    t = np.ones((4, 2, 2))
    e_bad = np.array(e, copy=True)
    e_bad[3, 1, 0] *= 1.001               # row 3 is padding (stop-start=2)
    v = _shadow_verifier(lambda fc: (e, t))
    with pytest.raises(ShadowMismatchError) as ei:
        v.check_chunk(0, 0, 2, None, e_bad, t)
    m = ei.value.mismatches[0]
    assert m["row"] == 0 and "padding" in m["term"]


def test_sampling_is_deterministic_and_fractional():
    picks = [StreamVerifier(verify_fraction=0.25, seed=7).sampled(ci)
             for ci in range(64)]
    again = [StreamVerifier(verify_fraction=0.25, seed=7).sampled(ci)
             for ci in range(64)]
    assert picks == again                 # (seed, chunk) alone decides
    assert 0 < sum(picks) < 64
    assert all(StreamVerifier(verify_fraction=1.0).sampled(ci)
               for ci in range(8))
    assert not any(StreamVerifier(verify_fraction=0.0).sampled(ci)
                   for ci in range(8))


def test_evidence_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY_EVIDENCE_DIR", str(tmp_path))
    e = np.ones((2, 2, 2))
    t = np.ones((2, 2, 2))
    v = _shadow_verifier(lambda fc: (e * 1.001, t))
    with pytest.raises(ShadowMismatchError):
        v.check_chunk(1, 5, 7, None, e, t)
    files = list(tmp_path.glob("shadow_mismatch_*.json"))
    assert len(files) == 1
    ev = json.loads(files[0].read_text())
    assert ev["chunk"] == 1
    assert ev["mismatches"][0]["network"] in NAMES


# -- at-rest checks + scrubber ---------------------------------------------

@pytest.fixture(scope="module")
def space():
    grid = ConfigGrid.product(arrays=((16, 16), (32, 32), (64, 64)),
                              gb_psum_kb=(13, 54, 216),
                              gb_ifmap_kb=(27, 108))
    networks = {n: topology.get_network(n)
                for n in ("AlexNet", "MobileNet")}
    st = energymodel.stream_layer_topk(grid, networks, topk=4, bound=0.05,
                                       chunk_size=6)
    return grid, networks, st


def _poisoned(st, rel=1.001):
    """Copy ``st`` with one top-k row's layer_energy cell scaled AND its
    ranking aggregate recomputed to match — a finite, SELF-CONSISTENT,
    checksum-proof corruption (the model of a fold poisoned by a wrong
    chunk evaluation, where value and rows corrupt together)."""
    arrays, meta = store_mod.stream_payload(st)
    for k, j in np.argwhere(np.asarray(st.topk_idx) >= 0):
        if k == 0:
            continue               # rank 0 would drag min_metric along too
        a = {kk: np.array(v, copy=True) for kk, v in arrays.items()}
        li = np.nonzero(a["layer_energy"][k, j])[0][0]
        a["layer_energy"][k, j, li] *= rel
        a["topk_metric"][k, j] = energymodel._metric_of(
            st.metric, a["layer_energy"][k, j].sum(),
            a["layer_latency"][k, j].sum())
        bad = store_mod.stream_from_payload(a, meta)
        if check_layer_topk_result(bad) is None:   # still sorted etc.
            return bad
    raise AssertionError("no poisonable self-consistent cell found")


def test_clean_result_passes_at_rest_checks(space):
    grid, networks, st = space
    assert check_layer_topk_result(st) is None
    assert scrub_layer_topk(st, grid, networks, rows=999) is None


def test_at_rest_structural_violations(space):
    _, _, st = space
    arrays, meta = store_mod.stream_payload(st)
    bad = {k: np.array(v, copy=True) for k, v in arrays.items()}
    bad["topk_metric"][0, 0], bad["topk_metric"][1, 0] = \
        bad["topk_metric"][1, 0], bad["topk_metric"][0, 0]
    reason = check_layer_topk_result(
        store_mod.stream_from_payload(bad, meta))
    assert reason is not None and "lex sorted" in reason

    bad2 = {k: np.array(v, copy=True) for k, v in arrays.items()}
    bad2["min_metric"][0] *= 0.5
    reason2 = check_layer_topk_result(
        store_mod.stream_from_payload(bad2, meta))
    assert reason2 is not None and "min_metric" in reason2


def test_scrub_catches_selfconsistent_poison(space):
    """The deep rung: a poisoned-but-SELF-CONSISTENT payload (both the
    ranking value and its per-layer rows corrupted together) passes every
    structural check and is only caught by re-deriving rows through the
    reference path."""
    grid, networks, st = space
    bad = _poisoned(st)
    assert check_layer_topk_result(bad) is None     # structure can't see it
    reason = scrub_layer_topk(bad, grid, networks, rows=999)
    assert reason is not None
    assert "diverges from the reference" in reason


def test_store_scrub_quarantines_with_reason(tmp_path, space):
    grid, networks, st = space
    store = store_mod.DurableStore(tmp_path)
    arrays, meta = store_mod.stream_payload(st)
    store.put(("g", "clean"), arrays=arrays, meta=meta)
    store.put(("g", "bad"), arrays=arrays, meta=dict(meta, poison=True))

    def checker(key_repr, a, m):
        return "injected reason" if m.get("poison") else None

    res = store.scrub(checker)
    assert res["scanned"] == 2 and res["bad"] == 1
    assert res["bad_keys"] == [repr(("g", "bad"))]
    assert store.get(("g", "bad")) is None          # gone (quarantined)
    assert store.get(("g", "clean")) is not None    # untouched
    reasons = list(store.quarantine.glob("*.reason"))
    assert len(reasons) == 1
    assert "injected reason" in reasons[0].read_text()
    assert store.stats["scrub_entries"] == 2
    assert store.stats["scrubbed_bad"] == 1


def test_store_scrub_integrity_and_cursor(tmp_path, space):
    _, _, st = space
    store = store_mod.DurableStore(tmp_path)
    arrays, meta = store_mod.stream_payload(st)
    for i in range(3):
        store.put(("g", i), arrays=arrays, meta=meta)
    # bit-rot one file on disk: the integrity rung (no checker) quarantines
    victim = sorted(store.entries.glob("*.npz"))[1]
    victim.write_bytes(victim.read_bytes()[:-7])
    seen, cursor = 0, None
    for _ in range(3):                     # one-entry incremental passes
        res = store.scrub(max_entries=1, cursor=cursor)
        seen += res["scanned"]
        cursor = res["cursor"]
    assert seen == 3
    assert store.stats["scrubbed_bad"] == 1
    assert sum(1 for _ in store.entries.glob("*.npz")) == 2

"""The one-device jax stream keeps the device queue full.

``stream_layer_topk`` enqueues each chunk's grid kernel and fold
together, and fetches chunk i only once chunk i+1's kernel and fold are
queued behind it (counted as ``dse.stream.fetch_ahead``).  The order of
enqueues and fetches changes nothing computed: results and ``on_chunk``
exports equal those of the serial order, in which every chunk commits
before the next one's fold is enqueued (what an installed, empty fault
plan forces), bit for bit.  Under a fault plan each fault fires with every
earlier chunk committed and exported.
"""

import time

import numpy as np
import pytest

from repro.core import energymodel, obs, topology
from repro.core.accelerator import ConfigGrid
from repro.ft.faults import FaultPlan, inject_chunk_faults
from repro.ft.verify import StreamVerifier

NETS = ("AlexNet", "MobileNet")
FIELDS = ("topk_idx", "topk_metric", "layer_energy", "layer_latency",
          "min_energy", "min_latency", "min_edp", "min_metric", "argmin",
          "layer_min_metric", "layer_argmin")
INDICES = ("topk_idx", "argmin", "layer_argmin")


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in NETS}


@pytest.fixture(scope="module")
def grid():
    return ConfigGrid.product(arrays=((16, 16), (32, 32), (64, 64)),
                              gb_psum_kb=(13, 54, 216),
                              gb_ifmap_kb=(27, 108))


def _stream(grid, networks, chunk, **kw):
    kw.setdefault("backend", "jax")
    return energymodel.stream_layer_topk(
        grid, networks, topk=4, bound=0.05, chunk_size=chunk, **kw)


def _traced(grid, networks, chunk, **kw):
    """One warm stream → (result, exports, its spans, its counter
    events)."""
    _stream(grid, networks, chunk, **kw)
    exports = []
    t0 = time.perf_counter()
    res = _stream(grid, networks, chunk, on_chunk=exports.append, **kw)
    t1 = time.perf_counter()
    return res, exports, obs.spans_between(t0, t1), obs.counts_between(t0,
                                                                        t1)


def _fetch_ahead(counts):
    return sum(c.n for c in counts if c.name == "dse.stream.fetch_ahead")


def _same_exports(got, want):
    assert [s.next_chunk for s in got] == [s.next_chunk for s in want]
    for a, b in zip(got, want):
        ea, eb = a.export_state(), b.export_state()
        assert set(ea) == set(eb)
        for key in ea:
            np.testing.assert_array_equal(np.asarray(ea[key]),
                                          np.asarray(eb[key]), err_msg=key)


@pytest.mark.parametrize("chunk", (3, 5, 7, 18))  # 6 / 4 / 3 / 1 chunks
def test_next_chunk_is_enqueued_before_each_fetch(grid, networks, chunk):
    n_chunks = -(-grid.n // chunk)
    _, exports, spans, counts = _traced(grid, networks, chunk)
    assert len(exports) == n_chunks
    dispatch = sorted((s for s in spans if s.name == "dse.stream.dispatch"),
                      key=lambda s: s.t0)
    wait = sorted((s for s in spans if s.name == "dse.stream.wait"),
                  key=lambda s: s.t0)
    # per chunk the kernel, then the fold; a fetch per chunk and the
    # final state's
    assert len(dispatch) == 2 * n_chunks
    assert len(wait) == n_chunks + 1
    for i in range(n_chunks - 1):
        kernel, fold = dispatch[2 * i + 2], dispatch[2 * i + 3]
        assert kernel.t1 <= wait[i].t0, i
        assert fold.t1 <= wait[i].t0, i
    # the last chunk's fetch has nothing queued behind it
    assert dispatch[-1].t1 <= wait[-2].t0
    assert _fetch_ahead(counts) == n_chunks - 1


@pytest.mark.parametrize("chunk", (3, 5, 18))
def test_results_and_exports_equal_the_serial_order(grid, networks, chunk):
    res, exports, _, _ = _traced(grid, networks, chunk)
    serial = []
    with inject_chunk_faults(FaultPlan()):      # drains every chunk
        want = _stream(grid, networks, chunk, on_chunk=serial.append)
    for f in FIELDS:
        np.testing.assert_array_equal(getattr(res, f), getattr(want, f),
                                      err_msg=f)
    for f in ("boundary_idx", "boundary_energy", "boundary_latency"):
        for nm in NETS:
            np.testing.assert_array_equal(getattr(res, f)[nm],
                                          getattr(want, f)[nm])
    _same_exports(exports, serial)

    # and the numpy backend's: the same rows chosen, values to rounding
    ref_exports = []
    ref = _stream(grid, networks, chunk, backend="numpy",
                  on_chunk=ref_exports.append)
    for f in FIELDS:
        if f in INDICES:
            np.testing.assert_array_equal(getattr(res, f), getattr(ref, f))
        else:
            np.testing.assert_allclose(getattr(res, f), getattr(ref, f),
                                       rtol=1e-12)
    for nm in NETS:
        np.testing.assert_array_equal(res.boundary_idx[nm],
                                      ref.boundary_idx[nm])
    assert ([s.next_chunk for s in exports]
            == [s.next_chunk for s in ref_exports])


@pytest.mark.parametrize("bad", (0, 2, 3))
def test_fault_fires_with_every_earlier_chunk_committed(grid, networks,
                                                        bad):
    _stream(grid, networks, 5)
    exports = []
    plan = FaultPlan(corrupt_at={bad: "nan"}, seed=3)
    t0 = time.perf_counter()
    with inject_chunk_faults(plan):
        with pytest.raises(energymodel.ChunkCorruption) as ei:
            _stream(grid, networks, 5, on_chunk=exports.append)
    counts = obs.counts_between(t0, time.perf_counter())
    assert ei.value.chunk == bad
    assert plan.fired == [(bad, "nan")]
    assert [s.next_chunk for s in exports] == list(range(1, bad + 1))
    # under a fault plan every chunk drains before the next one's fold
    assert _fetch_ahead(counts) == 0
    # the last export resumes to the clean result
    if exports:
        res = _stream(grid, networks, 5, resume_from=exports[-1])
        clean = _stream(grid, networks, 5)
        for f in FIELDS:
            np.testing.assert_array_equal(getattr(res, f),
                                          getattr(clean, f))


def test_verifier_sees_every_chunk_of_the_pipeline(grid, networks):
    v = StreamVerifier(verify_fraction=1.0)
    res, exports, _, counts = _traced(grid, networks, 5, verify=v)
    n_chunks = -(-grid.n // 5)
    assert v.stats["shadow_mismatches"] == 0
    assert v.stats["invariant_violations"] == 0
    assert v.stats["shadow_checks"] == 2 * n_chunks       # two runs
    assert v.stats["invariant_checks"] == 2 * n_chunks
    assert _fetch_ahead(counts) == n_chunks - 1
    plain = _stream(grid, networks, 5)
    for f in FIELDS:
        np.testing.assert_array_equal(getattr(res, f), getattr(plain, f))

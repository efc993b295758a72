"""In-program tracing (``repro.core.obs``): spans, counters, the ring, the
instrumented co-design path and the stable names of the jitted programs."""

import collections
import glob
import time

import numpy as np
import pytest

import jax

from repro.core import accelerator, energymodel, hetero, obs, partition
from repro.core import topology

NETS = ("AlexNet", "MobileNet", "ResNet50")
CHUNK = 40


@pytest.fixture(scope="module")
def grid():
    g = accelerator.ConfigGrid.product(
        arrays=((12, 14), (32, 32), (96, 96)), gb_psum_kb=(13, 54),
        gb_ifmap_kb=(13, 54, 216, 864), rf_psum_words=(16, 24),
        noc_words_per_cycle=(2.0, 4.0))
    assert g.n == 96
    return g


@pytest.fixture(scope="module")
def nets():
    return {n: topology.get_network(n) for n in NETS}


def _codesign(grid, nets):
    return hetero.co_design_streaming(grid, nets, 4, max_types=3,
                                      pool_size=6, bound=0.05, metric="edp",
                                      chunk_size=CHUNK)


def _traced_codesign(grid, nets):
    """One warm co-design → (result, its spans, its counter events)."""
    _codesign(grid, nets)
    t0 = time.perf_counter()
    cd = _codesign(grid, nets)
    t1 = time.perf_counter()
    return cd, obs.spans_between(t0, t1), obs.counts_between(t0, t1)


def test_spans_nest_and_share_the_outermost_id():
    t0 = time.perf_counter()
    with obs.span("dse.a"):
        with obs.span("dse.b"):
            with obs.span("dse.c"):
                time.sleep(0.002)
        with pytest.raises(KeyError):
            with obs.span("dse.b"):
                raise KeyError("recorded all the same")
    with obs.span("dse.d"):
        pass
    spans = obs.spans_between(t0, time.perf_counter())
    by = collections.defaultdict(list)
    for s in spans:
        by[s.name].append(s)
    (a,), (c,), (d,) = by["dse.a"], by["dse.c"], by["dse.d"]
    b = by["dse.b"]
    assert len(b) == 2
    assert a.parent_id is None and a.job_id == a.id
    assert all(s.parent_id == a.id and s.job_id == a.id for s in b)
    assert c.parent_id == b[0].id and c.job_id == a.id
    assert d.parent_id is None and d.job_id == d.id != a.id
    for s in spans:
        assert s.t0 <= s.t1
    assert a.t0 <= b[0].t0 <= c.t0 <= c.t1 <= b[0].t1 <= a.t1
    dur = {n: sum(s.t1 - s.t0 for s in v) for n, v in by.items()}
    assert obs.self_time(spans, "dse.a") == pytest.approx(
        dur["dse.a"] - dur["dse.b"])
    assert obs.self_time(spans, "dse.b") == pytest.approx(
        dur["dse.b"] - dur["dse.c"])
    assert obs.self_time(spans, "dse.c") == pytest.approx(dur["dse.c"])


def test_window_filtering_and_totals():
    before = obs.total("dse.test.n")
    t0 = time.perf_counter()
    with obs.span("dse.early"):
        obs.count("dse.test.n", 2)
    t_mid = time.perf_counter()
    with obs.span("dse.late"):
        obs.count("dse.test.n", 5)
    t1 = time.perf_counter()
    assert [s.name for s in obs.spans_between(t_mid, t1)] == ["dse.late"]
    assert [c.n for c in obs.counts_between(t_mid, t1)
            if c.name == "dse.test.n"] == [5]
    assert [c.n for c in obs.counts_between(t0, t1)
            if c.name == "dse.test.n"] == [2, 5]
    assert obs.spans_between(t1 + 1.0, t1 + 2.0) == []
    assert obs.total("dse.test.n") == before + 7


def test_ring_overflow_moves_oldest(monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=8))
    monkeypatch.setattr(obs, "_horizon", float("-inf"))
    t0 = time.perf_counter()
    for _ in range(8):
        obs.count("dse.test.fill")
    assert obs.oldest() == float("-inf")       # full, nothing dropped yet
    assert len(obs.counts_between(t0, time.perf_counter())) == 8
    for _ in range(12):
        obs.count("dse.test.fill")
    kept = list(obs._ring)
    assert len(kept) == 8
    assert kept[0].t >= obs.oldest() > t0
    # a window reaching back past what the ring dropped reads nothing
    t1 = time.perf_counter()
    assert obs.counts_between(t0, t1) is None
    assert obs.spans_between(t0, t1) is None
    got = obs.counts_between(kept[0].t, t1)
    assert len(got) == 8


def test_codesign_spans_per_chunk(grid, nets):
    _, spans, counts = _traced_codesign(grid, nets)
    n_chunks = -(-grid.n // CHUNK)
    names = collections.Counter(s.name for s in spans)
    # one root, and every span of the job carries its id
    (root,) = [s for s in spans if s.name == "dse.codesign"]
    assert root.parent_id is None
    assert {s.job_id for s in spans} == {root.id}
    for outer in ("dse.stream", "dse.pool", "dse.pool.eval",
                  "dse.schedule", "dse.score"):
        assert names[outer] == 1, outer
    # prep: once for the layer struct and hash, per chunk the slice and
    # pad and then _prepare_fields; dispatch: per chunk the grid kernel
    # and the fold; wait and collect: per chunk and once at the end
    assert names["dse.stream.prep"] == 2 * n_chunks + 1
    assert names["dse.stream.dispatch"] == 2 * n_chunks
    assert names["dse.stream.wait"] == n_chunks + 1
    assert names["dse.stream.collect"] == n_chunks + 1
    (stream,) = [s for s in spans if s.name == "dse.stream"]
    for s in spans:
        if s.name.startswith("dse.stream."):
            assert stream.t0 <= s.t0 <= s.t1 <= stream.t1
    rows = collections.defaultdict(list)
    for c in counts:
        rows[c.name].append(c.n)
    assert len(rows["dse.stream.rows_unique"]) == n_chunks
    for u, p in zip(rows["dse.stream.rows_unique"],
                    rows["dse.stream.rows_padded"]):
        assert 0 < u <= p
    assert rows["jit.call.grid_kernel_layers"] == [1] * (n_chunks + 1)
    assert rows["jit.call.layer_fold"] == [1] * n_chunks


def test_syncs_count_fetches_and_change_no_result(grid, nets, monkeypatch):
    want = _codesign(grid, nets)
    sites = []
    fetch = obs.fetch

    def spy(name, *arrays):
        sites.append(name)
        return fetch(name, *arrays)

    monkeypatch.setattr(obs, "fetch", spy)
    # a ring already full: every event recorded now drops an older one
    monkeypatch.setattr(obs, "_ring", collections.deque(
        [obs.Count("dse.test.fill", 0.0, 1)] * 4, maxlen=4))
    monkeypatch.setattr(obs, "_horizon", float("-inf"))
    before = obs.total("dse.syncs")
    got = _codesign(grid, nets)
    n_chunks = -(-grid.n // CHUNK)
    assert obs.total("dse.syncs") - before == len(sites)
    assert collections.Counter(sites) == {
        "dse.stream.wait": n_chunks + 1, "dse.eval.wait": 1,
        "dse.schedule.wait": 2}
    assert obs.oldest() > 0.0
    for f in ("core_types", "core_counts", "pool", "chip_types",
              "chip_counts", "energy", "latency", "score",
              "homogeneous_score"):
        assert getattr(got, f) == getattr(want, f), f
    np.testing.assert_array_equal(got.chip_scores, want.chip_scores)
    assert got.schedules == want.schedules


def test_program_spans_are_in_the_profiler_trace(grid, nets, tmp_path):
    _codesign(grid, nets)
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.perf_counter()
        _codesign(grid, nets)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = collections.defaultdict(list)
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dse."):
                    host[ev.name].append(ev)
    spans = obs.spans_between(t0, t1)
    mine = collections.Counter(s.name for s in spans)
    assert mine == {k: len(v) for k, v in host.items()}
    # the same intervals on the trace's clock as on perf_counter
    (root,) = [s for s in spans if s.name == "dse.codesign"]
    (ev,) = host["dse.codesign"]
    assert ev.duration_ns / 1e9 == pytest.approx(root.t1 - root.t0,
                                                 abs=2e-3)
    lo, hi = ev.start_ns, ev.start_ns + ev.duration_ns
    for name, evs in host.items():
        for e in evs:
            assert lo <= e.start_ns <= e.start_ns + e.duration_ns <= hi, name


def _programs(grid, nets):
    """(program, lowering arguments) of every jitted program, tiny."""
    pts = grid.take(np.arange(8))
    out = {}
    for per_layer in (False, True):
        lay, segments = energymodel._stack_networks(
            nets, absorb_pad=not per_layer)
        lay = {k: v[None, :] for k, v in lay.items()}
        cfg_m, cfg_u, inv_m, inv, coefs = energymodel._prepare_fields(
            pts.fields)
        args = (segments, cfg_m, cfg_u, lay, inv_m, inv, coefs)
        out[energymodel._jax_grid_kernel(per_layer)] = args
    n, n_net, k = 8, len(nets), 4
    lay, segments = energymodel._stack_networks(nets, absorb_pad=False)
    n_layer = energymodel._layer_axis_len(segments)
    f, i = np.float64, np.int64
    e = np.zeros((n, n_net))
    state = (np.zeros(n_net), np.zeros(n_net), np.zeros(n_net),
             np.zeros(n_net, i), np.zeros((k, n_net)),
             np.zeros((k, n_net), i))
    out[energymodel._jax_reduce_step()] = ("edp", k, e, e, state, i(0),
                                           i(n), f(0.05))
    el = np.zeros((n, n_net, n_layer))
    lstate = (np.zeros((k, n_net)), np.zeros((k, n_net), i),
              np.zeros((k, n_net, n_layer)), np.zeros((k, n_net, n_layer)),
              *(np.zeros(n_net) for _ in range(4)), np.zeros(n_net, i),
              np.zeros((n_net, n_layer)), np.zeros((n_net, n_layer), i))
    out[energymodel._jax_layer_reduce_step()] = (
        "edp", k, el, el, lstate, i(0), i(n), f(0.05),
        np.zeros((n_net, n_layer), bool))
    b, t, L, rows, d, m = 4, 3, 8, 8, 2, 8
    out[partition._jax_hetero_stage1()] = (
        np.zeros((b, t, L)), np.zeros((b, t), bool), np.zeros(b, i))
    out[partition._jax_solver()] = (
        np.zeros((b * t, L + 1)), *(np.zeros(rows, i) for _ in range(3)),
        np.zeros(rows), np.zeros(rows), 2, 4)
    cn = np.zeros((6, n_net))
    out[partition._jax_pareto()] = (cn, cn, cn, np.zeros((n_net, d)),
                                    f(n_net))
    out[partition._jax_slack_solver()] = (
        np.zeros((b, t, L)), np.zeros((b, L), i), np.zeros((b, t), i),
        np.zeros((b, m), i), np.zeros((b, m), i), np.zeros((b, m), bool),
        np.zeros((b, d), bool), np.zeros((b, d)), np.zeros(b, i), 2)
    return out


PROGRAMS = ("grid_kernel", "grid_kernel_layers", "stream_fold", "layer_fold",
            "schedule_stage1", "partition_solve", "pareto_scores",
            "slack_solve")


@pytest.mark.parametrize("name", PROGRAMS)
def test_program_has_a_stable_module_name(grid, nets, name):
    progs = {p.name: (p, a) for p, a in _programs(grid, nets).items()}
    assert set(progs) == set(PROGRAMS)
    prog, args = progs[name]
    with energymodel.x64():
        text = prog.lower(*args).as_text()
    assert text.splitlines()[0].startswith(f"module @jit_{name} ")
    assert obs.total(f"jit.trace.{name}") >= 1       # traces, per name

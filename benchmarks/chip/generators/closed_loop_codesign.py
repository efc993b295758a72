"""Closed loop of streamed co-design jobs (``hetero.co_design_streaming``).

One client sends the next job when the previous one has returned.  Job
``i`` is the configuration's co-design over its whole grid with the
energy table drawn from (seed, i): the same shapes every time, a
different answer every time.  Set-up builds the grid and the networks
and runs job 0, which compiles (or loads from the persistent cache)
every program the window uses, then the shapes other energy tables give
two of them (``_warm_shapes``); the window runs jobs 1, 2, ... until
``seconds`` have passed and the job in flight has returned.

``dse_job_s`` is the window, from the start of job 1 to the end of the
last job, over the number of jobs.  After the window, every job's answer
is compared with the plain reference (``reference.codesign``): see
:mod:`reference.compare`.
"""

from __future__ import annotations

import time

import numpy as np

import inputs
from reference import codesign as ref_codesign
from reference import compare, tool

#: (module, function, span): the layers the per-layer metrics read.
SPANS = (("repro.core.energymodel", "stream_layer_topk", "stream"),
         ("repro.core.hetero", "codesign_problems_streaming", "pool"),
         ("repro.core.partition", "batch_schedule_hetero", "solve.schedule"),
         ("repro.core.hetero", "score_codesign", "solve.score"))
JOB_SPAN = "job"


class State:
    pass


def setup(ctx) -> State:
    from repro.core import hetero
    from repro.core.accelerator import ConfigGrid
    from repro.core.topology import Layer

    st = State()
    st.ctx = ctx
    cfg = ctx.config
    st.params = dict(cfg["codesign"])
    st.fields = inputs.product_grid(cfg["grid"])
    st.nets_rows = cfg["networks"]
    st.networks = {
        name: [Layer(f"{name}.{i}", *row) for i, row in enumerate(rows)]
        for name, rows in st.nets_rows.items()}
    st.ConfigGrid = ConfigGrid
    st.hetero = hetero
    st.captured = {}
    orig = hetero.score_codesign

    def capture(probs, res, *a, **kw):
        st.captured["probs"] = probs
        return orig(probs, res, *a, **kw)

    hetero.score_codesign = capture
    st.undo = lambda: setattr(hetero, "score_codesign", orig)
    st.jobs = []
    _run_job(st, 0)            # compiles or loads every program
    st.jobs.clear()
    _warm_shapes(st, st.last_probs)
    return st


def _warm_shapes(st: State, probs) -> None:
    """Compile (or load) the two programs whose shapes a job's energy
    table sets, for the shapes jobs draw, so that none compiles in the
    window.  Which shapes those are was read off the plain reference over
    200 jobs (40 seeds x 5 jobs):

    * the per-layer evaluation of the pool (``evaluate_networks`` on its
      six grid points) is shaped by how many distinct mapping rows (array,
      GB_ifmap, RF sizes) and count rows (those and GB_psum) it holds:
      six count rows, and six, five or four mapping rows (149, 50, 1 of
      200);
    * the schedule solver pads the rows it bisects (a chip type with three
      or more cores and more layers than cores) to a bucket of 32: 577 to
      640 rows (buckets 608 and 640) of the 648 a job can have.
    """
    from repro.core import energymodel, partition
    g = st.ctx.config["grid"]
    dims = [len(g["arrays"]), len(g["gb_psum_kb"]), len(g["gb_ifmap_kb"]),
            len(g["rf_psum_words"]), len(g["noc_wpc"])]
    n = st.params["pool_size"]
    grid = st.ConfigGrid(st.fields)
    for m in (n, n - 1, n - 2):
        # m arrays; the other points share array 0 with another GB_psum
        if m > dims[0] or n - m >= dims[1]:
            continue               # a grid too small to hold the shape
        pts = ([(k, 0) for k in range(m)]
               + [(0, k) for k in range(1, n - m + 1)])
        idx = [np.ravel_multi_index((a, ps, 0, 0, 0), dims) for a, ps in pts]
        energymodel.evaluate_networks(grid.take(idx), st.networks,
                                      per_layer=True)
    B, T, L = probs.lat_dense.shape
    lat = np.full((B, T, L), 2.0)
    lat[:, 0, :] = 1.0                 # every layer on type 0
    big = np.argwhere(probs.counts >= 3)
    for r in (len(big) - 16, len(big) - 48, len(big) - 80):
        if r <= 0:
            continue
        counts = np.zeros_like(probs.counts)
        counts[:, 0] = 1
        sel = big[:r]
        counts[sel[:, 0], 0] = probs.counts[sel[:, 0], sel[:, 1]]
        partition.batch_schedule_hetero(lat, counts,
                                        n_layers=probs.n_layers_b)


def _run_job(st: State, i: int) -> dict:
    p = st.params
    table = inputs.energy_table(st.ctx.config["assumed"]["energy_draw"],
                                st.ctx.seed, i)
    with st.ctx.recorder.span(JOB_SPAN):
        t0 = time.perf_counter()
        grid = st.ConfigGrid(inputs.with_energy(st.fields, table))
        cd = st.hetero.co_design_streaming(
            grid, st.networks, p["m_cores"], max_types=p["max_types"],
            pool_size=p["pool_size"], bound=p["bound"], metric=p["metric"],
            chunk_size=p["chunk_size"])
        t1 = time.perf_counter()
    probs = st.last_probs = st.captured.pop("probs")
    job = dict(
        index=i, t0=t0, t1=t1, table=table,
        pool=list(probs.pool), e_layer=np.array(probs.e_layer),
        t_layer=np.array(probs.t_layer),
        min_energy=np.array(probs.min_energy),
        min_latency=np.array(probs.min_latency),
        min_edp=np.array(probs.min_edp),
        chips=list(zip(cd.chip_types, cd.chip_counts)),
        chip_scores=np.array(cd.chip_scores), score=cd.score,
        core_types=list(cd.core_types), core_counts=list(cd.core_counts),
        energy=[cd.energy[n] for n in st.networks],
        latency=[cd.latency[n] for n in st.networks])
    st.jobs.append(job)
    return job


def window(st: State, seconds: float) -> dict:
    failed = 0
    t_start = time.perf_counter()
    i = 1
    while True:
        try:
            _run_job(st, i)
        except Exception as e:          # a failed job counts, and is shown
            failed += 1
            st.ctx.log(f"job {i} failed: {e!r}")
        i += 1
        if time.perf_counter() - t_start >= seconds:
            break
    t_end = time.perf_counter()
    done = len(st.jobs)
    return dict(t0=t_start, t1=t_end, attempted=i - 1, failed=failed,
                jobs=done,
                end_to_end=dict(dse_job_s=(t_end - t_start) / max(done, 1)))


def release(st: State) -> None:
    st.undo()
    st.networks = st.hetero = st.ConfigGrid = None


def check(st: State, win: dict) -> dict:
    """Every job of the window against the plain reference: the widest
    ``rel_gap`` (see :mod:`reference.compare`)."""
    if not st.jobs:
        return dict(rel_gap=float("inf"))
    sums = tool.NetworkSums(st.fields, st.nets_rows, np.float64)
    worst = 0.0
    for job in st.jobs:
        fields = inputs.with_energy(st.fields, job["table"])
        ref = ref_codesign.codesign(fields, st.nets_rows, st.params, sums)
        gap, detail = compare.rel_gap(job, ref)
        st.ctx.log(f"job {job['index']}: rel_gap {gap!r} {detail}")
        worst = max(worst, gap)
    return dict(rel_gap=worst)


# -- readings that set the limit (``readings.py``) ---------------------------

def reading(ctx, seed: int) -> tuple:
    """Job 1 of ``seed`` through the timed path, against the reference."""
    st = getattr(ctx, "_codesign_state", None)
    if st is None:
        st = ctx._codesign_state = setup(ctx)
        st.sums = tool.NetworkSums(st.fields, st.nets_rows, np.float64)
    ctx.seed = seed
    job = _run_job(st, 1)
    st.jobs.clear()
    f = inputs.with_energy(st.fields, job["table"])
    return compare.rel_gap(job, ref_codesign.codesign(
        f, st.nets_rows, st.params, st.sums))


def _record(ref: dict) -> dict:
    """A reference co-design in the shape of a timed-path job record."""
    b = ref["best"]
    return dict(pool=ref["pool"], e_layer=ref["e_layer"],
                t_layer=ref["t_layer"], min_energy=ref["min_energy"],
                min_latency=ref["min_latency"], min_edp=ref["min_edp"],
                chips=ref["chips"], chip_scores=ref["chip_scores"],
                score=ref["chip_scores"][b], energy=ref["energy"][b],
                latency=ref["latency"][b])


def control(ctx, seed: int) -> tuple:
    """The reference in float32 in the program's place, job 1 of ``seed``."""
    cfg = ctx.config
    fields = inputs.product_grid(cfg["grid"])
    sums = getattr(ctx, "_control_sums", None)
    if sums is None:
        sums = ctx._control_sums = {
            dt: tool.NetworkSums(fields, cfg["networks"], dt)
            for dt in (np.float64, np.float32)}
    f = inputs.with_energy(fields, inputs.energy_table(
        cfg["assumed"]["energy_draw"], seed, 1))
    ref = ref_codesign.codesign(f, cfg["networks"], cfg["codesign"],
                                sums[np.float64])
    low = ref_codesign.codesign(f, cfg["networks"], cfg["codesign"],
                                sums[np.float32])
    return compare.rel_gap(_record(low), ref)

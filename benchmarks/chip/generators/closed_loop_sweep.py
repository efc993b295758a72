"""Closed loop of sharded per-layer sweeps
(``energymodel.stream_layer_topk(shard=True)``).

One client sends the next job when the previous one has returned.  Job
``i`` streams the configuration's whole grid against all its networks
over every chip of the host, with the energy table drawn from
(seed, i), as the co-design cell draws it: the per-layer top-k
(``pool_size`` points per network) and the boundary sets (``bound``,
``metric``) of the configuration's co-design, the first stage of every
co-design.  The chunk size is the traffic file's.  Set-up builds the grid
and the networks and runs job 0, which compiles (or loads from the
persistent cache) every program the window uses; every job has the same
shapes.  The window runs jobs 1, 2, ... until ``seconds`` have passed
and the job in flight has returned; each ends in host numpy.

``dse_job_s`` is the window, from the start of job 1 to the end of the
last job, over the number of jobs.  After the window, every job's answer
is compared with the plain reference (``reference.sweep``).

For ``device_balance`` the trace reduction of a traced run is given each
device's busy time too: set-up wraps ``trace_reduce.reduce`` once, and
the wrap removes itself after its first call (``device_busy.py``).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import device_busy
import inputs
from reference import sweep as ref_sweep
from reference import tool

#: (module, function, span): the layer the per-layer metrics read.
SPANS = (("repro.core.energymodel", "stream_layer_topk", "stream"),)
JOB_SPAN = "job"


class State:
    pass


def setup(ctx) -> State:
    from repro.core import energymodel
    from repro.core.accelerator import ConfigGrid
    from repro.core.topology import Layer

    st = State()
    st.ctx = ctx
    cd = ctx.config["codesign"]
    st.params = dict(topk=cd["pool_size"], bound=cd["bound"],
                     metric=cd["metric"],
                     chunk_size=ctx.traffic["chunk_size"])
    st.fields = inputs.product_grid(ctx.config["grid"])
    st.nets_rows = ctx.config["networks"]
    st.networks = {
        name: [Layer(f"{name}.{i}", *row) for i, row in enumerate(rows)]
        for name, rows in st.nets_rows.items()}
    st.ConfigGrid = ConfigGrid
    st.energymodel = energymodel
    st.jobs = []
    device_busy.report_per_device()
    _run_job(st, 0)            # compiles or loads every program
    st.jobs.clear()
    return st


def _run_job(st: State, i: int) -> dict:
    table = inputs.energy_table(st.ctx.config["assumed"]["energy_draw"],
                                st.ctx.seed, i)
    with st.ctx.recorder.span(JOB_SPAN):
        t0 = time.perf_counter()
        grid = st.ConfigGrid(inputs.with_energy(st.fields, table))
        res = st.energymodel.stream_layer_topk(grid, st.networks, shard=True,
                                               **st.params)
        t1 = time.perf_counter()
    job = dict(index=i, t0=t0, t1=t1, table=table,
               **{f: getattr(res, f) for f in (
                   "topk_idx", "topk_metric", "layer_energy",
                   "layer_latency", "min_energy", "min_latency", "min_edp",
                   "min_metric", "argmin", "boundary_idx",
                   "boundary_energy", "boundary_latency")})
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
    st.networks = st.energymodel = st.ConfigGrid = None


def check(st: State, win: dict) -> dict:
    """Every job of the window against the plain reference: the widest
    ``rel_gap`` (see :mod:`reference.sweep`)."""
    if not st.jobs:
        return dict(rel_gap=float("inf"))
    sums = tool.NetworkSums(st.fields, st.nets_rows, np.float64)
    with ThreadPoolExecutor(max_workers=8) as ex:
        gaps = list(ex.map(lambda job: _gap(st, job, sums), st.jobs))
    for job, (gap, detail) in zip(st.jobs, gaps):
        st.ctx.log(f"job {job['index']}: rel_gap {gap!r} {detail}")
    return dict(rel_gap=max(gap for gap, _ in gaps))


def _gap(st: State, job: dict, sums) -> tuple:
    fields = inputs.with_energy(st.fields, job["table"])
    ref = ref_sweep.sweep(fields, st.nets_rows, st.params["topk"],
                          st.params["bound"], sums)
    return ref_sweep.rel_gap(job, ref, fields, st.nets_rows)


# -- readings that set the limit (``readings.py``) ---------------------------

def reading(ctx, seed: int) -> tuple:
    """Job 1 of ``seed`` through the timed path, against the reference."""
    st = getattr(ctx, "_sweep_state", None)
    if st is None:
        st = ctx._sweep_state = setup(ctx)
        st.sums = tool.NetworkSums(st.fields, st.nets_rows, np.float64)
    ctx.seed = seed
    job = _run_job(st, 1)
    st.jobs.clear()
    return _gap(st, job, st.sums)


def control(ctx, seed: int) -> tuple:
    """The reference in float32 in the program's place, job 1 of ``seed``:
    its top-k and boundary sets, with the per-layer values of its top-k
    points, against the float64 reference."""
    cfg = ctx.config
    cd = cfg["codesign"]
    fields = inputs.product_grid(cfg["grid"])
    sums = getattr(ctx, "_control_sums", None)
    if sums is None:
        sums = ctx._control_sums = {
            dt: tool.NetworkSums(fields, cfg["networks"], dt)
            for dt in (np.float64, np.float32)}
    f = inputs.with_energy(fields, inputs.energy_table(
        cfg["assumed"]["energy_draw"], seed, 1))
    args = (f, cfg["networks"], cd["pool_size"], cd["bound"])
    ref = ref_sweep.sweep(*args, sums[np.float64])
    low = ref_sweep.sweep(*args, sums[np.float32])
    e_l, t_l = tool.per_layer(f, low["topk_idx"].ravel(), cfg["networks"],
                              np.float32)
    k, n_net = low["topk_idx"].shape
    cols = np.arange(n_net)
    rows = np.arange(k * n_net).reshape(k, n_net)
    best = low["topk_idx"][0]
    job = dict(low, layer_energy=e_l[rows, cols[None, :]],
               layer_latency=t_l[rows, cols[None, :]],
               min_metric=low["min_edp"], argmin=best)
    return ref_sweep.rel_gap(job, ref, f, cfg["networks"])

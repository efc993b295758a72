"""Open-loop queries to a long-lived ``DSEService``.

Queries arrive as a Poisson process at the traffic file's ``rate_per_s``:
kind uniform over ``kinds``, the network uniform over the configuration's
networks (none for ``best_config``), the deadline factor uniform over
``deadlines``, metric ``metric``; all of it drawn from the seed.  One
thread submits every query when it falls due and steps the service
whenever its queue holds work (the service is step-driven and coalesces
queued queries of one family into one computation).

Set-up builds the service the way the launcher does
(``serve_dse.build_service``) over the configuration's grid with the
energy table drawn from (seed, 0), then serves one best-config query and
one batch of chip queries for every number (1 to 4) of distinct deadline
factors, which streams the grid, solves the chip points and compiles
every program the window uses.

A query's latency runs from when it was due to when its answer returned.
``query_p50_s`` and ``query_p95_s`` are over every query due in the
window; a rejected or failed query counts in ``failed`` and lies beyond
the tail.  The window ends when the last query due in it is answered.
After it, every distinct (kind, network, deadline) answer is compared
with the plain reference (``reference.service``).
"""

from __future__ import annotations

import math
import time

import numpy as np

import inputs
from reference import service as ref_service

SPANS = (("repro.serving.dse_service", "DSEService.step", "step"),
         ("repro.core.hetero", "pareto_codesign", "pareto"))


class State:
    pass


def setup(ctx) -> State:
    from repro.launch import serve_dse

    st = State()
    st.ctx = ctx
    cfg, tr = ctx.config, ctx.traffic
    st.table = inputs.energy_table(cfg["assumed"]["energy_draw"], ctx.seed, 0)
    st.fields = inputs.with_energy(inputs.product_grid(cfg["grid"]),
                                   st.table)
    from repro.core.accelerator import ConfigGrid
    args = serve_dse.parse_args(cfg["service"]["launcher_argv"])
    st.svc = serve_dse.build_service(args, grid=ConfigGrid(st.fields))
    if list(st.svc.names) != list(cfg["networks"]):
        raise ValueError(f"service networks {st.svc.names} are not the "
                         f"configuration's {list(cfg['networks'])}")
    names = list(st.svc.names)
    g = inputs.rng(ctx.seed, 3)
    st.rate = float(tr["rate_per_s"])
    kinds, dls = tr["kinds"], tr["deadlines"]
    st.draw = lambda: _query(g, kinds, names, dls)
    st.metric = tr["metric"]
    # warm-up: every family, every number of distinct deadline factors
    warm = [("best_config", None, 2.0)]
    for k in range(1, len(dls) + 1):
        warm += [("best_chip", None, d) for d in dls[:k]]
        warm += [("pareto", names[i % len(names)], d)
                  for i, d in enumerate(dls[:k])]
    for k in range(len(warm)):
        _submit(st, *warm[k])
        if k == 0 or warm[k][0] == "pareto" and (
                k + 1 == len(warm) or warm[k + 1][0] == "best_chip"):
            _drain(st)
    return st


def _query(g, kinds, names, dls):
    kind = kinds[int(g.integers(len(kinds)))]
    net = None if kind == "best_config" else names[int(g.integers(len(names)))]
    return kind, net, float(dls[int(g.integers(len(dls)))])


def _submit(st, kind, net, d):
    return st.svc.submit(kind, network=net, metric=st.metric, deadline=d)


def _drain(st):
    while st.svc._queue:
        st.svc.step()


def window(st: State, seconds: float) -> dict:
    g = inputs.rng(st.ctx.seed, 4)
    due, t = [], 0.0
    while True:
        t += float(g.exponential(1.0 / st.rate))
        if t >= seconds:
            break
        due.append((t, st.draw()))
    svc = st.svc
    lat, late, failed = [], 0.0, 0
    waiting = {}                      # rid -> (due time, query)
    st.queries = {}
    step_s, steps, completed0 = 0.0, 0, svc.stats["completed"]
    depth = 0
    t0 = time.perf_counter()
    i = 0
    while i < len(due) or waiting:
        now = time.perf_counter() - t0
        while i < len(due) and due[i][0] <= now:
            q = due[i][1]
            late = max(late, now - due[i][0])
            sub = _submit(st, *q)
            if sub.accepted:
                waiting[sub.rid] = (due[i][0], q)
            else:
                failed += 1
            i += 1
        if svc._queue:
            depth = max(depth, len(svc._queue))
            ts = time.perf_counter()
            out = svc.step()
            te = time.perf_counter()
            step_s += te - ts
            steps += 1
            for r in out:
                d, q = waiting.pop(r.rid)
                if r.ok and not r.degraded:
                    lat.append(te - t0 - d)
                    st.queries.setdefault(q, {})[repr(r.answer)] = r.answer
                else:
                    failed += 1
        elif i < len(due):
            time.sleep(max(0.0, due[i][0] - (time.perf_counter() - t0)))
    t1 = time.perf_counter()
    n = len(due)
    # a failed query lies beyond the tail: +inf in the percentile
    full = sorted(lat) + [math.inf] * failed
    drain = t1 - t0 - seconds
    st.ctx.log(f"{n} queries due in {seconds} s, {failed} failed, "
               f"generator at most {late:.4f} s late, {steps} steps, "
               f"queue at most {depth}, last answer {drain:.4f} s after "
               "the window")
    return dict(t0=t0, t1=t1, attempted=n, failed=failed, jobs=steps,
                steps=steps, step_s=step_s, late_s=late, max_queue=depth,
                drain_s=drain,
                completed=svc.stats["completed"] - completed0,
                end_to_end=dict(query_p50_s=_pct(full, 0.50),
                                query_p95_s=_pct(full, 0.95)))


def _pct(xs, q):
    """The q-quantile of xs by linear interpolation (inf past the end)."""
    if not xs:
        return math.inf
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def release(st: State) -> None:
    st.svc.close()
    st.svc = None


def check(st: State, win: dict) -> dict:
    """Every distinct answer of the window against the plain reference:
    the widest ``rel_gap`` (see ``reference.service.compare``)."""
    if not st.queries:
        return dict(rel_gap=float("inf"))
    ref = ref_service.Service(st.fields, st.ctx.config["networks"],
                              st.ctx.config["service"], np.float64)
    worst, n = 0.0, 0
    for q, answers in sorted(st.queries.items(), key=str):
        for ans in answers.values():
            g = ref_service.compare(ref, *q, _plain(ans))
            st.ctx.log(f"{q}: rel_gap {g!r}")
            worst = max(worst, g)
            n += 1
    st.ctx.log(f"{n} distinct answers to {len(st.queries)} distinct "
               "queries checked")
    return dict(rel_gap=worst)


def _plain(x):
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, np.generic):
        return x.item()
    return x


# -- readings that set the limit (``readings.py``) ---------------------------

def _all_queries(ctx) -> list:
    tr = ctx.traffic
    names = list(ctx.config["networks"])
    out = [("best_config", None, 2.0)]
    out += [("best_chip", None, float(d)) for d in tr["deadlines"]]
    out += [("pareto", n, float(d)) for n in names for d in tr["deadlines"]]
    return out


def reading(ctx, seed: int) -> tuple:
    """A service set up from ``seed``, every distinct query answered
    through ``submit``/``step`` (one batch per family), against the
    reference."""
    ctx.seed = seed
    st = setup(ctx)
    qs = _all_queries(ctx)
    got = {}
    for q in qs:
        sub = _submit(st, *q)
        got[sub.rid] = q
    answers = {}
    while st.svc._queue:
        for r in st.svc.step():
            answers[got[r.rid]] = r.answer
    release(st)
    ref = ref_service.Service(st.fields, ctx.config["networks"],
                              ctx.config["service"], np.float64)
    gaps = {str(q): ref_service.compare(ref, *q, _plain(answers[q]))
            for q in qs}
    return max(gaps.values()), gaps


def control(ctx, seed: int) -> tuple:
    """The reference in float32 in the program's place, every distinct
    query of a service set up from ``seed``."""
    cfg = ctx.config
    table = inputs.energy_table(cfg["assumed"]["energy_draw"], seed, 0)
    f = inputs.with_energy(inputs.product_grid(cfg["grid"]), table)
    ref = ref_service.Service(f, cfg["networks"], cfg["service"], np.float64)
    low = ref_service.Service(f, cfg["networks"], cfg["service"], np.float32)
    qs = _all_queries(ctx)
    gaps = {str(q): ref_service.compare(ref, *q, _plain(low.answer(*q)))
            for q in qs}
    return max(gaps.values()), gaps

"""DSEService behavior: answer correctness vs. direct engine calls, query
coalescing, bounded admission, deadline degradation (fake clock), budget
abort + checkpoint resume, and the health snapshot."""

import numpy as np
import pytest

from repro.core import energymodel, hetero, topology
from repro.core.accelerator import ConfigGrid
from repro.ft.faults import inject_chunk_faults
from repro.serving.dse_service import DSEService

NETS = ("AlexNet", "MobileNet")


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in NETS}


@pytest.fixture(scope="module")
def grid():
    return ConfigGrid.product(arrays=((16, 16), (32, 32), (64, 64)),
                              gb_psum_kb=(13, 54, 216),
                              gb_ifmap_kb=(27, 108))


class FakeClock:
    """Deterministic service time: advances only when told to."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt

    def per_chunk_hook(self, seconds):
        def hook(ci, e, t):
            self.t += seconds
            return e, t
        return hook


def test_best_config_matches_direct_stream(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5)
    svc.submit("best_config")
    (r,), drained = svc.run_until_drained()
    assert drained and r.ok and not r.degraded
    ref = energymodel.stream_layer_topk(grid, networks, topk=8,
                                        bound=0.05, chunk_size=5)
    for j, nm in enumerate(NETS):
        assert r.answer[nm]["idx"] == int(ref.argmin[j])
        assert r.answer[nm]["metric"] == float(ref.min_metric[j])
        assert r.answer[nm]["energy"] == float(ref.min_energy[j])


def test_best_chip_matches_direct_codesign(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5, pool_size=4,
                     m_cores=4, max_types=2)
    svc.submit("best_chip", deadline=2.0)
    svc.submit("pareto", network="AlexNet", deadline=2.0)
    out, drained = svc.run_until_drained()
    assert drained and all(r.ok and not r.degraded for r in out)
    chip = next(r for r in out if r.kind == "best_chip")
    probs = hetero.codesign_problems_streaming(
        grid, networks, 4, max_types=2, pool_size=4, bound=0.05,
        metric="edp", chunk_size=5)
    par = hetero.pareto_codesign(probs, deadlines=np.asarray([2.0]))
    ci = int(par.best_chip[0])
    assert chip.answer["feasible"] == (ci >= 0)
    if ci >= 0:
        assert chip.answer["chip_types"] == [
            int(probs.pool[p]) for p in par.chip_types[ci]]
        assert chip.answer["chip_counts"] == list(par.chip_counts[ci])
    frontier = next(r for r in out if r.kind == "pareto")
    assert frontier.answer["frontier"] == par.frontier("AlexNet")


def test_coalescing_one_sweep_many_queries(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5)
    for nm in (None, "AlexNet", "MobileNet", None, "AlexNet"):
        svc.submit("best_config", network=nm)
    out = svc.step()                      # ONE step answers the batch
    assert len(out) == 5
    h = svc.health()
    assert h["coalesced_batches"] == 1
    # one exact + one calibration (subsampled) sweep, never five
    assert h["sweep_cache_misses"] == 2
    assert h["queue_depth"] == 0


def test_coalesced_deadlines_one_scoring_call(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5)
    for d in (1.2, 2.0, 3.0, 2.0):
        svc.submit("best_chip", deadline=d)
    out = svc.step()
    assert len(out) == 4
    assert {r.answer["deadline"] for r in out} == {1.2, 2.0, 3.0}
    assert svc.health()["points_cache_misses"] == 2   # exact + sub


def test_queue_overflow_rejects_with_retry_after(grid, networks):
    svc = DSEService(grid, networks, max_queue=3, chunk_size=5)
    results = [svc.submit("best_config") for _ in range(5)]
    assert [s.accepted for s in results] == [True] * 3 + [False] * 2
    for s in results[3:]:
        assert s.rid is None and s.retry_after_s > 0
    out, drained = svc.run_until_drained()
    assert drained and len(out) == 3
    h = svc.health()
    assert h["rejected"] == 2 and h["accepted"] == 3


def test_expired_deadline_gets_degraded_answer(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5, degrade_stride=4)
    svc.submit("best_config", deadline_s=0.0)     # already expired
    (r,), drained = svc.run_until_drained()
    assert drained and r.ok and r.degraded and r.deadline_missed
    # degraded answers index into the ORIGINAL grid, via the subsample map
    for nm in NETS:
        assert 0 <= r.answer[nm]["idx"] < grid.n
        assert r.answer[nm]["idx"] % 4 == 0       # stride-4 subsample
    assert svc.health()["degraded"] == 1


def test_tight_budget_projects_to_degraded(grid, networks):
    """Projection path: the measured subsampled sweep extrapolates the
    exact cost; a budget below it degrades WITHOUT attempting the exact
    sweep (no checkpoint left behind)."""
    clk = FakeClock()
    svc = DSEService(grid, networks, chunk_size=5, degrade_stride=4,
                     safety_factor=2.0, clock=clk, sleep=clk.sleep)
    with inject_chunk_faults(clk.per_chunk_hook(1.0)):
        svc.submit("best_config", deadline_s=3.0)
        (r,), drained = svc.run_until_drained()
    assert drained and r.ok and r.degraded
    assert svc.health()["checkpoints"] == 0
    assert svc.health()["budget_aborts"] == 0


def test_budget_abort_checkpoints_then_next_query_resumes(grid, networks):
    """Degradation ladder rung 4: an exact sweep that runs out of budget
    mid-stream answers degraded, leaves its checkpoint, and the next
    query with budget RESUMES it instead of restarting."""
    clk = FakeClock()
    svc = DSEService(grid, networks, chunk_size=5, degrade_stride=4,
                     safety_factor=0.1, clock=clk, sleep=clk.sleep)
    with inject_chunk_faults(clk.per_chunk_hook(1.0)):
        # sub sweep: 1 chunk -> cost 1s; projection 0.1 * (18/5) ~ 0.36s;
        # exact sweep needs 4 chunks = 4s > remaining budget -> abort
        svc.submit("best_config", deadline_s=3.0)
        (r1,), _ = svc.run_until_drained()
        assert r1.ok and r1.degraded
        h = svc.health()
        assert h["budget_aborts"] == 1 and h["checkpoints"] == 1
        svc.submit("best_config")                 # unbounded budget
        (r2,), _ = svc.run_until_drained()
    assert r2.ok and not r2.degraded
    assert svc.health()["resumes"] >= 1
    ref = energymodel.stream_layer_topk(grid, networks, topk=8,
                                        bound=0.05, chunk_size=5)
    for j, nm in enumerate(NETS):
        assert r2.answer[nm]["idx"] == int(ref.argmin[j])


def test_health_snapshot_shape(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5)
    svc.submit("best_config")
    svc.run_until_drained()
    h = svc.health()
    for key in ("uptime_s", "queue_depth", "max_queue", "p50_s", "p99_s",
                "submitted", "accepted", "rejected", "completed",
                "degraded", "faults", "retries", "backend_fallbacks",
                "resumes", "sweep_cache_hits", "sweep_cache_misses",
                "last_backend", "jit"):
        assert key in h
    assert h["p99_s"] >= h["p50_s"] >= 0.0
    assert h["completed"] == 1


def test_run_until_drained_reports_not_drained(grid, networks):
    svc = DSEService(grid, networks, chunk_size=5)
    svc.submit("best_config")
    svc.submit("best_chip")                       # second family: 2 steps
    out, drained = svc.run_until_drained(max_steps=1)
    assert not drained and len(out) == 1
    out2, drained2 = svc.run_until_drained()
    assert drained2 and len(out2) == 1


def test_submit_validates_inputs(grid, networks):
    svc = DSEService(grid, networks)
    with pytest.raises(ValueError):
        svc.submit("nonsense")
    with pytest.raises(ValueError):
        svc.submit("best_config", network="NotANet")
    with pytest.raises(ValueError):
        svc.submit("pareto")                      # needs a network


def test_backend_ladder_ends_at_the_device_path(grid, networks,
                                                monkeypatch):
    """On the CPU a faulting stream may fall back to numpy; on a TPU the
    ladder has no host rung, so exhausted retries raise ServiceFault
    instead of answering from numpy."""
    from repro.serving.dse_service import ServiceFault
    ran = []

    def always_fails(backend, resume):
        ran.append(backend)
        raise RuntimeError("device fault")

    def exhaust():
        svc = DSEService(grid, networks, chunk_size=5, max_retries=2,
                         backoff_s=0.0, verify=False)
        ran.clear()
        with pytest.raises(ServiceFault):
            svc._with_retries(always_fails, key=("exact", "edp"),
                              budget_end=None)
        assert svc.health()["last_fault"] == (
            "('exact', 'edp'): RuntimeError: device fault")
        return svc._backend_ladder(), list(ran), svc.stats

    ladder, backends, stats = exhaust()
    assert ladder == ["jax", "numpy"]
    assert backends == ["jax", "jax", "numpy"]
    assert stats["backend_fallbacks"] == 1

    monkeypatch.setattr(energymodel, "platform", lambda: "tpu")
    ladder, backends, stats = exhaust()
    assert ladder == ["jax"]
    assert backends == ["jax"] * 3
    assert stats["backend_fallbacks"] == 0
    assert DSEService(grid, networks,
                      backend="numpy")._backend_ladder() == ["numpy"]


def test_unknown_backend_is_refused(grid, networks):
    """A backend the engine does not know ("pallas" included) is refused
    when the service is built, with the engine's ValueError, rather than
    becoming a rung of its retry ladder."""
    with pytest.raises(ValueError, match="backend must be one of"):
        DSEService(grid, networks, backend="pallas")

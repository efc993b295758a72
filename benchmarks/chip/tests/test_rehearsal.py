"""CPU rehearsals of the harness at a tiny grid.

Each drives a whole run (set-up, window, check, result line) with the
CPU standing in for the chip: once sound, and once for each fault the
co-design cell can have, planted where the answer is produced, which
must turn ``correct`` false.  The control (the reference in float32 in
the program's place) must fail the same comparison.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from tiny import CELL, ROOT, SERVICE_CELL, run_tiny, tiny_checkout

END_TO_END = {CELL: {"dse_job_s", "peak_device_gb", "setup_s"},
              SERVICE_CELL: {"query_p50_s", "query_p95_s", "peak_device_gb",
                             "setup_s"}}


@pytest.mark.parametrize("cell", [CELL, SERVICE_CELL])
def test_sound_run_is_correct(tmp_path, cell):
    out = run_tiny(tmp_path, cell=cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert out["checks"]["rel_gap"]["value"] < 1e-13
    assert set(out["metrics"]) == END_TO_END[cell]
    assert all(m["value"] >= 0 for m in out["metrics"].values())


def _alter_answer(monkeypatch):
    """The chosen chip's scheduled energy of one network, off by 1e-9."""
    from repro.core import hetero
    orig = hetero.score_codesign

    def faulty(*a, **kw):
        cd = orig(*a, **kw)
        name = next(iter(cd.energy))
        energy = dict(cd.energy, **{name: cd.energy[name] * (1 + 1e-9)})
        return dataclasses.replace(cd, energy=energy)

    monkeypatch.setattr(hetero, "score_codesign", faulty)


def _alter_engine(monkeypatch):
    """Every per-layer latency the stream computes, off by 1e-9."""
    from repro.core import energymodel
    orig = energymodel._dispatch_chunk

    def faulty(*a, **kw):
        e, t = orig(*a, **kw)
        return e, t * (1 + 1e-9)

    monkeypatch.setattr(energymodel, "_dispatch_chunk", faulty)


def _alter_solver(monkeypatch):
    """Every schedule's bottleneck, off by 1e-9."""
    from repro.core import partition
    orig = partition.batch_schedule_hetero

    def faulty(*a, **kw):
        res = orig(*a, **kw)
        return dataclasses.replace(res, bottleneck=res.bottleneck * (1 + 1e-9))

    monkeypatch.setattr(partition, "batch_schedule_hetero", faulty)


def _alter_pareto(monkeypatch):
    """Every chip's Pareto score, off by 1e-9."""
    from repro.core import hetero
    orig = hetero.pareto_codesign

    def faulty(*a, **kw):
        par = orig(*a, **kw)
        return dataclasses.replace(par, scores=par.scores * (1 + 1e-9))

    monkeypatch.setattr(hetero, "pareto_codesign", faulty)


@pytest.mark.parametrize("cell,fault", [
    (CELL, _alter_answer), (CELL, _alter_engine), (CELL, _alter_solver),
    (SERVICE_CELL, _alter_engine), (SERVICE_CELL, _alter_pareto)])
def test_fault_turns_correct_false(tmp_path, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = run_tiny(tmp_path, cell=cell)
    assert out["correct"] is False
    check = out["checks"]["rel_gap"]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("cell", [CELL, SERVICE_CELL])
def test_control_fails_the_comparison(tmp_path, capsys, cell):
    import jax
    import readings
    files = tiny_checkout(tmp_path, cell)
    readings.main(["--workload", cell, "--seeds", "5",
                   "--control-seeds", "7,8,9"],
                  root=files, files=files, devices=jax.devices()[:1])
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    bench = json.loads((files / "BENCHMARK.json").read_text())
    config = next(w for w in bench["workloads"] if w["name"] == cell)["config"]
    limit = json.loads((files / "configs" / f"{config}.json")
                       .read_text())["check"]["rel_gap"]
    prog = [r["rel_gap"] for r in rows if r["kind"] == "program"]
    ctrl = [r["rel_gap"] for r in rows if r["kind"] == "control"]
    assert len(prog) == 1 and len(ctrl) == 3
    assert max(prog) < limit < min(ctrl)


def test_grid_is_the_repos_mega_grid():
    import inputs
    from repro.core import accelerator
    cfg = json.loads((ROOT / "benchmarks/chip/configs/mega49k-cnn18.json")
                     .read_text())
    mine = inputs.product_grid(cfg["grid"])
    theirs = accelerator.mega_grid().fields
    for k, v in theirs.items():
        np.testing.assert_array_equal(mine[k], v, err_msg=k)


def test_networks_are_the_repos_networks():
    from repro.core import topology
    cfg = json.loads((ROOT / "benchmarks/chip/configs/mega49k-cnn18.json")
                     .read_text())
    assert list(cfg["networks"]) == list(topology.NETWORKS)
    for name, rows in cfg["networks"].items():
        want = [[l.kind, l.c_in, l.c_out, l.k, l.stride, l.pad, l.h_in,
                 l.w_in] for l in topology.get_network(name)]
        assert rows == want, name

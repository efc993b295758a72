"""CPU rehearsal of the four-chip sweep cell (``mega49k-cnn18-sweep-4chip``)
on four host devices, at ``tiny.py``'s grid (96 points, three networks)
in chunks of 20: five chunks, a round of four and a round of one.

The runs go to a child process: the host's device count is fixed when
JAX starts.  Sound, the run is correct and reports the cell's metrics; a
merge that drops a device's state, or per-layer latencies off by 2e-9,
turn ``correct`` false.  The control (the reference in float32 in the
program's place) fails the comparison, and the readers of the new
per-layer metrics read what they should.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from tiny import HERE, ROOT, tiny_checkout

CELL = "mega49k-cnn18-sweep-4chip"
DATA = HERE / "tests" / "data" / "tiny.xplane.pb"

_SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, {chip!r})
    import jax
    import run as bench_run
    import trace_reduce
    from repro.core import energymodel
    assert len(jax.devices()) == 4, jax.devices()
    files = Path({files!r})
    args = ["--workload", {cell!r}, "--seed", "3000000019", "--seconds", "1"]

    def run(*extra):
        return bench_run.run(args + list(extra), root=files, files=files,
                             devices=jax.devices())

    out = dict(sound=run())
    reduce = trace_reduce.reduce
    trace_reduce.reduce = lambda *a, **k: dict(
        window_s=1.0, busy_s=0.5, idle_share=0.5, device_ops=[],
        idle_gaps=[])
    out["traced"] = run("--trace", "1")
    trace_reduce.reduce = reduce
    merge = energymodel._merge_layer_states
    energymodel._merge_layer_states = lambda states: merge(states[:1])
    out["merge_drops_a_device"] = run()
    energymodel._merge_layer_states = merge
    fold = energymodel._jax_layer_fold_devices

    def off_fold():
        step = fold()

        def faulty(metric, k, e, t, *rest):
            return step(metric, k, e, t * (1 + 2e-9), *rest)
        return faulty

    energymodel._jax_layer_fold_devices = off_fold
    out["latency_off"] = run()
    print("RESULT " + json.dumps(out))
""")


def _runs(tmp) -> dict:
    files = tiny_checkout(tmp, CELL)
    traffic = files / "traffic" / "sweep.json"
    t = json.loads(traffic.read_text())
    traffic.write_text(json.dumps(dict(t, chunk_size=20)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    script = _SCRIPT.format(chip=str(HERE), files=str(files),
                            cell=CELL)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    return _runs(tmp_path_factory.mktemp("sweep"))


def test_sound_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["count"] == 4
    assert out["checks"]["rel_gap"]["value"] < 1e-13
    assert set(out["metrics"]) == {"dse_job_s", "peak_device_gb",
                                   "setup_s"}


def test_traced_run_reports_the_cells_metrics(runs):
    out = runs["traced"]
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # the CPU's trace has no device plane: device_balance reads nothing
    assert set(m) == {"stream_merge_s", "device_idle.sweep",
                      "window_compiles.job", "stream_s", "stream_prep_s",
                      "stream_dispatch_s", "stream_wait_s",
                      "stream_collect_s", "stream_row_use",
                      "device_syncs.job"}
    assert m["stream_merge_s"] > 0
    assert m["window_compiles.job"] == 0
    parts = sum(m[f"stream_{p}_s"] for p in ("prep", "dispatch", "wait",
                                             "collect", "merge"))
    assert 0 < parts <= m["stream_s"]
    assert 0 < m["stream_row_use"] <= 1
    # per job: a copy of each round's (es, ts, mask) and of the states
    assert m["device_syncs.job"] == 3


@pytest.mark.parametrize("fault", ["merge_drops_a_device", "latency_off"])
def test_fault_turns_correct_false(runs, fault):
    out = runs[fault]
    assert out["correct"] is False
    check = out["checks"]["rel_gap"]
    assert check["value"] > check["limit"]


def test_control_fails_the_comparison(tmp_path, capsys):
    import jax
    import readings
    files = tiny_checkout(tmp_path, CELL)
    readings.main(["--workload", CELL, "--seeds", "5",
                   "--control-seeds", "7,8,9"],
                  root=files, files=files, devices=jax.devices()[:1])
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    bench = json.loads((files / "BENCHMARK.json").read_text())
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == CELL)
    limit = json.loads((files / "configs" / f"{config}.json")
                       .read_text())["check"]["rel_gap"]
    prog = [r["rel_gap"] for r in rows if r["kind"] == "program"]
    ctrl = [r["rel_gap"] for r in rows if r["kind"] == "control"]
    assert len(prog) == 1 and len(ctrl) == 3
    assert max(prog) < limit < min(ctrl)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_config_is_the_mega_grid_deployed_on_the_cells_chips():
    """The cell's configuration is ``mega49k-cnn18``'s Tool inputs as
    they are (grid, networks, co-design, energy draw, check), with a
    cluster of as many devices as the cell has chips."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json")
                     .read_text())
    base = json.loads((HERE / "configs" / "mega49k-cnn18.json").read_text())
    assert cell["config"] != "mega49k-cnn18"
    assert cfg["cluster"]["devices"] == cell["chips"] == 4
    for key in set(base) | set(cfg):
        if key not in ("name", "source", "cluster"):
            assert cfg.get(key) == base.get(key), key


def test_device_balance_reads_each_devices_busy_time():
    read = _reader("device_balance").read
    assert read(SimpleNamespace(trace=dict(
        busy_per_device_s=[0.5, 0.25, 0.4, 0.5]))) == 0.5
    assert read(SimpleNamespace(trace=None)) is None
    assert read(SimpleNamespace(trace=dict(busy_s=0.5))) is None


def test_per_device_busy_agrees_with_the_reduction():
    """On a trace recorded on one v5e, the one device's busy time is the
    reduction's, and a wrapped reduction reports it once."""
    import device_busy
    import trace_reduce as tr
    data = tr.load(str(DATA))
    busy = device_busy.per_device(data, 1)
    # the plain reduction, whatever wrap an earlier set-up left in place
    orig = tr.reduce = getattr(tr.reduce, "__wrapped__", tr.reduce)
    device_busy.report_per_device()
    device_busy.report_per_device()
    try:
        out = tr.reduce(data, ["stream", "pool"], n_devices=1)
        assert tr.reduce is orig          # the wrap removed itself
    finally:
        tr.reduce = orig
    assert len(busy) == 1 and busy[0] > 0
    assert out["busy_per_device_s"] == busy
    assert abs(busy[0] - out["busy_s"]) < 1e-9


@pytest.mark.parametrize("name", ["stream_merge_s", "device_idle.sweep"])
def test_reader_without_events_reads_nothing(name):
    """No program event in the window (a program without the merge span),
    no trace: no value, and no error."""
    ctx = SimpleNamespace(window=(-2.0, -1.0), result=dict(jobs=3),
                          trace=None)
    assert _reader(name).read(ctx) is None

"""The per-layer metrics that read the program's own spans and counters
(``repro.core.obs``), in a CPU rehearsal of a traced run of the co-design
cell.

The CPU's trace has no device plane, so the trace reduction (tested in
``test_trace_reduce.py``) is stood in by a fixed one here: the readers
under test read the program's events, not the trace.
"""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace

import pytest

from tiny import HERE, run_tiny

NEW = ("stream_prep_s", "stream_dispatch_s", "stream_wait_s",
       "stream_collect_s", "pool_eval_s", "schedule_host_s",
       "schedule_wait_s", "stream_row_use", "device_syncs.job")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_run_reports_the_program_spans(tmp_path, monkeypatch):
    import trace_reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda *a, **k: dict(
        window_s=1.0, busy_s=0.5, idle_share=0.5, device_ops=[],
        idle_gaps=[]))
    out = run_tiny(tmp_path, "--trace", "1")
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert all(m[k] > 0 for k in NEW)
    assert m["stream_row_use"] <= 1.0
    # 96 points in chunks of 40: three chunk copies and the final state,
    # the pool's copy, stage 1 and (where a row needs it) the bisection
    assert 6 <= m["device_syncs.job"] <= 7
    parts = sum(m[f"stream_{p}_s"] for p in
                ("prep", "dispatch", "wait", "collect"))
    assert 0.8 * m["stream_s"] < parts <= m["stream_s"]
    sched = m["schedule_host_s"] + m["schedule_wait_s"]
    assert sched <= m["solve_s"]


@pytest.mark.parametrize("name", NEW)
def test_reader_without_program_events_reads_nothing(name):
    """A window with no program event (as on a program without
    ``repro.core.obs``) gives no value, and no error."""
    ctx = SimpleNamespace(window=(-2.0, -1.0), result=dict(jobs=3))
    assert _reader(name).read(ctx) is None

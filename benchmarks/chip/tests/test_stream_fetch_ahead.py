"""The ``stream_fetch_ahead`` reader (``dse.stream.fetch_ahead``), in a CPU
rehearsal of a traced run of the co-design cell, and on a window with no
program events."""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace

import pytest

from tiny import HERE, run_tiny

NAME = "stream_fetch_ahead"


def _reader():
    spec = importlib.util.spec_from_file_location(
        f"metric_{NAME}", HERE / "metrics" / f"{NAME}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_run_reports_fetch_ahead(tmp_path, monkeypatch):
    import trace_reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda *a, **k: dict(
        window_s=1.0, busy_s=0.5, idle_share=0.5, device_ops=[],
        idle_gaps=[]))
    out = run_tiny(tmp_path, "--trace", "1")
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # 96 points in chunks of 40: each chunk but the last is fetched with
    # the next one's kernel and fold queued
    assert m[NAME] == pytest.approx(2.0)


def test_reader_without_program_events_reads_nothing():
    """A window with no program event (as on a program without the
    counter) gives no value, and no error."""
    ctx = SimpleNamespace(window=(-2.0, -1.0), result=dict(jobs=3))
    assert _reader().read(ctx) is None

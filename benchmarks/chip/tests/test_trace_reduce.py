"""The trace reduction, on a small trace recorded on one TPU v5e
(``data/tiny.xplane.pb``, made by ``record_trace.py``: two jobs of a
1024x1024 matmul program, each 20 calls under a ``stream`` span and a
20 ms host sleep under a ``pool`` span) and on hand-made planes."""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_union_and_gaps():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tr.gaps(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_reduce_hand_made():
    host = plane("/host:CPU", python=[
        ev("bench.window", 0, 1000), ev("job", 0, 1000),
        ev("pool", 600, 300), ev("unrelated", 0, 1000)])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1 = f32[] fusion(x)", -50, 150),
                         ev("fusion.2", 50, 250), ev("fusion.1", 900, 200)],
                XLA_Modules=[ev("jit(f)", -50, 1200)])
    r = tr.reduce(NS(planes=[host, dev]), ["job", "pool"])
    # busy: [0, 300) and [900, 1000) inside the window
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["device_ops"][0] == ["jit(f)/fusion.2", pytest.approx(250e-9)]
    assert r["device_ops"][1] == ["jit(f)/fusion.1", pytest.approx(200e-9)]
    # the gap [300, 900) has its middle (600) inside "pool"
    assert r["idle_gaps"] == [["pool", pytest.approx(600e-9)]]


def test_reduce_without_window_or_ops_reads_nothing():
    dev = plane("/device:TPU:0", XLA_Ops=[ev("f", 0, 10)])
    assert tr.reduce(NS(planes=[dev]), []) is None
    host = plane("/host:CPU", python=[ev("bench.window", 0, 100)])
    assert tr.reduce(NS(planes=[host]), []) is None


def test_reduce_recorded_tpu_trace():
    r = tr.reduce(tr.load(str(DATA)), ["job", "stream", "pool"])
    assert r is not None
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    labels = dict(r["idle_gaps"])
    # the two 20 ms sleeps: the device idles under "pool" for about 40 ms
    assert labels["pool"] == pytest.approx(0.04, rel=0.5)
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert r["device_ops"][0][0].startswith("jit__lambda(")
    assert r["device_ops"][0][0].endswith(")/%fusion")

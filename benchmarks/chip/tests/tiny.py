"""A tiny copy of a cell, for rehearsals on the CPU.

``tiny_checkout(tmp)`` writes ``BENCHMARK.json``, ``configs/``,
``traffic/`` and a link to ``src/`` under ``tmp`` for one cell, with its
configuration cut to a 96-point grid (and, for the co-design cell, three
networks; for the service cell, 20 queries a second); the harness then
runs it with ``root`` and ``files`` at ``tmp``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

CELL = "mega49k-cnn18-codesign"
SERVICE_CELL = "svc-ext5400-cnn4-steady"
NETS = ("AlexNet", "MobileNet", "ResNet50")


#: The service cell and its metrics, built and proven but not in
#: BENCHMARK.json (see PERF.md, Open questions): added here so its
#: generator stays rehearsed.
SERVICE_ENTRIES = dict(
    workloads=[dict(name=SERVICE_CELL, config="svc-ext5400-cnn4",
                    traffic="steady", chips=1)],
    end_to_end=[dict(name=n, unit="s", workloads=[SERVICE_CELL])
                for n in ("query_p50_s", "query_p95_s")],
    per_layer=[dict(name=n, unit=u, workloads=[SERVICE_CELL]) for n, u in (
        ("step_ms", "ms"), ("requests_per_step", "requests"),
        ("pareto_ms", "ms"), ("device_idle.query", "%"),
        ("window_compiles.query", "compiles"))])


def tiny_checkout(tmp: Path, cell: str = CELL) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if all(w["name"] != SERVICE_CELL for w in bench["workloads"]):
        for k, v in SERVICE_ENTRIES.items():
            bench[k] += v
    c = next(w for w in bench["workloads"] if w["name"] == cell)
    cfg = json.loads((HERE / "configs" / f"{c['config']}.json").read_text())
    cfg["grid"].update(arrays=[[12, 14], [32, 32], [96, 96]],
                       gb_psum_kb=[13, 54], gb_ifmap_kb=[13, 54, 216, 864],
                       rf_psum_words=[16, 24], noc_wpc=[2.0, 4.0])
    traffic = json.loads(
        (HERE / "traffic" / f"{c['traffic']}.json").read_text())
    if "codesign" in cfg:
        cfg["networks"] = {n: cfg["networks"][n] for n in NETS}
        cfg["codesign"]["chunk_size"] = 40
    else:
        traffic["rate_per_s"] = 20.0
    tmp = Path(tmp)
    (tmp / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "traffic").mkdir(parents=True, exist_ok=True)
    (tmp / "configs" / f"{c['config']}.json").write_text(json.dumps(cfg))
    (tmp / "traffic" / f"{c['traffic']}.json").write_text(
        json.dumps(traffic))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    if not (tmp / "src").exists():
        (tmp / "src").symlink_to(ROOT / "src")
    return tmp


def run_tiny(tmp: Path, *args: str, cell: str = CELL) -> dict:
    import jax
    import run as bench_run
    files = tiny_checkout(tmp, cell)
    return bench_run.run(["--workload", cell, "--seed", "3000000019",
                          "--seconds", "1", *args],
                         root=files, files=files, devices=jax.devices()[:1])

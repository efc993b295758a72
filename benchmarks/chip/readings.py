#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's, and the control's.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 11,12,... --control-seeds 21,22,23

For each of ``--seeds`` the cell's generator runs its timed path as a run
does (``reading``) and compares the answers with the plain reference:
the lower readings.  For each of ``--control-seeds`` it puts the
reference computed in float32, the precision below the configuration's
float64, in the program's place (``control``) and makes the same
comparison: the upper readings.  One JSON line per reading on standard
output; a TPU is needed, as for a run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench_run  # noqa: E402


def main(argv=None, root=None, files=HERE, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    root = Path(root) if root is not None else HERE.parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = bench_run.find_cell(bench, args.workload)
    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    if devices is None:
        bench_run.require_devices(cell["chips"])
    enable_compile_cache()
    ctx = bench_run.Context(Path(files), cell, 0)
    generator = bench_run.load_module(
        HERE / "generators" / f"{ctx.traffic['generator']}.py", "generator")
    for kind, fn, group in (("program", generator.reading, seeds),
                            ("control", generator.control, cseeds)):
        for seed in group:
            t0 = time.perf_counter()
            gap, detail = fn(ctx, seed)
            print(json.dumps(dict(kind=kind, seed=seed, rel_gap=gap,
                                  seconds=time.perf_counter() - t0,
                                  detail=detail)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

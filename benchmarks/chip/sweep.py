#!/usr/bin/env python3
"""Rate sweep of an open-loop service cell: the knee its rate is set from.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds 10 --rates 10,20,40,80

Sets the cell up once, as a run does, then runs one measured window per
rate (the traffic file's rate replaced) and prints, per rate, one JSON
line: the query tails, failures, steps, the deepest queue, how late the
last answer came after the window and how late the generator ran.  A
TPU is needed, as for a run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = HERE.parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = bench_run.find_cell(bench, args.workload)
    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    bench_run.require_devices(cell["chips"])
    enable_compile_cache()
    ctx = bench_run.Context(HERE, cell, args.seed)
    generator = bench_run.load_module(
        HERE / "generators" / f"{ctx.traffic['generator']}.py", "generator")
    st = generator.setup(ctx)
    for rate in (float(r) for r in args.rates.split(",")):
        st.rate = rate
        w = generator.window(st, args.seconds)
        print(json.dumps(dict(rate_per_s=rate, **w["end_to_end"], **{
            k: w[k] for k in ("attempted", "failed", "steps", "completed",
                              "max_queue", "drain_s", "late_s")})),
              flush=True)
    generator.release(st)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Chip benchmark of the DSE system: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` (at the root of the checkout).
Everything the run needs is found by name under this directory:

* ``configs/<config>.json``: the configuration as it is run;
* ``traffic/<traffic>.json``: the traffic mix's parameters, among them
  ``generator``, the program that reads them
  (``generators/<generator>.py``);
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``peaks.json``: published peaks of each device kind.

A run needs a TPU with at least the cell's chips, else it exits non-zero
and prints no result.  It sets up (counted as ``setup_s``), measures for
``--seconds``, reads the device's peak memory, frees the program's state,
compares a sample of the answers with the plain reference and prints, as
the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse        # noqa: E402
import importlib.util  # noqa: E402
import json            # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402
import tempfile        # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import spans as spans_mod  # noqa: E402
import trace_reduce  # noqa: E402


class Failure(SystemExit):
    """A run that cannot produce a result: message on stderr, exit 2."""

    def __init__(self, msg: str):
        print(f"benchmark: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise Failure(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a generator and a metric reader see of the run."""

    def __init__(self, files: Path, cell: dict, seed: int):
        self.seed = seed
        self.config = json.loads(
            (files / "configs" / f"{cell['config']}.json").read_text())
        self.traffic = json.loads(
            (files / "traffic" / f"{cell['traffic']}.json").read_text())
        self.recorder = spans_mod.Recorder()
        self.log = log


def find_cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise Failure(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str):
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def require_devices(n: int):
    """The first ``n`` TPU chips, or a Failure."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failure(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise Failure(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def device_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Failure(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def run(argv=None, root: Path | None = None, files: Path = HERE,
        devices=None) -> dict:
    """One run; ``root`` holds BENCHMARK.json and ``src/``, ``files`` the
    ``configs/`` and ``traffic/`` directories, and ``devices`` (tests
    only) stands in for the TPU chips."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = Path(root) if root is not None else HERE.parents[1]
    bench_path = root / "BENCHMARK.json"
    if not bench_path.is_file():
        raise Failure(f"no BENCHMARK.json at {root}")
    bench = json.loads(bench_path.read_text())
    cell = find_cell(bench, args.workload)
    sys.path.insert(0, str(root / "src"))
    try:
        import repro.core.hetero  # noqa: F401  (the system under test)
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise Failure(f"the system under test is not importable: {e}")

    devs = devices if devices is not None else require_devices(cell["chips"])
    kind = devs[0].device_kind
    if devices is None:
        device_peaks(kind)
    log(f"device: {devs[0].platform} {kind} x {len(devs)}; compile cache "
        f"{enable_compile_cache()}")

    ctx = Context(Path(files), cell, args.seed)
    traffic = ctx.traffic
    gen_name = traffic["generator"]
    generator = load_module(HERE / "generators" / f"{gen_name}.py",
                            f"generator_{gen_name}")
    rec = ctx.recorder
    for module, attr, name in generator.SPANS:
        rec.wrap(module, attr, name)
    rec.listen_compiles()
    try:
        state = generator.setup(ctx)
        tdir = None
        if args.trace:
            import jax
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
        setup_s = time.perf_counter() - _T_PROCESS
        with rec.span(trace_reduce.WINDOW_SPAN):
            win = generator.window(state, args.seconds)
        if args.trace:
            jax.profiler.stop_trace()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        generator.release(state)
    finally:
        rec.restore()
    lo, hi = win["t0"], win["t1"]
    for t, fun in rec.compiles:
        if lo <= t <= hi:
            log(f"compiled in the window: {fun} at {t - lo:.3f} s")
    readings = generator.check(state, win)

    limits = ctx.config["check"]
    checks = {k: dict(value=v, limit=limits[k]) for k, v in readings.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    ctx.window = (win["t0"], win["t1"])
    ctx.result = win
    ctx.trace = None
    breakdown = None
    device = dict(platform=devs[0].platform, kind=kind, count=len(devs),
                  memory_peak_bytes=int(peak))
    if args.trace:
        names = {s[0] for s in rec.spans}
        ctx.trace = trace_reduce.reduce(trace_reduce.load(tdir), sorted(names),
                                     n_devices=len(devs))
        shutil.rmtree(tdir, ignore_errors=True)
        if ctx.trace is None:
            raise Failure("the trace shows no device operation in the window")
        device.update(busy_s=ctx.trace["busy_s"],
                      window_s=ctx.trace["window_s"])
        breakdown = dict(device_ops=ctx.trace["device_ops"],
                         idle_gaps=ctx.trace["idle_gaps"])
        metrics = {}
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 f"metric_{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        values = dict(win["end_to_end"], setup_s=setup_s,
                      peak_device_gb=peak / 1e9)
        metrics = {}
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            if m["name"] not in values:
                raise Failure(f"the generator gives no {m['name']}")
            metrics[m["name"]] = dict(value=values[m["name"]],
                                      unit=m["unit"])
    out = dict(correct=correct, attempted=win["attempted"],
               failed=win["failed"], metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return out


def main(argv=None) -> int:
    out = run(argv)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

On one chip: two jobs of a small jitted program inside the harness's
window annotation, with host spans named like a generator's, and an idle
gap between them; prints the trace's planes and lines, and the
reduction, and copies the ``.xplane.pb`` to ``<out_dir>/tiny.xplane.pb``.
"""

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import spans  # noqa: E402
import trace_reduce  # noqa: E402


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", jax.devices()
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    rec = spans.Recorder()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with rec.span(trace_reduce.WINDOW_SPAN):
        for _ in range(2):
            with rec.span("job"):
                with rec.span("stream"):
                    for _ in range(20):
                        f(x).block_until_ready()
                with rec.span("pool"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    data = trace_reduce.load(path)
    for p in data.planes:
        print("plane", p.name, [(l.name, sum(1 for _ in l.events))
                                for l in p.lines])
    print(trace_reduce.reduce(data, ["job", "stream", "pool"]))
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(path, Path(out) / "tiny.xplane.pb")


if __name__ == "__main__":
    main(sys.argv[1])

#!/usr/bin/env python3
"""Smoke run of the DSE system's main path on a TPU.

One process, no children.  With no arguments it needs one chip and runs,
in order:

  (a) engine parity: ``evaluate_networks`` on the 5,400-point extended
      grid × all 18 networks, device (``backend="jax"``) against the numpy
      reference;
  (b) the streamed co-design over the 49,000-point mega grid, cold and
      warm (the warm call must not retrace), against the same co-design
      on the numpy reference: same pool and chip, energies, latencies
      and score within the cross-backend tolerance;
  (c) the ``DSEService`` as ``repro.launch.serve_dse`` builds it on the
      extended grid, answering best-config, best-chip and Pareto queries
      until drained;

and asserts that every answer came from the device with no fault,
fallback, degraded answer, shadow mismatch or invariant violation.

``--chips 4`` runs only the sharded engine on four chips: the streamed
per-layer sweep of the mega grid and the dense extended-grid evaluation,
each with ``shard=True`` against the same call with ``shard=False``.

Any failure exits non-zero.  A host without a TPU is a failure.  The last
line of standard output is the device line, printed only on success:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

    python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: Chunk of the mega-grid streams: a multiple of the grid's innermost
#: (NoC) axis, so chunk-local dedup matches the global one.
MEGA_CHUNK = 9800
#: Top-k of the four-chip sweep (the chip benchmark's ``sweep`` cell: the
#: co-design's pool size), whose sharded programs at ``MEGA_CHUNK`` a
#: benchmark run of that cell leaves in the compile cache.
SWEEP_TOPK = 6


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {devs[0].platform!r}); "
            "this smoke runs on the chip only")
    if len(devs) != count:
        raise SystemExit(f"chip_smoke: expected {count} TPU chip(s), "
                         f"JAX sees {len(devs)}")
    return devs


def _all_networks():
    from repro.core import topology
    return {n: topology.get_network(n) for n in topology.NETWORKS}


def phase_parity(grid, nets) -> None:
    """(a) Device engine against the numpy reference, same grid."""
    import numpy as np
    from repro.core import energymodel
    from repro.ft.verify import SHADOW_RTOL

    t0 = time.perf_counter()
    e_d, t_d = energymodel.evaluate_networks(grid, nets, backend="jax")
    t_dev = time.perf_counter() - t0
    check(energymodel.last_backend() == "jax",
          f"parity ran on {energymodel.last_backend()!r}, not the device")
    t0 = time.perf_counter()
    e_r, t_r = energymodel.evaluate_networks(grid, nets, backend="numpy")
    t_np = time.perf_counter() - t0
    log(f"[a] {grid.n} points x {len(nets)} networks: device "
        f"{t_dev:.2f}s incl. compile, numpy {t_np:.2f}s (smoke times, "
        "not metrics)")
    for name, d, r in (("energy", e_d, e_r), ("latency", t_d, t_r)):
        check(d.shape == r.shape == (grid.n, len(nets)),
              f"{name} shape {d.shape} != {r.shape}")
        check(np.isfinite(d).all(), f"non-finite device {name}")
        rel = float(np.max(np.abs(d - r) / np.abs(r)))
        log(f"[a] {name}: {int(np.count_nonzero(d != r))} of {d.size} "
            f"elements differ from numpy, max rel err {rel!r}")
        check(rel <= SHADOW_RTOL,
              f"{name} max rel err {rel!r} > SHADOW_RTOL {SHADOW_RTOL!r}")
    for metric, d, r in (("energy", e_d, e_r), ("latency", t_d, t_r),
                         ("edp", e_d * t_d, e_r * t_r)):
        a_d, a_r = np.argmin(d, axis=0), np.argmin(r, axis=0)
        check(np.array_equal(a_d, a_r),
              f"per-network {metric} argmin differs: networks "
              f"{[n for n, x, y in zip(nets, a_d, a_r) if x != y]}")
    log("[a] per-network energy/latency/EDP argmin cells identical")


def _check_codesign_on_reference(grid, nets, chunk: int, cd) -> None:
    """The same streamed co-design on the numpy reference must choose the
    same pool and chip, with energies, latencies and score within the
    cross-backend tolerance."""
    import numpy as np
    from repro.core import hetero
    from repro.ft.verify import SHADOW_RTOL

    t0 = time.perf_counter()
    ref = hetero.co_design_streaming(grid, nets, chunk_size=chunk,
                                     backend="numpy")
    log(f"[b] numpy reference co-design {time.perf_counter() - t0:.2f}s: "
        f"pool {ref.pool}, chip {ref.summary(grid)}")
    check(cd.pool == ref.pool, f"pool {cd.pool} != numpy {ref.pool}")
    check(cd.core_types == ref.core_types
          and cd.core_counts == ref.core_counts,
          f"chip {cd.core_types}x{cd.core_counts} != numpy "
          f"{ref.core_types}x{ref.core_counts}")
    for name in nets:
        for what, got, want in (("energy", cd.energy, ref.energy),
                                ("latency", cd.latency, ref.latency)):
            check(abs(got[name] - want[name]) <= SHADOW_RTOL * want[name],
                  f"{name}: scheduled {what} {got[name]!r} != numpy "
                  f"{want[name]!r}")
    check(np.isclose(cd.score, ref.score, rtol=SHADOW_RTOL, atol=0.0),
          f"score {cd.score!r} != numpy {ref.score!r}")


def phase_codesign(grid, nets, chunk: int) -> None:
    """(b) Streamed co-design, cold then warm."""
    import numpy as np
    from repro.core import energymodel, hetero

    s0 = energymodel.jit_cache_stats()
    t0 = time.perf_counter()
    cold = hetero.co_design_streaming(grid, nets, chunk_size=chunk)
    t_cold = time.perf_counter() - t0
    check(energymodel.last_backend() == "jax",
          f"co-design ran on {energymodel.last_backend()!r}")
    s1 = energymodel.jit_cache_stats()
    t0 = time.perf_counter()
    warm = hetero.co_design_streaming(grid, nets, chunk_size=chunk)
    t_warm = time.perf_counter() - t0
    s2 = energymodel.jit_cache_stats()
    log(f"[b] {grid.n} points x {len(nets)} networks, chunk {chunk}: "
        f"pool {len(cold.pool)} {cold.pool}, {cold.n_chips} chips")
    log(f"[b] chosen chip: {cold.summary(grid)} (types {cold.core_types}, "
        f"counts {cold.core_counts}), score {cold.score!r} vs homogeneous "
        f"{cold.homogeneous_score!r}")
    log(f"[b] smoke times, not metrics: cold {t_cold:.2f}s, warm "
        f"{t_warm:.2f}s")
    log(f"[b] jit_cache_stats: before {s0}, after cold {s1}, after warm {s2}")
    check(s2["traces"] == s1["traces"],
          f"warm co-design retraced {s2['traces'] - s1['traces']} programs")
    check(sum(cold.core_counts) == cold.m_cores, "core counts do not sum")
    check(np.isfinite(cold.chip_scores).all(), "non-finite chip scores")
    check(cold.score <= cold.homogeneous_score,
          "heterogeneous chip scores worse than the best homogeneous one")
    check(cold.pool == warm.pool and cold.core_types == warm.core_types
          and cold.core_counts == warm.core_counts
          and np.array_equal(cold.chip_scores, warm.chip_scores)
          and cold.energy == warm.energy and cold.latency == warm.latency,
          "warm co-design differs from the cold one")
    _check_codesign_on_reference(grid, nets, chunk, cold)
    log("[b] pool, chip, energies, latencies and score match the numpy "
        "reference")


def phase_service(grid) -> dict:
    """(c) The DSE service on the device, drained."""
    from repro.core import energymodel
    from repro.launch import serve_dse

    svc = serve_dse.build_service(serve_dse.parse_args([]), grid=grid)
    try:
        names = list(svc.names)
        queries = [("best_config", None, 2.0), ("best_chip", None, 1.5),
                   ("pareto", names[0], 1.5), ("best_config", names[1], 2.0),
                   ("best_chip", names[2], 3.0), ("pareto", names[3], 2.0)]
        for kind, net, deadline in queries:
            sub = svc.submit(kind, network=net, deadline=deadline)
            check(sub.accepted, f"{kind} query rejected")
        t0 = time.perf_counter()
        responses, drained = svc.run_until_drained()
        dt = time.perf_counter() - t0
        health = svc.health()
    finally:
        svc.close()
    n_deg = sum(r.degraded for r in responses)
    log(f"[c] served {len(responses)} responses on {grid.n} points in "
        f"{dt:.2f}s (smoke time, not a metric), {n_deg} degraded, "
        f"drained={drained}")
    for r in responses:
        log(f"[c] rid {r.rid} {r.kind}: ok={r.ok} backend={r.backend} "
            f"latency {r.latency_s:.3f}s")
    keys = ("faults", "backend_fallbacks", "shadow_checks",
            "shadow_mismatches", "invariant_checks", "invariant_violations",
            "errors", "retries")
    log("[c] health: " + json.dumps({k: health[k] for k in keys}))
    # the failure message carries the service's own account, so a refusal
    # that shows only the traceback still says what went wrong
    why = (f"; health {json.dumps({k: health[k] for k in keys})}, "
           f"last fault: {health['last_fault']}")
    check(drained, "service did not drain" + why)
    check(len(responses) == len(queries), "a query went unanswered")
    check(n_deg == 0, f"{n_deg} degraded answers" + why)
    check(all(r.ok for r in responses),
          f"failed answers: {[r.error for r in responses if not r.ok]}")
    check(all(r.backend == "jax" for r in responses),
          f"answers from {sorted({r.backend for r in responses})}")
    for k in ("faults", "backend_fallbacks", "shadow_mismatches",
              "invariant_violations"):
        check(health[k] == 0, f"health {k} = {health[k]}" + why)
    check(health["shadow_checks"] > 0,
          "no chunk was shadow-checked against the numpy reference")
    check(energymodel.last_backend() == "jax",
          f"last backend {energymodel.last_backend()!r}")
    return health


def phase_sharded_dense(ext, nets) -> None:
    """Four chips: the dense evaluation sharded against unsharded, bit for
    bit."""
    import jax
    import numpy as np
    from repro.core import energymodel

    devs = jax.devices()
    t0 = time.perf_counter()
    e1, t1 = energymodel.evaluate_networks(ext, nets, shard=True)
    mesh = energymodel._cfg_mesh()
    e0, t0_ = energymodel.evaluate_networks(ext, nets, shard=False)
    log(f"[4] evaluate_networks {ext.n} points: shard_map over a "
        f"{mesh.devices.size}-device mesh, both calls "
        f"{time.perf_counter() - t0:.2f}s incl. compile (smoke time)")
    check(mesh.devices.size == len(devs),
          f"mesh holds {mesh.devices.size} devices, not {len(devs)}")
    check(np.array_equal(e1, e0) and np.array_equal(t1, t0_),
          "sharded evaluate_networks differs from unsharded")
    check(energymodel.last_backend() == "jax",
          f"last backend {energymodel.last_backend()!r}")
    log("[4] sharded evaluate_networks == unsharded, bit for bit")


def phase_sharded_stream(mega, nets) -> None:
    """Four chips: the streamed per-layer sweep sharded against unsharded,
    bit for bit, with every device computing chunks and no device
    computing a chunk twice."""
    import jax
    import numpy as np
    from repro.core import energymodel

    devs = jax.devices()
    # per round of the sharded stream, the devices whose shard of the
    # grid kernel's output holds values (an idle device returns zeros)
    computed = []
    device_kernel = energymodel._jax_device_kernel

    def recording(*a, **k):
        kern = device_kernel(*a, **k)

        def run(*args):
            out = kern(*args)
            computed.append(sorted(s.device.id
                                   for s in out[0].addressable_shards
                                   if bool(s.data.any())))
            return out
        return run

    energymodel._jax_device_kernel = recording
    try:
        t0 = time.perf_counter()
        sh = energymodel.stream_layer_topk(mega, nets, chunk_size=MEGA_CHUNK,
                                           topk=SWEEP_TOPK, shard=True,
                                           bound=0.05)
        t_sh = time.perf_counter() - t0
    finally:
        energymodel._jax_device_kernel = device_kernel
    t0 = time.perf_counter()
    un = energymodel.stream_layer_topk(mega, nets, chunk_size=MEGA_CHUNK,
                                       topk=SWEEP_TOPK, shard=False,
                                       bound=0.05)
    t_un = time.perf_counter() - t0
    n_chunks = -(-mega.n // MEGA_CHUNK)
    log(f"[4] stream_layer_topk {mega.n} points, chunk {MEGA_CHUNK}: "
        f"sharded {t_sh:.2f}s, unsharded {t_un:.2f}s incl. compile (smoke "
        f"times, not metrics); devices that computed, by round {computed}")
    check({d for rnd in computed for d in rnd} == {d.id for d in devs},
          f"sharded chunks computed on devices {computed} by round, not on "
          f"all {len(devs)}")
    check(sum(map(len, computed)) == n_chunks,
          f"{sum(map(len, computed))} device computations by round "
          f"{computed} for {n_chunks} chunks")
    for f in ("topk_idx", "topk_metric", "layer_energy", "layer_latency",
              "min_energy", "min_latency", "min_edp", "min_metric",
              "argmin", "layer_min_metric", "layer_argmin"):
        check(np.array_equal(getattr(sh, f), getattr(un, f)),
              f"sharded stream {f} differs from unsharded")
    for f in ("boundary_idx", "boundary_energy", "boundary_latency"):
        a, b = getattr(sh, f), getattr(un, f)
        check(all(np.array_equal(a[n], b[n]) for n in nets),
              f"sharded stream {f} differs from unsharded")
    log("[4] sharded stream == unsharded stream, bit for bit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c); 4: only the sharded engine")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.core import accelerator
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    nets = _all_networks()
    t0 = time.perf_counter()
    if args.chips == 4:
        # the dense check first, the quicker of the two
        phase_sharded_dense(accelerator.extended_grid(), nets)
        phase_sharded_stream(accelerator.mega_grid(), nets)
    else:
        phase_parity(accelerator.extended_grid(), nets)
        phase_codesign(accelerator.mega_grid(), nets, MEGA_CHUNK)
        phase_service(accelerator.extended_grid())
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

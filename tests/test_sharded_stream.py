"""The sharded per-layer stream on several host devices.

``stream_layer_topk(shard=True)`` sends chunks out in rounds, one per
device, under one program for all devices, folds each device's chunks
into that device's own state and merges the states once at the end.  A
child process forced to 1, 2 or 4 CPU devices (the count is fixed when
JAX starts) runs it on a 96-point grid x 3 networks in chunks of 20 (five
chunks: rounds of 4 + 1 on four devices, 2 + 2 + 1 on two) and compares it
with the one-device stream bit for bit, with and without boundary sets;
the chunked dense evaluation likewise, and plants faults in the sharded
stream.  The same child reports the stream's compiles, spans and
counters.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np
    import jax
    from repro.core import accelerator, energymodel, obs, topology
    from repro.ft import faults

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, d, **kw: compiles.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    nets = {n: topology.get_network(n)
            for n in ("AlexNet", "MobileNet", "ResNet50")}
    mega = accelerator.mega_grid()
    grid = mega.take(np.arange(0, mega.n, mega.n // 96)[:96])
    FIELDS = ("topk_idx", "topk_metric", "layer_energy", "layer_latency",
              "min_energy", "min_latency", "min_edp", "min_metric",
              "argmin", "layer_min_metric", "layer_argmin")

    def differ(a, b):
        out = [f for f in FIELDS
               if not np.array_equal(getattr(a, f), getattr(b, f))]
        if a.boundary_idx is not None:
            out += [f"{f}[{n}]" for f in ("boundary_idx", "boundary_energy",
                                          "boundary_latency") for n in nets
                    if not np.array_equal(getattr(a, f)[n],
                                          getattr(b, f)[n])]
        return out

    out = dict(devices=len(jax.devices()), differ={})
    for bound in (None, 0.05):
        kw = dict(chunk_size=20, topk=6, bound=bound)
        one = energymodel.stream_layer_topk(grid, nets, shard=False, **kw)
        compiles.clear()
        sh = energymodel.stream_layer_topk(grid, nets, shard=True, **kw)
        out.setdefault("compiles", compiles[:])
        out["differ"][str(bound)] = differ(sh, one)

    # faults on the sharded path: a NaN is caught with its chunk, and a
    # stream killed mid-way resumes from its last export to the same end
    kw = dict(chunk_size=20, topk=6, bound=0.05, shard=True)
    try:
        with faults.inject_chunk_faults(faults.FaultPlan(corrupt_at={2: "nan"})):
            energymodel.stream_layer_topk(grid, nets, **kw)
    except energymodel.ChunkCorruption as e:
        out["corrupt_chunk"] = e.chunk
    states = []
    try:
        with faults.inject_chunk_faults(faults.FaultPlan(kill_at=4)):
            energymodel.stream_layer_topk(grid, nets, on_chunk=states.append,
                                          **kw)
    except faults.StreamKill:
        pass
    out["exports"] = [st.next_chunk for st in states]
    res = energymodel.stream_layer_topk(grid, nets, resume_from=states[-1],
                                        **kw)
    out["resumed_differ"] = differ(res, one)
    for per_layer in (False, True):
        a = energymodel.evaluate_networks(grid, nets, use_jax=True,
                                          chunk_size=20, per_layer=per_layer)
        b = energymodel.evaluate_networks(grid, nets, use_jax=True,
                                          chunk_size=20, per_layer=per_layer,
                                          shard=True)
        out[f"dense_equal_{per_layer}"] = all(
            np.array_equal(x, y) for x, y in zip(a, b))

    # a round of one chunk: the first device computes it as the
    # one-device kernel does, the others are inactive and return zeros
    with energymodel.x64():
        lay, segments = energymodel._stack_networks(nets, absorb_pad=False)
        lay = {k: v[None, :] for k, v in lay.items()}
        prep = energymodel._prepare_chunk(grid.take(np.arange(20)).fields)
        one_e, one_t = energymodel._jax_grid_kernel("jax", True)(
            segments, prep[0], prep[1], lay, *prep[2:])
        args = energymodel._round_inputs([prep], energymodel._cfg_mesh())
        e_g, t_g = energymodel._jax_device_kernel("jax", True)(
            segments, args[0], args[1], lay, *args[2:])
    e_g, t_g = np.asarray(e_g), np.asarray(t_g)
    out["round_first_equal"] = bool(
        np.array_equal(e_g[:20], np.asarray(one_e))
        and np.array_equal(t_g[:20], np.asarray(one_t)))
    out["round_rest_zero"] = bool(not e_g[20:].any() and not t_g[20:].any())

    # one more (warm) job: its spans, counters and compiles
    n_compiles = len(compiles)
    with obs.span("job"):
        energymodel.stream_layer_topk(grid, nets, shard=True, chunk_size=20,
                                      topk=6, bound=0.05)
    spans = [s for s in obs.spans_between(0.0, float("inf"))]
    job = max(s.job_id for s in spans if s.name == "job")
    names = [s.name for s in spans if s.job_id == job]
    out["spans"] = {n: names.count(n) for n in set(names)}
    t0 = min(s.t0 for s in spans if s.job_id == job)
    out["chunks_per_device"] = [
        c.n for c in obs.counts_between(t0, float("inf"))
        if c.name == "dse.stream.chunks_per_device"]
    out["compiles_warm"] = len(compiles) - n_compiles
    print("RESULT " + json.dumps(out))
""")

_CACHE: dict = {}


def _child(n_dev: int) -> dict:
    if n_dev not in _CACHE:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}")
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (os.path.abspath("src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith("RESULT "))
        _CACHE[n_dev] = json.loads(line[len("RESULT "):])
    return _CACHE[n_dev]


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_sharded_stream_equals_one_device_stream(n_dev):
    out = _child(n_dev)
    assert out["devices"] == n_dev
    assert out["differ"] == {"None": [], "0.05": []}
    assert out["dense_equal_False"] and out["dense_equal_True"]


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_partial_round_leaves_spare_devices_idle(n_dev):
    """A round with fewer chunks than devices: the first device's output
    is the one-device kernel's, and every other device skips the kernel
    (zeros) rather than computing a stand-in chunk."""
    out = _child(n_dev)
    assert out["round_first_equal"]
    assert out["round_rest_zero"]


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_sharded_stream_faults_and_resume(n_dev):
    """A NaN planted in chunk 2 raises with that chunk; killed at chunk 4,
    the stream's last export (after a round on several devices) resumes
    to the one-device stream's result."""
    out = _child(n_dev)
    assert out["corrupt_chunk"] == 2
    assert out["exports"][-1] == 4
    assert out["resumed_differ"] == []


@pytest.mark.parametrize("n_dev", [2, 4])
def test_one_compile_serves_every_device(n_dev):
    """The grid kernel and the fold compile once each for all devices,
    and a warm job compiles nothing."""
    out = _child(n_dev)
    assert sorted(out["compiles"]) == ["jit(grid_kernel_devices_layers)",
                                       "jit(layer_fold_devices)"]
    assert out["compiles_warm"] == 0


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_spans_and_counters_of_a_sharded_job(n_dev):
    """Per job: one ``dse.stream.merge`` (none on one device), one
    ``dse.stream.dispatch.device`` per chunk, and one
    ``dse.stream.chunks_per_device`` per device, round-robin."""
    out = _child(n_dev)
    spans = out["spans"]
    assert spans["dse.stream"] == 1
    n_chunks, rounds = 5, -(-5 // n_dev)
    if n_dev == 1:
        assert "dse.stream.merge" not in spans
        assert "dse.stream.dispatch.device" not in spans
        assert out["chunks_per_device"] == []
        assert spans["dse.stream.dispatch"] == 2 * n_chunks
        return
    assert spans["dse.stream.merge"] == 1
    assert spans["dse.stream.dispatch.device"] == n_chunks
    assert spans["dse.stream.dispatch"] == rounds
    assert spans["dse.stream.wait"] == rounds + 1
    assert out["chunks_per_device"] == [
        len(range(d, n_chunks, n_dev)) for d in range(n_dev)]


@pytest.mark.parametrize("n_dev", [2, 3, 4])
def test_merge_of_device_folds_equals_one_fold_under_ties(n_dev):
    """Chunks folded round-robin into one state per device, then merged,
    give the one sequential fold's state exactly, on small-integer
    energies and latencies where the metric ties across devices (the
    lower flat index must win every tie, as the sequential fold keeps the
    earlier row)."""
    import numpy as np

    from repro.core import energymodel as em

    rng = np.random.default_rng(n_dev)
    n_chunks, chunk, n_net, n_layer, k = 7, 6, 3, 4, 5
    lay_valid = np.arange(n_layer)[None, :] < np.array([4, 3, 2])[:, None]
    e = rng.integers(1, 3, (n_chunks, chunk, n_net, n_layer)).astype(float)
    t = rng.integers(1, 3, (n_chunks, chunk, n_net, n_layer)).astype(float)

    def fold(state, c):
        return em._layer_reduce_body(np, "edp", k, e[c], t[c], c * chunk,
                                     chunk, 0.05, lay_valid, state)[0]

    one = em._layer_fold_init(k, n_net, n_layer)
    per_dev = [em._layer_fold_init(k, n_net, n_layer) for _ in range(n_dev)]
    for c in range(n_chunks):
        one = fold(one, c)
        per_dev[c % n_dev] = fold(per_dev[c % n_dev], c)
    merged = em._merge_layer_states(per_dev)
    assert np.unique(one[0]).size < one[0].size     # the top-k holds ties
    for got, want in zip(merged, one):
        np.testing.assert_array_equal(got, want)

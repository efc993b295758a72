"""The engine's device programs compile for a TPU v5e.

Compiles (never runs) for one chip of a described ``v5e:2x2`` topology:
the grid kernel in aggregate and per-layer mode, the streamed per-layer
fold, and the two jitted stages of ``partition.batch_schedule_hetero``'s
solve.  The layer axis is the real one of all 18 networks (L = 2,048);
the config-row axis is shrunk, which keeps each compile short.  A refusal
here (an unsupported dtype, an over-size program) is what the chip's
compiler would raise.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from repro.core import accelerator, energymodel, partition, topology

N_ROWS = 16            # grid points fed to the engine kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a TPU compile written to a persistent cache cannot be read back
    # without a chip; keep these compiles out of any cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def networks():
    return {n: topology.get_network(n) for n in topology.NETWORKS}


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


def _kernel_args(networks, per_layer):
    lay, segments = energymodel._stack_networks(networks,
                                                absorb_pad=not per_layer)
    lay = {k: v[None, :] for k, v in lay.items()}
    grid = accelerator.extended_grid().take(np.arange(N_ROWS))
    cfg_m, cfg_u, inv_m, inv, coefs = energymodel._prepare_fields(
        grid.fields)
    return segments, (cfg_m, cfg_u, lay, inv_m, inv, coefs)


def _compile(fn, *args, static=()):
    with energymodel.x64():
        return fn.lower(*static, *args).compile()


@pytest.mark.parametrize("per_layer", [False, True],
                         ids=["aggregate", "per_layer"])
def test_grid_kernel_compiles_for_v5e(one_chip, networks, per_layer):
    segments, args = _kernel_args(networks, per_layer)
    assert args[2]["macs"].shape == (1, 2048)
    compiled = _compile(energymodel._jax_grid_kernel(per_layer),
                        *_shapes(args, one_chip), static=(segments,))
    # energy + latency in float64; the chip's tiled layout may pad them
    n_layer = energymodel._layer_axis_len(segments) if per_layer else 1
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= 2 * N_ROWS * len(networks) * n_layer * 8


def test_layer_reduce_step_compiles_for_v5e(one_chip, networks):
    segments, _ = _kernel_args(networks, True)
    n_net, n_layer, k = len(networks), energymodel._layer_axis_len(segments), 8
    f64 = np.zeros((), np.float64)
    i64 = np.zeros((), np.int64)
    state = (np.zeros((k, n_net)), np.zeros((k, n_net), np.int64),
             np.zeros((k, n_net, n_layer)), np.zeros((k, n_net, n_layer)),
             *(np.zeros(n_net) for _ in range(4)),
             np.zeros(n_net, np.int64), np.zeros((n_net, n_layer)),
             np.zeros((n_net, n_layer), np.int64))
    e = np.zeros((N_ROWS, n_net, n_layer))
    args = (e, e, state, i64, i64, f64, np.zeros((n_net, n_layer), bool))
    _compile(energymodel._jax_layer_reduce_step(), *_shapes(args, one_chip),
             static=("edp", k))


@pytest.fixture
def mesh(one_chip, topo, monkeypatch):
    """All four chips of the v5e 2x2 as the engine's device mesh."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(topo.devices), ("cfg",))
    monkeypatch.setattr(energymodel, "_cfg_mesh", lambda: mesh)
    monkeypatch.setattr(energymodel, "_jitted_device_kernels", {})
    monkeypatch.setattr(energymodel, "_jitted_layer_fold_devices", {})
    return mesh


def _stacked(tree, mesh):
    """One chunk's shapes per device, stacked along the row axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows, n_dev = NamedSharding(mesh, P("cfg")), mesh.devices.size
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (n_dev * np.shape(x)[0],) + np.shape(x)[1:], np.asarray(x).dtype,
        sharding=rows), tree)


def _round_kernel(mesh, networks, per_layer):
    """The grid kernel of one round, one chunk of ``N_ROWS`` points on
    each chip, compiled; returns (segments, compiled)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    segments, (cfg_m, cfg_u, lay, inv_m, inv, coefs) = _kernel_args(
        networks, per_layer)
    return segments, _compile(
        energymodel._jax_device_kernel(per_layer), _stacked(cfg_m, mesh),
        _stacked(cfg_u, mesh), _shapes(lay, NamedSharding(mesh, P())),
        *(_stacked(a, mesh) for a in (inv_m, inv, coefs, np.ones(1, bool))),
        static=(segments,))


def _no_collective(compiled) -> bool:
    hlo = compiled.as_text()
    return not any(op in hlo for op in ("all-gather", "all-reduce",
                                        "collective-permute"))


def test_device_round_programs_compile_for_v5e(mesh, networks):
    """The sharded stream's two programs, the per-layer grid kernel and
    the fold on all four chips of the v5e 2x2 at once, one chunk of
    ``N_ROWS`` points each: they compile, and with no collective."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    segments, kernel = _round_kernel(mesh, networks, True)
    n_net, n_layer, k = len(networks), energymodel._layer_axis_len(segments), 8
    e = np.zeros((N_ROWS, n_net, n_layer))
    fold = _compile(
        energymodel._jax_layer_fold_devices(), _stacked(e, mesh),
        _stacked(e, mesh),
        _stacked(tuple(s[None] for s in energymodel._layer_fold_init(
            k, n_net, n_layer)), mesh),
        _stacked(np.zeros(1, np.int64), mesh),
        _stacked(np.zeros(1, np.int64), mesh),
        *_shapes((np.zeros(()), np.zeros((n_net, n_layer), bool)),
                 NamedSharding(mesh, P())),
        static=("edp", k))
    assert _no_collective(kernel) and _no_collective(fold)


def test_one_round_aggregate_kernel_compiles_for_v5e(mesh, networks):
    """The aggregate grid kernel on all four chips at once, which an
    unchunked ``evaluate_networks(shard=True)`` runs as one round: it
    compiles, with no collective."""
    assert _no_collective(_round_kernel(mesh, networks, False)[1])


def test_batch_schedule_hetero_solve_compiles_for_v5e(one_chip):
    """Both jitted stages at the co-design's shapes: 111 chips × 18
    networks = 1,998 problems of up to 3 core types over the 256-layer
    padded axis, shrunk to 64 problems."""
    b, t, n_pad = 64, 3, 256
    stage1 = (np.zeros((b, t, n_pad)), np.zeros((b, t), bool),
              np.zeros(b, np.int64))
    _compile(partition._jax_hetero_stage1(), *_shapes(stage1, one_chip))
    rows = 128
    solve = (np.zeros((b * t, n_pad + 1)), *(np.zeros(rows, np.int64)
                                             for _ in range(3)),
             np.zeros(rows), np.zeros(rows))
    bs_steps = int(np.ceil(np.log2(n_pad + 1))) + 1
    with energymodel.x64():
        partition._jax_solver().lower(
            *_shapes(solve, one_chip), partition._K_MAX, bs_steps).compile()

"""B&B-staged pipeline parallelism (the paper's §IV.B on a TPU mesh).

The paper distributes a network's layers across homogeneous cores with a
branch-and-bound search balancing per-core latency (Algorithm II); the
pipeline flows DRAM→core→DRAM.  Here the *same* algorithm
(`core.partition.bb_partition`) places transformer layers onto mesh pipeline
stages using per-layer latency estimates from the TPU cost model, and the
runtime is a GPipe schedule under ``shard_map``: activations move stage→
stage over ``collective-permute`` (the ICI analogue of the paper's
DRAM hand-off), microbatches fill the pipe, and the bubble fraction is
(S−1)/(M+S−1).

Stages hold *contiguous, possibly unequal* layer slices — exactly what B&B
produces — padded to the max stage depth with masked identity layers so the
program stays SPMD.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.partition import Partition, bb_partition


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    n_stages: int
    boundaries: Tuple[int, ...]        # start layer of each stage
    stage_sizes: Tuple[int, ...]
    max_depth: int
    partition: Partition

    @property
    def bubble_fraction(self) -> float:
        return 0.0

    def bubble(self, n_microbatches: int) -> float:
        s = self.n_stages
        return (s - 1) / (n_microbatches + s - 1)


def plan_stages(layer_latencies, n_stages: int) -> PipelinePlan:
    """Algorithm II over per-layer latency estimates → stage plan."""
    part = bb_partition(list(layer_latencies), n_stages)
    bounds = list(part.boundaries)
    n = len(list(layer_latencies))
    sizes = [
        (bounds[i + 1] if i + 1 < len(bounds) else n) - bounds[i]
        for i in range(len(bounds))]
    return PipelinePlan(n_stages=n_stages, boundaries=tuple(bounds),
                        stage_sizes=tuple(sizes), max_depth=max(sizes),
                        partition=part)


def stage_params(stacked_params, plan: PipelinePlan):
    """[L, ...] param tree → ([S, D_max, ...] tree, mask [S, D_max]).

    Pads each stage's slice to the max depth; the mask disables the padded
    layers (identity)."""
    s, dmax = plan.n_stages, plan.max_depth
    bounds = list(plan.boundaries)
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]

    def per_leaf(x):
        outs = []
        for i in range(s):
            start = bounds[i]
            size = plan.stage_sizes[i]
            sl = x[start:start + size]
            pad = [(0, dmax - size)] + [(0, 0)] * (x.ndim - 1)
            outs.append(jnp.pad(sl, pad))
        return jnp.stack(outs)                    # [S, D_max, ...]

    mask = jnp.zeros((s, dmax), bool)
    for i in range(s):
        mask = mask.at[i, : plan.stage_sizes[i]].set(True)
    return jax.tree.map(per_leaf, stacked_params), mask


def pipeline_forward(staged_params, mask, x_micro, *, mesh: Mesh,
                     stage_axis: str, layer_fn: Callable,
                     data_axes: Tuple[str, ...] = ()):
    """GPipe schedule under shard_map.

    staged_params: [S, D_max, ...] tree (sharded on ``stage_axis`` dim 0)
    mask:          [S, D_max] layer validity
    x_micro:       [M, B_m, T, D] microbatch queue (replicated over stages,
                   optionally sharded on batch over ``data_axes``)
    layer_fn:      (layer_params, x) -> x  (one transformer block)
    Returns y_micro [M, B_m, T, D] — outputs of the final stage.
    """
    s = mesh.shape[stage_axis]
    m = x_micro.shape[0]
    ticks = m + s - 1

    def per_stage(params_blk, mask_blk, xq):
        # local blocks carry a leading length-1 stage dim
        params_blk = jax.tree.map(lambda a: a[0], params_blk)
        mask_blk = mask_blk[0]
        stage_id = jax.lax.axis_index(stage_axis)

        def apply_stage(x):
            def body(h, lp_m):
                lp, valid = lp_m
                out = layer_fn(lp, h)
                return jnp.where(valid, out, h), None

            y, _ = jax.lax.scan(body, x, (params_blk, mask_blk))
            return y

        bm, t, d = xq.shape[1:]
        zero = jnp.zeros((bm, t, d), xq.dtype)
        ys = jnp.zeros((m, bm, t, d), xq.dtype)

        def tick_fn(carry, tick):
            recv, ys = carry
            inject = jax.lax.dynamic_index_in_dim(
                xq, jnp.minimum(tick, m - 1), 0, keepdims=False)
            x_in = jnp.where(stage_id == 0, inject, recv)
            out = apply_stage(x_in)
            # stage s-1 emits its output for microbatch (tick - (s-1))
            emit_idx = jnp.clip(tick - (s - 1), 0, m - 1)
            do_emit = (stage_id == s - 1) & (tick >= s - 1)
            ys = jax.lax.dynamic_update_index_in_dim(
                ys, jnp.where(do_emit,
                              out,
                              jax.lax.dynamic_index_in_dim(
                                  ys, emit_idx, 0, keepdims=False)),
                emit_idx, 0)
            nxt = jax.lax.ppermute(
                out, stage_axis,
                [(i, (i + 1) % s) for i in range(s)])
            return (nxt, ys), None

        (_, ys), _ = jax.lax.scan(
            tick_fn, (zero, ys), jnp.arange(ticks))
        # broadcast final outputs from the last stage so the result is
        # replicated over the stage axis
        ys = jax.lax.psum(
            jnp.where(stage_id == s - 1, ys, jnp.zeros_like(ys)),
            stage_axis)
        return ys

    pspecs_params = jax.tree.map(lambda _: P(stage_axis), staged_params)
    batch_spec = P(None, data_axes if data_axes else None)
    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(pspecs_params, P(stage_axis), batch_spec),
        out_specs=batch_spec, check_vma=False)
    return fn(staged_params, mask, x_micro)

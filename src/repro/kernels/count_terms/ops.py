"""Padding/stacking wrapper around the fused count-terms Pallas kernel.

Takes the engine's native operands — the [n_u, 1] count-unique config
columns, the [1, L] stacked layer columns, and the static per-network
``segments`` tuple — pads both tiled axes to block multiples, builds the
one-hot segment matrix, and returns the tuple of 14 [n_u, n_net] partial
sums ``energymodel._gather_combine_body`` consumes.  Traceable under
``jax.jit`` (all shapes static at trace time).

Two engine paths consume the per-layer variant
(:func:`count_term_layers`, no segment reduction):
``evaluate_networks(..., per_layer=True)`` for dense per-layer tensors,
and the streamed per-layer reduction
(:func:`repro.core.energymodel.stream_layer_topk`) which dispatches one
``count_term_layers`` call per fixed-shape chunk — the chunk padding
upstream keeps ``n_u`` stable so the whole stream shares one trace.

Interpret mode follows the platform (:func:`repro.kernels.default_interpret`);
explicit ``interpret=`` arguments win.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.energymodel import _PAD_LAYER_ROW
from repro.kernels import default_interpret
from .kernel import (CFG_COLUMNS, LAYER_FIELDS, N_TERMS,
                     count_layers_kernel, count_terms_kernel)


def _pad_operands(cfg_u, lay, block_u: int, block_l: int):
    """Stack the struct-of-arrays operands into the kernel's 2-D layout
    and pad both tiled axes to block multiples (config rows repeat row 0
    — a benign valid config; layer columns get ``_PAD_LAYER_ROW``, whose
    terms are exactly zero)."""
    cfg = jnp.concatenate(
        [jnp.asarray(cfg_u[k]).reshape(1, -1) for k in CFG_COLUMNS], axis=0)
    laym = jnp.concatenate(
        [jnp.asarray(lay[k]).reshape(1, -1) for k in LAYER_FIELDS], axis=0)
    n_u = cfg.shape[1]
    l_tot = laym.shape[1]

    bu = min(block_u, max(8, n_u))
    pad_u = (-n_u) % bu
    if pad_u:
        cfg = jnp.concatenate([cfg, jnp.broadcast_to(
            cfg[:, :1], (cfg.shape[0], pad_u))], axis=1)
    bl = min(block_l, l_tot)
    pad_l = (-l_tot) % bl
    if pad_l:
        pad_col = np.array([[_PAD_LAYER_ROW[k]] for k in LAYER_FIELDS])
        laym = jnp.concatenate([laym, jnp.broadcast_to(
            jnp.asarray(pad_col, laym.dtype),
            (laym.shape[0], pad_l))], axis=1)
    return cfg, laym.astype(cfg.dtype), n_u, l_tot, bu, bl, pad_l


def _segment_onehot(segments, l_pad: int) -> np.ndarray:
    """Static one-hot [l_pad, n_net] segment matrix: rows past the last
    segment's stop stay all-zero, so layer padding is annihilated by the
    in-kernel reduction regardless of its term values."""
    seg = np.zeros((l_pad, len(segments)))
    for j, (a, b) in enumerate(segments):
        seg[a:b, j] = 1.0
    return seg


def count_term_sums(cfg_u, lay, segments, *, block_u: int = 128,
                    block_l: int = 128, interpret: bool | None = None):
    """Fused mapping → 14 count terms → per-network segment reduction.

    cfg_u: dict of [n_u, 1] arrays keyed by ``_COUNT_COLUMNS``;
    lay: dict of [1, L] arrays keyed like ``rs_mapping.layer_struct``;
    segments: static ((start, stop), ...).  Returns a 14-tuple of
    [n_u, n_net] float64 arrays, drop-in for ``_term_sums_body``'s output
    (config-independent terms arrive broadcast along the unique axis).

    ``interpret`` defaults to the platform's choice: the Pallas
    interpreter (still XLA-jitted end to end) on the CPU, the native
    lowering elsewhere.  On a TPU Mosaic refuses this float64 tile
    program (and its float32 variant), so the engine never routes here
    there (``energymodel.pallas_available``).
    """
    if interpret is None:
        interpret = default_interpret()
    cfg, laym, n_u, l_tot, bu, bl, pad_l = _pad_operands(
        cfg_u, lay, block_u, block_l)
    seg = jnp.asarray(_segment_onehot(segments, l_tot + pad_l), cfg.dtype)

    out = count_terms_kernel(cfg, laym, seg,
                             block_u=bu, block_l=bl, interpret=interpret)
    out = out[:, :n_u, :]
    return tuple(out[i] for i in range(N_TERMS))


def count_term_layers(cfg_u, lay, *, block_u: int = 128,
                      block_l: int = 128, interpret: bool | None = None):
    """Fused mapping → 14 PER-LAYER count terms (no segment reduction).

    Same operands as :func:`count_term_sums` minus ``segments``; returns
    a 14-tuple of [n_u, L] float64 arrays, drop-in for
    ``energymodel._term_layers_body``'s output (config-independent terms
    arrive per-row, which the consumer treats as already gathered).  The
    engine routes here when ``backend="pallas"`` in per-layer mode — both
    the dense ``per_layer=True`` path and the streamed per-layer
    reduction (``stream_layer_topk``), which calls once per fixed-shape
    chunk."""
    if interpret is None:
        interpret = default_interpret()
    cfg, laym, n_u, l_tot, bu, bl, _ = _pad_operands(
        cfg_u, lay, block_u, block_l)
    out = count_layers_kernel(cfg, laym, block_u=bu, block_l=bl,
                              interpret=interpret)
    out = out[:, :n_u, :l_tot]
    return tuple(out[i] for i in range(N_TERMS))

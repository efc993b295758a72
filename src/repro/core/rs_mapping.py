"""Row-stationary spatial mapping (Eyeriss-style) for the simulator.

§II of the paper adopts the row-stationary (RS) dataflow [41]: every PE runs a
1-D convolution of one filter row against one ifmap row, producing one psum
row.  A *PE set* of (Ky filter rows) × (Oy_pass output rows) computes a 2-D
convolution plane; the physical array replicates PE sets vertically (channel
accumulation first — psums add in-array — then extra filters) and horizontally
(extra filters once all output rows fit).

"Processing capacity" in the paper = the number of ifmap channels the array
can take per pass (``cap_c`` here); Observation 2's breakpoints come from the
per-pass ifmap working set ``W_ifmap = cap_c · Ix · ((Oy_pass−1)·stride+Ky)``
crossing ``GB_ifmap``; Observation 1's from the per-pass psum working set
``W_psum = cap_m · Ox · Oy_pass`` crossing ``GB_psum``.

All formulas are written against an array-API module ``xp`` (numpy or
jax.numpy) and broadcast over arbitrary leading axes, so the same code path
serves the scalar per-layer report and the fully vectorised design-space
sweep (configs × layers in one shot).

jit-safety audit (the batched DSE engine traces this module):

* no data-dependent Python control flow — the only ``if`` is on
  ``gb_ifmap_words is None``, which is static at trace time;
* every op is an ``xp`` ufunc (``where`` / ``minimum`` / ``floor_divide``),
  so numpy and the jitted jax path produce bit-identical graphs;
* all quantities are exact integers in float64: the largest intermediate
  (layer MACs, ~1e10) is far below 2^53.  The floor/ceil divisions run in
  integer arithmetic on the device path (:func:`_fdiv`), so the mapping is
  exact on every backend and the numpy↔jax parity holds to machine
  epsilon.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _fdiv(xp, a, b):
    """Floor division of integer-valued float operands (all below 2^31).

    numpy divides the float64 values, which is exact in IEEE float64.  The
    device path divides in int32: XLA:TPU emulates float64 as pairs of
    float32, and there an integer quotient can come out a rounding step
    low; integer division is exact on every backend."""
    if xp is np:
        return np.floor_divide(a, b)
    q = xp.floor_divide(xp.asarray(a).astype(xp.int32),
                        xp.asarray(b).astype(xp.int32))
    return q.astype(xp.float64)


def _cdiv(xp, a, b):
    return -_fdiv(xp, -a, b)


def mapping(xp, *,
            rows, cols,                 # physical array [R, C]
            c_ch, m, ky, kx, stride,    # layer loop bounds
            ix, iy, oy, ox,             # ifmap row length/height, output rows/cols
            is_acc, is_dw, is_pool,     # layer-kind flags (0/1 arrays)
            gb_ifmap_words=None,
            rf_ifmap_words=12, rf_weight_words=224, rf_psum_words=16):
    """Return the RS mapping quantities for (config × layer) grids.

    All arguments are broadcastable integer arrays.  Output dict values are
    arrays of the broadcast shape.

    Spatial mapping: PE sets of (ky × oy_pass) PEs; vertical replication over
    channels (in-array psum accumulation) then filters; horizontal leftover
    replicates filters.  Temporal mapping (Eyeriss RF multiplexing): each PE
    interleaves ``q`` channels and ``p`` filters out of its scratch pads
    (weight RF holds p·q filter rows, psum RF holds p running rows), so the
    filters in flight per pass are ``cap_m = spatial · p`` and channels per
    accumulation round are ``cap_c = spatial · q``.
    """
    one = xp.ones_like(rows * c_ch)

    # A filter row taller than the array folds serially over ky_serial passes.
    ky_serial = _cdiv(xp, ky, rows)
    ky_map = _cdiv(xp, ky, ky_serial)            # PE-set height actually used

    fold = xp.maximum(one, _fdiv(xp, rows, ky_map))   # vertical PE-set slots

    oy_pass = xp.minimum(oy, cols)                    # output rows per pass
    col_rep = xp.maximum(one, _fdiv(xp, cols, oy_pass))  # leftover cols → filters

    # Vertical replication covers the remaining output-row blocks FIRST
    # ("processing capacity refers to the number of rows (or channels) of the
    # input image that can be loaded to the array", §III): only when the
    # array out-sizes the feature map does multi-channel processing start.
    sets_rows = xp.minimum(_cdiv(xp, oy, oy_pass), fold)
    fold2 = xp.maximum(one, _fdiv(xp, fold, sets_rows))

    # Channel accumulation (conv / pointwise / fc): psums of cap_c channels
    # add in-array.  Depthwise / pool: channels are independent planes.
    cap_c_sp = xp.where(is_acc, xp.minimum(c_ch, fold2), one)
    fold_m = xp.maximum(one, _fdiv(xp, fold2, cap_c_sp))  # leftover rows → filters

    plane_count = xp.where(is_acc, m, c_ch)
    cap_m_sp = xp.maximum(
        xp.minimum(plane_count, fold_m * col_rep), one)

    # --- RF temporal multiplexing (filters) ----------------------------------
    # Each PE interleaves p filters out of its weight/psum scratch pads
    # (Eyeriss: p = 16); channels are accumulated spatially only.
    q = one
    cap_c = cap_c_sp
    p_rf = xp.maximum(one, xp.minimum(
        rf_psum_words * one, _fdiv(xp, rf_weight_words * one, kx)))
    p = xp.minimum(p_rf, _cdiv(xp, plane_count, cap_m_sp))
    cap_m = xp.maximum(xp.minimum(plane_count, cap_m_sp * p), one)

    # --- GB_ifmap gating of the processing capacity (Observation 2) ---------
    # "If the GB_ifmap capacity is not sufficient to accommodate all the
    # channels the array needs for processing, [...] extra energy [is]
    # required to write the result of the processed channels back to the
    # buffer and re-read it to add it to those just processed" (§III).
    # Multi-channel processing buffers whole channel planes; the channels
    # feedable per accumulation round are capped by how many planes fit in
    # GB_ifmap.  Fewer channels per round ⇒ more rounds ⇒ more psum RMW
    # traffic.  (Single-channel row streaming needs no plane buffering, so
    # the gate never pushes capacity below one.)
    if gb_ifmap_words is not None:
        ch_fit = xp.maximum(one, _fdiv(xp, gb_ifmap_words, ix * iy))
        cap_c = xp.minimum(cap_c, ch_fit)
        cap_m = xp.where(is_acc, cap_m, xp.minimum(cap_m, ch_fit))
    ifmap_rows = (oy_pass - 1) * stride + ky

    n_c = xp.where(is_acc, _cdiv(xp, c_ch, cap_c), one)   # channel rounds
    n_m = _cdiv(xp, plane_count, cap_m)                   # filter blocks
    n_oy = _cdiv(xp, oy, oy_pass * sets_rows)             # output-row blocks

    # Per-pass working sets (words).
    ch_in_flight = xp.where(is_acc, cap_c, cap_m)
    w_ifmap = ch_in_flight * ix * ifmap_rows
    # psums persist in GB as full output planes for the filters in flight
    # across the n_c channel-accumulation rounds (loop order of Alg. I:
    # filters outer, channels next, spatial inner).
    w_psum = cap_m * ox * oy
    w_weight = cap_m * xp.where(is_acc, cap_c, one) * kx * ky

    # GB-gated capacity below the spatial capacity idles PEs (Obs. 2:
    # "reduced GB_ifmap storage space, in addition to reducing array
    # utilization, ...").
    cap_c_sp_eff = xp.minimum(cap_c_sp, cap_c)
    cap_m_sp_eff = xp.minimum(cap_m_sp, cap_m)
    active_pes = ky_map * oy_pass * sets_rows * xp.where(
        is_acc, cap_c_sp_eff * cap_m_sp_eff, cap_m_sp_eff)
    active_pes = xp.minimum(active_pes, rows * cols)

    return dict(
        ky_serial=ky_serial, ky_map=ky_map, fold=fold, cap_c=cap_c,
        fold_m=fold_m, oy_pass=oy_pass, col_rep=col_rep, cap_m=cap_m,
        n_c=n_c, n_m=n_m, n_oy=n_oy, w_ifmap=w_ifmap, w_psum=w_psum,
        w_weight=w_weight, active_pes=active_pes,
        ch_in_flight=ch_in_flight, q=q, p=p,
    )


def layer_struct(xp, layers) -> Dict[str, Any]:
    """Struct-of-arrays view of a ``List[Layer]`` for the vectorised path."""
    from .topology import KIND_CONV, KIND_DW, KIND_FC, KIND_POOL, KIND_PW

    def arr(fn, dtype=None):
        a = xp.asarray([fn(l) for l in layers])
        return a if dtype is None else a.astype(dtype)

    return dict(
        c_ch=arr(lambda l: l.c_in),
        m=arr(lambda l: l.c_out),
        ky=arr(lambda l: l.k),
        kx=arr(lambda l: l.k),
        stride=arr(lambda l: l.stride),
        ix=arr(lambda l: l.w_in),
        iy=arr(lambda l: l.h_in),
        oy=arr(lambda l: l.h_out),
        ox=arr(lambda l: l.w_out),
        macs=arr(lambda l: l.macs),
        weight_words=arr(lambda l: l.weight_words),
        ifmap_words=arr(lambda l: l.ifmap_words),
        ofmap_words=arr(lambda l: l.ofmap_words),
        is_acc=arr(lambda l: l.kind in (KIND_CONV, KIND_PW, KIND_FC)),
        is_dw=arr(lambda l: l.kind == KIND_DW),
        is_pool=arr(lambda l: l.kind == KIND_POOL),
    )

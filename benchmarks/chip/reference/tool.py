"""Plain reference of the paper's Tool (arXiv:2206.12605, sec. II).

Energy and latency of one array-based accelerator core running one CNN
layer under the row-stationary dataflow, written out directly from the
equations: the RS mapping, the access counts at every level of the
memory hierarchy (eq. (1) unrolled), and the serial latency of the
paper's controller.  It reads only plain numbers (grid columns and layer
rows from the configuration file) and imports nothing of the system
under test.  ``dtype`` selects the arithmetic: float64 is the reference,
float32 the benchmark's control.

Energy and latency are linear in fourteen per-layer count terms whose
coefficients are per-configuration constants, so whole-network sums are
taken over the count terms of each distinct core geometry once and the
coefficients are applied per grid point afterwards.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: Columns of a design-space grid, in the order of the configuration file's
#: ``grid.base``.
GRID_COLUMNS = (
    "rows", "cols", "gb_ifmap_kb", "gb_psum_kb", "gb_weight_kb",
    "rf_ifmap_words", "rf_weight_words", "rf_psum_words", "bitwidth",
    "noc_wpc", "dram_wpc", "cycle_ns",
    "e_rf", "e_dram_r", "e_dram_w", "e_mac", "e_pe_idle", "e_noc_hop",
    "gb_e_ref", "gb_t_ref", "gb_ref_kb", "mac_t")

#: Columns the access counts depend on; everything else only scales them.
GEOMETRY_COLUMNS = ("rows", "cols", "gb_ifmap_kb", "gb_psum_kb",
                    "rf_ifmap_words", "rf_weight_words", "rf_psum_words",
                    "bitwidth")

POOL_OP_ENERGY = 0.2        # one pooling compare/add relative to a MAC
ACC_KINDS = ("conv", "pointwise", "fc")


def layer_columns(rows: Sequence[Sequence], dtype=np.float64
                  ) -> Dict[str, np.ndarray]:
    """Compute layers of one network (input rows dropped) as [L] columns.

    A row is ``[kind, c_in, c_out, k, stride, pad, h_in, w_in]``."""
    rows = [r for r in rows if r[0] != "input"]
    kind = [r[0] for r in rows]
    c_in, c_out, k, s, pad, h, w = (np.asarray([r[i] for r in rows],
                                               dtype=np.int64)
                                    for i in range(1, 8))
    ho = (h - k + 2 * pad) // s + 1
    wo = (w - k + 2 * pad) // s + 1
    is_pool = np.asarray([x == "pool" for x in kind])
    is_dw = np.asarray([x == "depthwise" for x in kind])
    is_acc = np.asarray([x in ACC_KINDS for x in kind])
    macs = np.where(is_pool, 0,
                    np.where(is_dw, c_in * ho * wo * k * k,
                             c_out * c_in * ho * wo * k * k))
    weights = np.where(is_pool, 0,
                       np.where(is_dw, c_in * k * k, c_out * c_in * k * k))
    cols = dict(c_ch=c_in, m=c_out, ky=k, kx=k, stride=s, ix=w, iy=h,
                oy=ho, ox=wo, macs=macs, weight_words=weights,
                ifmap_words=c_in * h * w, ofmap_words=c_out * ho * wo)
    out = {k_: v.astype(dtype) for k_, v in cols.items()}
    out.update(is_acc=is_acc, is_dw=is_dw, is_pool=is_pool)
    return out


def _cdiv(a, b):
    return -np.floor_divide(-a, b)


def count_terms(geo: Dict[str, np.ndarray], lay: Dict[str, np.ndarray]
                ) -> Tuple[np.ndarray, ...]:
    """The fourteen count terms of every (geometry row, layer) pair.

    ``geo`` columns are [n, 1], ``lay`` columns [1, L]; returns [n, L]
    arrays.  RS mapping first (PE sets of ky x oy_pass PEs, vertical
    replication over output-row blocks, then channels, then filters;
    RF multiplexing of filters; GB_ifmap gating of the channels per
    round), then the traffic at every level."""
    dt = geo["rows"].dtype
    rows, cols = geo["rows"], geo["cols"]
    bpw = geo["bitwidth"] / 8.0
    gb_ifmap_words = np.floor(geo["gb_ifmap_kb"] * 1024 / bpw)
    gb_psum_words = np.floor(geo["gb_psum_kb"] * 1024 / bpw)
    c_ch, m, ky, kx = lay["c_ch"], lay["m"], lay["ky"], lay["kx"]
    stride, ix, iy, oy, ox = (lay["stride"], lay["ix"], lay["iy"],
                              lay["oy"], lay["ox"])
    is_acc, is_pool = lay["is_acc"], lay["is_pool"]
    one = np.ones(np.broadcast_shapes(rows.shape, c_ch.shape), dtype=dt)

    # -- RS mapping ---------------------------------------------------------
    ky_serial = _cdiv(ky * one, rows)
    ky_map = _cdiv(ky * one, ky_serial)
    fold = np.maximum(one, np.floor_divide(rows, ky_map))
    oy_pass = np.minimum(oy, cols)
    col_rep = np.maximum(one, np.floor_divide(cols, oy_pass))
    sets_rows = np.minimum(_cdiv(oy, oy_pass), fold)
    fold2 = np.maximum(one, np.floor_divide(fold, sets_rows))
    cap_c_sp = np.where(is_acc, np.minimum(c_ch, fold2), one)
    fold_m = np.maximum(one, np.floor_divide(fold2, cap_c_sp))
    planes = np.where(is_acc, m, c_ch)
    cap_m_sp = np.maximum(np.minimum(planes, fold_m * col_rep), one)
    p_rf = np.maximum(one, np.minimum(geo["rf_psum_words"] * one,
                                      np.floor_divide(
                                          geo["rf_weight_words"] * one, kx)))
    p = np.minimum(p_rf, _cdiv(planes, cap_m_sp))
    cap_m = np.maximum(np.minimum(planes, cap_m_sp * p), one)
    ch_fit = np.maximum(one, np.floor_divide(gb_ifmap_words, ix * iy))
    cap_c = np.minimum(cap_c_sp, ch_fit)
    cap_m = np.where(is_acc, cap_m, np.minimum(cap_m, ch_fit))
    n_c = np.where(is_acc, _cdiv(c_ch, cap_c), one)
    n_m = _cdiv(planes, cap_m)
    w_psum = cap_m * ox * oy
    active = ky_map * oy_pass * sets_rows * np.where(
        is_acc, np.minimum(cap_c_sp, cap_c) * np.minimum(cap_m_sp, cap_m),
        np.minimum(cap_m_sp, cap_m))
    active = np.minimum(active, rows * cols)

    # -- traffic ------------------------------------------------------------
    ifmap, ofmap, weights = (lay["ifmap_words"], lay["ofmap_words"],
                             lay["weight_words"])
    pool_ops = c_ch * ox * oy * kx * ky
    gb_if_r = ifmap * np.where(is_acc, n_m, one)
    gb_if_w = ifmap * one
    gb_wt_r = weights * ky_serial
    inter = np.maximum(n_c * ky_serial - 1, 0)
    overflow = np.maximum(w_psum - gb_psum_words, 0)
    held = np.minimum(w_psum, gb_psum_words)
    spill = inter * overflow
    gb_ps_inter = inter * held
    gb_ps_w = gb_ps_inter + ofmap
    gb_ps_r = gb_ps_inter + ofmap
    dram_r = ifmap + weights + spill
    dram_w = ofmap + spill
    into = gb_if_r + gb_wt_r + gb_ps_inter + spill
    out = gb_ps_w + spill
    ops = np.where(is_pool, pool_ops, lay["macs"]) * one
    rf = 4 * ops + into + out
    return (dram_r, dram_w, gb_if_r + gb_if_w, gb_ps_r + gb_ps_w,
            weights * one + gb_wt_r,
            rf, np.where(is_pool, 0, lay["macs"]) * one,
            np.where(is_pool, pool_ops, 0) * one,
            (rows * cols - active) * ops / active, into + out,
            gb_if_r + gb_wt_r, gb_ps_r, out, ops / active)


def coefficients(f: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Per-point constants the count terms are weighted with."""
    ref = f["gb_ref_kb"]

    def gb_e(kb):
        return f["gb_e_ref"] * np.sqrt(np.maximum(kb, 1.0) / ref)

    def gb_t(kb):
        return f["gb_t_ref"] * np.sqrt(np.sqrt(np.maximum(kb, 1.0) / ref))

    return dict(
        e_dram_r=f["e_dram_r"], e_dram_w=f["e_dram_w"],
        gb_e_if=gb_e(f["gb_ifmap_kb"]), gb_e_ps=gb_e(f["gb_psum_kb"]),
        gb_e_wt=gb_e(f["gb_weight_kb"]), e_rf=f["e_rf"], e_mac=f["e_mac"],
        e_pe_idle=f["e_pe_idle"],
        e_noc=f["e_noc_hop"] * (f["rows"] + f["cols"]) / 2.0,
        lat_if=gb_t(f["gb_ifmap_kb"]) / f["gb_t_ref"],
        lat_ps=gb_t(f["gb_psum_kb"]) / f["gb_t_ref"],
        noc=f["noc_wpc"], dram=f["dram_wpc"],
        mac_cy=f["mac_t"] / f["cycle_ns"], cycle=f["cycle_ns"])


def combine(S, c) -> Tuple[np.ndarray, np.ndarray]:
    """Count terms x coefficients -> (energy pJ, latency ns)."""
    (d_r, d_w, gb_if, gb_ps, gb_wt, rf, mac, pool, idle, noc_w,
     dlv_if, dlv_ps, wout, ops_pe) = S
    energy = (d_r * c["e_dram_r"] + d_w * c["e_dram_w"]
              + gb_if * c["gb_e_if"] + gb_ps * c["gb_e_ps"]
              + gb_wt * c["gb_e_wt"] + rf * c["e_rf"] + mac * c["e_mac"]
              + pool * (c["e_mac"] * POOL_OP_ENERGY)
              + idle * c["e_pe_idle"] + noc_w * c["e_noc"])
    array_cy = ((dlv_if * c["lat_if"] + dlv_ps * c["lat_ps"]
                 + wout * c["lat_ps"]) / c["noc"] + ops_pe * c["mac_cy"])
    latency = (array_cy + (d_r + d_w) / c["dram"]) * c["cycle"]
    return energy, latency


def _cast(f: Dict[str, np.ndarray], dtype) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v).astype(dtype) for k, v in f.items()}


class NetworkSums:
    """Whole-network count-term sums over the distinct core geometries of
    a grid: computed once, reused by every energy table drawn for it."""

    def __init__(self, fields: Dict[str, np.ndarray],
                 networks: Dict[str, list], dtype=np.float64,
                 block: int = 128, threads: int = 8):
        self.dtype = dtype
        key = np.stack([fields[k] for k in GEOMETRY_COLUMNS], axis=1)
        uniq, self.inverse = np.unique(key, axis=0, return_inverse=True)
        self.inverse = self.inverse.reshape(-1)
        geo = {k: uniq[:, i].astype(dtype)[:, None]
               for i, k in enumerate(GEOMETRY_COLUMNS)}
        lays = [layer_columns(rows, dtype) for rows in networks.values()]
        n_geo = uniq.shape[0]
        self.sums = np.zeros((14, n_geo, len(lays)), dtype=dtype)

        def run(lo):
            g = {k: v[lo:lo + block] for k, v in geo.items()}
            for j, lay in enumerate(lays):
                lay2 = {k: v[None, :] for k, v in lay.items()}
                for t, term in enumerate(count_terms(g, lay2)):
                    self.sums[t, lo:lo + block, j] = term.sum(axis=1)

        with ThreadPoolExecutor(max_workers=threads) as ex:
            list(ex.map(run, range(0, n_geo, block)))

    def evaluate(self, fields: Dict[str, np.ndarray]
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """[n, n_net] network energy and latency of every grid point."""
        c = {k: v[:, None] for k, v in
             coefficients(_cast(fields, self.dtype)).items()}
        S = tuple(s[self.inverse] for s in self.sums)
        return combine(S, c)


def per_layer(fields: Dict[str, np.ndarray], idx: Sequence[int],
              networks: Dict[str, list], dtype=np.float64
              ) -> Tuple[np.ndarray, np.ndarray]:
    """[len(idx), n_net, L_max] per-layer energy and latency of the grid
    points ``idx``, zero past each network's last layer."""
    f = _cast({k: np.asarray(v)[np.asarray(idx)] for k, v in fields.items()},
              dtype)
    geo = {k: f[k][:, None] for k in GEOMETRY_COLUMNS}
    c = {k: v[:, None] for k, v in coefficients(f).items()}
    lays = [layer_columns(rows, dtype) for rows in networks.values()]
    L = max(len(l["m"]) for l in lays)
    e = np.zeros((len(idx), len(lays), L), dtype=dtype)
    t = np.zeros_like(e)
    for j, lay in enumerate(lays):
        lay2 = {k: v[None, :] for k, v in lay.items()}
        ej, tj = combine(count_terms(geo, lay2), c)
        e[:, j, :ej.shape[1]] = ej
        t[:, j, :tj.shape[1]] = tj
    return e, t


def layer_counts(networks: Dict[str, list]) -> List[int]:
    return [sum(1 for r in rows if r[0] != "input")
            for rows in networks.values()]

"""The Tool (§II): first-order energy & latency estimation of an array-based
accelerator executing a network under the row-stationary dataflow.

Energy is cumulative (§II.A.1): every data movement at every hierarchy level
(eq. (1)) and every MAC is counted.  Latency (§II.A.2) follows the paper's
controller assumption — *"processing does not start unless the last processing
element responsible for the pass receives its data"* (Fig. 4) — so per-pass
time is delivery + compute + writeback, serialised with the DRAM interface
time (latency is **not** cumulative across hierarchy levels in general, but
this controller gives the serial composition the paper describes).

The two mechanisms behind the paper's Observations are modelled explicitly:

* **psum spill** (Obs. 1/3): with ``n_c`` channel-accumulation rounds, the
  per-pass psum working set is read-modify-written ``n_c−1`` times.  The
  fraction exceeding ``GB_psum`` travels to off-chip DRAM instead of the
  global buffer.
* **ifmap re-fetch** (Obs. 2/4): when the per-pass ifmap working set exceeds
  ``GB_ifmap`` the block cannot persist across the ``n_m`` filter blocks and
  is re-read from DRAM for each of them.

Global-buffer access energy/latency scales with the configured partition
capacity (CACTI-like √capacity), so oversizing a buffer costs energy — the
right-hand tails of Fig. 5/6.

Array-shape conventions of the batched engine (see also
``docs/architecture.md``):

* Struct-of-arrays everywhere: a "config" is a dict of equal-length float64
  columns (:class:`repro.core.accelerator.ConfigGrid.fields`), a "layer
  struct" a dict of per-layer columns (``rs_mapping.layer_struct``).
* The heavy stage broadcasts ``[n_unique, 1]`` config columns against
  ``[1, n_layers]`` layer columns → ``[n_unique, n_layers]`` tiles, where
  ``n_unique`` is the **two-level dedup** of the grid: the RS mapping runs
  on the mapping-unique rows (``_MAPPING_COLUMNS``), access counts on the
  count-unique rows (``_COUNT_COLUMNS``), and ``inv`` / ``inv_m`` int32
  indices gather back out (grid point → count row → mapping row).
* All networks share ONE concatenated, bucket-padded layer axis;
  ``segments`` is the static tuple of per-network (start, stop) slices on
  it (the segment ids of the per-network reduction), so energy/latency —
  linear in the 14 count terms of :func:`_count_terms` (eq. (1) unrolled)
  — reduce to ``[n_unique, n_networks]`` partial sums before any
  per-config coefficient is applied.
* ``per_layer=True`` keeps the layer axis instead of segment-summing:
  the same heavy stage emits the raw per-layer terms, the coefficient
  combine broadcasts over the concatenated axis, and the result is
  re-split into a padded ``[n_cfg, n_networks, n_layer]`` tensor
  (``n_layer`` = longest network; shorter networks zero-padded).  This
  is the input of the heterogeneous layer→core co-design solver
  (:func:`repro.core.partition.batch_schedule_hetero`).

Two backends evaluate the grid kernel (selected by ``backend=`` on the
public entry points): ``"jax"``, the jitted device path, and ``"numpy"``,
the reference.  Without jax a ``"jax"`` request degrades to numpy with
ONE :class:`RuntimeWarning` per process (see :func:`resolve_backend`);
:func:`last_backend` always reports what actually executed.  Several
devices share work one way only: whole chunks in rounds, one per device
(:func:`_jax_device_kernel`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import warnings
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import numpy as np

from .accelerator import AcceleratorConfig, ConfigGrid
from . import obs, rs_mapping
from .topology import Layer

_POOL_OP_ENERGY = 0.2      # a pooling compare/add relative to a MAC


def _mapping(xp, cfg: Dict[str, Any], lay: Dict[str, Any]) -> Dict[str, Any]:
    """RS mapping over (configs × layers) from struct-of-arrays inputs."""
    return rs_mapping.mapping(
        xp,
        rows=cfg["rows"], cols=cfg["cols"],
        c_ch=lay["c_ch"], m=lay["m"], ky=lay["ky"], kx=lay["kx"],
        stride=lay["stride"], ix=lay["ix"], iy=lay["iy"],
        oy=lay["oy"], ox=lay["ox"],
        is_acc=lay["is_acc"], is_dw=lay["is_dw"], is_pool=lay["is_pool"],
        gb_ifmap_words=cfg["gb_ifmap_words"],
        rf_ifmap_words=cfg["rf_ifmap_words"],
        rf_weight_words=cfg["rf_weight_words"],
        rf_psum_words=cfg["rf_psum_words"])


@dataclasses.dataclass(frozen=True)
class LayerReport:
    """Per-layer outputs of the Tool (§II.B.2)."""

    name: str
    energy: float            # pJ
    latency: float           # ns
    macs: float
    dram_reads: float
    dram_writes: float
    gb_reads: float
    gb_writes: float
    rf_accesses: float
    utilization: float       # active PEs / total PEs (compute-time weighted)
    mem_time: float          # ns spent on the memory hierarchy
    array_time: float        # ns spent computing in the array
    psum_spilled: float      # words of psum traffic that went to DRAM
    ifmap_refetched: float   # extra ifmap words re-read from DRAM


@dataclasses.dataclass(frozen=True)
class NetworkReport:
    name: str
    energy: float
    latency: float
    layers: List[LayerReport]

    @property
    def edp(self) -> float:
        return self.energy * self.latency

    @property
    def layer_latencies(self) -> np.ndarray:
        return np.array([l.latency for l in self.layers])

    @property
    def layer_energies(self) -> np.ndarray:
        return np.array([l.energy for l in self.layers])


def _counts(xp, cfg: Dict[str, Any], lay: Dict[str, Any],
            mp: Dict[str, Any] | None = None) -> Dict[str, Any]:
    """Access counts + time terms; broadcast over (configs × layers).

    ``mp`` lets callers pass a precomputed RS mapping (the batched engine
    evaluates it on the smaller mapping-unique config set and gathers)."""
    if mp is None:
        mp = _mapping(xp, cfg, lay)

    n_c, n_m, n_oy = mp["n_c"], mp["n_m"], mp["n_oy"]
    w_psum = mp["w_psum"]
    ky_serial = mp["ky_serial"]

    ifmap_vol = lay["ifmap_words"]
    ofmap = lay["ofmap_words"]
    weights = lay["weight_words"]
    macs = lay["macs"]
    is_pool = lay["is_pool"]
    pool_ops = lay["c_ch"] * lay["ox"] * lay["oy"] * lay["kx"] * lay["ky"]

    # ---- ifmap traffic (Observation 2) -------------------------------------
    # Channel rounds partition the channel set, so every ifmap word streams
    # DRAM→GB exactly once (compulsory traffic — the Eyeriss-RS reuse ideal).
    # GB_ifmap capacity acts through the mapping instead: fewer channels held
    # per round ⇒ more accumulation rounds ⇒ more psum RMW traffic below.
    # Within a round the resident channel planes are re-delivered GB→array
    # for each of the n_m filter blocks (cheap on-chip reads).
    ifmap_dram_reads = ifmap_vol * xp.ones_like(n_m)
    ifmap_refetched = ifmap_vol * 0.0
    gb_ifmap_writes = ifmap_dram_reads                 # DRAM → GB
    gb_ifmap_reads = ifmap_vol * xp.where(lay["is_acc"], n_m, 1)

    # ---- weight traffic ---------------------------------------------------
    # GB_weight is provisioned for the in-flight working set (§III); weights
    # stream from DRAM once, land in the PE weight RFs once per use phase and
    # are reused across the spatial loop from there.
    wt_dram_reads = weights
    gb_wt_writes = weights
    gb_wt_reads = weights * ky_serial

    # ---- psum traffic (Observation 1) --------------------------------------
    # The psum planes of the in-flight filter block (w_psum = cap_m·Ox·Oy
    # words) are read-modify-written on every channel-accumulation round
    # after the first; the slice exceeding GB_psum makes the round trip to
    # off-chip DRAM instead (write + re-read, §III).
    inter_rounds = xp.maximum(n_c * ky_serial - 1, 0)
    overflow = xp.maximum(w_psum - cfg["gb_psum_words"], 0.0)
    held = xp.minimum(w_psum, cfg["gb_psum_words"] * xp.ones_like(w_psum))
    psum_dram_writes = inter_rounds * overflow
    psum_dram_reads = psum_dram_writes
    psum_gb_inter = inter_rounds * held
    gb_psum_writes = psum_gb_inter + ofmap             # + final results
    gb_psum_reads = psum_gb_inter + ofmap              # reload + writeback
    ofmap_dram_writes = ofmap

    # ---- totals -------------------------------------------------------------
    dram_reads = ifmap_dram_reads + wt_dram_reads + psum_dram_reads
    dram_writes = ofmap_dram_writes + psum_dram_writes
    gb_reads = gb_ifmap_reads + gb_wt_reads + gb_psum_reads
    gb_writes = gb_ifmap_writes + gb_wt_writes + gb_psum_writes

    words_into_array = gb_ifmap_reads + gb_wt_reads + psum_gb_inter + psum_dram_reads
    words_out_of_array = gb_psum_writes + psum_dram_writes

    ops = xp.where(is_pool, pool_ops, macs)
    rf_accesses = (4.0 * ops) + words_into_array + words_out_of_array

    return dict(
        mp=mp, ops=ops, macs=macs, pool_ops=pool_ops,
        dram_reads=dram_reads, dram_writes=dram_writes,
        gb_ifmap_reads=gb_ifmap_reads, gb_ifmap_writes=gb_ifmap_writes,
        gb_wt_reads=gb_wt_reads, gb_wt_writes=gb_wt_writes,
        gb_psum_reads=gb_psum_reads, gb_psum_writes=gb_psum_writes,
        gb_reads=gb_reads, gb_writes=gb_writes,
        rf_accesses=rf_accesses,
        words_into_array=words_into_array,
        words_out_of_array=words_out_of_array,
        psum_spilled=psum_dram_writes + psum_dram_reads,
        ifmap_refetched=ifmap_refetched,
    )


def _energy_latency(xp, cfg: Dict[str, Any], lay: Dict[str, Any],
                    ct: Dict[str, Any]) -> Dict[str, Any]:
    e = cfg  # per-access constants pre-flattened into the cfg dict
    mp = ct["mp"]

    gb_e_if, gb_e_ps, gb_e_wt = e["gb_e_ifmap"], e["gb_e_psum"], e["gb_e_wt"]
    mac_e = xp.where(lay["is_pool"], e["e_mac"] * _POOL_OP_ENERGY, e["e_mac"])

    noc_hops = (cfg["rows"] + cfg["cols"]) / 2.0
    # idle PEs still burn clock/leakage power for the whole layer occupancy
    idle_cycles = (cfg["rows"] * cfg["cols"] - mp["active_pes"]) \
        * ct["ops"] / mp["active_pes"]
    energy = (
        ct["dram_reads"] * e["e_dram_r"] + ct["dram_writes"] * e["e_dram_w"]
        + (ct["gb_ifmap_reads"] + ct["gb_ifmap_writes"]) * gb_e_if
        + (ct["gb_psum_reads"] + ct["gb_psum_writes"]) * gb_e_ps
        + (ct["gb_wt_reads"] + ct["gb_wt_writes"]) * gb_e_wt
        + ct["rf_accesses"] * e["e_rf"]
        + ct["ops"] * mac_e
        + idle_cycles * e["e_pe_idle"]
        + (ct["words_into_array"] + ct["words_out_of_array"])
        * e["e_noc_hop"] * noc_hops
    )

    # Latency: GB→array delivery is paced by the NoC *and* by the access time
    # of the partition it drains (bigger buffer ⇒ slower access, Fig. 9).
    lat_if = e["gb_t_ifmap"] / e["gb_t_base"]
    lat_ps = e["gb_t_psum"] / e["gb_t_base"]
    delivery_cy = (
        (ct["gb_ifmap_reads"] + ct["gb_wt_reads"]) * lat_if
        + (ct["gb_psum_reads"]) * lat_ps
    ) / e["noc_wpc"]
    writeback_cy = ct["words_out_of_array"] * lat_ps / e["noc_wpc"]
    compute_cy = ct["ops"] / mp["active_pes"] * e["mac_t_cy"]
    array_cy = delivery_cy + compute_cy + writeback_cy

    dram_words = ct["dram_reads"] + ct["dram_writes"]
    dram_cy = dram_words / e["dram_wpc"]

    array_time = array_cy * e["cycle_ns"]
    mem_time = dram_cy * e["cycle_ns"] + (delivery_cy + writeback_cy) * e["cycle_ns"]
    latency = (array_cy + dram_cy) * e["cycle_ns"]

    total_pes = cfg["rows"] * cfg["cols"]
    utilization = xp.where(
        array_cy > 0, (compute_cy / xp.maximum(array_cy, 1e-30))
        * mp["active_pes"] / total_pes, 0.0)

    return dict(energy=energy, latency=latency, array_time=array_time,
                mem_time=mem_time, utilization=utilization)


def _cfg_struct(xp, cfg: AcceleratorConfig) -> Dict[str, Any]:
    et = cfg.energy
    return dict(
        rows=xp.asarray(cfg.array_rows), cols=xp.asarray(cfg.array_cols),
        gb_ifmap_words=xp.asarray(cfg.gb_ifmap_words()),
        gb_psum_words=xp.asarray(cfg.gb_psum_words()),
        rf_ifmap_words=xp.asarray(cfg.rf_ifmap_words),
        rf_weight_words=xp.asarray(cfg.rf_weight_words),
        rf_psum_words=xp.asarray(cfg.rf_psum_words),
        e_rf=xp.asarray(et.rf_read),
        e_dram_r=xp.asarray(et.dram_read), e_dram_w=xp.asarray(et.dram_write),
        e_mac=xp.asarray(et.mac), e_noc_hop=xp.asarray(et.noc_hop),
        e_pe_idle=xp.asarray(et.pe_idle),
        gb_e_ifmap=xp.asarray(et.gb_energy(cfg.gb_ifmap_kb)),
        gb_e_psum=xp.asarray(et.gb_energy(cfg.gb_psum_kb)),
        gb_e_wt=xp.asarray(et.gb_energy(cfg.gb_weight_kb)),
        gb_t_ifmap=xp.asarray(et.gb_latency(cfg.gb_ifmap_kb)),
        gb_t_psum=xp.asarray(et.gb_latency(cfg.gb_psum_kb)),
        gb_t_base=xp.asarray(et.gb_t),
        noc_wpc=xp.asarray(cfg.noc_words_per_cycle),
        dram_wpc=xp.asarray(cfg.dram_words_per_cycle),
        mac_t_cy=xp.asarray(et.mac_t / cfg.cycle_ns),
        cycle_ns=xp.asarray(cfg.cycle_ns),
    )


def simulate_network(cfg: AcceleratorConfig, layers: Sequence[Layer],
                     name: str = "net") -> NetworkReport:
    """Scalar (per-network, per-config) entry point → full layer reports."""
    xp = np
    compute = [l for l in layers if l.kind != "input"]
    lay = rs_mapping.layer_struct(xp, compute)
    lay = {k: np.asarray(v, dtype=np.float64) for k, v in lay.items()}
    cfgs = _cfg_struct(xp, cfg)
    cfgs = {k: v.astype(np.float64) for k, v in cfgs.items()}

    ct = _counts(xp, cfgs, lay)
    el = _energy_latency(xp, cfgs, lay, ct)

    reports = []
    for i, l in enumerate(compute):
        reports.append(LayerReport(
            name=l.name,
            energy=float(el["energy"][i]), latency=float(el["latency"][i]),
            macs=float(lay["macs"][i]),
            dram_reads=float(ct["dram_reads"][i]),
            dram_writes=float(ct["dram_writes"][i]),
            gb_reads=float(ct["gb_reads"][i]), gb_writes=float(ct["gb_writes"][i]),
            rf_accesses=float(ct["rf_accesses"][i]),
            utilization=float(el["utilization"][i]),
            mem_time=float(el["mem_time"][i]),
            array_time=float(el["array_time"][i]),
            psum_spilled=float(ct["psum_spilled"][i]),
            ifmap_refetched=float(ct["ifmap_refetched"][i]),
        ))
    return NetworkReport(
        name=name,
        energy=float(el["energy"].sum()),
        latency=float(el["latency"].sum()),
        layers=reports)


# ---------------------------------------------------------------------------
# Batched, jit-cached design-space engine.
#
# The whole (configs × networks × layers) evaluation runs as ONE program.
# Two structural facts keep it fast at multi-thousand-point scale:
#
# * **Count dedup** — the RS mapping and access counts depend on a config
#   only through (array, GB words, RF words); knobs like the NoC width or
#   per-access energies don't change counts.  The grid is deduplicated on
#   those columns (5,400 extended-space points → 1,800 unique count rows)
#   and the heavy (unique × layers) math runs once per unique row.
# * **Early layer reduction** — per-network energy/latency are LINEAR in
#   the per-layer count terms with config-only coefficients, so the layer
#   axis is summed per network (static segment slices of the concatenated
#   layer axis) *before* the coefficients are applied: the expensive
#   [points × layers] stage collapses to [unique × networks] partial sums,
#   and the coefficient combine runs on tiny [points × networks] arrays.
#
# The jitted kernel lives at module level, so its compile cache persists
# across sweeps: jax.jit keys on input shapes, and the layer axis is padded
# to multiples of _LAYER_BUCKET, so every network (all 18 paper benchmarks
# are ≤ 251 layers) shares one trace per grid size.  The kernel needs 64-bit
# floats (access counts exceed float32's exact-integer range), so every
# trace and dispatch of it runs inside :func:`x64`.
# ---------------------------------------------------------------------------

_LAYER_BUCKET = 256

#: The engine's programs: the grid kernel in its four forms and the three
#: streamed folds (every other program counts under its own name too).
_ENGINE_PROGRAMS = ("grid_kernel", "grid_kernel_layers",
                    "grid_kernel_devices", "grid_kernel_devices_layers",
                    "stream_fold", "layer_fold", "layer_fold_devices")


def jit_cache_stats() -> Dict[str, int]:
    """Traces and dispatches of the engine's programs since the process
    started: ``traces`` counts actual retraces, ``calls`` every dispatch;
    a warm engine has calls ≫ traces.  Per program they are the
    ``jit.trace.<name>`` / ``jit.call.<name>`` totals of :mod:`obs`."""
    return dict(
        traces=sum(obs.total(f"jit.trace.{p}") for p in _ENGINE_PROGRAMS),
        calls=sum(obs.total(f"jit.call.{p}") for p in _ENGINE_PROGRAMS))


def x64():
    """The one 64-bit scope of every jitted engine and solver program:
    a context manager that enables float64/int64 for the traces and
    dispatches inside it and restores the process setting on exit."""
    import jax
    return jax.enable_x64(True)


def _cfg_struct_from_grid(xp, grid) -> Dict[str, Any]:
    """Vectorised twin of :func:`_cfg_struct`: derives the per-access model
    columns for every grid point at once (float64, shape [n]).  Accepts a
    ConfigGrid or a bare column dict (the chunked paths slice columns)."""
    fields = grid.fields if isinstance(grid, ConfigGrid) else grid
    f = {k: np.asarray(v, dtype=np.float64) for k, v in fields.items()}
    bpw = f["bitwidth"] / 8.0
    ref = f["gb_ref_kb"]

    def gb_e(kb):
        return f["gb_e_ref"] * np.sqrt(np.maximum(kb, 1.0) / ref)

    def gb_t(kb):
        return f["gb_t_ref"] * np.sqrt(np.sqrt(np.maximum(kb, 1.0) / ref))

    return dict(
        rows=f["rows"], cols=f["cols"],
        gb_ifmap_words=np.floor(f["gb_ifmap_kb"] * 1024 / bpw),
        gb_psum_words=np.floor(f["gb_psum_kb"] * 1024 / bpw),
        rf_ifmap_words=f["rf_ifmap_words"],
        rf_weight_words=f["rf_weight_words"],
        rf_psum_words=f["rf_psum_words"],
        e_rf=f["e_rf"], e_dram_r=f["e_dram_r"], e_dram_w=f["e_dram_w"],
        e_mac=f["e_mac"], e_noc_hop=f["e_noc_hop"], e_pe_idle=f["e_pe_idle"],
        gb_e_ifmap=gb_e(f["gb_ifmap_kb"]),
        gb_e_psum=gb_e(f["gb_psum_kb"]),
        gb_e_wt=gb_e(f["gb_weight_kb"]),
        gb_t_ifmap=gb_t(f["gb_ifmap_kb"]),
        gb_t_psum=gb_t(f["gb_psum_kb"]),
        gb_t_base=f["gb_t_ref"],
        noc_wpc=f["noc_wpc"], dram_wpc=f["dram_wpc"],
        mac_t_cy=f["mac_t"] / f["cycle_ns"], cycle_ns=f["cycle_ns"],
    )


# A benign do-nothing layer: unit shapes keep every mapping quantity ≥ 1
# (no division hazards) while zero macs/words make its energy and latency
# exactly 0.0, so padding is invisible even before the one-hot masking.
_PAD_LAYER_ROW = dict(
    c_ch=1.0, m=1.0, ky=1.0, kx=1.0, stride=1.0, ix=1.0, iy=1.0,
    oy=1.0, ox=1.0, macs=0.0, weight_words=0.0, ifmap_words=0.0,
    ofmap_words=0.0, is_acc=1.0, is_dw=0.0, is_pool=0.0)


def _bucketed(n: int, bucket: int) -> int:
    return max(bucket, -(-n // bucket) * bucket)


#: Layer-struct columns that must be ≥ 1 — shapes/strides act as tile
#: divisors in the RS mapping — vs. counts that only need to be ≥ 0.
_LAYER_DIM_COLUMNS = ("c_ch", "m", "ky", "kx", "stride", "ix", "iy",
                      "oy", "ox")


def _validate_layer_struct(name: str, struct: Dict[str, np.ndarray]):
    """Reject NaN/inf/non-positive layer parameters at the engine boundary,
    naming the network, layer index and field (the layer-axis analogue of
    :func:`repro.core.accelerator.validate_fields`)."""
    for k, v in struct.items():
        bad = ~np.isfinite(v)
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"network {name!r}: layer {i} field {k!r} is non-finite "
                f"({v[i]!r})")
        floor = 1 if k in _LAYER_DIM_COLUMNS else 0
        bad = v < floor
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"network {name!r}: layer {i} field {k!r} must be >= "
                f"{floor}, got {v[i]!r}")


def _stack_networks(networks: Mapping[str, Sequence[Layer]],
                    bucket: int = _LAYER_BUCKET,
                    absorb_pad: bool = True):
    """Concatenate all networks' compute layers along one padded axis.

    Returns ``(lay, segments)``: ``lay`` values have shape [L_pad] and
    ``segments`` is a static tuple of per-network (start, stop) on that
    axis.  With ``absorb_pad`` the LAST segment extends to L_pad — pad
    layers contribute exactly zero (see ``_PAD_LAYER_ROW``), and
    absorbing them into the last segment makes the static jit key depend
    only on the bucketed length: every single-network sweep of a
    ≤ ``bucket``-layer network shares the one ``((0, bucket),)`` trace,
    rather than retracing per layer count.  The per-layer path passes
    ``absorb_pad=False`` — it needs the TRUE per-network lengths to size
    the padded ``n_layer`` output axis (at the cost of one extra trace
    per distinct length multiset).
    """
    if not networks:
        raise ValueError("evaluate_networks needs at least one network")
    structs = []
    seg_lens = []
    for name, layers in networks.items():
        compute = [l for l in layers if l.kind != "input"]
        s = rs_mapping.layer_struct(np, compute)
        s = {k: np.asarray(v, dtype=np.float64) for k, v in s.items()}
        _validate_layer_struct(name, s)
        structs.append(s)
        seg_lens.append(len(compute))
    total = int(np.sum(seg_lens))
    l_pad = _bucketed(total, bucket)

    lay = {}
    for k in structs[0]:
        col = np.full(l_pad, _PAD_LAYER_ROW[k], dtype=np.float64)
        col[:total] = np.concatenate([s[k] for s in structs])
        lay[k] = col
    offs = np.concatenate([[0], np.cumsum(seg_lens)]).astype(int)
    if absorb_pad:
        offs[-1] = l_pad                    # zero-energy pad → last segment
    segments = tuple((int(a), int(b)) for a, b in zip(offs[:-1], offs[1:]))
    return lay, segments


def network_layer_counts(networks: Mapping[str, Sequence[Layer]]
                         ) -> np.ndarray:
    """Per-network compute-layer counts, ordered like ``networks`` — the
    valid lengths of the per-layer path's padded ``n_layer`` axis."""
    return np.array([sum(1 for l in layers if l.kind != "input")
                     for layers in networks.values()], dtype=np.int64)


#: Config columns the RS mapping / access counts depend on.  Everything
#: else (per-access energies, NoC width, DRAM width, clock) only scales the
#: counts linearly and is applied after the layer reduction.
_COUNT_COLUMNS = ("rows", "cols", "gb_ifmap_words", "gb_psum_words",
                  "rf_ifmap_words", "rf_weight_words", "rf_psum_words")

#: Subset of _COUNT_COLUMNS the RS mapping itself depends on — GB_psum only
#: enters the spill accounting in `_counts`, never the mapping, so on the
#: extended space the mapping runs on 180 unique rows, not 1,800.
_MAPPING_COLUMNS = ("rows", "cols", "gb_ifmap_words",
                    "rf_ifmap_words", "rf_weight_words", "rf_psum_words")

#: Mapping outputs `_counts` / `_count_terms` consume (gathered back to the
#: count-unique axis after the mapping-unique evaluation).
_MAPPING_KEYS = ("n_c", "n_m", "n_oy", "w_psum", "ky_serial", "active_pes")


#: A partial row key is compacted to dense ranks before its radix product
#: would reach this, so the int64 key never overflows.
_KEY_SPAN_LIMIT = 1 << 62


def _dedup_rows(cfgs: Dict[str, np.ndarray], columns):
    """→ (unique column dict [n_u], inverse index [n]) over ``columns``:
    ``np.unique(np.stack(cols, 1), axis=0, return_inverse=True)``'s answer,
    unique rows in lexicographic order.  Each column is ranked with a 1-D
    ``np.unique``; the ranks fold into one int64 key in mixed radix, the
    first column most significant, so the key sorts rows lexicographically
    and one 1-D ``np.unique`` of it dedups them.  A partial key whose radix
    product would reach 2**62 is first compacted to dense ranks (counted
    as ``dse.dedup.rekeys``)."""
    key, span = np.zeros(len(cfgs[columns[0]]), np.int64), 1
    for k in columns:
        vals, code = np.unique(cfgs[k], return_inverse=True)
        if span * len(vals) >= _KEY_SPAN_LIMIT:
            uniq_key, key = np.unique(key, return_inverse=True)
            span = len(uniq_key)
            obs.count("dse.dedup.rekeys")
        key = key * len(vals) + code
        span *= len(vals)
    _, first, inv = np.unique(key, return_index=True, return_inverse=True)
    return ({k: cfgs[k][first] for k in columns},
            inv.astype(np.int32))


def _dedup_count_rows(cfgs: Dict[str, np.ndarray]):
    return _dedup_rows(cfgs, _COUNT_COLUMNS)


def _count_terms(xp, cfg_u: Dict[str, Any], lay: Dict[str, Any],
                 mp: Dict[str, Any] | None = None):
    """The 14 per-layer count terms that energy/latency are linear in.

    ``cfg_u`` holds the [n_u, 1] unique count columns; returns a tuple of
    [n_u, L] (or [1, L] for config-independent) arrays.  Kept as separate
    arrays — stacking them into one [14, n_u, L] tile would materialise
    hundreds of MB that the segment reduction immediately collapses.
    """
    ct = _counts(xp, cfg_u, lay, mp)
    active = ct["mp"]["active_pes"]
    ops = ct["ops"]
    is_pool = lay["is_pool"]
    return (
        ct["dram_reads"],                                   # 0 e_dram_r
        ct["dram_writes"],                                  # 1 e_dram_w
        ct["gb_ifmap_reads"] + ct["gb_ifmap_writes"],       # 2 gb_e_ifmap
        ct["gb_psum_reads"] + ct["gb_psum_writes"],         # 3 gb_e_psum
        ct["gb_wt_reads"] + ct["gb_wt_writes"],             # 4 gb_e_wt
        ct["rf_accesses"],                                  # 5 e_rf
        xp.where(is_pool, 0.0, ct["macs"]),                 # 6 e_mac
        xp.where(is_pool, ct["pool_ops"], 0.0),             # 7 e_mac·pool
        (cfg_u["rows"] * cfg_u["cols"] - active) * ops / active,  # 8 idle
        ct["words_into_array"] + ct["words_out_of_array"],  # 9 noc energy
        ct["gb_ifmap_reads"] + ct["gb_wt_reads"],           # 10 delivery@if
        ct["gb_psum_reads"],                                # 11 delivery@ps
        ct["words_out_of_array"],                           # 12 writeback
        ops / active,                                       # 13 compute cy
    )


def _combine_reduced(xp, S, coefs: Dict[str, Any]):
    """14 × [n_cfg, n_net] reduced sums × per-config coefficients →
    (energy, latency), both [n_cfg, n_net].  Mirrors `_energy_latency`."""
    C = {k: v[:, None] for k, v in coefs.items()}
    (d_r, d_w, gb_if, gb_ps, gb_wt, rf, ops_mac, ops_pool, idle,
     words_noc, dlv_if, dlv_ps, wout, ops_pe) = S
    energy = (
        d_r * C["e_dram_r"] + d_w * C["e_dram_w"]
        + gb_if * C["gb_e_ifmap"] + gb_ps * C["gb_e_psum"]
        + gb_wt * C["gb_e_wt"] + rf * C["e_rf"]
        + ops_mac * C["e_mac"] + ops_pool * (C["e_mac"] * _POOL_OP_ENERGY)
        + idle * C["e_pe_idle"]
        + words_noc * C["e_noc_hop"] * C["noc_hops"])
    lat_if = C["gb_t_ifmap"] / C["gb_t_base"]
    lat_ps = C["gb_t_psum"] / C["gb_t_base"]
    array_cy = ((dlv_if * lat_if + dlv_ps * lat_ps + wout * lat_ps)
                / C["noc_wpc"] + ops_pe * C["mac_t_cy"])
    dram_cy = (d_r + d_w) / C["dram_wpc"]
    latency = (array_cy + dram_cy) * C["cycle_ns"]
    return energy, latency


def _coef_struct(cfgs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    keys = ("e_dram_r", "e_dram_w", "gb_e_ifmap", "gb_e_psum", "gb_e_wt",
            "e_rf", "e_mac", "e_pe_idle", "e_noc_hop", "gb_t_ifmap",
            "gb_t_psum", "gb_t_base", "noc_wpc", "mac_t_cy", "dram_wpc",
            "cycle_ns")
    out = {k: cfgs[k] for k in keys}
    out["noc_hops"] = (cfgs["rows"] + cfgs["cols"]) / 2.0
    return out


def _term_sums_body(xp, segments, cfg_m, cfg_u, lay, inv_m):
    """Mapping on the mapping-unique rows, counts on the count-unique rows,
    per-network segment sums: tuple of [n_u, n_net] (or [1, n_net] for the
    two config-independent terms).  This is the heavy stage."""
    mp_m = _mapping(xp, cfg_m, lay)
    mp = {k: mp_m[k][inv_m] for k in _MAPPING_KEYS}
    terms = _count_terms(xp, cfg_u, lay, mp)
    return tuple(
        xp.stack([t[..., a:b].sum(-1) for a, b in segments], axis=-1)
        for t in terms)


def _gather_combine_body(xp, S, inv, coefs):
    """Gather the reduced sums back to the full config axis and apply the
    per-config coefficients — the cheap [n_cfg, n_net] stage."""
    gathered = []
    for s in S:
        if s.shape[0] == 1:                  # config-independent term
            g = xp.broadcast_to(s, (inv.shape[0], s.shape[1]))
        else:
            g = s[inv]
        gathered.append(g)
    return _combine_reduced(xp, tuple(gathered), coefs)


# ---------------------------------------------------------------------------
# Per-layer path: the SAME heavy stage without the early segment reduction.
# The 14 terms stay [n_u, L], the coefficient combine broadcasts over the
# concatenated layer axis, and the result is re-split per network onto a
# padded n_layer axis.
# ---------------------------------------------------------------------------


def _term_layers_body(xp, cfg_m, cfg_u, lay, inv_m):
    """Per-layer twin of :func:`_term_sums_body`: the raw 14 count terms,
    each [n_u, L] ([1, L] for the config-independent two) — no segment
    reduction."""
    mp_m = _mapping(xp, cfg_m, lay)
    mp = {k: mp_m[k][inv_m] for k in _MAPPING_KEYS}
    return _count_terms(xp, cfg_u, lay, mp)


def _layer_axis_len(segments) -> int:
    """Padded n_layer of the per-layer output: the longest segment."""
    return max(b - a for a, b in segments)


def _split_layers(xp, arr, segments):
    """[n, L_concat] → [n, n_net, n_layer]: slice each network's segment
    off the concatenated axis and zero-pad to the longest one (pad rows
    of shorter networks are exactly 0 — see ``_PAD_LAYER_ROW``)."""
    n_layer = _layer_axis_len(segments)
    outs = []
    for a, b in segments:
        seg = arr[:, a:b]
        if b - a < n_layer:
            seg = xp.pad(seg, ((0, 0), (0, n_layer - (b - a))))
        outs.append(seg)
    return xp.stack(outs, axis=1)


def _grid_kernel_body(xp, segments, cfg_m, cfg_u, lay, inv_m, inv, coefs,
                      per_layer: bool = False):
    """Shared numpy/jax kernel: mapping on the mapping-unique rows, counts
    on the count-unique rows, segment-reduce, then coefficient combine.
    ``per_layer=True`` skips the segment reduction: the combine runs on
    the [*, L] terms and the outputs are re-split to [n, n_net, n_layer]."""
    if per_layer:
        S = _term_layers_body(xp, cfg_m, cfg_u, lay, inv_m)
        e, t = _gather_combine_body(xp, S, inv, coefs)
        return _split_layers(xp, e, segments), _split_layers(xp, t, segments)
    S = _term_sums_body(xp, segments, cfg_m, cfg_u, lay, inv_m)
    return _gather_combine_body(xp, S, inv, coefs)


def _np_grid_kernel(segments, cfg_m, cfg_u, lay, inv_m, inv, coefs,
                    per_layer: bool = False):
    return _grid_kernel_body(np, segments, cfg_m, cfg_u, lay, inv_m, inv,
                             coefs, per_layer=per_layer)


_jitted_grid_kernels: Dict[bool, Any] = {}      # per mode (per_layer)


def _jax_grid_kernel(per_layer: bool = False):
    if per_layer not in _jitted_grid_kernels:
        import jax.numpy as jnp

        def kernel(segments, cfg_m, cfg_u, lay, inv_m, inv, coefs):
            return _grid_kernel_body(jnp, segments, cfg_m, cfg_u, lay,
                                     inv_m, inv, coefs, per_layer=per_layer)

        name = "grid_kernel_layers" if per_layer else "grid_kernel"
        _jitted_grid_kernels[per_layer] = obs.Program(name, kernel,
                                                      static_argnums=0)
    return _jitted_grid_kernels[per_layer]


_jitted_device_kernels: Dict[Tuple[bool, int], Any] = {}


def _jax_device_kernel(per_layer: bool = False):
    """The grid kernel on every device of the mesh at once, each on its
    own chunk and with no collective: one round of a chunked sweep.  The
    devices' chunk inputs are stacked along the row axis
    (:func:`_round_inputs`) and the outputs come back stacked the same
    way.  A device whose ``active`` entry is False (no chunk in a last,
    partial round) skips the kernel and returns zeros.  One program
    serves every device; a single-device program committed to device
    ``d`` would compile once per device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _cfg_mesh()
    key = (per_layer, mesh.devices.size)
    if key not in _jitted_device_kernels:
        rows, rep = P("cfg"), P()

        def kernel(segments, cfg_m, cfg_u, lay, inv_m, inv, coefs, active):
            def local(cfg_m_, cfg_u_, lay_, inv_m_, inv_, coefs_, active_):
                def run():
                    return _grid_kernel_body(
                        jnp, segments, cfg_m_, cfg_u_, lay_, inv_m_, inv_,
                        coefs_, per_layer=per_layer)

                out = jax.eval_shape(run)
                return jax.lax.cond(
                    active_[0], run,
                    lambda: tuple(jnp.zeros(o.shape, o.dtype) for o in out))

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=({k: rows for k in cfg_m}, {k: rows for k in cfg_u},
                          {k: rep for k in lay}, rows, rows,
                          {k: rows for k in coefs}, rows),
                out_specs=(rows, rows),
                check_vma=False)(cfg_m, cfg_u, lay, inv_m, inv, coefs,
                                 active)

        name = ("grid_kernel_devices_layers" if per_layer
                else "grid_kernel_devices")
        _jitted_device_kernels[key] = obs.Program(name, kernel,
                                                  static_argnums=0)
    return _jitted_device_kernels[key]


def jax_available() -> bool:
    try:
        import jax                                     # noqa: F401
        return True
    except Exception:                                  # pragma: no cover
        return False


def platform() -> str:
    """The platform the device path runs on (``jax.default_backend()``:
    ``"cpu"``, ``"tpu"``, ...); ``"cpu"`` without jax.  Every platform
    decision — whether the service's retries may end on the numpy
    reference, whether kernels run interpreted
    (``repro.kernels.default_interpret``) — reads this one function."""
    if not jax_available():
        return "cpu"                                   # pragma: no cover
    import jax
    return jax.default_backend()


#: Selectable grid-kernel backends, in auto-fallback order.
BACKENDS = ("jax", "numpy")

_LAST_BACKEND: str | None = None

#: (requested, resolved) degradation edges already warned about — the
#: auto-fallback warns exactly ONCE per process per edge, never per call
#: (a mega-grid chunked sweep resolves the backend thousands of times).
_FALLBACK_WARNED: set = set()


def last_backend() -> str | None:
    """Backend the most recent engine dispatch actually ran on
    (``"jax" | "numpy"``), after auto-fallback — ``None``
    before the first call.  Lets callers report truthfully what executed
    (see ``examples/dse_hetero.py``)."""
    return _LAST_BACKEND


def _warn_fallback(requested: str, resolved: str) -> None:
    key = (requested, resolved)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(
        f"engine backend {requested!r} is unavailable on this host; "
        f"falling back to {resolved!r} (check energymodel.last_backend() "
        "for what each dispatch ran on; this warning fires once per "
        "process)", RuntimeWarning, stacklevel=3)


def resolve_backend(backend: str | None = None,
                    use_jax: bool | None = None) -> str:
    """Resolve the requested backend with auto-fallback.

    Explicit ``backend`` wins over the legacy ``use_jax`` tri-state; a
    name outside :data:`BACKENDS` raises ``ValueError``.  ``"jax"``
    degrades to ``"numpy"`` when jax cannot be imported, with one
    ``RuntimeWarning`` per process (not per call); the silent path is
    only the auto-selection where nothing was requested."""
    if backend is None:
        if use_jax is None:
            backend = "jax" if jax_available() else "numpy"
        else:
            backend = "jax" if use_jax else "numpy"
        requested = None                     # auto-selection: never warn
    else:
        requested = backend
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, "
                         f"got {backend!r}")
    if backend == "jax" and not jax_available():
        backend = "numpy"
    if requested is not None and backend != requested:
        _warn_fallback(requested, backend)
    return backend


# ---------------------------------------------------------------------------
# Several devices: the config axis is the embarrassingly-parallel axis of
# the engine, so whole chunks of it go out in rounds, one per device, each
# device running the complete kernel on its chunk with no collective
# (:func:`_jax_device_kernel`).  Multiple host devices come from XLA's
# ``--xla_force_host_platform_device_count`` flag, which MUST be set in
# XLA_FLAGS before jax first initialises its backend (see launch/dryrun.py
# and benchmarks/run.py for the pattern).
# ---------------------------------------------------------------------------

#: Bucket sizes for the unique axes under chunked evaluation: padding the
#: deduped rows (duplicates of row 0 — valid math, never gathered back) to
#: these multiples keeps jit input shapes stable across chunks, so a whole
#: chunked sweep shares a handful of traces.
_UNIQUE_BUCKET = 256
_MAPPING_BUCKET = 64


def host_device_count() -> int:
    """Number of (possibly XLA-forced) host devices; 1 without jax."""
    if not jax_available():
        return 1
    import jax
    return len(jax.devices())


def request_host_devices(n: int) -> bool:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS.

    Must run before anything imports jax (the backend locks the device
    count on first init); returns False — and changes nothing — if jax is
    already imported."""
    import os
    import sys
    if "jax" in sys.modules:
        return False
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(n)}")
    return True


_MESH = None


def _cfg_mesh():
    global _MESH
    import jax
    devs = np.array(jax.devices())
    if _MESH is None or _MESH.devices.size != devs.size:
        from jax.sharding import Mesh
        _MESH = Mesh(devs, ("cfg",))
    return _MESH


def _pad_rows(arr: np.ndarray, n_to: int) -> np.ndarray:
    """Pad axis 0 to ``n_to`` by repeating row 0 (benign duplicate)."""
    if arr.shape[0] >= n_to:
        return arr
    reps = np.broadcast_to(arr[:1], (n_to - arr.shape[0],) + arr.shape[1:])
    return np.concatenate([arr, reps], axis=0)


def _prepare_fields(fields: Dict[str, np.ndarray],
                    u_bucket: int | None = None,
                    m_bucket: int | None = None):
    """Grid columns → two-level-deduped kernel inputs, with the unique
    axes optionally padded to bucket multiples."""
    cfgs = _cfg_struct_from_grid(np, fields)
    coefs = _coef_struct(cfgs)
    cfg_u, inv = _dedup_count_rows(cfgs)            # counts level
    cfg_m, inv_m = _dedup_rows(cfg_u, _MAPPING_COLUMNS)  # mapping level
    n_u = inv_m.shape[0]
    if u_bucket is not None:
        tgt = _bucketed(n_u, u_bucket)
        if tgt > n_u:
            cfg_u = {k: _pad_rows(v, tgt) for k, v in cfg_u.items()}
            inv_m = np.concatenate(
                [inv_m, np.zeros(tgt - n_u, inv_m.dtype)])
    if m_bucket is not None:
        n_m = next(iter(cfg_m.values())).shape[0]
        cfg_m = {k: _pad_rows(v, _bucketed(n_m, m_bucket))
                 for k, v in cfg_m.items()}
    cfg_u = {k: v[:, None] for k, v in cfg_u.items()}
    cfg_m = {k: v[:, None] for k, v in cfg_m.items()}
    return cfg_m, cfg_u, inv_m, inv, coefs


def _eval_fields(fields, lay, segments, backend: str,
                 u_bucket: int | None = None,
                 m_bucket: int | None = None,
                 per_layer: bool = False):
    """Evaluate one batch of grid columns on one device → ([n, n_net],
    [n, n_net]) (or [n, n_net, n_layer] pairs in per-layer mode)."""
    cfg_m, cfg_u, inv_m, inv, coefs = _prepare_fields(
        fields, u_bucket, m_bucket)
    if backend == "numpy":
        e, t = _np_grid_kernel(segments, cfg_m, cfg_u, lay, inv_m, inv,
                               coefs, per_layer=per_layer)
        return np.asarray(e), np.asarray(t)
    with x64():
        return obs.fetch("dse.eval.wait", *_jax_grid_kernel(per_layer)(
            segments, cfg_m, cfg_u, lay, inv_m, inv, coefs))


def _prepare_chunk(fc):
    """One padded chunk's kernel inputs at the stream's buckets, counted
    in ``dse.stream.rows_unique`` / ``dse.stream.rows_padded``."""
    with obs.span("dse.stream.prep"):
        prep = _prepare_fields(fc, _UNIQUE_BUCKET, _MAPPING_BUCKET)
    obs.count("dse.stream.rows_unique", int(prep[3].max()) + 1)
    obs.count("dse.stream.rows_padded", prep[2].shape[0])
    return prep


def _dispatch_chunk(fc, lay, segments, per_layer: bool = False):
    """Async-dispatch one padded chunk (jax path): returns uncollected
    device arrays so the host can prepare the next chunk while this one
    runs."""
    cfg_m, cfg_u, inv_m, inv, coefs = _prepare_chunk(fc)
    with obs.span("dse.stream.dispatch"):
        return _jax_grid_kernel(per_layer)(
            segments, cfg_m, cfg_u, lay, inv_m, inv, coefs)


def _dispatch_chunks(chunks, lay, segments, per_layer: bool, shard: bool):
    """Async-dispatch every chunk of ``chunks`` (``(ci, start, stop, fc)``)
    and yield ``(ci, start, stop, fc, e, t)`` with ``e``/``t`` on the
    device that computes them.  With ``shard`` and several devices the
    chunks go out in rounds, one per device, each round one launch of
    :func:`_jax_device_kernel` for all devices."""
    if not (shard and host_device_count() > 1):
        for ci, start, stop, fc in chunks:
            yield (ci, start, stop, fc,
                   *_dispatch_chunk(fc, lay, segments, per_layer))
        return
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _cfg_mesh()
    lay_d = jax.device_put(lay, NamedSharding(mesh, P()))
    kern = _jax_device_kernel(per_layer)
    for rnd in _rounds(chunks, mesh.devices.size):
        args = _round_inputs([_prepare_chunk(fc) for *_, fc in rnd], mesh)
        with obs.span("dse.stream.dispatch"):
            e_g, t_g = kern(segments, args[0], args[1], lay_d, *args[2:])
        e_on = {s.device: s.data for s in e_g.addressable_shards}
        t_on = {s.device: s.data for s in t_g.addressable_shards}
        for chunk, dev in zip(rnd, mesh.devices.flat):
            yield (*chunk, e_on[dev], t_on[dev])


def _rounds(items, n: int):
    """``items`` in lists of ``n``, the last one possibly shorter."""
    it = iter(items)
    while rnd := list(itertools.islice(it, n)):
        yield rnd


def _round_inputs(preps, mesh):
    """One round's kernel inputs, one prepared chunk per device, and the
    [n_dev] ``active`` flags: each device's put on it under
    ``dse.stream.dispatch.device``, then the global arrays, the chunks
    stacked along the row axis, for :func:`_jax_device_kernel`.  A device
    past the last chunk gets the first chunk's inputs for their shapes
    and ``active`` False, so it computes nothing.  The unique-row axes
    are padded to the round's largest, as :func:`_prepare_fields` pads
    them to a bucket."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_u = max(p[2].shape[0] for p in preps)
    n_m = max(next(iter(p[0].values())).shape[0] for p in preps)
    parts = []
    for d, dev in enumerate(mesh.devices.flat):
        cfg_m, cfg_u, inv_m, inv, coefs = preps[d if d < len(preps) else 0]
        if inv_m.shape[0] < n_u:
            cfg_u = {k: _pad_rows(v, n_u) for k, v in cfg_u.items()}
            inv_m = np.concatenate(
                [inv_m, np.zeros(n_u - inv_m.shape[0], inv_m.dtype)])
        cfg_m = {k: _pad_rows(v, n_m) for k, v in cfg_m.items()}
        with (obs.span("dse.stream.dispatch.device") if d < len(preps)
              else contextlib.nullcontext()):
            parts.append(jax.device_put(
                (cfg_m, cfg_u, inv_m, inv, coefs,
                 np.array([d < len(preps)])), dev))
    rows = NamedSharding(mesh, P("cfg"))
    return jax.tree.map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (len(xs) * xs[0].shape[0],) + xs[0].shape[1:], rows, list(xs)),
        *parts)


def _eval_chunked(fields, lay, segments, backend: str, shard: bool,
                  chunk_size: int, n: int, n_net: int,
                  per_layer: bool = False):
    """Chunked evaluation of the full grid → dense [n, n_net] outputs
    ([n, n_net, n_layer] in per-layer mode).

    With ``shard=True`` and several host devices, whole chunks round-robin
    across the devices in rounds, one chunk per device: each device runs
    the complete two-level-dedup kernel on its chunk (no duplicated
    mapping work, no collectives) under one program for all of them
    (:func:`_jax_device_kernel`), and asynchronous dispatch keeps the
    devices busy while the host dedups the next round.  In-flight chunks
    are bounded to 2 per device."""
    shape = ((n, n_net, _layer_axis_len(segments)) if per_layer
             else (n, n_net))
    e = np.empty(shape)
    t = np.empty(shape)

    def chunks():
        for ci, start in enumerate(range(0, n, chunk_size)):
            stop = min(start + chunk_size, n)
            fc = {k: _pad_rows(v[start:stop], chunk_size)
                  for k, v in fields.items()}
            yield ci, start, stop, fc

    if backend == "numpy":
        for _, start, stop, fc in chunks():
            ec, tc = _eval_fields(fc, lay, segments, "numpy",
                                  _UNIQUE_BUCKET, _MAPPING_BUCKET,
                                  per_layer=per_layer)
            e[start:stop] = ec[:stop - start]
            t[start:stop] = tc[:stop - start]
        return e, t

    n_dev = host_device_count() if shard else 1
    pending: list = []

    def drain(item):
        start, stop, ec, tc = item
        e[start:stop] = np.asarray(ec)[:stop - start]
        t[start:stop] = np.asarray(tc)[:stop - start]

    with x64():
        for _, start, stop, _, ec, tc in _dispatch_chunks(
                chunks(), lay, segments, per_layer, shard):
            pending.append((start, stop, ec, tc))
            if len(pending) > 2 * n_dev:
                drain(pending.pop(0))
        for item in pending:
            drain(item)
    return e, t


def evaluate_networks(grid: ConfigGrid,
                      networks: Mapping[str, Sequence[Layer]],
                      use_jax: bool | None = None,
                      *,
                      backend: str | None = None,
                      shard: bool = False,
                      chunk_size: int | None = None,
                      per_layer: bool = False,
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Evaluate every network against every grid point.

    Returns ``(energy, latency)`` float64 arrays of shape
    ``[grid.n, len(networks)]``, columns ordered like ``networks``.
    ``backend`` selects the grid kernel — ``"jax"`` (the jitted device
    path) or ``"numpy"`` (reference) — with auto-fallback when jax is
    unavailable; the legacy ``use_jax`` tri-state maps onto it (None
    auto-selects).  ``chunk_size`` evaluates the grid in fixed-shape
    chunks so the heavy (unique-rows × layers) intermediates stay
    bounded — mega-scale spaces would otherwise materialise multi-GB
    tiles.  ``shard=True`` sends the chunks out in rounds over all host
    devices (see :func:`request_host_devices`); with no ``chunk_size``
    that is one round, a chunk of ``ceil(n / n_devices)`` rows on each
    device.

    ``per_layer=True`` keeps the layer axis: the outputs become
    ``[grid.n, len(networks), n_layer]`` where ``n_layer`` is the longest
    network's compute-layer count (shorter networks zero-padded — see
    :func:`network_layer_counts` for the valid lengths).  Summing the
    last axis reproduces the default outputs exactly (the default path
    merely performs that sum earlier, before the coefficients).  This is
    the input of the heterogeneous layer→core co-design stack
    (:func:`repro.core.hetero.co_design`).
    """
    global _LAST_BACKEND
    backend = resolve_backend(backend, use_jax)
    _LAST_BACKEND = backend
    lay, segments = _stack_networks(networks, absorb_pad=not per_layer)
    lay = {k: v[None, :] for k, v in lay.items()}
    fields = grid.fields if isinstance(grid, ConfigGrid) else dict(grid)
    n = int(next(iter(fields.values())).shape[0])

    n_dev = host_device_count() if backend != "numpy" and shard else 1
    if chunk_size is None and n_dev > 1:
        chunk_size = -(-n // n_dev)                  # one round
    if chunk_size is not None and n > chunk_size:
        return _eval_chunked(fields, lay, segments, backend, shard,
                             chunk_size, n, len(networks),
                             per_layer=per_layer)

    return _eval_fields(fields, lay, segments, backend, per_layer=per_layer)


# ---------------------------------------------------------------------------
# Streaming evaluation: chunked sweep with on-device running reductions.
#
# A mega-scale sweep does not need the full [n_cfg, n_net] energy/latency
# matrices — the paper's §III/§IV consumers want per-network minima
# (Tables 1–4), the ≤bound boundary sets (Table 5 / chip design), and a
# handful of near-optimal cells.  ``stream_networks`` evaluates the grid
# chunk by chunk and folds each chunk into a running reduction ON DEVICE
# (min / argmin / top-k via one jitted step that fuses the grid kernel
# with the reducer); only per-chunk boundary candidates cross to the host,
# pruned against the running minimum (monotone ⇒ no false negatives).
# ---------------------------------------------------------------------------


def _metric_of(metric: str, e, t):
    if metric == "edp":
        return e * t
    return e if metric == "energy" else t


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Running reductions of a streamed sweep (flat grid indices)."""

    networks: Tuple[str, ...]
    n_cfg: int
    metric: str
    bound: float
    min_energy: np.ndarray          # [n_net]
    min_latency: np.ndarray         # [n_net]
    min_metric: np.ndarray          # [n_net]
    argmin: np.ndarray              # [n_net] flat grid index of metric min
    topk_idx: np.ndarray            # [k, n_net] flat indices, best first
    topk_metric: np.ndarray         # [k, n_net]
    boundary_idx: Dict[str, np.ndarray]      # per net, sorted by metric
    boundary_energy: Dict[str, np.ndarray]
    boundary_latency: Dict[str, np.ndarray]

    def boundary_metric(self, name: str) -> np.ndarray:
        return _metric_of(self.metric, self.boundary_energy[name],
                          self.boundary_latency[name])


# ---------------------------------------------------------------------------
# Crash-safe resumable streaming.  Both streamed sweeps are a fold over a
# deterministic chunk schedule; everything the fold carries (the reduction
# state tuple plus the boundary candidate triples) is exportable after every
# chunk, so a run killed at chunk i restarts from chunk i and — because the
# (value, flat index) tie-break discipline makes the fold independent of how
# the rows were chunked or where the fold was split — produces results
# bit-identical to an uninterrupted run.  A content hash over (grid columns,
# network layer structs, metric, bound, topk, chunk schedule) is stamped
# into every exported state; resuming against changed inputs is rejected
# instead of silently folding incompatible partial results.
# ---------------------------------------------------------------------------


class StreamStateError(ValueError):
    """Resume state incompatible with the requested stream: wrong stream
    kind, inputs changed since the state was exported, or a truncated /
    corrupt payload."""


class ChunkCorruption(RuntimeError):
    """Non-finite energy/latency detected in a streamed chunk.

    Raised by the per-chunk NaN/inf guard BEFORE the chunk is folded, so
    the running state is never poisoned; carries chunk provenance
    (``chunk``, grid row range ``start:stop``, affected ``networks``)."""

    def __init__(self, msg: str, *, chunk: int, start: int, stop: int,
                 networks: Sequence[str] = ()):
        super().__init__(msg)
        self.chunk = int(chunk)
        self.start = int(start)
        self.stop = int(stop)
        self.networks = tuple(networks)


#: Fault-injection seam: when set, called as ``hook(chunk_index, e, t)`` on
#: every chunk's raw evaluation right before it is folded (both backends,
#: both streamed sweeps) and must return the possibly-modified ``(e, t)``.
#: ``repro.ft.faults.inject_chunk_faults`` installs a deterministic
#: :class:`repro.ft.faults.FaultPlan` here; production code leaves it None.
_CHUNK_HOOK = None


def _apply_chunk_hook(ci, e, t):
    if _CHUNK_HOOK is None:
        return e, t
    return _CHUNK_HOOK(ci, e, t)


def _guard_chunk(ci, start, stop, es, ts, names):
    """NaN/inf guard with chunk provenance.

    ``es``/``ts`` are the [chunk, n_net] aggregates; only the valid rows
    (< stop-start) are checked — padded rows are legitimately +inf."""
    m = stop - start
    esn = np.asarray(es)[:m]
    tsn = np.asarray(ts)[:m]
    bad = ~np.isfinite(esn) | ~np.isfinite(tsn)
    if bad.any():
        nets = [names[j] for j in np.unique(np.nonzero(bad)[1])]
        raise ChunkCorruption(
            f"non-finite energy/latency in streamed chunk {ci} (grid rows "
            f"{start}:{stop}, networks {nets}); the fold state was NOT "
            f"updated with this chunk — retry the chunk or resume from the "
            f"last exported state", chunk=ci, start=start, stop=stop,
            networks=nets)


def stream_input_hash(grid: ConfigGrid | Mapping[str, Any],
                      networks: Mapping[str, Sequence[Layer]],
                      *, kind: str, metric: str, bound: float | None,
                      topk: int, chunk: int) -> str:
    """Content hash of everything that determines a streamed fold.

    Covers the grid columns byte-for-byte, each network's layer struct,
    and the reduction parameters including the effective chunk schedule —
    two streams with equal hashes fold identical chunk sequences, which
    is the precondition for bit-exact resume."""
    import hashlib
    h = hashlib.sha256()
    h.update(repr((kind, metric,
                   None if bound is None else float(bound),
                   int(topk), int(chunk))).encode())
    fields = grid.fields if isinstance(grid, ConfigGrid) else dict(grid)
    for k in sorted(fields):
        h.update(k.encode())
        h.update(np.ascontiguousarray(
            np.asarray(fields[k], dtype=np.float64)).tobytes())
    for nm in networks:
        h.update(nm.encode())
        struct = rs_mapping.layer_struct(
            np, [l for l in networks[nm] if l.kind != "input"])
        for sk in sorted(struct):
            h.update(sk.encode())
            h.update(np.ascontiguousarray(
                np.asarray(struct[sk], dtype=np.float64)).tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class StreamFoldState:
    """Serializable fold state of a streamed sweep after ``next_chunk``
    chunks.

    Emitted via the ``on_chunk=`` callback of :func:`stream_networks` /
    :func:`stream_layer_topk` after every folded chunk and accepted back
    through ``resume_from=``; :meth:`export_state` flattens it to plain
    numpy arrays (device buffers materialised to host) and
    :meth:`save`/:meth:`load` persist that export crash-safely (write to
    a temp file, then atomic rename)."""

    kind: str                       # "networks" | "layer_topk"
    input_hash: str
    next_chunk: int                 # chunks [0, next_chunk) are folded
    n_chunks: int
    chunk_size: int                 # effective chunk row count
    n_cfg: int
    networks: Tuple[str, ...]
    metric: str
    bound: float | None
    topk: int
    state: tuple                    # reduction state arrays (may be device)
    cand: Dict[str, list]           # boundary triples (idx, e, t) per net

    @property
    def complete(self) -> bool:
        return self.next_chunk >= self.n_chunks

    def export_state(self) -> Dict[str, Any]:
        """Flatten to a ``{name: np.ndarray}`` dict (+ a ``meta`` JSON
        string) — npz-serializable, no pickling."""
        import json
        out: Dict[str, Any] = {}
        for i, s in enumerate(self.state):
            out[f"state_{i}"] = np.array(np.asarray(s), copy=True)
        for j, nm in enumerate(self.networks):
            entries = self.cand.get(nm, [])
            if entries:
                out[f"cand{j}_idx"] = np.concatenate(
                    [np.asarray(c[0], np.int64) for c in entries])
                out[f"cand{j}_e"] = np.concatenate(
                    [np.asarray(c[1], np.float64) for c in entries])
                out[f"cand{j}_t"] = np.concatenate(
                    [np.asarray(c[2], np.float64) for c in entries])
            else:
                out[f"cand{j}_idx"] = np.zeros(0, np.int64)
                out[f"cand{j}_e"] = np.zeros(0)
                out[f"cand{j}_t"] = np.zeros(0)
        out["meta"] = json.dumps(dict(
            kind=self.kind, input_hash=self.input_hash,
            next_chunk=int(self.next_chunk), n_chunks=int(self.n_chunks),
            chunk_size=int(self.chunk_size), n_cfg=int(self.n_cfg),
            networks=list(self.networks), metric=self.metric,
            bound=self.bound, topk=int(self.topk),
            n_state=len(self.state)))
        return out

    @classmethod
    def from_export(cls, d: Mapping[str, Any]) -> "StreamFoldState":
        import json
        try:
            meta_raw = d["meta"]
            if not isinstance(meta_raw, str):
                meta_raw = str(np.asarray(meta_raw)[()])
            meta = json.loads(meta_raw)
            state = tuple(np.asarray(d[f"state_{i}"])
                          for i in range(int(meta["n_state"])))
            cand: Dict[str, list] = {}
            for j, nm in enumerate(meta["networks"]):
                idx = np.asarray(d[f"cand{j}_idx"], np.int64)
                cand[nm] = ([(idx, np.asarray(d[f"cand{j}_e"]),
                              np.asarray(d[f"cand{j}_t"]))]
                            if idx.size else [])
        except (KeyError, ValueError, TypeError) as e:
            raise StreamStateError(
                f"truncated or corrupt stream fold-state payload: {e}")
        return cls(kind=meta["kind"], input_hash=meta["input_hash"],
                   next_chunk=int(meta["next_chunk"]),
                   n_chunks=int(meta["n_chunks"]),
                   chunk_size=int(meta["chunk_size"]),
                   n_cfg=int(meta["n_cfg"]),
                   networks=tuple(meta["networks"]), metric=meta["metric"],
                   bound=meta["bound"], topk=int(meta["topk"]),
                   state=state, cand=cand)

    def save(self, path) -> None:
        """Crash-safe persist: write the npz to ``path + '.tmp'``, fsync,
        then atomically rename over ``path``."""
        import os
        d = self.export_state()
        tmp = str(path) + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **d)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, str(path))

    @classmethod
    def load(cls, path) -> "StreamFoldState":
        with np.load(str(path), allow_pickle=False) as z:
            d = {k: z[k] for k in z.files}
        return cls.from_export(d)


def _resume_fold(resume_from, *, kind, ihash, names):
    """Validate a resume payload against the live call and unpack it."""
    fs = (resume_from if isinstance(resume_from, StreamFoldState)
          else StreamFoldState.from_export(resume_from))
    if fs.kind != kind:
        raise StreamStateError(
            f"resume_from carries a {fs.kind!r} fold state but this is a "
            f"{kind!r} stream")
    if fs.input_hash != ihash:
        raise StreamStateError(
            "resume_from was exported from different inputs — the (grid, "
            "networks, metric, bound, topk, chunk schedule) content hash "
            "does not match; refusing to resume because the folded result "
            "would not be bit-identical")
    state = tuple(np.asarray(s) for s in fs.state)
    cand = {nm: list(fs.cand.get(nm, [])) for nm in names}
    return state, cand, int(fs.next_chunk)


def _stream_reduce_body(xp, metric, topk, e, t, base, m_valid, bound,
                        state):
    """Fold one [chunk, n_net] evaluation into the running state.

    Padded chunk rows (row index ≥ m_valid) are masked to +inf so they
    never win a reduction; the returned boundary mask compares against the
    *updated* running minimum, a superset of the final boundary set."""
    min_e, min_t, min_m, argm, top_v, top_i = state
    rows = xp.arange(e.shape[0])
    invalid = (rows >= m_valid)[:, None]
    e_m = xp.where(invalid, np.inf, e)
    t_m = xp.where(invalid, np.inf, t)
    v = _metric_of(metric, e_m, t_m)
    min_e = xp.minimum(min_e, e_m.min(axis=0))
    min_t = xp.minimum(min_t, t_m.min(axis=0))
    cmin = v.min(axis=0)
    better = cmin < min_m
    min_m = xp.where(better, cmin, min_m)
    argm = xp.where(better, base + xp.argmin(v, axis=0), argm)
    idx = xp.broadcast_to((base + rows)[:, None], v.shape)
    all_v = xp.concatenate([top_v, v], axis=0)
    all_i = xp.concatenate([top_i, idx], axis=0)
    # Ties at the top-k boundary break by LOWER flat config index — NOT by
    # fold order (a stable sort on the value alone keeps whichever tied row
    # entered the state first, which depends on the chunk size).  Lexsort
    # on (value, index) makes the streamed top-k chunk-size-invariant; the
    # +inf initial state rows carry index -1, so they still sort ahead of
    # masked padding rows and the sentinel survives under-filled states.
    order = xp.lexsort((all_i, all_v), axis=0)[:topk]
    top_v = xp.take_along_axis(all_v, order, axis=0)
    top_i = xp.take_along_axis(all_i, order, axis=0)
    mask = v <= min_m[None, :] * (1.0 + bound)
    return (min_e, min_t, min_m, argm, top_v, top_i), mask


_jitted_reduce_step = None


def _jax_reduce_step():
    """Jitted running reduction: a chunk's [chunk, n_net] energies are
    folded into the state on device — only the small state, the boundary
    mask, and the masked candidate rows ever leave it."""
    global _jitted_reduce_step
    if _jitted_reduce_step is None:
        import jax.numpy as jnp

        def red(metric, topk, e, t, state, base, m_valid, bound):
            return _stream_reduce_body(jnp, metric, topk, e, t, base,
                                       m_valid, bound, state)

        _jitted_reduce_step = obs.Program("stream_fold", red,
                                          static_argnums=(0, 1))
    return _jitted_reduce_step


def stream_networks(grid: ConfigGrid,
                    networks: Mapping[str, Sequence[Layer]],
                    *,
                    chunk_size: int = 4096,
                    use_jax: bool | None = None,
                    backend: str | None = None,
                    shard: bool = False,
                    bound: float = 0.05,
                    metric: str = "edp",
                    topk: int = 16,
                    resume_from: "StreamFoldState | Mapping | None" = None,
                    on_chunk=None,
                    nan_guard: bool = True,
                    verify=None) -> StreamResult:
    """Chunked streaming sweep with on-device running reductions.

    Never materialises the full ``[n_cfg, n_net]`` matrices: each chunk is
    evaluated (optionally in rounds over the host devices) and folded into
    per-network running minima, top-k cells, and ≤``bound`` boundary
    candidate sets.  Equivalent to reducing :func:`evaluate_networks`'s
    output, at bounded memory.  ``backend`` routes the per-chunk kernel
    like :func:`evaluate_networks` (jax / numpy, auto-fallback).

    Crash-safety: ``on_chunk`` receives a :class:`StreamFoldState` after
    every folded chunk; pass one back as ``resume_from=`` to restart from
    the first unfolded chunk — the resumed result is bit-identical to an
    uninterrupted run, and a state exported from different inputs is
    rejected (:class:`StreamStateError`).  ``nan_guard`` checks every
    chunk for NaN/inf before folding (:class:`ChunkCorruption`).

    ``verify=`` accepts a :class:`repro.ft.verify.StreamVerifier` (duck-
    typed: ``bind`` / ``check_resume`` / ``check_chunk`` / ``check_fold``)
    — fold-invariant checks and sampled numpy-reference shadow recomputes
    run per chunk BEFORE the new state commits, so a finite silent
    corruption raises instead of poisoning the fold.
    """
    global _LAST_BACKEND
    backend = resolve_backend(backend, use_jax)
    _LAST_BACKEND = backend
    use_jax = backend != "numpy"
    names = tuple(networks)
    n_net = len(names)
    lay, segments = _stack_networks(networks)
    lay = {k: v[None, :] for k, v in lay.items()}
    fields = grid.fields if isinstance(grid, ConfigGrid) else dict(grid)
    n = int(next(iter(fields.values())).shape[0])
    chunk = max(1, min(chunk_size, n))
    n_dev = host_device_count() if (shard and use_jax) else 1
    n_chunks = -(-n // chunk)
    ihash = stream_input_hash(fields, networks, kind="networks",
                              metric=metric, bound=bound, topk=topk,
                              chunk=chunk)

    state = (np.full(n_net, np.inf), np.full(n_net, np.inf),
             np.full(n_net, np.inf), np.full(n_net, -1, np.int64),
             np.full((topk, n_net), np.inf),
             np.full((topk, n_net), -1, np.int64))
    cand: Dict[str, list] = {nm: [] for nm in names}
    done = 0
    if resume_from is not None:
        state, cand, done = _resume_fold(resume_from, kind="networks",
                                         ihash=ihash, names=names)
    if verify is not None:
        verify.bind(kind="networks", names=names, metric=metric,
                    topk=topk, bound=bound, backend=backend,
                    ref_eval=lambda fc: _eval_fields(
                        fc, lay, segments, "numpy",
                        _UNIQUE_BUCKET, _MAPPING_BUCKET))
        if resume_from is not None:
            verify.check_resume(state, cand)

    def emit(ci):
        if on_chunk is None:
            return
        on_chunk(StreamFoldState(
            kind="networks", input_hash=ihash, next_chunk=ci + 1,
            n_chunks=n_chunks, chunk_size=chunk, n_cfg=n, networks=names,
            metric=metric, bound=bound, topk=topk, state=state,
            cand={nm: list(v) for nm, v in cand.items()}))

    def collect(mask, e, t, start):
        rows_i, cols_i = np.nonzero(mask)
        for j in range(n_net):
            sel = rows_i[cols_i == j]
            if sel.size:
                cand[names[j]].append((start + sel, e[sel, j], t[sel, j]))

    def chunks():
        for ci, start in enumerate(range(0, n, chunk)):
            if ci < done:
                continue
            stop = min(start + chunk, n)
            fc = {k: _pad_rows(v[start:stop], chunk)
                  for k, v in fields.items()}
            yield ci, start, stop, fc

    if not use_jax:
        for ci, start, stop, fc in chunks():
            cfg_m, cfg_u, inv_m, inv, coefs = _prepare_fields(
                fc, _UNIQUE_BUCKET, _MAPPING_BUCKET)
            e, t = _np_grid_kernel(segments, cfg_m, cfg_u, lay, inv_m,
                                   inv, coefs)
            e, t = _apply_chunk_hook(ci, e, t)
            if nan_guard:
                _guard_chunk(ci, start, stop, e, t, names)
            if verify is not None:      # raises BEFORE the fold commits
                verify.check_chunk(ci, start, stop, fc, e, t)
            new_state, mask = _stream_reduce_body(
                np, metric, topk, e, t, start, stop - start, bound, state)
            if verify is not None:
                verify.check_fold(ci, start, stop, state, new_state,
                                  es=e, ts=t, mask=mask)
            state = new_state
            collect(mask, e, t, start)
            emit(ci)
    else:
        # Chunk kernels go out in rounds over the devices (async
        # dispatch); the cheap stateful reduction runs in chunk order on
        # device 0.
        import jax
        devs = jax.devices()
        pending: list = []

        with x64():
            def reduce_one(item):
                nonlocal state
                ci, start, stop, e_d, t_d, fc = item
                if n_dev > 1:
                    e_d = jax.device_put(e_d, devs[0])
                    t_d = jax.device_put(t_d, devs[0])
                e_d, t_d = _apply_chunk_hook(ci, e_d, t_d)
                if nan_guard:
                    _guard_chunk(ci, start, stop, e_d, t_d, names)
                if verify is not None:  # raises BEFORE the fold commits
                    verify.check_chunk(ci, start, stop, fc, e_d, t_d)
                new_state, mask = _jax_reduce_step()(
                    metric, topk, e_d, t_d, state, np.int64(start),
                    np.int64(stop - start), float(bound))
                if verify is not None:
                    verify.check_fold(ci, start, stop, state, new_state,
                                      es=np.asarray(e_d),
                                      ts=np.asarray(t_d),
                                      mask=np.asarray(mask))
                state = new_state
                # only the boundary mask and the hit rows cross to the
                # host — the [chunk, n_net] matrices stay on device
                rows_i, cols_i = np.nonzero(np.asarray(mask))
                if rows_i.size:
                    urows = np.unique(rows_i)
                    e_h = np.asarray(e_d[urows, :])
                    t_h = np.asarray(t_d[urows, :])
                    pos = np.searchsorted(urows, rows_i)
                    for j in range(n_net):
                        m = cols_i == j
                        if m.any():
                            cand[names[j]].append(
                                (start + rows_i[m], e_h[pos[m], j],
                                 t_h[pos[m], j]))
                emit(ci)

            for ci, start, stop, fc, e_d, t_d in _dispatch_chunks(
                    chunks(), lay, segments, False, n_dev > 1):
                pending.append((ci, start, stop, e_d, t_d,
                                fc if verify is not None else None))
                if len(pending) > 2 * n_dev:
                    reduce_one(pending.pop(0))
            for item in pending:
                reduce_one(item)

    min_e, min_t, min_m, argm, top_v, top_i = (
        np.asarray(s) for s in state)

    b_idx, b_e, b_t = {}, {}, {}
    for j, nm in enumerate(names):
        if cand[nm]:
            idx = np.concatenate([c[0] for c in cand[nm]])
            ee = np.concatenate([c[1] for c in cand[nm]])
            tt = np.concatenate([c[2] for c in cand[nm]])
        else:                                          # pragma: no cover
            idx, ee, tt = (np.zeros(0, np.int64),) + (np.zeros(0),) * 2
        v = _metric_of(metric, ee, tt)
        keep = v <= min_m[j] * (1.0 + bound)   # prune to the final min
        idx, ee, tt, v = idx[keep], ee[keep], tt[keep], v[keep]
        order = np.argsort(v, kind="stable")
        b_idx[nm], b_e[nm], b_t[nm] = idx[order], ee[order], tt[order]

    return StreamResult(
        networks=names, n_cfg=n, metric=metric, bound=bound,
        min_energy=min_e, min_latency=min_t, min_metric=min_m,
        argmin=argm, topk_idx=top_i, topk_metric=top_v,
        boundary_idx=b_idx, boundary_energy=b_e, boundary_latency=b_t)


# ---------------------------------------------------------------------------
# Streaming per-layer reduction: the per-layer tensors of a mega-scale sweep
# are far too large to keep ([n_cfg, n_net, n_layer] at 49k points × 18 nets
# × 256 layers ≈ 1.8 GB each), but the co-design consumers only ever need
# the per-layer rows of the few near-optimal configs per network plus the
# ≤bound boundary candidate sets.  This variant evaluates chunk by chunk in
# per-layer mode and folds each chunk ON DEVICE into (a) a running
# per-network top-k that KEEPS the [n_layer] energy/latency rows of the
# current top-k configs only, (b) running per-network minima of energy /
# latency / EDP / the selected metric, (c) running per-(network, layer)
# metric minima, and (d) — with ``bound=`` — the ≤bound threshold mask
# whose hits become the per-network boundary sets
# ``repro.core.hetero.codesign_problems_streaming`` builds its candidate
# pool from.  One mega-grid pass therefore emits exactly the co-design
# candidate pool without ever materialising [n_cfg, n_net, n_layer].
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerTopK:
    """Running reductions of a streamed per-layer sweep.

    The boundary-set fields are ``None`` unless the sweep ran with a
    ``bound=``; everything else is always populated.  ``topk_idx`` ranks
    by (metric, flat index) — ties break toward the LOWER grid index, so
    the result is invariant to the chunk size."""

    networks: Tuple[str, ...]
    n_cfg: int
    metric: str
    layer_counts: np.ndarray        # [n_net] valid lengths of the layer axis
    topk_idx: np.ndarray            # [k, n_net] flat grid indices, best first
    topk_metric: np.ndarray         # [k, n_net]
    layer_energy: np.ndarray        # [k, n_net, n_layer]
    layer_latency: np.ndarray       # [k, n_net, n_layer]
    # -- aggregate running minima (refs for co-design chip scoring) --------
    min_energy: np.ndarray | None = None      # [n_net]
    min_latency: np.ndarray | None = None     # [n_net]
    min_edp: np.ndarray | None = None         # [n_net]
    min_metric: np.ndarray | None = None      # [n_net]
    argmin: np.ndarray | None = None          # [n_net] flat index of min
    # -- per-(network, layer) running minima -------------------------------
    layer_min_metric: np.ndarray | None = None   # [n_net, n_layer]
    layer_argmin: np.ndarray | None = None       # [n_net, n_layer]
    # -- ≤bound boundary sets (None when bound was not requested) ----------
    bound: float | None = None
    boundary_idx: Dict[str, np.ndarray] | None = None   # sorted by metric
    boundary_energy: Dict[str, np.ndarray] | None = None
    boundary_latency: Dict[str, np.ndarray] | None = None

    def boundary_metric(self, name: str) -> np.ndarray:
        if self.boundary_energy is None:
            raise ValueError("this stream carries no boundary sets — "
                             "run stream_layer_topk with bound=")
        return _metric_of(self.metric, self.boundary_energy[name],
                          self.boundary_latency[name])


def _layer_reduce_body(xp, metric, topk, e, t, base, m_valid, bound,
                       lay_valid, state):
    """Fold one [chunk, n_net, n_layer] per-layer evaluation into the
    running state; returns ``(state, mask, es, ts)`` where ``mask`` is
    the ≤bound threshold mask against the *updated* running minimum (a
    superset of the final boundary set — pruned at the end) and
    ``es``/``ts`` are the layer-summed [chunk, n_net] aggregates the
    boundary collection reads.  Padded chunk rows (index ≥ ``m_valid``)
    are masked to +inf so they never win a reduction; ``lay_valid`` masks
    each network's zero-padded layer tail out of the per-layer minima."""
    (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m, argm,
     lmin, larg) = state
    rows = xp.arange(e.shape[0])
    invalid = (rows >= m_valid)[:, None]
    es = xp.where(invalid, np.inf, e.sum(-1))
    ts = xp.where(invalid, np.inf, t.sum(-1))
    v = _metric_of(metric, es, ts)
    min_e = xp.minimum(min_e, es.min(axis=0))
    min_t = xp.minimum(min_t, ts.min(axis=0))
    min_edp = xp.minimum(min_edp, xp.where(invalid, np.inf,
                                           es * ts).min(axis=0))
    cmin = v.min(axis=0)
    better = cmin < min_m
    min_m = xp.where(better, cmin, min_m)
    argm = xp.where(better, base + xp.argmin(v, axis=0), argm)

    # per-(network, layer) metric minima; strict < keeps the earlier
    # (lower-index) config on ties, so this too is chunk-size-invariant
    vl = _metric_of(metric, e, t)
    vl = xp.where(invalid[:, :, None] | ~lay_valid[None, :, :], np.inf, vl)
    clmin = vl.min(axis=0)
    lbetter = clmin < lmin
    lmin = xp.where(lbetter, clmin, lmin)
    larg = xp.where(lbetter, base + xp.argmin(vl, axis=0), larg)

    # top-k fold with the per-layer rows gathered alongside; the same
    # (value, index) lexsort tie-break as _stream_reduce_body
    idx = xp.broadcast_to((base + rows)[:, None], v.shape)
    all_v = xp.concatenate([top_v, v], axis=0)
    all_i = xp.concatenate([top_i, idx], axis=0)
    order = xp.lexsort((all_i, all_v), axis=0)[:topk]
    top_v = xp.take_along_axis(all_v, order, axis=0)
    top_i = xp.take_along_axis(all_i, order, axis=0)
    all_e = xp.concatenate([top_e, e], axis=0)
    all_t = xp.concatenate([top_t, t], axis=0)
    top_e = xp.take_along_axis(all_e, order[:, :, None], axis=0)
    top_t = xp.take_along_axis(all_t, order[:, :, None], axis=0)

    mask = v <= min_m[None, :] * (1.0 + bound)
    state = (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m,
             argm, lmin, larg)
    return state, mask, es, ts


_jitted_layer_reduce = None


def _jax_layer_reduce_step():
    """Jitted streaming per-layer reduction: a chunk's
    [chunk, n_net, n_layer] tensors fold into the state on device — only
    the small state, the boundary mask, and the [chunk, n_net] aggregates
    ever cross to the host."""
    global _jitted_layer_reduce
    if _jitted_layer_reduce is None:
        import jax.numpy as jnp

        def red(metric, topk, e, t, state, base, m_valid, bound,
                lay_valid):
            return _layer_reduce_body(jnp, metric, topk, e, t, base,
                                      m_valid, bound, lay_valid, state)

        _jitted_layer_reduce = obs.Program("layer_fold", red,
                                           static_argnums=(0, 1))
    return _jitted_layer_reduce


_jitted_layer_fold_devices: Dict[int, Any] = {}


def _jax_layer_fold_devices():
    """The per-layer fold on every device of the mesh at once: device
    ``d`` folds its chunk of a round (:func:`_jax_device_kernel`) into its
    own state, held at index ``d`` of every state leaf's leading axis, in
    the body of :func:`_jax_layer_reduce_step`.  ``base`` and ``m_valid``
    are [n_dev]; a device with no chunk in the round gets ``m_valid`` 0:
    it skips the fold, keeps its state and returns an empty mask."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _cfg_mesh()
    n_dev = mesh.devices.size
    if n_dev not in _jitted_layer_fold_devices:
        rows, rep = P("cfg"), P()

        def red(metric, topk, e, t, state, base, m_valid, bound,
                lay_valid):
            def local(e_, t_, state_, base_, m_valid_, bound_, lay_valid_):
                state0 = tuple(s[0] for s in state_)

                def run():
                    return _layer_reduce_body(
                        jnp, metric, topk, e_, t_, base_[0], m_valid_[0],
                        bound_, lay_valid_, state0)

                def skip():
                    es = jnp.full(e_.shape[:2], np.inf, e_.dtype)
                    return state0, jnp.zeros(es.shape, bool), es, es

                new, mask, es, ts = jax.lax.cond(m_valid_[0] > 0, run, skip)
                return tuple(s[None] for s in new), mask, es, ts

            leaves = tuple(rows for _ in state)
            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(rows, rows, leaves, rows, rows, rep, rep),
                out_specs=(leaves, rows, rows, rows),
                check_vma=False)(e, t, state, base, m_valid, bound,
                                 lay_valid)

        _jitted_layer_fold_devices[n_dev] = obs.Program(
            "layer_fold_devices", red, static_argnums=(0, 1))
    return _jitted_layer_fold_devices[n_dev]


def _layer_fold_init(k: int, n_net: int, n_layer: int) -> tuple:
    """The per-layer fold's state before any chunk."""
    return (np.full((k, n_net), np.inf),              # top_v
            np.full((k, n_net), -1, np.int64),        # top_i
            np.zeros((k, n_net, n_layer)),            # top_e
            np.zeros((k, n_net, n_layer)),            # top_t
            np.full(n_net, np.inf),                   # min_energy
            np.full(n_net, np.inf),                   # min_latency
            np.full(n_net, np.inf),                   # min_edp
            np.full(n_net, np.inf),                   # min_metric
            np.full(n_net, -1, np.int64),             # argmin
            np.full((n_net, n_layer), np.inf),        # layer_min_metric
            np.full((n_net, n_layer), -1, np.int64))  # layer_argmin


def _merge_layer_states(states: Sequence[tuple]) -> tuple:
    """Fold states over disjoint sets of grid rows (host arrays, flat
    indices global) → the state one fold over all of them ends in, bit for
    bit: the top-k by (metric, flat index), and each minimum with the
    lower flat index on a tie, as the fold keeps the earlier row.  Pure
    selection: no value is recomputed."""
    (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m, argm,
     lmin, larg) = states[0]
    k = top_v.shape[0]
    for (v2, i2, e2, t2, e_min2, t_min2, edp2, m2, arg2, lm2,
         la2) in states[1:]:
        all_v = np.concatenate([top_v, v2], axis=0)
        all_i = np.concatenate([top_i, i2], axis=0)
        order = np.lexsort((all_i, all_v), axis=0)[:k]
        top_v = np.take_along_axis(all_v, order, axis=0)
        top_i = np.take_along_axis(all_i, order, axis=0)
        top_e = np.take_along_axis(np.concatenate([top_e, e2], axis=0),
                                   order[:, :, None], axis=0)
        top_t = np.take_along_axis(np.concatenate([top_t, t2], axis=0),
                                   order[:, :, None], axis=0)
        min_e = np.minimum(min_e, e_min2)
        min_t = np.minimum(min_t, t_min2)
        min_edp = np.minimum(min_edp, edp2)
        better = (m2 < min_m) | ((m2 == min_m) & (arg2 < argm))
        min_m = np.where(better, m2, min_m)
        argm = np.where(better, arg2, argm)
        lbetter = (lm2 < lmin) | ((lm2 == lmin) & (la2 < larg))
        lmin = np.where(lbetter, lm2, lmin)
        larg = np.where(lbetter, la2, larg)
    return (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m, argm,
            lmin, larg)


def _layer_topk_rounds(chunks, state, lay, segments, metric, k, bound,
                       lay_valid, chunk, names, nan_guard, verify, collect,
                       emit):
    """The jax path of :func:`stream_layer_topk` over every device of the
    mesh.  Chunks go out in rounds, one chunk per device, and each round
    is one launch of the grid kernel and one of the fold for all devices
    (:func:`_jax_device_kernel`, :func:`_jax_layer_fold_devices`).  Each
    device folds its chunks into its own state, which stays on it; the
    states merge on the host once, at the end (``dse.stream.merge``,
    :func:`_merge_layer_states`), into the one-device fold's state bit for
    bit.  ``state`` (initial or resumed) starts on device 0.  Guard,
    verifier and boundary collection run per chunk as on one device; the
    round commits when all of its chunks pass.  ``emit(ci, state)``, when
    given, gets the merged state after each round.  Returns the merged
    state as host arrays."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _cfg_mesh()
    n_dev = mesh.devices.size
    rows = NamedSharding(mesh, P("cfg"))
    kern = _jax_device_kernel(per_layer=True)
    fold = _jax_layer_fold_devices()
    b = 0.0 if bound is None else float(bound)
    init = _layer_fold_init(k, *lay_valid.shape)
    keep = verify is not None
    per_dev = np.zeros(n_dev, np.int64)
    pending: list = []

    def merged(dstate):
        host = obs.fetch("dse.stream.wait", *dstate)
        with obs.span("dse.stream.merge"):
            return _merge_layer_states(
                [tuple(s[d] for s in host) for d in range(n_dev)])

    def finish(item):
        nonlocal committed
        rnd, prev, new, mask, es, ts, e_g, t_g = item
        if nan_guard or bound is not None or keep:   # the host reads them
            es, ts, mask = obs.fetch("dse.stream.wait", es, ts, mask)
        with obs.span("dse.stream.collect"):
            for d, (ci, start, stop, fc) in enumerate(rnd):
                r = slice(d * chunk, (d + 1) * chunk)
                if nan_guard:       # raises BEFORE the round commits
                    _guard_chunk(ci, start, stop, es[r], ts[r], names)
                if keep:
                    verify.check_chunk(ci, start, stop, fc,
                                       np.asarray(e_g)[r],
                                       np.asarray(t_g)[r])
                    verify.check_fold(
                        ci, start, stop,
                        tuple(np.asarray(s)[d] for s in prev),
                        tuple(np.asarray(s)[d] for s in new),
                        es=es[r], ts=ts[r], mask=mask[r])
            committed = new
            if bound is not None:
                for d, (ci, start, stop, fc) in enumerate(rnd):
                    r = slice(d * chunk, (d + 1) * chunk)
                    collect(mask[r], es[r], ts[r], start)
        if emit is not None:
            emit(rnd[-1][0], merged(committed))

    with x64():
        lay_d = jax.device_put(lay, NamedSharding(mesh, P()))
        dstate = committed = jax.device_put(
            tuple(np.stack([s] + [i] * (n_dev - 1))
                  for s, i in zip(state, init)), rows)
        for rnd in _rounds(chunks, n_dev):
            args = _round_inputs([_prepare_chunk(fc) for *_, fc in rnd],
                                 mesh)
            pad = [0] * (n_dev - len(rnd))
            base = np.array([s for _, s, _, _ in rnd] + pad, np.int64)
            valid = np.array([p - s for _, s, p, _ in rnd] + pad, np.int64)
            if _CHUNK_HOOK is not None:     # faults fire with every earlier
                for item in pending:        # round committed, as on one
                    finish(item)            # device
                pending.clear()
            with obs.span("dse.stream.dispatch"):
                e_g, t_g = kern(segments, args[0], args[1], lay_d,
                                *args[2:])
                if _CHUNK_HOOK is not None:
                    e_g, t_g = _hook_round(rnd, e_g, t_g, chunk, rows)
                prev = dstate
                dstate, mask, es, ts = fold(
                    metric, k, e_g, t_g, dstate, jax.device_put(base, rows),
                    jax.device_put(valid, rows), b, lay_valid)
            per_dev[:len(rnd)] += 1
            pending.append(
                ([(ci, s, p, fc if keep else None) for ci, s, p, fc in rnd],
                 prev if keep else None, dstate, mask, es, ts,
                 e_g if keep else None, t_g if keep else None))
            if len(pending) > 2:
                finish(pending.pop(0))
        for item in pending:
            finish(item)
        state = merged(committed)
    for n_chunks in per_dev:
        obs.count("dse.stream.chunks_per_device", int(n_chunks))
    return state


def _hook_round(rnd, e_g, t_g, chunk, rows):
    """The chunk hook (fault injection) on each chunk of a round."""
    import jax
    e_h, t_h = np.array(e_g), np.array(t_g)
    for d, (ci, *_) in enumerate(rnd):
        r = slice(d * chunk, (d + 1) * chunk)
        e_c, t_c = _apply_chunk_hook(ci, e_h[r], t_h[r])
        e_h[r], t_h[r] = np.asarray(e_c), np.asarray(t_c)
    return jax.device_put(e_h, rows), jax.device_put(t_h, rows)


@obs.traced("dse.stream")
def stream_layer_topk(grid: ConfigGrid,
                      networks: Mapping[str, Sequence[Layer]],
                      *,
                      topk: int = 8,
                      chunk_size: int = 4096,
                      use_jax: bool | None = None,
                      backend: str | None = None,
                      shard: bool = False,
                      metric: str = "edp",
                      bound: float | None = None,
                      resume_from: "StreamFoldState | Mapping | None" = None,
                      on_chunk=None,
                      nan_guard: bool = True,
                      verify=None) -> LayerTopK:
    """Streamed per-layer sweep: one pass, every co-design reduction.

    Equivalent to ``evaluate_networks(..., per_layer=True)`` followed by
    per-network reductions on the layer-summed metric — at bounded
    memory: only one chunk's ``[chunk, n_net, n_layer]`` tensors are ever
    alive (the jax path folds each chunk on device through one jitted
    step), and the state carries ``k`` per-layer rows per network plus
    the running aggregate / per-(network, layer) minima.  With
    ``bound=``, the ≤bound threshold mask is maintained alongside and the
    result carries the per-network boundary candidate sets (flat indices
    + aggregate energy/latency, metric-sorted) — exactly the candidate
    pool inputs :func:`repro.core.hetero.codesign_problems_streaming`
    consumes, so a 49,000-point mega grid feeds the co-design search
    without materialising ``[n_cfg, n_net, n_layer]``.  Ties rank by
    lower flat grid index everywhere (chunk-size-invariant).

    Crash-safety: ``on_chunk`` receives a :class:`StreamFoldState` after
    every folded chunk; pass one back as ``resume_from=`` to restart from
    the first unfolded chunk — the resumed result is bit-identical to an
    uninterrupted run, and a state exported from different inputs is
    rejected (:class:`StreamStateError`).  ``nan_guard`` checks every
    chunk's layer-summed aggregates for NaN/inf before the fold commits
    (:class:`ChunkCorruption` with chunk provenance); ``verify=`` takes a
    :class:`repro.ft.verify.StreamVerifier` for the finite-corruption
    rungs — per-chunk fold-invariant checks and sampled numpy-reference
    shadow recomputes, both raising BEFORE the poisoned state commits."""
    global _LAST_BACKEND
    backend = resolve_backend(backend, use_jax)
    _LAST_BACKEND = backend
    names = tuple(networks)
    n_net = len(names)
    with obs.span("dse.stream.prep"):
        lay, segments = _stack_networks(networks, absorb_pad=False)
        lay = {k: v[None, :] for k, v in lay.items()}
        n_layer = _layer_axis_len(segments)
        fields = grid.fields if isinstance(grid, ConfigGrid) else dict(grid)
        n = int(next(iter(fields.values())).shape[0])
        chunk = max(1, min(chunk_size, n))
        lay_counts = network_layer_counts(networks)
        lay_valid = np.arange(n_layer)[None, :] < lay_counts[:, None]

        k = int(topk)
        state = _layer_fold_init(k, n_net, n_layer)
        b = 0.0 if bound is None else float(bound)
        cand: Dict[str, list] = {nm: [] for nm in names}
        n_chunks = -(-n // chunk)
        ihash = stream_input_hash(fields, networks, kind="layer_topk",
                                  metric=metric, bound=bound, topk=k,
                                  chunk=chunk)
    done = 0
    if resume_from is not None:
        state, cand, done = _resume_fold(resume_from, kind="layer_topk",
                                         ihash=ihash, names=names)
    if verify is not None:
        verify.bind(kind="layer_topk", names=names, metric=metric,
                    topk=k, bound=bound, backend=backend,
                    ref_eval=lambda fc: _eval_fields(
                        fc, lay, segments, "numpy",
                        _UNIQUE_BUCKET, _MAPPING_BUCKET, per_layer=True))
        if resume_from is not None:
            verify.check_resume(state, cand)

    def emit(ci, state):
        if on_chunk is None:
            return
        on_chunk(StreamFoldState(
            kind="layer_topk", input_hash=ihash, next_chunk=ci + 1,
            n_chunks=n_chunks, chunk_size=chunk, n_cfg=n, networks=names,
            metric=metric, bound=bound, topk=k, state=state,
            cand={nm: list(v) for nm, v in cand.items()}))

    def collect(mask, es, ts, start):
        if bound is None:
            return
        rows_i, cols_i = np.nonzero(np.asarray(mask))
        if not rows_i.size:
            return
        es, ts = np.asarray(es), np.asarray(ts)
        for j in range(n_net):
            sel = rows_i[cols_i == j]
            if sel.size:
                cand[names[j]].append((start + sel, es[sel, j],
                                       ts[sel, j]))

    def chunks():
        for ci, start in enumerate(range(0, n, chunk)):
            if ci < done:
                continue
            stop = min(start + chunk, n)
            with obs.span("dse.stream.prep"):
                fc = {k_: _pad_rows(v[start:stop], chunk)
                      for k_, v in fields.items()}
            yield ci, start, stop, fc

    if backend == "numpy":
        for ci, start, stop, fc in chunks():
            ec, tc = _eval_fields(fc, lay, segments, "numpy",
                                  _UNIQUE_BUCKET, _MAPPING_BUCKET,
                                  per_layer=True)
            ec, tc = _apply_chunk_hook(ci, ec, tc)
            if nan_guard:     # raises BEFORE the fold commits
                _guard_chunk(ci, start, stop, ec.sum(axis=2),
                             tc.sum(axis=2), names)
            if verify is not None:
                verify.check_chunk(ci, start, stop, fc, ec, tc)
            new_state, mask, es, ts = _layer_reduce_body(
                np, metric, k, ec, tc, start, stop - start, b,
                lay_valid, state)
            if verify is not None:
                verify.check_fold(ci, start, stop, state, new_state,
                                  es=es, ts=ts, mask=mask)
            state = new_state
            collect(mask, es, ts, start)
            emit(ci, state)
    elif shard and host_device_count() > 1:
        state = _layer_topk_rounds(
            chunks(), state, lay, segments, metric, k, bound, lay_valid,
            chunk, names, nan_guard, verify, collect,
            emit if on_chunk is not None else None)
    else:
        # Each chunk's kernel and fold are enqueued together, and chunk i
        # is fetched only once chunk i+1's are queued behind it, so the
        # device keeps working while the host collects and preps.  Chunks
        # commit in order: ``state`` is the committed fold state, and a
        # chunk that fails its guard or verifier raises before it commits
        # (the fold enqueued after it is dropped).
        keep = verify is not None
        pending: list = []
        with x64():
            def finish(item, ahead):
                nonlocal state
                ci, start, stop, fc, new_state, mask, es, ts, e_d, t_d = item
                if nan_guard or bound is not None:   # the host reads them
                    es, ts, mask = obs.fetch("dse.stream.wait", es, ts,
                                             mask)
                    if ahead:
                        obs.count("dse.stream.fetch_ahead", 1)
                with obs.span("dse.stream.collect"):
                    if nan_guard:     # raises BEFORE the fold commits
                        _guard_chunk(ci, start, stop, es, ts, names)
                    if keep:
                        verify.check_chunk(ci, start, stop, fc,
                                           np.asarray(e_d),
                                           np.asarray(t_d))
                        verify.check_fold(ci, start, stop, state,
                                          new_state, es=np.asarray(es),
                                          ts=np.asarray(ts),
                                          mask=np.asarray(mask))
                    state = new_state
                    collect(mask, es, ts, start)
                    emit(ci, state)

            dstate = state
            for ci, start, stop, fc in chunks():
                e_d, t_d = _dispatch_chunk(fc, lay, segments, per_layer=True)
                if _CHUNK_HOOK is not None:     # faults fire with every
                    for item in pending:        # earlier chunk committed
                        finish(item, False)
                    pending.clear()
                with obs.span("dse.stream.dispatch"):
                    e_d, t_d = _apply_chunk_hook(ci, e_d, t_d)
                    dstate, mask, es, ts = _jax_layer_reduce_step()(
                        metric, k, e_d, t_d, dstate, np.int64(start),
                        np.int64(stop - start), float(b), lay_valid)
                pending.append((ci, start, stop, fc if keep else None,
                                dstate, mask, es, ts,
                                e_d if keep else None,
                                t_d if keep else None))
                del e_d, t_d
                if len(pending) > 1:
                    finish(pending.pop(0), True)
            for item in pending:
                finish(item, False)
        state = obs.fetch("dse.stream.wait", *state)

    (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m, argm,
     lmin, larg) = (np.asarray(s) for s in state)

    with obs.span("dse.stream.collect"):
        b_idx = b_e = b_t = None
        if bound is not None:
            b_idx, b_e, b_t = {}, {}, {}
            for j, nm in enumerate(names):
                if cand[nm]:
                    idx = np.concatenate([c[0] for c in cand[nm]])
                    ee = np.concatenate([c[1] for c in cand[nm]])
                    tt = np.concatenate([c[2] for c in cand[nm]])
                else:                                  # pragma: no cover
                    idx, ee, tt = ((np.zeros(0, np.int64),)
                                   + (np.zeros(0),) * 2)
                v = _metric_of(metric, ee, tt)
                keep = v <= min_m[j] * (1.0 + b)   # prune to the final min
                idx, ee, tt, v = idx[keep], ee[keep], tt[keep], v[keep]
                order = np.lexsort((idx, v))   # metric, then lower index
                b_idx[nm], b_e[nm], b_t[nm] = (idx[order], ee[order],
                                               tt[order])

    return LayerTopK(
        networks=names, n_cfg=n, metric=metric,
        layer_counts=lay_counts,
        topk_idx=top_i, topk_metric=top_v,
        layer_energy=top_e, layer_latency=top_t,
        min_energy=min_e, min_latency=min_t, min_edp=min_edp,
        min_metric=min_m, argmin=argm,
        layer_min_metric=lmin, layer_argmin=larg,
        bound=bound, boundary_idx=b_idx,
        boundary_energy=b_e, boundary_latency=b_t)


def _shift_idx(idx: np.ndarray, offset: int) -> np.ndarray:
    """Shift flat grid indices by ``offset``, preserving -1 sentinels."""
    idx = np.asarray(idx)
    return np.where(idx >= 0, idx + offset, idx)


def merge_layer_topk(a: LayerTopK, b: LayerTopK) -> LayerTopK:
    """Fold two completed streamed sweeps over consecutive grid-row ranges.

    ``a`` covers rows ``[0, a.n_cfg)`` of some grid and ``b`` the APPENDED
    rows ``[a.n_cfg, a.n_cfg + b.n_cfg)`` streamed as a standalone grid
    (its flat indices are local, so they are shifted by ``a.n_cfg`` here).
    Because every streamed reduction tie-breaks by (value, flat index),
    the fold is split-point-invariant: the merge is BIT-identical to
    re-streaming the concatenated grid from scratch — this is the
    incremental-grid-delta entry point
    :meth:`repro.serving.dse_service.DSEService.extend_grid` folds
    appended config rows through.

    Boundary-set exactness: each part's sets were pruned against its own
    running minimum; the merged threshold ``min(a_min, b_min)·(1+bound)``
    is no looser than either part's, so every merged-boundary row was
    already retained by its part — nothing pruned early is ever needed.
    """
    if a.networks != b.networks:
        raise ValueError(
            f"cannot merge streams over different network sets "
            f"{a.networks} vs {b.networks}")
    if a.metric != b.metric or a.bound != b.bound:
        raise ValueError(
            f"cannot merge streams with different reduction parameters: "
            f"(metric, bound) = ({a.metric!r}, {a.bound}) vs "
            f"({b.metric!r}, {b.bound})")
    if a.topk_idx.shape != b.topk_idx.shape:
        raise ValueError(
            f"cannot merge streams with different top-k sizes "
            f"{a.topk_idx.shape[0]} vs {b.topk_idx.shape[0]}")
    off = int(a.n_cfg)
    # b's rows follow a's, so the lower index on a tie is a's, as a fold
    # over the concatenated grid keeps the earlier row
    (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m, argm,
     lmin, larg) = _merge_layer_states([
        (a.topk_metric, a.topk_idx, a.layer_energy, a.layer_latency,
         a.min_energy, a.min_latency, a.min_edp, a.min_metric, a.argmin,
         a.layer_min_metric, a.layer_argmin),
        (b.topk_metric, _shift_idx(b.topk_idx, off), b.layer_energy,
         b.layer_latency, b.min_energy, b.min_latency, b.min_edp,
         b.min_metric, _shift_idx(b.argmin, off), b.layer_min_metric,
         _shift_idx(b.layer_argmin, off))])

    b_idx = b_e = b_t = None
    if a.bound is not None:
        bd = float(a.bound)
        b_idx, b_e, b_t = {}, {}, {}
        for j, nm in enumerate(a.networks):
            idx = np.concatenate([a.boundary_idx[nm],
                                  b.boundary_idx[nm] + off])
            ee = np.concatenate([a.boundary_energy[nm],
                                 b.boundary_energy[nm]])
            tt = np.concatenate([a.boundary_latency[nm],
                                 b.boundary_latency[nm]])
            v = _metric_of(a.metric, ee, tt)
            keep = v <= min_m[j] * (1.0 + bd)   # prune to the merged min
            idx, ee, tt, v = idx[keep], ee[keep], tt[keep], v[keep]
            order = np.lexsort((idx, v))        # metric, then lower index
            b_idx[nm], b_e[nm], b_t[nm] = idx[order], ee[order], tt[order]

    return LayerTopK(
        networks=a.networks, n_cfg=off + int(b.n_cfg), metric=a.metric,
        layer_counts=a.layer_counts,
        topk_idx=top_i, topk_metric=top_v,
        layer_energy=top_e, layer_latency=top_t,
        min_energy=min_e, min_latency=min_t, min_edp=min_edp,
        min_metric=min_m, argmin=argm,
        layer_min_metric=lmin, layer_argmin=larg,
        bound=a.bound, boundary_idx=b_idx,
        boundary_energy=b_e, boundary_latency=b_t)


def simulate_grid(configs: Sequence[AcceleratorConfig] | ConfigGrid,
                  layers: Sequence[Layer], use_jax: bool = False,
                  backend: str | None = None):
    """Vectorised sweep: returns (energy, latency) arrays of shape [n_cfg].

    ``use_jax=True`` evaluates the whole design space inside the batched,
    module-level jit-cached engine under 64-bit mode (counts exceed
    float32's integer range); repeated same-shape sweeps reuse the compile.
    ``backend`` overrides the kernel choice (jax / numpy).
    """
    grid = (configs if isinstance(configs, ConfigGrid)
            else ConfigGrid.from_configs(configs))
    e, t = evaluate_networks(grid, {"net": layers}, use_jax=use_jax,
                             backend=backend)
    return e[:, 0], t[:, 0]

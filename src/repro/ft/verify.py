"""Silent-data-corruption defense for the streamed DSE engine.

The chunk guard (:func:`repro.core.energymodel._guard_chunk`) only trips
on *loud* corruption — NaN/inf.  A bit-flip or kernel miscompile that
yields a plausible **finite** wrong value sails through it, poisons the
streamed fold, gets faithfully checksummed by the durable store, and is
then served as a cached "exact" answer forever.  This module is the
defense-in-depth ladder against exactly that:

* :class:`StreamVerifier` — threaded through
  :func:`repro.core.energymodel.stream_networks` /
  :func:`~repro.core.energymodel.stream_layer_topk` via ``verify=``:

  1. **Fold-invariant checks** after every chunk, BEFORE the new state
     commits: running minima are monotone non-increasing, top-k rows
     stay (value, flat-index)-lex sorted with no duplicate indices,
     per-layer sums reproduce the aggregate metric, and boundary hits
     respect ``bound`` against the updated running minimum.  A violation
     raises :class:`FoldInvariantError` with chunk/row provenance — the
     poisoned state never commits, so a retry resumes from the last good
     chunk.  These catch corruption of the CARRIED state (and of resumed
     checkpoint payloads, which carry no checksum); corruption of a raw
     chunk evaluation is usually self-consistent and sails through.

  2. **Sampled dual-backend shadow recompute** — a seeded, deterministic
     fraction of chunks (``verify_fraction``, default 1/16) is
     re-evaluated through the numpy reference kernel and compared to the
     fast-path result: bit-exactly when the fast path IS numpy, within
     ``SHADOW_RTOL`` (1e-12, ~2 decades above the measured cross-backend
     rounding noise and ~9 decades below the injected 1e-3
     perturbation) for jax.  A mismatch raises
     :class:`ShadowMismatchError` with provenance down to (grid row,
     network, term).  This is the layer that catches finite wrong chunk
     evaluations.

* :func:`check_layer_topk_result` / :func:`scrub_layer_topk` — the
  at-rest rung: structural invariants plus a sampled re-derivation of a
  completed (possibly store-loaded) :class:`~repro.core.energymodel.
  LayerTopK`'s rows through the reference path.
  :meth:`repro.serving.store.DurableStore.scrub` walks cached entries
  through these and quarantines-with-reason on mismatch — the store's
  checksum only protects against damage AFTER the write; the scrubber
  catches entries that were poisoned BEFORE it.

Everything is deterministic: chunk sampling derives from
``(seed, chunk_index)`` alone, so a resumed stream samples the same
chunks as an uninterrupted one.  When ``REPRO_VERIFY_EVIDENCE_DIR`` is
set, every mismatch dumps its full provenance as JSON there before
raising — CI uploads the directory as a failure artifact.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import energymodel

#: Relative tolerance for cross-backend comparisons.  On the CPU the jax
#: path agrees with numpy to ≤4.2e-16 relative (last-ulp differences in
#: sums).  On a TPU v5e, where XLA emulates float64, the per-layer and
#: aggregate energies and latencies of the extended grid agree to
#: ≤1.2e-14, and single emulated operations to ≤3.7e-14 (division);
#: 1e-12 sits ~27x above the worst of these, so it keeps zero false
#: positives while still catching any perturbation large enough to
#: change a reduction.  When the fast path is the numpy reference itself
#: the comparison is bit-exact (rtol 0).
SHADOW_RTOL = 1e-12

#: Relative tolerance for "per-layer sums reproduce the aggregate": the
#: fold computed the aggregate with the backend's summation order, the
#: checker re-sums on the host — last-ulp noise only.
SUM_RTOL = 1e-9


class FoldInvariantError(RuntimeError):
    """A streamed fold state violates a structural invariant.

    Raised BEFORE the offending state commits (or, for resumed states,
    before any chunk folds into it), so the in-memory fold is never
    poisoned; carries the violated ``invariant`` name plus chunk / grid
    row / network provenance."""

    def __init__(self, msg: str, *, invariant: str, chunk: int | None = None,
                 start: int | None = None, stop: int | None = None,
                 network: str | None = None, row: int | None = None):
        super().__init__(msg)
        self.invariant = invariant
        self.chunk = chunk
        self.start = start
        self.stop = stop
        self.network = network
        self.row = row


class ShadowMismatchError(RuntimeError):
    """The fast-path chunk evaluation diverges from the numpy reference.

    ``mismatches`` holds one provenance dict per diverging element —
    ``{"row": <flat grid row>, "network": <name>, "term": "energy" |
    "latency" (with the layer index in per-layer streams), "got": ...,
    "want": ...}`` — capped at ``MAX_MISMATCH_RECORDS``."""

    MAX_MISMATCH_RECORDS = 32

    def __init__(self, msg: str, *, chunk: int, start: int, stop: int,
                 mismatches: Sequence[Dict[str, Any]] = ()):
        super().__init__(msg)
        self.chunk = int(chunk)
        self.start = int(start)
        self.stop = int(stop)
        self.mismatches = list(mismatches)[:self.MAX_MISMATCH_RECORDS]


def _dump_evidence(kind: str, payload: Dict[str, Any]) -> None:
    """Persist mismatch provenance for the CI failure artifact."""
    root = os.environ.get("REPRO_VERIFY_EVIDENCE_DIR")
    if not root:
        return
    try:
        os.makedirs(root, exist_ok=True)
        n = len(os.listdir(root))
        path = os.path.join(root, f"{kind}_{n:04d}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2, default=str)
    except OSError:                                    # pragma: no cover
        pass          # evidence is best-effort, never masks the raise


@dataclasses.dataclass
class VerifyConfig:
    """Knobs of one :class:`StreamVerifier`.

    ``verify_fraction`` is the seeded share of chunks shadow-recomputed
    on the reference backend (1.0 = every chunk, 0.0 = invariants only);
    ``rtol=None`` auto-selects 0.0 (bit-exact) when the stream's fast
    path is numpy and :data:`SHADOW_RTOL` otherwise."""

    verify_fraction: float = 1.0 / 16.0
    seed: int = 0
    invariants: bool = True
    shadow: bool = True
    rtol: Optional[float] = None
    sum_rtol: float = SUM_RTOL


class StreamVerifier:
    """Per-stream verification hooks; pass as ``verify=`` to the engines.

    The engine calls :meth:`bind` once at stream start (handing over the
    reduction parameters and a numpy-reference chunk evaluator), then
    :meth:`check_chunk` (shadow) and :meth:`check_fold` (invariants) per
    chunk and :meth:`check_resume` on resumed states.  ``stats`` counts
    checks and violations; violations also raise."""

    def __init__(self, config: VerifyConfig | None = None, **kw):
        self.cfg = config if config is not None else VerifyConfig(**kw)
        self.stats: Dict[str, int] = dict(
            shadow_checks=0, shadow_mismatches=0,
            invariant_checks=0, invariant_violations=0)
        self._kind: Optional[str] = None
        self._names: Tuple[str, ...] = ()
        self._metric = "edp"
        self._topk = 0
        self._bound: Optional[float] = None
        self._rtol = 0.0
        self._ref_eval: Optional[Callable] = None

    # -- engine contract ---------------------------------------------------

    def bind(self, *, kind: str, names: Sequence[str], metric: str,
             topk: int, bound: Optional[float], backend: str,
             ref_eval: Optional[Callable] = None) -> None:
        """Called by the engine at stream start.  ``ref_eval(fc)`` must
        return the numpy-reference ``(e, t)`` of one padded chunk."""
        self._kind = kind
        self._names = tuple(names)
        self._metric = metric
        self._topk = int(topk)
        self._bound = None if bound is None else float(bound)
        self._ref_eval = ref_eval
        self._rtol = (self.cfg.rtol if self.cfg.rtol is not None
                      else (0.0 if backend == "numpy" else SHADOW_RTOL))

    def sampled(self, ci: int) -> bool:
        """Deterministic per-chunk sampling from ``(seed, chunk)`` alone
        — independent of the chunk count and of any resume point."""
        f = self.cfg.verify_fraction
        if f >= 1.0:
            return True
        if f <= 0.0:
            return False
        return bool(np.random.default_rng(
            (int(self.cfg.seed), int(ci))).random() < f)

    # -- shadow recompute --------------------------------------------------

    def check_chunk(self, ci: int, start: int, stop: int, fc, e, t) -> None:
        """Sampled dual-backend shadow recompute of one chunk."""
        if not self.cfg.shadow or self._ref_eval is None:
            return
        if not self.sampled(ci):
            return
        self.stats["shadow_checks"] += 1
        e = np.asarray(e)
        t = np.asarray(t)
        e_ref, t_ref = self._ref_eval(fc)
        # compare the FULL padded chunk: padded rows are deterministic
        # duplicates of the chunk's first row (see _pad_rows), so the
        # reference reproduces them too and corruption landing in the
        # padding is still caught
        m = stop - start
        mism: List[Dict[str, Any]] = []
        for term, got, want in (("energy", e, np.asarray(e_ref)),
                                ("latency", t, np.asarray(t_ref))):
            if self._rtol == 0.0:
                bad = (got != want) & ~(np.isnan(got) & np.isnan(want))
            else:
                bad = ~np.isclose(got, want, rtol=self._rtol, atol=0.0,
                                  equal_nan=True)
            for pos in np.argwhere(bad):
                r, j = int(pos[0]), int(pos[1])
                layer = f"[layer {int(pos[2])}]" if len(pos) > 2 else ""
                pad = " (padding dup of first row)" if r >= m else ""
                mism.append(dict(
                    row=(start + r if r < m else start),
                    network=self._names[j],
                    term=f"{term}{layer}{pad}",
                    got=float(got[tuple(pos)]),
                    want=float(want[tuple(pos)])))
        if not mism:
            return
        self.stats["shadow_mismatches"] += 1
        worst = mism[0]
        err = ShadowMismatchError(
            f"shadow recompute mismatch in streamed chunk {ci} (grid rows "
            f"{start}:{stop}): {len(mism)} element(s) diverge from the "
            f"numpy reference beyond rtol={self._rtol:g}; first at grid "
            f"row {worst['row']}, network {worst['network']}, term "
            f"{worst['term']} (got {worst['got']!r}, want "
            f"{worst['want']!r}).  The fold state was NOT updated with "
            f"this chunk — retry the chunk or resume from the last "
            f"exported state", chunk=ci, start=start, stop=stop,
            mismatches=mism)
        _dump_evidence("shadow_mismatch", dict(
            chunk=ci, start=start, stop=stop, rtol=self._rtol,
            kind=self._kind, metric=self._metric,
            mismatches=err.mismatches))
        raise err

    # -- fold invariants ---------------------------------------------------

    def check_fold(self, ci: int, start: int, stop: int, prev_state,
                   new_state, *, es=None, ts=None, mask=None) -> None:
        """Invariant-check the post-chunk state BEFORE it commits."""
        if not self.cfg.invariants:
            return
        self.stats["invariant_checks"] += 1
        try:
            prov = dict(chunk=ci, start=start, stop=stop)
            if self._kind == "networks":
                self._check_networks_state(prev_state, new_state, prov)
            else:
                self._check_layer_state(prev_state, new_state, prov)
            if mask is not None and self._bound is not None:
                self._check_boundary_hits(new_state, es, ts, mask, start,
                                          prov)
        except FoldInvariantError as err:
            self.stats["invariant_violations"] += 1
            _dump_evidence("invariant_violation", dict(
                chunk=ci, start=start, stop=stop, kind=self._kind,
                invariant=err.invariant, network=err.network, row=err.row,
                message=str(err)))
            raise

    def check_resume(self, state, cand) -> None:
        """Invariant-check a RESUMED fold state before any chunk folds
        into it — checkpoint files carry no checksum, so a finite
        corruption of the npz payload is only caught here."""
        if not self.cfg.invariants:
            return
        self.stats["invariant_checks"] += 1
        try:
            prov: Dict[str, Any] = dict(chunk=None, start=None, stop=None)
            self._check_finite_state(state, prov)
            if self._kind == "networks":
                self._check_networks_state(None, state, prov)
                min_m = np.asarray(state[2])
            else:
                self._check_layer_state(None, state, prov)
                min_m = np.asarray(state[7])
            self._check_cand(cand, min_m, prov)
        except FoldInvariantError as err:
            self.stats["invariant_violations"] += 1
            _dump_evidence("invariant_violation", dict(
                where="resume", kind=self._kind,
                invariant=err.invariant, network=err.network, row=err.row,
                message=str(err)))
            raise

    # -- invariant internals -----------------------------------------------

    def _raise(self, invariant: str, detail: str, prov: Dict[str, Any],
               *, network: str | None = None, row: int | None = None):
        where = ("resumed fold state" if prov.get("chunk") is None else
                 f"streamed chunk {prov['chunk']} (grid rows "
                 f"{prov['start']}:{prov['stop']})")
        raise FoldInvariantError(
            f"fold invariant {invariant!r} violated after {where}: "
            f"{detail}; the poisoned state was NOT committed",
            invariant=invariant, chunk=prov.get("chunk"),
            start=prov.get("start"), stop=prov.get("stop"),
            network=network, row=row)

    def _floor(self, min_m):
        """Lowest host-side metric a row folded into the device minimum
        ``min_m`` can have.  The host recomputes the metric (``e * t``)
        in IEEE float64; the fold computed it in the backend's own
        arithmetic, which on a TPU is emulated float64 and agrees only to
        the cross-backend tolerance.  On the numpy path ``_rtol`` is 0
        and the bound is exact."""
        return np.asarray(min_m) * (1.0 - self._rtol)

    def _check_finite_state(self, state, prov):
        for i, s in enumerate(state):
            a = np.asarray(s)
            if a.dtype.kind == "f" and np.isnan(a).any():
                self._raise("state_finite",
                            f"state array {i} contains NaN", prov)

    def _check_monotone(self, label, prev, new, prov):
        """Running minima may only move down (or stay)."""
        p = np.asarray(prev)
        worse = np.asarray(new) > p
        # +inf "not seen yet" sentinels compare equal, never worse
        if worse.any():
            pos = np.argwhere(worse)[0]
            j = int(pos[0]) if pos.size else None
            self._raise(
                "monotone_min",
                f"running {label} increased at position {tuple(pos)} "
                f"(network {self._names[j] if j is not None and j < len(self._names) else j})",
                prov, network=(self._names[j]
                               if j is not None and j < len(self._names)
                               else None))

    def _check_topk(self, top_v, top_i, prov):
        """Top-k rows must be (value, flat-index)-lex sorted per network
        with no duplicate valid indices; -1 sentinels (unfilled slots)
        carry +inf and may repeat."""
        top_v = np.asarray(top_v)
        top_i = np.asarray(top_i)
        for j, nm in enumerate(self._names):
            v, i = top_v[:, j], top_i[:, j]
            if np.isnan(v).any():
                self._raise("topk_sorted", f"NaN in top-k values of {nm}",
                            prov, network=nm)
            with np.errstate(invalid="ignore"):   # inf-inf on sentinels
                dv, di = np.diff(v), np.diff(i)
                bad = (dv < 0) | ((dv == 0) & (di < 0) & (i[1:] >= 0))
            if bad.any():
                k = int(np.nonzero(bad)[0][0])
                self._raise(
                    "topk_sorted",
                    f"top-k rows {k}..{k + 1} of network {nm} are not "
                    f"(value, flat-index)-lex sorted: "
                    f"({v[k]!r}, {i[k]}) then ({v[k + 1]!r}, {i[k + 1]})",
                    prov, network=nm, row=int(i[k + 1]))
            valid = i[i >= 0]
            if valid.size != np.unique(valid).size:
                dup = valid[np.nonzero(np.diff(np.sort(valid)) == 0)[0][0]]
                self._raise(
                    "topk_unique",
                    f"duplicate flat grid index {int(dup)} in the top-k "
                    f"of network {nm}", prov, network=nm, row=int(dup))

    def _check_min_is_top(self, min_m, top_v, prov):
        """The running metric minimum IS the best top-k value — they fold
        the same chunk values, so they must agree exactly."""
        min_m = np.asarray(min_m)
        best = np.asarray(top_v)[0]
        bad = (min_m != best) & ~(np.isinf(min_m) & np.isinf(best))
        if bad.any():
            j = int(np.nonzero(bad)[0][0])
            self._raise(
                "min_equals_top",
                f"running min_metric {min_m[j]!r} != best top-k value "
                f"{best[j]!r} for network {self._names[j]}",
                prov, network=self._names[j])

    def _check_networks_state(self, prev, new, prov):
        min_e, min_t, min_m, argm, top_v, top_i = new
        if prev is not None:
            for label, p, q in (("min_energy", prev[0], min_e),
                                ("min_latency", prev[1], min_t),
                                ("min_metric", prev[2], min_m)):
                self._check_monotone(label, p, q, prov)
        self._check_topk(top_v, top_i, prov)
        self._check_min_is_top(min_m, top_v, prov)

    def _check_layer_state(self, prev, new, prov):
        (top_v, top_i, top_e, top_t, min_e, min_t, min_edp, min_m, argm,
         lmin, larg) = new
        if prev is not None:
            for label, p, q in (("min_energy", prev[4], min_e),
                                ("min_latency", prev[5], min_t),
                                ("min_edp", prev[6], min_edp),
                                ("min_metric", prev[7], min_m),
                                ("layer_min_metric", prev[9], lmin)):
                self._check_monotone(label, p, q, prov)
        self._check_topk(top_v, top_i, prov)
        self._check_min_is_top(min_m, top_v, prov)
        # per-layer sums reproduce the aggregate the row was ranked by
        top_v = np.asarray(top_v)
        top_i = np.asarray(top_i)
        with np.errstate(invalid="ignore"):       # inf*0 on -1 sentinels
            agg = energymodel._metric_of(
                self._metric, np.asarray(top_e).sum(-1),
                np.asarray(top_t).sum(-1))
        valid = top_i >= 0
        if valid.any():
            with np.errstate(invalid="ignore"):   # inf-inf on -1 sentinels
                err = (np.abs(agg - top_v)
                       > self.cfg.sum_rtol * np.abs(top_v))
            bad = valid & err
            if bad.any():
                k, j = (int(x) for x in np.argwhere(bad)[0])
                self._raise(
                    "layer_sum_aggregate",
                    f"per-layer rows of top-{k} config (grid row "
                    f"{int(top_i[k, j])}, network {self._names[j]}) sum "
                    f"to metric {agg[k, j]!r} but the fold ranked it at "
                    f"{top_v[k, j]!r}", prov, network=self._names[j],
                    row=int(top_i[k, j]))

    def _check_boundary_hits(self, new_state, es, ts, mask, start, prov):
        """This chunk's boundary hits respect ``bound`` against the
        updated running minimum — and none beats the minimum itself
        (every hit also folded into it)."""
        if es is None or ts is None:
            return
        mask = np.asarray(mask)
        if not mask.any():
            return
        min_m = np.asarray(new_state[2] if self._kind == "networks"
                           else new_state[7])
        v = energymodel._metric_of(self._metric, np.asarray(es),
                                   np.asarray(ts))
        thresh = min_m[None, :] * (1.0 + self._bound)
        bad = mask & ((v < self._floor(min_m)[None, :])
                      | (v > thresh * (1.0 + self._rtol)))
        if bad.any():
            r, j = (int(x) for x in np.argwhere(bad)[0])
            self._raise(
                "boundary_bound",
                f"boundary hit at grid row {start + r} of network "
                f"{self._names[j]} has metric {v[r, j]!r} outside "
                f"[min, min*(1+bound)] = [{min_m[j]!r}, {thresh[0, j]!r}]",
                prov, network=self._names[j], row=start + r)

    def _check_cand(self, cand, min_m, prov):
        """Resumed boundary candidates: finite, and none beats the fold
        minimum (every candidate was folded into it when collected)."""
        for j, nm in enumerate(self._names):
            for idx, ee, tt in cand.get(nm, ()):
                v = energymodel._metric_of(self._metric, np.asarray(ee),
                                           np.asarray(tt))
                if np.isnan(v).any():
                    self._raise("boundary_bound",
                                f"NaN boundary candidate in network {nm}",
                                prov, network=nm)
                bad = v < self._floor(min_m[j])
                if bad.any():
                    r = int(np.nonzero(bad)[0][0])
                    self._raise(
                        "boundary_bound",
                        f"boundary candidate at grid row "
                        f"{int(np.asarray(idx)[r])} of network {nm} has "
                        f"metric {v[r]!r} BELOW the running minimum "
                        f"{min_m[j]!r} — the fold missed an update",
                        prov, network=nm, row=int(np.asarray(idx)[r]))


# ---------------------------------------------------------------------------
# At-rest verification: completed LayerTopK results and store payloads
# ---------------------------------------------------------------------------


def check_layer_topk_result(st, *, sum_rtol: float = SUM_RTOL
                            ) -> Optional[str]:
    """Structural invariants of a completed (possibly store-loaded)
    :class:`~repro.core.energymodel.LayerTopK`; returns a reason string
    on the first violation, ``None`` when clean."""
    top_v = np.asarray(st.topk_metric)
    top_i = np.asarray(st.topk_idx)
    for j, nm in enumerate(st.networks):
        v, i = top_v[:, j], top_i[:, j]
        if np.isnan(v).any():
            return f"NaN in top-k metrics of network {nm}"
        with np.errstate(invalid="ignore"):       # inf-inf on sentinels
            dv, di = np.diff(v), np.diff(i)
            unsorted = (dv < 0) | ((dv == 0) & (di < 0) & (i[1:] >= 0))
        if unsorted.any():
            return (f"top-k of network {nm} is not (value, flat-index)-"
                    f"lex sorted")
        valid = i[i >= 0]
        if valid.size != np.unique(valid).size:
            return f"duplicate flat grid index in the top-k of network {nm}"
        if st.min_metric is not None and v.size:
            mm = float(np.asarray(st.min_metric)[j])
            if mm != float(v[0]) and not (np.isinf(mm) and np.isinf(v[0])):
                return (f"min_metric {mm!r} != best top-k value "
                        f"{float(v[0])!r} for network {nm}")
    # per-layer rows reproduce the ranking aggregate
    with np.errstate(invalid="ignore"):           # inf*0 on -1 sentinels
        agg = energymodel._metric_of(
            st.metric, np.asarray(st.layer_energy).sum(-1),
            np.asarray(st.layer_latency).sum(-1))
    with np.errstate(invalid="ignore"):           # inf-inf on -1 sentinels
        bad = ((top_i >= 0)
               & (np.abs(agg - top_v) > sum_rtol * np.abs(top_v)))
    if bad.any():
        k, j = (int(x) for x in np.argwhere(bad)[0])
        return (f"per-layer rows of top-{k} config (grid row "
                f"{int(top_i[k, j])}, network {st.networks[j]}) sum to "
                f"{agg[k, j]!r} but were ranked at {top_v[k, j]!r}")
    if st.bound is not None:
        for j, nm in enumerate(st.networks):
            bv = energymodel._metric_of(st.metric,
                                        np.asarray(st.boundary_energy[nm]),
                                        np.asarray(st.boundary_latency[nm]))
            if np.isnan(bv).any():
                return f"NaN in the boundary set of network {nm}"
            if bv.size:
                mm = float(np.asarray(st.min_metric)[j])
                if (bv < mm).any():
                    return (f"boundary entry of network {nm} beats the "
                            f"minimum {mm!r} — the fold missed an update")
                if (bv > mm * (1.0 + float(st.bound))).any():
                    return (f"boundary entry of network {nm} exceeds "
                            f"min*(1+bound)")
                if (np.diff(bv) < 0).any():
                    return (f"boundary set of network {nm} is not "
                            f"metric-sorted")
    return None


def scrub_layer_topk(st, grid, networks, *, rows: int = 2, seed: int = 0,
                     rtol: float = SHADOW_RTOL,
                     sum_rtol: float = SUM_RTOL) -> Optional[str]:
    """At-rest audit of one stream payload: structural invariants plus a
    seeded sample of its top-k rows re-derived through the numpy
    reference path (`evaluate_networks(per_layer=True)` of exactly those
    grid rows) and compared within ``rtol``.  Returns a quarantine
    reason, or ``None`` when the payload checks out."""
    reason = check_layer_topk_result(st, sum_rtol=sum_rtol)
    if reason is not None:
        return reason
    top_i = np.asarray(st.topk_idx)
    cells = np.argwhere(top_i >= 0)
    if not cells.size or rows <= 0:
        return None
    rng = np.random.default_rng(seed)
    pick = cells[rng.choice(len(cells), size=min(int(rows), len(cells)),
                            replace=False)]
    rows_idx = np.unique(top_i[pick[:, 0], pick[:, 1]])
    e_ref, t_ref = energymodel.evaluate_networks(
        grid.take(rows_idx), networks, backend="numpy", per_layer=True)
    pos = {int(r): i for i, r in enumerate(rows_idx)}
    for k, j in pick:
        k, j = int(k), int(j)
        gi = int(top_i[k, j])
        i = pos[gi]
        nm = st.networks[j]
        for term, stored, ref in (
                ("energy", np.asarray(st.layer_energy)[k, j],
                 np.asarray(e_ref)[i, j]),
                ("latency", np.asarray(st.layer_latency)[k, j],
                 np.asarray(t_ref)[i, j])):
            bad = ~np.isclose(stored, ref, rtol=rtol, atol=0.0)
            if bad.any():
                li = int(np.nonzero(bad)[0][0])
                _dump_evidence("scrub_mismatch", dict(
                    grid_row=gi, network=nm, term=f"{term}[layer {li}]",
                    got=float(stored[li]), want=float(ref[li]),
                    rtol=rtol))
                return (f"stored per-layer {term} of grid row {gi}, "
                        f"network {nm} diverges from the reference "
                        f"recompute at layer {li} (got {stored[li]!r}, "
                        f"want {ref[li]!r}, rtol {rtol:g}) — the entry "
                        f"was poisoned before it was written")
    return None

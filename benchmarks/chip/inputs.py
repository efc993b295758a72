"""Inputs of every cell, made from the configuration file and the seed.

The same numbers go to the system under test and to the plain reference:
the design-space grid (a cross product of the file's axes), one energy
table per job drawn from (seed, job index), and the networks' layer rows.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference.tool import GRID_COLUMNS

#: Grid columns one energy table sets, for every point of the grid.
ENERGY_COLUMNS = ("e_rf", "gb_e_ref", "e_dram_r", "e_dram_w", "e_mac",
                  "e_noc_hop")


def product_grid(grid: dict) -> Dict[str, np.ndarray]:
    """The configuration file's grid as float64 columns: the cross product
    of arrays x gb_psum_kb x gb_ifmap_kb x rf_psum_words x noc_wpc, outer
    to inner, every other column at its ``base`` value."""
    arrays = np.asarray(grid["arrays"], dtype=np.float64)
    axes = (np.arange(len(arrays)),
            np.asarray(grid["gb_psum_kb"], np.float64),
            np.asarray(grid["gb_ifmap_kb"], np.float64),
            np.asarray(grid["rf_psum_words"], np.float64),
            np.asarray(grid["noc_wpc"], np.float64))
    ai, ps, ifm, rf, nw = (g.ravel() for g in
                           np.meshgrid(*axes, indexing="ij"))
    out = {k: np.full(ai.size, float(grid["base"][k])) for k in GRID_COLUMNS}
    out["rows"] = arrays[ai.astype(np.intp), 0]
    out["cols"] = arrays[ai.astype(np.intp), 1]
    out["gb_psum_kb"] = ps
    out["gb_ifmap_kb"] = ifm
    out["rf_psum_words"] = rf
    out["noc_wpc"] = nw
    return out


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def energy_table(draw: dict, seed: int, job: int) -> Dict[str, float]:
    """Per-access energies (pJ) of job ``job``: RF energy, then the other
    levels as ratios to it, each uniform over the file's range."""
    g = rng(seed, 1, job)

    def u(key):
        lo, hi = draw[key]
        return float(g.uniform(lo, hi))

    e_rf = u("e_rf")
    e_dram_r = e_rf * u("e_dram_r_over_e_rf")
    return dict(e_rf=e_rf, gb_e_ref=e_rf * u("gb_e_ref_over_e_rf"),
                e_dram_r=e_dram_r,
                e_dram_w=e_dram_r * u("e_dram_w_over_e_dram_r"),
                e_mac=e_rf * u("e_mac_over_e_rf"),
                e_noc_hop=e_rf * u("e_noc_hop_over_e_rf"))


def with_energy(fields: Dict[str, np.ndarray], table: Dict[str, float]
                ) -> Dict[str, np.ndarray]:
    out = dict(fields)
    n = next(iter(fields.values())).shape[0]
    for k in ENERGY_COLUMNS:
        out[k] = np.full(n, table[k])
    return out

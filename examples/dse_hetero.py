"""The full §IV flow: DSE over 18 CNNs → heterogeneous chip → cross-core
penalties (Table 6) → Algorithm II distribution (Tables 7–8) — and the TPU
adaptation: the same search over sharding policies for the 10 assigned LM
architectures (fleet design).

    PYTHONPATH=src python examples/dse_hetero.py
"""

import collections

import numpy as np

from repro.configs import ARCHS
from repro.core import autoshard, dse, energymodel, hetero, partition
from repro.core import accelerator, topology


def main():
    # --- paper: 18 CNNs, 150-point space, 5% boundary, greedy cover ------
    # one batched, jit-cached call evaluates all networks × the whole grid
    sweeps = dse.sweep_networks(
        {n: topology.get_network(n) for n in topology.NETWORKS})
    # self-describing output: what the engine actually executed on
    print(f"engine backend: {energymodel.last_backend()} "
          f"({energymodel.host_device_count()} host device(s))")
    chip = hetero.design_chip(sweeps, bound=0.05, max_cores=3)
    groups = collections.defaultdict(list)
    for net, i in chip.assignment.items():
        groups[i].append(net)
    print("=== heterogeneous chip (paper §IV.A) ===")
    for i in sorted(groups):
        print(f"core type {i} {chip.core_label(i)}: "
              f"{', '.join(sorted(groups[i]))}")
    sav = hetero.savings_summary(chip)
    es = [v["energy_saved"] for v in sav.values()]
    ed = [v["edp_saved"] for v in sav.values()]
    print(f"savings vs worst single core: energy up to {max(es):.0f}% "
          f"(mean {np.mean(es):.0f}%), EDP up to {max(ed):.0f}% "
          f"(mean {np.mean(ed):.0f}%)  [paper: up to 36% / 67%]")

    # --- same chip designed from the streaming engine (no full cubes) ----
    grid = accelerator.ConfigGrid.product()
    nets = {n: topology.get_network(n) for n in topology.NETWORKS}
    stream = dse.stream_grid(nets, grid, chunk_size=50, bound=0.05)
    schip = hetero.design_chip_streaming(stream, grid, nets, max_cores=3)
    shape = next(iter(sweeps.values())).edp.shape
    same = schip.core_cells(shape) == chip.core_types
    print(f"\nstreaming design_chip reproduces the cover: {same} "
          f"(boundary sets only, no [n_cfg, n_net] matrices)")

    # --- per-layer co-design: which chip AND which layer→core schedule ---
    # one per_layer=True engine call + one batched hetero-schedule solve
    # evaluates every candidate (type multiset × core counts) chip
    print("\n=== per-layer chip + schedule co-design (§IV.A × §IV.B) ===")
    cd = hetero.co_design(grid, nets, m_cores=4, max_types=3, pool_size=6)
    print(f"co-designed chip ({cd.n_chips} candidates searched): "
          f"{cd.summary(grid)}")
    print(f"mean normalized EDP {cd.score:.3f} vs best homogeneous "
          f"{cd.homogeneous_score:.3f} "
          f"({(1 - cd.score / cd.homogeneous_score) * 100:.1f}% better)")
    for net in ("ResNet50", "MobileNetV2", "VGG16"):
        s = cd.schedules[net]
        moves = sum(1 for a, b in zip(s.layer_core, s.layer_core[1:])
                    if a != b)
        print(f"  {net}: {s.n_layers} layers over {s.n_cores} cores "
              f"({len(set(s.layer_type))} type(s)), pipeline speedup "
              f"{s.speedup:.2f}x, {moves} core hand-offs")

    # --- latency-bound Pareto sweep: one compiled call, ALL deadlines ----
    # the streamed problem set (boundary sets from one chunked pass, no
    # dense [n_cfg, n_net] matrices) feeds the same batched solve, then
    # every chip is scored against the whole deadline grid at once
    print("\n=== latency-bound Pareto co-design (streamed pool) ===")
    probs = hetero.codesign_problems_streaming(grid, nets, m_cores=4,
                                               max_types=3, pool_size=6,
                                               chunk_size=50)
    pc = hetero.pareto_codesign(probs, n_deadlines=8)
    print(f"{pc.n_chips} chips x {len(nets)} networks x "
          f"{pc.deadlines.size} deadlines (x min single-core latency):")
    for di, (d, c) in enumerate(zip(pc.deadlines, pc.best_chip)):
        if c < 0:
            print(f"  deadline {d:.2f}: no chip feasible")
        else:
            print(f"  deadline {d:.2f}: chip {int(c)} "
                  f"({pc.chip_summary(int(c), grid)}), "
                  f"mean norm energy {pc.scores[int(c), di]:.3f}")
    net = "ResNet50"
    print(f"Pareto frontier for {net} (latency ns, energy pJ):")
    for c, lat, en in pc.frontier(net)[:5]:
        print(f"  chip {c}: latency {lat:.3e}, energy {en:.3e}")

    # --- energy-aware deadline slack: spend latency headroom on energy ---
    # the same problem set re-scored with slack=True: layers migrate to
    # lower-energy core types while the pipeline stays under each
    # deadline (bit-exact vs partition.slack_schedule_oracle)
    print("\n=== energy-aware deadline-slack scheduling ===")
    ps = hetero.pareto_codesign(probs, n_deadlines=8, slack=True)
    moved = int(ps.slack_moves.sum())
    saved = 100.0 * (1.0 - np.nanmean(
        np.where(np.isfinite(ps.slack_energy)
                 & np.isfinite(ps.energy)[:, :, None],
                 ps.slack_energy / ps.energy[:, :, None], np.nan)))
    print(f"{moved} layer moves across "
          f"{ps.n_chips} chips x {len(nets)} networks x "
          f"{ps.deadlines.size} deadlines; mean energy saved {saved:.2f}% "
          f"(never worse than the latency-argmin schedule)")
    for di in (0, ps.deadlines.size - 1):
        c = int(ps.best_chip_slack[di])
        tag = (f"chip {c}, mean norm energy {ps.slack_scores[c, di]:.3f}"
               if c >= 0 else "no chip feasible")
        print(f"  deadline {ps.deadlines[di]:.2f}x: {tag}")

    # --- Algorithm II on each group's core type ---------------------------
    # one batch_partition call solves every (network, k) split at once
    print("\n=== model parallelism on homogeneous cores (§IV.B) ===")
    show = ("ResNet50", "GoogleNet", "VGG16")
    lats = []
    for net in show:
        cell = chip.core_types[chip.assignment[net]]
        a, p, i = cell
        sw = sweeps[net]
        cfg = accelerator.AcceleratorConfig(
            array_rows=sw.arrays[a][0], array_cols=sw.arrays[a][1],
            gb_psum_kb=sw.psum_kb[p], gb_ifmap_kb=sw.ifmap_kb[i])
        rep = energymodel.simulate_network(cfg, topology.get_network(net))
        lats.append(rep.layer_latencies)
    batch = partition.batch_partition(lats, (3, 4))
    for j, net in enumerate(show):
        for k in (3, 4):
            print(f"  {net} on {k} cores: speedup {batch[j][k].speedup:.2f}x")

    # --- TPU adaptation: fleet design over sharding policies ---------------
    print("\n=== TPU fleet design (Table-5 analogue over shardings) ===")
    fleet = autoshard.design_fleet(dict(ARCHS), n_chips=256, seq_len=4096,
                                   global_batch=256, max_policies=3)
    for pol in fleet["policies"]:
        archs = [a for a, p in fleet["assignment"].items() if p == pol]
        print(f"policy {pol}: {', '.join(sorted(archs))}")


if __name__ == "__main__":
    main()

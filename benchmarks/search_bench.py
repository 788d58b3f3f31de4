"""Mapping-search benchmark: searched vs greedy hop energy (the artifact).

For every Tab. IV network, runs ``repro.search.search_mapping`` under the
default architecture and records the greedy and searched mapping costs
side by side — the ps/ifm hop-energy decomposition (closed-form base +
serpentine-NoC transit), the energy ratio, and the ``searched ≤ greedy``
/ strictly-better verdicts CI gates on. A ``greedy_matches_baseline``
fidelity bool asserts, per network, that the cost model's greedy score is
bitwise the committed baseline: the greedy candidate realizes the exact
``greedy_place`` allocations and its link/off-chip components equal the
committed ``CompiledProgram``/``DominoModel`` quantities with ``==``, not
allclose.

A pareto section sweeps the geometry axes (``tiles_per_chip`` × ``n_c`` ×
``n_m``) on one network, searching each point and reporting the
non-dominated front over (searched hop energy, tile area).

Search costs are scored in deterministic NumPy float64, so the fidelity
metrics reproduce bit-for-bit across runners for a fixed
budget/seed/engine; ``--backend jax`` routes the recorded per-candidate
Tab. IV columns through the jitted sweep kernel (the population-
evaluation path the engines share with the 1e6-scenario sweeps).

    PYTHONPATH=src python benchmarks/search_bench.py --out search-bench.json
    PYTHONPATH=src python benchmarks/search_bench.py \
        --budget 96 --pareto-budget 48 --seed 0    # the CI/baseline recipe
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

from repro.core.arch import DEFAULT_ARCH
from repro.core.program import Workload, compile_program
from repro.core.simulator import DominoModel
from repro.search import (
    PopulationEvaluator,
    greedy_candidate,
    search_mapping,
)
from repro.search.space import candidate_allocs
from repro.sweep.registry import resolve_network

DEFAULT_NETWORKS = ("vgg11-cifar", "vgg16-imagenet", "vgg19-imagenet",
                    "resnet18-cifar")
PARETO_TILES = (192, 240)
PARETO_NC = (128, 256)
PARETO_NM = (128, 256)


def _cost_dict(c) -> dict:
    return dict(
        hop_energy_pj=c.hop_energy_pj, link_pj=c.link_pj,
        offchip_pj=c.offchip_pj, transit_pj=c.transit_pj,
        steady_cycles=c.steady_cycles, fill_cycles=c.fill_cycles,
        n_tiles=c.n_tiles, n_chips=c.n_chips,
    )


def _greedy_matches_baseline(wl: Workload, arch, gcost) -> bool:
    """The cost model's greedy score vs the committed compile artifacts,
    compared with ``==`` (bitwise), not allclose."""
    program = compile_program(wl, arch)
    model = DominoModel(program)
    cand = greedy_candidate(wl.layers, arch)
    allocs, _ = candidate_allocs(wl.layers, arch, cand)
    tot = program.event_totals
    link = (tot["ps_bits"] + tot["ifm_bits"]) \
        * arch.energy.link_pj_per_bit * arch.energy_scale()
    return (
        list(allocs) == list(program.allocs)
        and gcost.link_pj == link
        and gcost.offchip_pj == model.offchip_energy_img_j() * 1e12
        and gcost.steady_cycles == model.bottleneck_px()
        and gcost.n_tiles == program.n_tiles
        and gcost.n_chips == program.n_chips
    )


PARETO_OBJECTIVES = ("searched_hop_energy_pj", "area_mm2", "n_chips")


def _pareto_front(points):
    """Indices of the non-dominated points minimizing
    ``PARETO_OBJECTIVES`` (hop energy, tile area, chip count — chip count
    is the axis that trades against energy: more tiles per chip packs the
    network onto fewer chips but stretches the on-chip spans)."""
    front = []
    for i, p in enumerate(points):
        dominated = any(
            all(q[o] <= p[o] for o in PARETO_OBJECTIVES)
            and any(q[o] < p[o] for o in PARETO_OBJECTIVES)
            for q in points)
        if not dominated:
            front.append(i)
    return front


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--networks", nargs="*", default=list(DEFAULT_NETWORKS),
                    help="networks to search (default: the Tab. IV four)")
    ap.add_argument("--budget", type=int, default=96,
                    help="candidate evaluations per network (default: 96)")
    ap.add_argument("--engine", choices=("evolve", "anneal"),
                    default="evolve")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="jax",
                    help="sweep backend for the recorded per-candidate "
                         "Tab. IV columns (objectives are deterministic "
                         "NumPy either way)")
    ap.add_argument("--pareto-network", default="vgg11-cifar")
    ap.add_argument("--pareto-budget", type=int, default=48,
                    help="evaluations per pareto grid point (default: 48; "
                         "0 disables the pareto section)")
    ap.add_argument("--out", default=None,
                    help="write JSON here (default: stdout)")
    args = ap.parse_args(argv)

    if args.backend == "jax":
        from repro.launch.compile_cache import init_compile_cache

        init_compile_cache()

    t_start = time.perf_counter()
    networks = {}
    all_le, any_strict, all_base = True, False, True
    ratios = []
    for name in args.networks:
        wl = resolve_network(name)
        res = search_mapping(wl, DEFAULT_ARCH, budget=args.budget,
                             engine=args.engine, seed=args.seed,
                             backend=args.backend)
        g, s = res.greedy_cost, res.cost
        base_ok = _greedy_matches_baseline(wl, DEFAULT_ARCH, g)
        le = s.hop_energy_pj <= g.hop_energy_pj
        all_le &= le
        any_strict |= res.improved
        all_base &= base_ok
        ratios.append(res.energy_ratio)
        # the searched candidate's Tab. IV columns through the sweep
        # backend (the shared population-evaluation path)
        ev = PopulationEvaluator(wl.layers, DEFAULT_ARCH,
                                 backend=args.backend)
        cols = ev.columns([res.candidate])
        networks[name] = dict(
            greedy=_cost_dict(g),
            searched=_cost_dict(s),
            hop_ratio=res.energy_ratio,
            searched_le_greedy=le,
            strictly_better=res.improved,
            greedy_matches_baseline=base_ok,
            evaluations=res.evaluations,
            engine=res.engine,
            wall_s=res.wall_s,
            searched_columns={k: float(v[0]) for k, v in cols.items()},
        )
        print(f"{name}: greedy {g.hop_energy_pj:.6g} pJ -> searched "
              f"{s.hop_energy_pj:.6g} pJ (ratio {res.energy_ratio:.4f}, "
              f"strict={res.improved}, baseline_bitwise={base_ok})",
              file=sys.stderr)

    payload = dict(
        budget=args.budget,
        engine=args.engine,
        seed=args.seed,
        backend=args.backend,
        networks=networks,
        searched_le_greedy=all_le,
        strictly_better_any=any_strict,
        greedy_matches_baseline=all_base,
        energy_ratio_mean=sum(ratios) / len(ratios) if ratios else 1.0,
    )

    if args.pareto_budget > 0:
        wl = resolve_network(args.pareto_network)
        points = []
        for tpc, nc, nm in itertools.product(PARETO_TILES, PARETO_NC,
                                             PARETO_NM):
            arch = DEFAULT_ARCH.replace(tiles_per_chip=tpc, n_c=nc, n_m=nm)
            res = search_mapping(wl, arch, budget=args.pareto_budget,
                                 engine=args.engine, seed=args.seed,
                                 backend=args.backend)
            points.append(dict(
                tiles_per_chip=tpc, n_c=nc, n_m=nm,
                greedy_hop_energy_pj=res.greedy_cost.hop_energy_pj,
                searched_hop_energy_pj=res.cost.hop_energy_pj,
                hop_ratio=res.energy_ratio,
                n_tiles=res.cost.n_tiles,
                n_chips=res.cost.n_chips,
                area_mm2=res.cost.n_tiles * arch.tile_area_um2() / 1e6,
            ))
        front = _pareto_front(points)
        for i in front:
            points[i]["on_front"] = True
        payload["pareto"] = dict(
            network=args.pareto_network,
            budget=args.pareto_budget,
            axes=dict(tiles_per_chip=list(PARETO_TILES),
                      n_c=list(PARETO_NC), n_m=list(PARETO_NM)),
            points=points,
            n_points=len(points),
            n_front=len(front),
        )
        print(f"pareto[{args.pareto_network}]: {len(front)}/{len(points)} "
              f"non-dominated over (hop energy, area)", file=sys.stderr)

    payload["wall_s"] = time.perf_counter() - t_start

    text = json.dumps(payload, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Table 1: parallel (grid) speedup of NO-MP / SMP / MMP.

The paper ran DBLP-BIG on a 30-machine Hadoop grid (speedup ~11x,
limited by setup overhead + neighborhood-size skew).  Here the grid is
the SPMD mesh: rounds of shard_mapped matcher evaluation with bitset
all-reduce.  On this 1-CPU container the mesh has one shard, so we
report measured 1-shard wall time plus a *skew-derived* speedup model:
the per-round critical path on N shards is the max over shards of
summed per-neighborhood cost (the paper's statistical-skew argument),
with per-neighborhood cost ~ k^2 from the padded bins.

Two instances are measured:

* ``hepth`` — the blocking-bound synthetic corpus (few rounds; cost is
  dominated by the first full evaluation pass);
* ``lattice`` — the paper's §2.1 evidence chain scaled up
  (``data.synthetic.make_lattice_cover``): resolution takes ``depth``
  message-passing rounds, which is the *multi-round* configuration
  where the per-round host overhead the device-resident engine removes
  (re-grounding, per-bin dispatch, active-set bookkeeping) dominates.

Each scheme runs on the device-resident engine (cached groundings,
multi-round ``while_loop`` closure) and its fixpoint is checked against
the sequential driver's.  ``dispatches_per_round`` is the host-dispatch
metric the CI smoke gate tracks against the committed
``BENCH_parallel.json``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import SMOKE, prepared, row, timed
from repro import obs
from repro.core.driver import run_mmp, run_nomp, run_smp
from repro.core.global_grounding import build_global_grounding
from repro.core.mln import MLNMatcher
from repro.core.parallel import run_parallel
from repro.data.synthetic import make_lattice_cover

LATTICE_DEPTH, LATTICE_WIDTH = (6, 2) if SMOKE else (96, 16)


def skew_speedup(packed, rounds_hist, n_shards: int, overhead_s: float,
                 t_total: float) -> float:
    """Speedup model: round time = max over shards of sum of k^2 costs
    under random assignment (paper §6.3: 'statistical skew')."""
    rng = np.random.default_rng(0)
    costs = np.array(
        [float(packed.neighborhood_bin[n]) ** 2
         for n in range(packed.num_neighborhoods)]
    )
    per_round_frac = np.asarray(rounds_hist, dtype=np.float64)
    per_round_frac /= max(per_round_frac[0], 1)
    t_seq = t_total
    t_par = overhead_s
    for frac in per_round_frac:
        active = costs[rng.random(len(costs)) < frac]
        if len(active) == 0:
            continue
        shard = rng.integers(0, n_shards, size=len(active))
        per_shard = np.bincount(shard, weights=active, minlength=n_shards)
        t_par += per_shard.max() / max(costs.sum(), 1) * t_seq
    return t_seq / max(t_par, 1e-9)


def _measure(name: str, packed, gg, matcher, schemes) -> dict:
    out = {
        "n_neighborhoods": int(packed.num_neighborhoods),
        "n_bins": len(packed.bins),
        "schemes": {},
    }
    row(f"# table1[{name}]: parallel rounds (SPMD mesh; model for 30 shards)")
    row(
        "scheme,wall_s,rounds,evals,dispatches,dispatches_per_round,"
        "promote_host_scans,modeled_speedup_30"
    )
    sequential = {
        "nomp": lambda: run_nomp(packed, matcher),
        "smp": lambda: run_smp(packed, matcher),
        "mmp": lambda: run_mmp(packed, matcher, gg),
    }
    for scheme in schemes:
        # Each run gets its own registry window: run_parallel publishes
        # its EMResult as cumulative ``em.*`` counters, so the bench
        # reads one snapshot instead of dataclass fields.
        obs.reset()
        res, wall = timed(
            lambda s=scheme: run_parallel(packed, matcher, gg, scheme=s)
        )
        counters = obs.get_registry().snapshot()["counters"]
        seq = sequential[scheme]().matches.as_set()
        assert res.matches.as_set() == seq, (name, scheme)
        rounds = counters.get("em.rounds", 0)
        evals = counters.get("em.neighborhood_evals", 0)
        dispatches = counters.get("em.dispatches", 0)
        host_scans = counters.get("em.promote_host_scans", 0)
        hist = res.history or [packed.num_neighborhoods]
        sp = skew_speedup(packed, hist, 30, overhead_s=0.05 * wall,
                          t_total=wall)
        dpr = dispatches / max(rounds, 1)
        row(
            scheme,
            f"{wall:.3f}",
            rounds,
            evals,
            dispatches,
            f"{dpr:.2f}",
            host_scans,
            f"{sp:.1f}",
        )
        out["schemes"][scheme] = {
            "wall_s": round(wall, 4),
            "rounds": int(rounds),
            "evals": int(evals),
            "dispatches": int(dispatches),
            "dispatches_per_round": round(dpr, 3),
            # host coupling-COO promotion walks of the fused engine —
            # device-resident promotion keeps this 0 (gated in CI)
            "promote_host_scans": int(host_scans),
        }
    return out


def main() -> dict:
    out = {"benchmark": "table1_parallel", "smoke": SMOKE, "instances": {}}

    ds, packed, gg, _ = prepared("hepth")
    out["instances"]["hepth"] = _measure(
        "hepth", packed, gg, MLNMatcher(), ("nomp", "smp", "mmp")
    )

    row("")
    lat_packed, lat_rel, lat_weights = make_lattice_cover(
        LATTICE_DEPTH, LATTICE_WIDTH
    )
    lat_gg = build_global_grounding(
        lat_packed.pair_levels, lat_rel, lat_weights
    )
    lat = _measure(
        f"lattice d{LATTICE_DEPTH} w{LATTICE_WIDTH}",
        lat_packed, lat_gg, MLNMatcher(lat_weights), ("smp", "mmp"),
    )
    lat["depth"] = LATTICE_DEPTH
    lat["width"] = LATTICE_WIDTH
    out["instances"]["lattice"] = lat
    return out


if __name__ == "__main__":
    main()

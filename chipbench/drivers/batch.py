"""Batch resolution: ``pipeline.prepare`` -> ``run_parallel`` on one chip.

The corpus is the configuration's (``corpus_seed``): a seed-drawn corpus
or reference order changes the cover, and with it the work, by a few
percent, so every run resolves the same corpus and ``--seed`` changes no
input of this cell.  Set-up makes the corpus, builds the cover
and the global grounding (``prepare``) and runs one resolution, which
compiles every program the window uses.  The window repeats the
resolution of that cover, each with a fresh grounding cache as a batch
caller gets it, and ends with the resolution running at ``--seconds``.

Metric: ``batch_resolve_s``, the window over the number of complete
resolutions.

Correctness: every resolution of the window must reach the plain
reference's fixpoint (``scheme`` of the traffic) over the whole corpus.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import corpus as corpuslib
from chipbench import reference


class Driver:
    def __init__(self, run, devices, log):
        self.run, self.log = run, log
        self.cfg, self.tr = run.config, run.traffic

    def setup(self) -> None:
        from repro.core import pipeline
        from repro.core.mln import MLNMatcher, MLNWeights
        from repro.core.parallel import make_em_mesh
        from repro.core.types import EntityTable, Relations

        m = self.cfg["matcher"]
        self.corpus = c = corpuslib.generate(self.cfg["corpus"], self.cfg["corpus_seed"])
        weights = MLNWeights(w_sim=tuple(m["weights"]["w_sim"]), w_co=m["weights"]["w_co"])
        t = time.perf_counter()
        self.packed, self.gg, _ = pipeline.prepare(
            EntityTable(names=list(c.names), truth=c.truth),
            Relations(edges={"coauthor": c.edges}),
            weights=weights, k_max=m["k_max"], t_loose=m["t_loose"], t_tight=m["t_tight"],
            thresholds=tuple(m["level_thresholds"]),
        )
        t_prep = time.perf_counter() - t
        self.matcher = MLNMatcher(weights)
        self.mesh = make_em_mesh(1)
        t = time.perf_counter()
        first = self._resolve()
        self.log(f"corpus {len(c)} refs; prepare {t_prep:.3f} s: "
                 f"{self.packed.num_neighborhoods} neighborhoods, {len(self.gg.gids)} pairs; "
                 f"first resolution {time.perf_counter() - t:.3f} s, {len(first)} matches")

    def _resolve(self) -> np.ndarray:
        from repro.core.parallel import run_parallel

        res = run_parallel(self.packed, self.matcher, self.gg,
                           scheme=self.tr["scheme"], mesh=self.mesh)
        return np.asarray(res.matches.gids, dtype=np.int64)

    def window(self, tracer) -> dict:
        run = self.run
        self.results: dict[bytes, np.ndarray] = {}
        n = 0
        t0 = run.t0 = time.perf_counter()
        while True:
            gids = self._resolve()
            n += 1
            self.results.setdefault(gids.tobytes(), gids)
            now = time.perf_counter()
            tracer.tick(now - t0)
            if now - t0 >= run.seconds:
                break
        run.t1 = now
        run.units = n
        self.log(f"{n} resolutions, {len(self.results)} distinct results")
        return {"batch_resolve_s": (now - t0) / n}

    def counts(self) -> tuple[int, int]:
        return self.run.units, 0

    def release(self) -> None:
        del self.packed, self.gg, self.matcher

    def check(self) -> dict:
        m = self.cfg["matcher"]
        c = self.corpus
        t = time.perf_counter()
        self.ref_input = (c.names, c.edges, self.tr["scheme"])
        inst = reference.instance(c.names, c.edges, m)
        want = self.want = reference.fixpoint(inst, self.tr["scheme"], m)
        diff = max(len(np.setxor1d(want, got)) for got in self.results.values())
        self.log(f"reference: {inst.n} neighborhoods, {len(inst.gids)} candidate pairs, "
                 f"{len(want)} matches in {time.perf_counter() - t:.3f} s")
        return {"match_diff": {"value": diff, "limit": self.cfg["limits"]["match_diff"]}}

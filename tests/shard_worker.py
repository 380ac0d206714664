"""Subprocess worker for the sharded-equivalence battery.

Usage: ``python shard_worker.py <mode> <scheme> <n_batches> <perm_seed>``

* ``mode`` — ``hepth`` (stream a synthetic corpus through a
  :class:`~repro.stream.shard.ShardCoordinator`) or ``lattice`` (drive
  ``run_parallel`` on the hand-packed evidence lattice).
* ``perm_seed`` — ``-1`` for arrival order; otherwise the seed of a
  batch-order permutation (global ids are preserved via ``ingest(...,
  ids=...)``, so the permuted schedule resolves the same corpus).

Topology comes entirely from the environment, set by the parent test:
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for the
single-process multi-device leg, ``REPRO_SHARD_COORD`` / ``_N`` /
``_ID`` for the true multi-process leg (both must be set before jax
imports, which is why this is a subprocess).  Prints ``DIGEST <hex>``
and ``AGREE <0|1>`` on stdout.
"""

from __future__ import annotations

import sys


def main() -> None:
    mode, scheme, n_batches, perm_seed = sys.argv[1:5]

    import numpy as np

    from repro.stream.shard import ShardContext

    ctx = ShardContext.create()

    if mode == "lattice":
        from repro.core.global_grounding import build_global_grounding
        from repro.core.mln import MLNMatcher
        from repro.core.parallel import run_parallel
        from repro.data.synthetic import make_lattice_cover
        from repro.stream.digest import match_digest

        packed, relations, weights = make_lattice_cover(depth=6, width=4)
        gg = (
            build_global_grounding(packed.pair_levels, relations, weights)
            if scheme == "mmp"
            else None
        )
        res = run_parallel(
            packed, MLNMatcher(weights), gg, scheme=scheme, mesh=ctx.mesh
        )
        print("DIGEST", match_digest(res.matches))
        print("AGREE", 1, flush=True)
        return

    from repro.data.synthetic import SynthConfig, arrival_stream, make_dataset
    from repro.stream.shard import ShardCoordinator

    batches = arrival_stream(
        make_dataset(SynthConfig.hepth(scale=0.02, seed=3)), int(n_batches)
    )
    order = list(range(len(batches)))
    if int(perm_seed) >= 0:
        order = [
            int(i)
            for i in np.random.default_rng(int(perm_seed)).permutation(
                len(batches)
            )
        ]
    coord = ShardCoordinator(ctx, scheme=scheme, parallel=True)
    for i in order:
        b = batches[i]
        coord.ingest(list(b.names), b.edges, ids=[int(x) for x in b.ids])
    print("DIGEST", coord.digest())
    print("AGREE", int(coord.digests_agree()), flush=True)


if __name__ == "__main__":
    main()

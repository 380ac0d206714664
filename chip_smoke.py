"""Smoke run of the entity-matching path on a TPU.

    python chip_smoke.py               # one chip: batch, equality, served
    python chip_smoke.py --four-chips  # four chips: sharded served stream

One process drives the system through the entry points a user calls and
checks the results by the repo's own oracles:

(a) batch: HEPTH-shaped corpus -> ``pipeline.prepare`` -> ``run_parallel``
    for SMP and MMP on one chip, scored against the generator's ground
    truth (recall ordering and floor of ``tests/test_e2e_em.py``);
(b) equality: the ``run_parallel`` fixpoints at HEPTH scale 2 have the
    ``match_digest`` of the sequential ``run_smp`` / ``run_mmp``;
(c) served: a DBLP-shaped arrival stream (one bulk ingest, then
    micro-batches) through ``ServingFrontend`` -> ``ResolveService`` with
    the device engine while a reader thread resolves ids; the final
    ``state_digest`` equals that of a sequential-engine service fed the
    same batches.

``--four-chips`` runs only the served stream of (c), once on a mesh of
four chips (bin rows sharded, the match bitset exchanged with ``psum``)
and once on one chip, and requires equal state digests.

Every phase prints its wall time, the XLA compilations it triggered and
the device's peak HBM so far.  Any mismatch or exception exits non-zero.
Without a TPU, or with the kernels routed anywhere but the compiled
Pallas path, the script refuses to run.  The last line of stdout is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# keep the TPU runtime's logs out of the shared temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# Full HEPTH is 58,515 references (scale ~31.4) and does not fit one v5e
# (15.75 GiB of HBM): the TPU compiler refuses its 32-entity-bin
# grounding, whose cold build pads that bin's ~8,050 rows to 8,192 and
# would need 30 GB.  The compiler's peak for that grounding is 11.6 GB at
# 2,048 padded rows and 20.2 GB at 4,096, so the bin must stay within
# 2,048 rows to leave 20% of HBM free.  Scale 5.8 gives it about 1,840
# rows; scale 6 already gives 2,056.
HEPTH_SCALE = 5.8
EQUALITY_SCALE = 2.0
STREAM_SCALE = 4.0  # DBLP-shaped: about 6,800 references
MICRO_BATCHES = 10
MICRO_BATCH = 64
INGEST_TIMEOUT_S = 600.0


class CompileCounter:
    """Counts XLA backend compilations through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_hbm(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


class Phase:
    """Prints one phase's wall time, compilations and peak HBM."""

    def __init__(self, name, counter, device):
        self.name, self.counter, self.device = name, counter, device

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.counter.compiles
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(
                f"[{self.name}] wall_s={time.perf_counter() - self.t0:.3f} "
                f"compiles={self.counter.compiles - self.c0} "
                f"peak_hbm_bytes={peak_hbm(self.device)}",
                flush=True,
            )
        return False


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_tpu():
    import jax

    from repro.kernels.common import pallas_mode

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}"
        )
    check(pallas_mode() == "compiled", "kernels not on the compiled Pallas path")
    return devices


# ---------------------------------------------------------------------------
# (a) batch resolution through run_parallel
# ---------------------------------------------------------------------------


def phase_batch(counter, device, mesh):
    from repro.core import metrics as metricslib
    from repro.core import pipeline
    from repro.core.closure import transitive_closure
    from repro.core.mln import MLNMatcher
    from repro.core.parallel import run_parallel
    from repro.data.synthetic import SynthConfig, make_dataset

    with Phase("batch", counter, device):
        print(
            f"[batch] HEPTH scale {HEPTH_SCALE} (full size is ~31.4): cut to "
            "fit one chip's HBM, see HEPTH_SCALE", flush=True,
        )
        ds = make_dataset(SynthConfig.hepth(scale=HEPTH_SCALE, seed=7))
        packed, gg, t_prep = pipeline.prepare(ds.entities, ds.relations)
        shapes = {k: nb.pair_mask.shape for k, nb in sorted(packed.bins.items())}
        print(
            f"[batch] refs={ds.n_refs} neighborhoods={packed.num_neighborhoods} "
            f"pairs={len(gg.gids)} bins(rows,pairs)={shapes} "
            f"prepare_s={t_prep:.3f} compiles={counter.compiles}",
            flush=True,
        )
        matcher = MLNMatcher()
        prf = {}
        for scheme in ("smp", "mmp"):
            c0 = counter.compiles
            res = run_parallel(packed, matcher, gg, scheme=scheme, mesh=mesh)
            prf[scheme] = metricslib.prf(
                transitive_closure(res.matches), ds.entities.truth,
                candidate_gids=gg.gids,
            )
            print(
                f"[batch] {scheme}: wall_s={res.wall_time_s:.3f} "
                f"rounds={res.rounds} dispatches={res.dispatches} "
                f"matches={len(res.matches)} P={prf[scheme].precision:.4f} "
                f"R={prf[scheme].recall:.4f} F1={prf[scheme].f1:.4f} "
                f"compiles={counter.compiles - c0} "
                f"peak_hbm_bytes={peak_hbm(device)}",
                flush=True,
            )
        check(prf["smp"].recall <= prf["mmp"].recall + 1e-9, "recall SMP > MMP")
        check(prf["mmp"].recall > 0.5, f"MMP recall {prf['mmp'].recall}")


# ---------------------------------------------------------------------------
# (b) device fixpoint == sequential fixpoint
# ---------------------------------------------------------------------------


def phase_equality(counter, device, mesh):
    from repro.core import pipeline
    from repro.core.driver import run_mmp, run_smp
    from repro.core.mln import MLNMatcher
    from repro.core.parallel import run_parallel
    from repro.data.synthetic import SynthConfig, make_dataset
    from repro.stream.digest import match_digest

    with Phase("equality", counter, device):
        ds = make_dataset(SynthConfig.hepth(scale=EQUALITY_SCALE, seed=7))
        packed, gg, _ = pipeline.prepare(ds.entities, ds.relations)
        matcher = MLNMatcher()
        seq = {"smp": run_smp(packed, matcher), "mmp": run_mmp(packed, matcher, gg)}
        for scheme, ref in seq.items():
            res = run_parallel(packed, matcher, gg, scheme=scheme, mesh=mesh)
            d_par, d_seq = match_digest(res.matches), match_digest(ref.matches)
            print(
                f"[equality] {scheme}: refs={ds.n_refs} matches={len(res.matches)} "
                f"parallel={d_par[:16]} sequential={d_seq[:16]}",
                flush=True,
            )
            check(d_par == d_seq, f"{scheme} parallel fixpoint != sequential")


# ---------------------------------------------------------------------------
# (c) served stream
# ---------------------------------------------------------------------------


def dblp_stream():
    """One bulk batch, then MICRO_BATCHES batches of about MICRO_BATCH."""
    from repro.data.synthetic import SynthConfig, arrival_stream, make_dataset

    ds = make_dataset(SynthConfig.dblp(scale=STREAM_SCALE, seed=11))
    batches = arrival_stream(ds, batch_size=MICRO_BATCH)
    head, tail = batches[:-MICRO_BATCHES], batches[-MICRO_BATCHES:]
    bulk = (
        [int(i) for b in head for i in b.ids],
        [n for b in head for n in b.names],
        np.concatenate([b.edges for b in head]),
    )
    return [bulk] + [([int(i) for i in b.ids], list(b.names), b.edges) for b in tail]


def _reader(frontend, stop, published, errors, reads):
    rng = np.random.default_rng(0)
    try:
        # a millisecond between reads: a spinning reader would hold the
        # interpreter lock against the ingest worker
        while not stop.wait(0.001):
            if not published:
                continue
            ids = rng.choice(published, size=16)
            for e, members in zip(ids, frontend.resolve_many(ids)):
                if int(e) not in set(int(m) for m in members):
                    raise AssertionError(f"resolve({e}) misses itself: {members}")
            reads[0] += len(ids)
    except BaseException as err:  # reported by the main thread
        errors.append(err)


def serve_stream(stream, counter, tag, shard):
    """Feed ``stream`` through ServingFrontend -> ResolveService(parallel)
    on the shard context's mesh; return the service's state digest."""
    from repro.stream.digest import state_digest
    from repro.stream.service import ResolveService, ServiceConfig
    from repro.stream.serving import ServingConfig, ServingFrontend

    svc = ResolveService(ServiceConfig(scheme="smp", parallel=True), shard=shard)
    stop, errors, reads, published = threading.Event(), [], [0], []
    with ServingFrontend(svc, ServingConfig(max_delay_ms=0)) as fe:
        reader = threading.Thread(
            target=_reader, args=(fe, stop, published, errors, reads), daemon=True
        )
        reader.start()
        try:
            for i, (ids, names, edges) in enumerate(stream):
                c0, t0 = counter.compiles, time.perf_counter()
                # waiting for each ticket keeps one request per ingest, so
                # the reference below sees the same batch boundaries
                report = fe.submit(names, edges, ids).wait(INGEST_TIMEOUT_S)
                published.extend(ids)
                print(
                    f"[{tag}] ingest {i}: batch={len(ids)} wall_s="
                    f"{time.perf_counter() - t0:.3f} compiles={counter.compiles - c0} "
                    f"dirty={report.n_dirty} entities={report.n_entities}",
                    flush=True,
                )
        finally:
            stop.set()
            reader.join(60)
    check(not reader.is_alive(), "reader thread did not stop")
    if errors:
        raise errors[0]
    check(reads[0] > 0, "reader thread resolved nothing")
    print(f"[{tag}] reader resolved {reads[0]} ids during the stream", flush=True)
    return state_digest(svc)


def reference_digest(stream):
    from repro.stream.digest import state_digest
    from repro.stream.service import ResolveService, ServiceConfig

    ref = ResolveService(ServiceConfig(scheme="smp", parallel=False))
    for ids, names, edges in stream:
        ref.ingest(names, edges, ids=ids)
    return state_digest(ref)


def phase_served(counter, device, shard):
    with Phase("served", counter, device):
        stream = dblp_stream()
        print(
            f"[served] DBLP scale {STREAM_SCALE}: bulk {len(stream[0][0])} refs, "
            f"then {len(stream) - 1} micro-batches", flush=True,
        )
        got = serve_stream(stream, counter, "served", shard)
        want = reference_digest(stream)
        print(f"[served] digest served={got[:16]} reference={want[:16]}", flush=True)
        check(got == want, "served state digest != sequential reference")


def phase_four_chips(counter, devices):
    from repro.stream.shard import ShardContext

    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    with Phase("four_chips", counter, devices[0]):
        stream = dblp_stream()
        ctx4 = ShardContext.create(4)
        check(ctx4.mesh.devices.size == 4, "four-chip mesh")
        d4 = serve_stream(stream, counter, "four_chips/4", ctx4)
        peaks = [peak_hbm(d) for d in devices[:4]]
        print(f"[four_chips] peak_hbm_bytes per chip after the 4-chip leg: {peaks}",
              flush=True)
        check(all(p > 0 for p in peaks), "a chip of the mesh held nothing")
        d1 = serve_stream(stream, counter, "four_chips/1", ShardContext.create(1))
        print(f"[four_chips] digest 4 chips={d4[:16]} 1 chip={d1[:16]}", flush=True)
        check(d4 == d1, "4-chip state digest != 1-chip state digest")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the served stream on four chips vs one")
    args = ap.parse_args(argv)

    import jax

    from repro.core.parallel import make_em_mesh
    from repro.kernels.common import use_compile_cache
    from repro.stream.shard import ShardContext

    cache = use_compile_cache()
    devices = require_tpu()
    print(
        f"devices: {len(devices)} x {devices[0].device_kind}; jax {jax.__version__}; "
        f"hbm_bytes_limit={devices[0].memory_stats()['bytes_limit']}; "
        f"compile cache {cache}", flush=True,
    )
    counter = CompileCounter()
    if args.four_chips:
        phase_four_chips(counter, devices)
        count = 4
    else:
        device = devices[0]
        mesh = make_em_mesh(1)
        phase_batch(counter, device, mesh)
        phase_equality(counter, device, mesh)
        phase_served(counter, device, ShardContext.create(1))
        count = len(devices)
    print(f"compiles total={counter.compiles} cache_hits={counter.cache_hits}",
          flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": count,
        },
    }))


if __name__ == "__main__":
    main()

"""Sharded-serving scaling: ingest throughput + resolve QPS vs shards.

Spawns one ``jax.distributed`` CPU-mesh worker process per shard
(``shard_scaling_worker.py``) at shard counts {1, 2, 4} over a
10x-hepth synthetic corpus (``scale=1.2`` vs the 0.12 the stream
benchmark uses; smoke drops back to 0.12) and reports, per count:

* **ingest throughput** — refs/s through the full arrival stream,
  bounded by the slowest replica (the host state is SPMD-replicated;
  the device bin rounds and the LSH probe union are what's sharded);
* **aggregate resolve QPS** — the sum of per-replica Zipf-read QPS.
  Reads are replica-local (no collectives), so read capacity is the
  axis that scales with the shard count;
* the **state digest** of every replica — all replicas of a count must
  agree, and every count must land on the 1-shard digest bit-for-bit
  (the ISSUE-9 equivalence bar, re-checked at benchmark scale).

Wall-clock scaling on one box is bounded by the physical core count —
N co-scheduled replicas on fewer than N cores timeshare — so the JSON
records ``cpu_count`` and ``check_bench --gate=shard`` only enforces
the 2-shard efficiency floor where two shards could actually run in
parallel.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

from benchmarks.common import SMOKE, row

SHARD_COUNTS = (1, 2, 4)
SCALE = float(os.environ.get("BENCH_SHARD_SCALE", "0.12" if SMOKE else "1.2"))
# resolves are ~microsecond dict lookups: the count must be large
# enough that the timed read phase spans a scheduler-meaningful window,
# or the QPS ratio between shard counts is pure timer noise
N_QUERIES = 200_000 if SMOKE else 1_000_000
SCHEME = os.environ.get("BENCH_SHARD_SCHEME", "smp")
# per-replica wall: N co-scheduled replicas on a box with < N cores
# timeshare one corpus ingest each, so the 4-shard leg can run ~4x the
# 1-shard wall — the bound must leave headroom for that, not just for
# the single-replica cost
TIMEOUT_S = 900 if SMOKE else 7200

_WORKER = str(Path(__file__).resolve().with_name("shard_scaling_worker.py"))
_SRC = str(Path(__file__).resolve().parent.parent / "src")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _base_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC
    env["SHARD_BENCH_SCALE"] = str(SCALE)
    env["SHARD_BENCH_QUERIES"] = str(N_QUERIES)
    env["SHARD_BENCH_SCHEME"] = SCHEME
    # topology is per-spawn; never inherit a stale mesh from the caller
    for k in ("REPRO_SHARD_COORD", "REPRO_SHARD_N", "REPRO_SHARD_ID"):
        env.pop(k, None)
    return env


def _collect(procs) -> list[dict]:
    outs, fail = [], []
    try:
        for p in procs:
            out, err = p.communicate(timeout=TIMEOUT_S)
            if p.returncode != 0:
                fail.append(f"rc={p.returncode}\n{out}\n{err}")
                continue
            res = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
            if not res:
                fail.append(f"no RESULT line\n{out}\n{err}")
                continue
            outs.append(json.loads(res[-1][len("RESULT "):]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if fail:
        raise RuntimeError("shard worker failed:\n" + "\n".join(fail))
    return outs


def _run_multiprocess(n_shards: int) -> list[dict]:
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for i in range(n_shards):
        env = _base_env()
        if n_shards > 1:
            env["REPRO_SHARD_COORD"] = coord
            env["REPRO_SHARD_N"] = str(n_shards)
            env["REPRO_SHARD_ID"] = str(i)
        procs.append(
            subprocess.Popen(
                [sys.executable, _WORKER],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=env,
            )
        )
    return _collect(procs)


def main() -> dict:
    shards = []
    row("n_shards", "mode", "refs", "ingest_s", "refs_per_s",
        "resolve_qps", "agree")
    for n in SHARD_COUNTS:
        t0 = time.perf_counter()
        workers = _run_multiprocess(n)
        mode = "multiprocess" if n > 1 else "single"
        wall = time.perf_counter() - t0
        digests = {w["digest"] for w in workers}
        if len(digests) != 1:
            raise RuntimeError(f"replica digests diverged at {n} shards")
        if not all(w["agree"] for w in workers):
            raise RuntimeError(f"replica digest all-gather disagreed at {n}")
        refs = workers[0]["refs"]
        # system ingest throughput: the corpus is ingested once
        # logically; the slowest replica bounds it
        ingest_s = max(w["ingest_s"] for w in workers)
        entry = {
            "n_shards": n,
            "mode": mode,
            "refs": refs,
            "ingest_s": round(ingest_s, 3),
            "ingest_refs_per_s": round(refs / ingest_s, 2),
            "resolve_qps_total": round(
                sum(w["resolve_qps"] for w in workers), 1
            ),
            "n_queries_per_replica": workers[0]["n_queries"],
            "digest": digests.pop(),
            "replicas_agree": True,
            "wall_s": round(wall, 3),
        }
        shards.append(entry)
        row(n, mode, refs, entry["ingest_s"],
            entry["ingest_refs_per_s"], entry["resolve_qps_total"], 1)
    digest_equal = len({e["digest"] for e in shards}) == 1
    if not digest_equal:
        raise RuntimeError(
            "sharded fixpoint digests diverged across shard counts: "
            + ", ".join(f"{e['n_shards']}:{e['digest'][:12]}" for e in shards)
        )
    base_qps = shards[0]["resolve_qps_total"]
    for e in shards:
        e["qps_scaling_eff"] = round(
            e["resolve_qps_total"] / (e["n_shards"] * base_qps), 3
        )
    row("qps_eff", *[e["qps_scaling_eff"] for e in shards])
    return {
        "benchmark": "shard_scaling",
        "smoke": SMOKE,
        "scheme": SCHEME,
        "scale": SCALE,
        "cpu_count": os.cpu_count(),
        "mesh": mesh_ok,
        "shards": shards,
        "digest_equal": digest_equal,
    }


if __name__ == "__main__":
    main()

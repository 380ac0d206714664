"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--smoke] [--json] [--json-dir=DIR] [name ...]``
— default runs all.  Output is CSV-ish blocks, one per artifact.

``--smoke`` shrinks every benchmark to a CI-sized instance (tiny
corpora, fewer shapes) so the benchmark modules are exercised end to
end on every push without burning CI minutes — the numbers are
meaningless at that scale; the point is that the modules can't silently
rot.  It must be handled here, before any benchmark module (and hence
``benchmarks.common``) is imported, because the scale factors are read
from the environment at import time.

``--json`` additionally writes the structured results of the modules
that return them (``table1_parallel`` -> ``BENCH_parallel.json``,
``stream_throughput`` -> ``BENCH_stream.json``, ``shard_scaling`` ->
``BENCH_shard.json``; ``fig4_matchers`` merges into
``BENCH_parallel.json`` under its own key) into ``--json-dir``
(default: the repo root).  The committed copies are the perf baseline
trajectory; CI regenerates them at smoke scale and fails if the
per-round host dispatch counts regress (``benchmarks.check_bench``).

A module that raises fails the run with a non-zero exit *after* the
remaining modules have run, and its JSON is never written — a partial
file would otherwise feed ``check_bench`` a stale or truncated result
that mis-compares against the committed baseline.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

try:  # installed package (pip install -e .) ...
    import repro  # noqa: F401
except ImportError:  # ... or the src-layout checkout without install
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "src"))

MODULES = [
    ("fig3_accuracy", "Fig 3(a)-(c): P/R/F1 + completeness, MLN"),
    ("fig3_runtime", "Fig 3(d)/(e): running times, MLN"),
    ("fig3_scaling", "Fig 3(f): time vs #neighborhoods"),
    ("table1_parallel", "Table 1: parallel rounds / grid speedup"),
    ("fig4_rules", "Fig 4: RULES matcher"),
    ("fig4_matchers", "Fig 4 ext: registered matcher families, quality + runtime"),
    ("stream_throughput", "Streaming ingest: entities/sec vs micro-batch size"),
    ("loadgen", "Serving load generator: Poisson ingest + Zipf readers"),
    ("kernels_bench", "Pallas-kernel roofline microbench"),
    ("shard_scaling", "Sharded serving: ingest/QPS scaling vs shard count"),
]

JSON_FILES = {
    "table1_parallel": "BENCH_parallel.json",
    "stream_throughput": "BENCH_stream.json",
    "shard_scaling": "BENCH_shard.json",
}

# Modules whose result is merged into another module's JSON as one top-
# level key instead of owning a file (fig4_matchers rides in the
# parallel baseline, where check_bench's parallel-family gates look).
JSON_MERGE = {
    "fig4_matchers": ("BENCH_parallel.json", "fig4_matchers"),
}


def main() -> None:
    args = [a for a in sys.argv[1:]]
    if "--smoke" in args:
        args = [a for a in args if a != "--smoke"]
        os.environ["BENCH_SMOKE"] = "1"
    emit_json = "--json" in args
    args = [a for a in args if a != "--json"]
    json_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for a in list(args):
        if a.startswith("--json-dir="):
            json_dir = a.split("=", 1)[1]
            args.remove(a)
    want = set(args)
    unknown = want - {name for name, _ in MODULES}
    if unknown:
        raise SystemExit(f"unknown benchmark(s): {sorted(unknown)}")
    from repro.kernels.common import use_compile_cache

    use_compile_cache()
    failures: list[str] = []
    for name, desc in MODULES:
        if want and name not in want:
            continue
        print(f"\n==== {name}: {desc} ====", flush=True)
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            result = mod.main()
        except Exception:
            # A raising module must fail the whole run (non-zero exit) and
            # must NOT leave a JSON for check_bench to mis-compare; the
            # remaining modules still run so one breakage doesn't mask
            # another's results.
            traceback.print_exc()
            failures.append(name)
            print(f"==== {name} FAILED in {time.perf_counter()-t0:.1f}s ====",
                  flush=True)
            continue
        print(f"==== {name} done in {time.perf_counter()-t0:.1f}s ====", flush=True)
        if emit_json and result is not None and name in JSON_FILES:
            os.makedirs(json_dir, exist_ok=True)
            path = os.path.join(json_dir, JSON_FILES[name])
            with open(path, "w") as f:
                json.dump(result, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote {path}", flush=True)
        elif emit_json and result is not None and name in JSON_MERGE:
            fname, key = JSON_MERGE[name]
            os.makedirs(json_dir, exist_ok=True)
            path = os.path.join(json_dir, fname)
            blob = {}
            if os.path.exists(path):
                with open(path) as f:
                    blob = json.load(f)
            blob[key] = result
            with open(path, "w") as f:
                json.dump(blob, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"merged {key!r} into {path}", flush=True)
    if failures:
        raise SystemExit(f"benchmark module(s) raised: {failures}")


if __name__ == "__main__":
    main()

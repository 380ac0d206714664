"""Readings that set a cell's limits: the program's and the control's.

    python3 -m chipbench.readings --workload <name> --seeds 1,2,3 --seconds 20 [--control]

For each seed, in one process (so set-up compiles once per shape): the
cell's set-up and a window of ``--seconds`` at the cell's own load, then
the comparison with the plain reference that decides ``correct``, which
gives the program's reading of each compared number.  With ``--control``
the reference is also computed in bfloat16 (``reference.py``,
``bf16=True``) and put in the program's place: its reading of
``match_diff`` is the control's, which the limit must reject.  One JSON
line per seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench import harness, reference


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--corpus-seeds", action="store_true",
                    help="make each seed's corpus from the seed instead of the configuration's "
                         "corpus_seed (readings over many corpora)")
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = harness.load_json(harness.PKG / "configs" / f"{cell['config']}.json")
    traffic = harness.load_json(harness.PKG / "traffic" / f"{cell['traffic']}.json")
    sys.path.insert(0, str(harness.ROOT / "src"))
    import importlib

    import jax

    from repro import obs
    from repro.kernels.common import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = harness.require_devices(int(cell["chips"]))
    compiles = harness.CompileLog()
    registry = obs.get_registry()
    registry.set_tracing(False)
    driver_mod = importlib.import_module(f"chipbench.drivers.{config['kind']}")
    m = config["matcher"]

    def log(msg):
        print(f"[{args.workload}] {msg}", file=sys.stderr, flush=True)

    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.corpus_seeds:
            config = dict(config, corpus_seed=seed)
        run = harness.Run(cell, config, traffic, seed, args.seconds, registry, compiles)
        run.device_kind = devices[0].device_kind
        run.cache_dir = cache_dir
        driver = driver_mod.Driver(run, devices, log)
        t = time.perf_counter()
        driver.setup()
        e2e = driver.window(harness.Tracer(False, args.seconds, None))
        driver.counts()
        driver.release()
        checks = driver.check()
        out = {"seed": seed, "program": {k: v["value"] for k, v in checks.items()},
               "window": e2e, "seconds": time.perf_counter() - t}
        if args.control:
            names, edges, scheme = driver.ref_input
            t = time.perf_counter()
            ctl = reference.fixpoint(reference.instance(names, edges, m, bf16=True),
                                     scheme, m, bf16=True)
            out["control"] = {"match_diff": int(len(set(ctl.tolist()) ^ set(driver.want.tolist())))}
            out["control_seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

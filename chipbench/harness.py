"""Chip benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (``chipbench/configs/<config>.json``, whose
``kind`` picks the driver ``chipbench/drivers/<kind>.py``) and a traffic
mix (``chipbench/traffic/<traffic>.json``); each per-layer metric is read
by ``chipbench/metrics/<metric>.py``.  A run:

1. refuses to run without a TPU holding the cell's chips, or with the
   kernels anywhere but on the compiled Pallas path;
2. sets up: the corpus, loading, and every program the window will use,
   compiled or taken from JAX's persistent compilation cache (kept where
   ``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``);
3. measures for ``--seconds`` (``--trace 1``: obs spans on and a
   ``jax.profiler`` trace over the middle half of the window);
4. reads the device's peak memory, frees the program's state, and
   compares what the window produced with the plain reference
   (``chipbench/reference.py``);
5. prints the compared numbers beside their limits as the last lines of
   standard error, and one JSON result as the last line of standard
   output.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent
OUT = ROOT / "bench-out" / "chipbench"


def process_age_s() -> float:
    """Seconds since this process started (set-up counts from there)."""
    start_ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
    uptime = float(Path("/proc/uptime").read_text().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def fail(msg: str, code: int = 3):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


class CompileLog:
    """XLA programs loaded, by ``jax.monitoring``'s backend-compile event
    (which also fires when the persistent cache serves the program), and
    the persistent cache's misses: the programs really compiled."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, float]] = []  # (end perf_counter, seconds)
        self.miss_times: list[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.events.append((time.perf_counter(), float(duration)))

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.miss_times.append(time.perf_counter())

    @property
    def misses(self) -> int:
        return len(self.miss_times)

    def misses_between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(t0 <= t <= t1 for t in self.miss_times)

    def between(self, t0: float, t1: float) -> tuple[int, float]:
        with self._lock:
            sel = [d for t, d in self.events if t0 <= t <= t1]
        return len(sel), sum(sel)


class Tracer:
    """Profiler trace over the middle half of the window, started and
    stopped from the driver's loop through :meth:`tick`."""

    def __init__(self, enabled: bool, seconds: float, outdir: Path):
        self.enabled = enabled
        self.lo, self.hi = 0.25 * seconds, 0.75 * seconds
        self.outdir = outdir
        self.state = "idle"
        self.t_start = self.t_stop = None
        self.anchor = None  # (time.time_ns(), perf_counter_ns()) at start

    def tick(self, elapsed: float) -> None:
        import jax

        if not self.enabled:
            return
        if self.state == "idle" and elapsed >= self.lo:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.outdir), profiler_options=opts)
            self.anchor = (time.time_ns(), time.perf_counter_ns())
            self.t_start = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.hi:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.state = "done"


class Run:
    """What a driver's window leaves for the per-layer readers."""

    def __init__(self, cell, config, traffic, seed, seconds, registry, compiles):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = seed, seconds
        self.registry, self.compiles = registry, compiles
        self.t0 = self.t1 = None  # window, perf_counter
        self.units = 0  # units of work done in the window (resolutions)
        self.counters0: dict = {}
        self.counters1: dict = {}
        self.spans: list = []
        self.trace = None  # trace_reduce.Reduced of the traced slice
        self.device_kind = None
        self.cache_dir = None

    def counter_delta(self, name: str) -> float:
        return self.counters1.get(name, 0) - self.counters0.get(name, 0)

    def window_spans(self):
        return [s for s in self.spans if self.t0 <= s.t_start and s.t_start + s.dur_s <= self.t1]

    def span_seconds(self, names, self_time: bool = True) -> float:
        from chipbench.trace_reduce import span_self_times

        spans = self.window_spans()
        own = span_self_times(spans) if self_time else [s.dur_s for s in spans]
        return sum(d for s, d in zip(spans, own) if s.name in names)

    def compile_seconds(self) -> float:
        return self.compiles.between(self.t0, self.t1)[1]


def per_layer_readers(bench: dict, cell: dict, reports: set[str]):
    """(metric entry, reader module) of every per-layer metric this cell reports."""
    out = []
    for m in bench["per_layer"]:
        listed = m.get("workloads")
        if (cell["name"] in listed) if listed is not None else (m["moves"] in reports):
            path = PKG / "metrics" / f"{m['name']}.py"
            spec = importlib.util.spec_from_file_location(f"chipbench_metric_{len(out)}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            out.append((m, mod))
    return out


def end_to_end_names(bench: dict, cell: dict) -> list[str]:
    return [m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def require_devices(chips: int):
    import jax

    from repro.kernels.common import pallas_mode

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devices)}")
    if pallas_mode() != "compiled":
        fail("the kernels are not on the compiled Pallas path")
    return devices[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench", description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        fail(f"no workload {args.workload!r} in BENCHMARK.json", 2)
    cell = cells[args.workload]
    config = load_json(PKG / "configs" / f"{cell['config']}.json")
    traffic = load_json(PKG / "traffic" / f"{cell['traffic']}.json")

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        fail(f"the system under test is not in this checkout ({src})", 2)
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro import obs
    from repro.kernels.common import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = require_devices(int(cell["chips"]))
    compiles = CompileLog()
    registry = obs.get_registry()
    registry.set_tracing(bool(args.trace))
    driver_mod = importlib.import_module(f"chipbench.drivers.{config['kind']}")
    outdir = OUT / args.workload / f"seed{args.seed}"
    outdir.mkdir(parents=True, exist_ok=True)
    run = Run(cell, config, traffic, args.seed, args.seconds, registry, compiles)
    run.device_kind = devices[0].device_kind
    run.cache_dir = cache_dir
    log = lambda msg: print(f"[{args.workload}] {msg}", file=sys.stderr, flush=True)  # noqa: E731
    log(f"{len(devices)} x {devices[0].device_kind}, jax {jax.__version__}, "
        f"compile cache {cache_dir}, seed {args.seed}, trace {args.trace}")

    driver = driver_mod.Driver(run, devices, log)
    driver.setup()
    setup_s = process_age_s()
    n_setup = len(compiles.events)
    log(f"set-up {setup_s:.3f} s, {n_setup} compiles ({compiles.misses} compiled, "
        f"the rest from the persistent cache)")
    tracer = Tracer(bool(args.trace), args.seconds, outdir / "trace")
    registry.spans.clear()
    run.counters0 = registry.snapshot()["counters"]
    e2e = driver.window(tracer)
    tracer.stop()
    run.counters1 = registry.snapshot()["counters"]
    run.spans = list(registry.spans)
    n_win, s_win = compiles.between(run.t0, run.t1)
    miss_win = compiles.misses_between(run.t0, run.t1)
    log(f"window {run.t1 - run.t0:.3f} s, {run.units} units, {n_win} programs loaded "
        f"taking {s_win:.3f} s, {miss_win} of them compiled (not in the persistent cache)")
    peak = max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    breakdown = None
    if args.trace:
        from chipbench import trace_reduce

        run.trace = trace_reduce.reduce_dir(outdir / "trace", tracer.anchor, run.spans)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        log(f"traced {run.trace.window_s:.3f} s, device busy {run.trace.busy_s:.3f} s")

    attempted, failed = driver.counts()
    driver.release()
    gc.collect()
    checks = driver.check()

    if args.trace:
        metrics = {}
        for m, reader in per_layer_readers(bench, cell, set(e2e)):
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        names = end_to_end_names(bench, cell)
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        e2e["setup_s"] = setup_s
        metrics = {n: {"value": e2e[n], "unit": units[n]} for n in names}

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)

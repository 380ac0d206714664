"""Reduce a ``jax.profiler`` trace of the window to device metrics.

From the ``.xplane.pb`` the profiler writes:

* the traced window: ``profile_stop_time - profile_start_time`` of the
  ``Task Environment`` plane;
* device busy time: the union of the intervals of the ``XLA Ops`` events
  of each ``/device:TPU:<n>`` plane, averaged over the chips;
* self time of every device op (an op's duration less the ops nested in
  it on the same line), named ``<jitted program>/<op>``;
* each Pallas kernel (``tpu_custom_call``): its self time, calls and the
  operand and result shapes printed in the event's HLO text, from which
  ``chipbench/roofline.py`` counts operations and bytes;
* idle gaps: the stretches in which no op runs, each named by the
  innermost obs span (``repro.obs`` span log, host ``perf_counter``)
  open at its midpoint.  The span clock is put on the trace's clock by
  one ``(time.time_ns(), perf_counter_ns())`` pair taken when tracing
  started; the trace's times are nanoseconds from ``profile_start_time``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def op_name(text: str) -> str:
    """``%sweep_matrix.9 = f32[...] custom-call(...)`` -> ``sweep_matrix``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(text: str) -> str:
    """``jit__bin_full_round(7435...)`` -> ``_bin_full_round``."""
    name = text.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def shapes(text: str) -> tuple[list, list]:
    """(result shapes, operand shapes) of an HLO instruction's text, each
    a list of ``(dtype, dims)``."""
    rhs = text.partition(" = ")[2]
    m = re.search(r" [a-z][\w-]*\(", rhs)
    if m is None:
        return [], []
    args = rhs[m.end():]
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    parse = lambda s: [(t, tuple(int(d) for d in dims.split(",") if d))  # noqa: E731
                       for t, dims in _SHAPE.findall(s)]
    return parse(rhs[: m.start()]), parse(args[:end])


def nbytes(shape) -> int:
    dtype, dims = shape
    n = _BYTES.get(dtype, 4)
    for d in dims:
        n *= d
    return n


def union_length(intervals) -> tuple[int, list[tuple[int, int]]]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def self_times(events) -> list[int]:
    """Self time of each ``(start, dur)`` event of one timeline: its
    duration less the events nested directly inside it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    own = [d for _, d in events]
    stack: list[int] = []
    for i in order:
        s, d = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def span_self_times(spans) -> list[float]:
    """Self time of each obs ``SpanRecord``: its duration less the spans
    opened directly inside it on the same thread."""
    by_thread: dict[int, list[int]] = {}
    for i, s in enumerate(spans):
        by_thread.setdefault(s.thread_id, []).append(i)
    own = [0.0] * len(spans)
    for idx in by_thread.values():
        ev = [(spans[i].t_start, spans[i].dur_s) for i in idx]
        for i, v in zip(idx, self_times(ev)):
            own[i] = v
    return own


@dataclasses.dataclass
class Kernel:
    name: str
    calls: int = 0
    seconds: float = 0.0
    shapes: list = dataclasses.field(default_factory=list)  # (results, operands) per call


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    op_seconds: dict  # "<program>/<op>" -> self seconds, summed over chips
    kernels: dict  # kernel name -> Kernel
    idle_gaps: dict  # host span name -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top], "idle_gaps": [[k, v] for k, v in gaps]}


def _stat(obj, key):
    for k, v in obj.stats:
        if k == key:
            return v
    return None


def reduce_profile(pd, anchor=None, spans=()) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``; ``anchor`` and ``spans`` name
    the idle gaps (see the module docstring)."""
    t_start = t_stop = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            t_start = int(_stat(plane, "profile_start_time"))
            t_stop = int(_stat(plane, "profile_stop_time"))
    window_ns = t_stop - t_start
    to_trace = None
    if anchor is not None:
        wall_ns, perf_ns = anchor
        offset = wall_ns - t_start - perf_ns
        to_trace = lambda perf_s: perf_s * 1e9 + offset  # noqa: E731
    named = []
    if to_trace is not None:
        for s in spans:
            named.append((to_trace(s.t_start), to_trace(s.t_start + s.dur_s), s.depth, s.name))

    busy_total, chips = 0, 0
    op_seconds: dict[str, float] = {}
    kernels: dict[str, Kernel] = {}
    idle: dict[str, float] = {}
    for plane in pd.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = lines.get("XLA Ops", [])
        if not ops:
            continue
        chips += 1
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, module_name(e.name))
                      for e in lines.get("XLA Modules", []))
        ev = [(int(e.start_ns), int(e.duration_ns)) for e in ops]
        busy, merged = union_length([(s, s + d) for s, d in ev])
        busy_total += busy
        own = self_times(ev)
        mi = 0
        for e, (s, _), o in sorted(zip(ops, ev, own), key=lambda t: t[1][0]):
            while mi < len(mods) and mods[mi][1] < s:
                mi += 1
            prog = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s else "?"
            name = op_name(e.name)
            key = f"{prog}/{name}"
            op_seconds[key] = op_seconds.get(key, 0.0) + o / 1e9
            if 'custom_call_target="tpu_custom_call"' in e.name:
                k = kernels.setdefault(name, Kernel(name))
                k.calls += 1
                k.seconds += o / 1e9
                k.shapes.append(shapes(e.name))
        edges = [0] + [x for iv in merged for x in iv] + [window_ns]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            open_ = [n for n in named if n[0] <= mid <= n[1]]
            who = max(open_, key=lambda n: n[2])[3] if open_ else "no span open"
            idle[who] = idle.get(who, 0.0) + (b - a) / 1e9
    chips = max(chips, 1)
    return Reduced(
        window_s=window_ns / 1e9,
        busy_s=busy_total / chips / 1e9,
        chips=chips,
        op_seconds=op_seconds,
        kernels=kernels,
        idle_gaps={k: v / chips for k, v in idle.items()},
    )


def newest_xplane(directory) -> str:
    paths = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(directory, anchor=None, spans=()) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(newest_xplane(directory)), anchor, spans)

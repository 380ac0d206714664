"""Batch resolution of a corpus at its source's size.

The ``batch`` driver's set-up, window and metric, with two differences
that the size asks for:

- the set-up's spans and the ``cover.*`` counters of ``prepare`` are
  kept on the run (``run.setup_spans``, ``run.setup_counters``): the
  harness clears the span log before the window, and the cover build
  (``cover.canopies``, ``cover.pack``, ``cover.levels``) runs only in
  set-up.  Spans are recorded with ``--trace 1`` only; the counters in
  every run.
- set-up first fixes glibc's mmap and trim thresholds at the largest
  values its dynamic rule reaches (32 and 64 MiB).  Left dynamic, they
  rise with the large blocks a process has freed, so the window's host
  temporaries of a few to tens of MB were served from the heap or from
  fresh pages depending on how much garbage set-up left: the same
  window and the same cover resolved 1.5% faster in one process after
  an older, slower pack had run in it (measured on one v5e chip).
- the check computes the reference's instance with
  ``chipbench/reference_at_scale.py``, which equals
  ``reference.instance`` without its two quadratic host steps; the
  matcher, grounding and fixpoint are ``reference.py``'s.
"""

from __future__ import annotations

import ctypes
import time

import numpy as np

from chipbench import reference, reference_at_scale
from chipbench.drivers import batch


M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3  # glibc mallopt parameters


def fix_malloc_thresholds() -> None:
    """Fix glibc's mmap and trim thresholds at the maxima of their dynamic
    rule; nothing happens where the C library is not glibc."""
    try:
        libc = ctypes.CDLL("libc.so.6")
        mallopt = libc.mallopt
    except (OSError, AttributeError):
        return
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], ctypes.c_int
    mallopt(M_MMAP_THRESHOLD, 32 << 20)
    mallopt(M_TRIM_THRESHOLD, 64 << 20)


class Driver(batch.Driver):
    def setup(self) -> None:
        fix_malloc_thresholds()
        reg = self.run.registry
        before = reg.snapshot()["counters"]
        super().setup()
        after = reg.snapshot()["counters"]
        self.run.setup_spans = list(reg.spans)
        self.run.setup_counters = {
            k: v - before.get(k, 0) for k, v in after.items() if k.startswith("cover.")
        }
        self.log(f"cover counters {self.run.setup_counters}")

    def check(self) -> dict:
        m = self.cfg["matcher"]
        c = self.corpus
        t = time.perf_counter()
        self.ref_input = (c.names, c.edges, self.tr["scheme"])
        inst = reference_at_scale.instance(c.names, c.edges, m)
        t_inst = time.perf_counter() - t
        want = self.want = reference.fixpoint(inst, self.tr["scheme"], m)
        diff = max(len(np.setxor1d(want, got)) for got in self.results.values())
        self.log(f"reference: {inst.n} neighborhoods, {len(inst.gids)} candidate pairs, "
                 f"{len(want)} matches in {time.perf_counter() - t:.3f} s "
                 f"(instance {t_inst:.3f} s)")
        return {"match_diff": {"value": diff, "limit": self.cfg["limits"]["match_diff"]}}

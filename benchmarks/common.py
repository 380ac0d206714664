"""Shared benchmark utilities: datasets, timing, CSV emission.

``SMOKE`` (env ``BENCH_SMOKE=1``, set by ``benchmarks.run --smoke``)
shrinks the default corpora so CI can exercise every benchmark module
end to end in seconds; modules consult it to trim their own grids too.
"""

from __future__ import annotations

import functools
import os
import time

from repro.core import metrics as metricslib
from repro.core import pipeline
from repro.data.synthetic import SynthConfig, make_dataset

SMOKE = os.environ.get("BENCH_SMOKE") == "1"

# CPU-CI scale factors.  Scale 1 gives about 1,842 HEPTH references and
# 1,704 DBLP references, so the paper's full sizes (HEPTH 58,515 refs /
# DBLP 50,195 / DBLP-BIG 4.6M) are about scale 31.4, 29.4 and 2,700.
_DEFAULT_SCALE = "0.03" if SMOKE else "0.12"
HEPTH_SCALE = float(os.environ.get("BENCH_HEPTH_SCALE", _DEFAULT_SCALE))
DBLP_SCALE = float(os.environ.get("BENCH_DBLP_SCALE", _DEFAULT_SCALE))


@functools.lru_cache(maxsize=None)
def hepth():
    return make_dataset(SynthConfig.hepth(scale=HEPTH_SCALE, seed=7))


@functools.lru_cache(maxsize=None)
def dblp():
    return make_dataset(SynthConfig.dblp(scale=DBLP_SCALE, seed=11))


@functools.lru_cache(maxsize=None)
def prepared(which: str):
    ds = hepth() if which == "hepth" else dblp()
    packed, gg, t = pipeline.prepare(ds.entities, ds.relations)
    return ds, packed, gg, t


def evaluate(ds, res) -> metricslib.PRF:
    return pipeline.evaluate(res, ds.entities.truth)


def row(*cols) -> str:
    line = ",".join(str(c) for c in cols)
    print(line, flush=True)
    return line


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0

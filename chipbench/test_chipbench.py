"""CPU tests of the chip benchmark.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/test_chipbench.py

They cover the trace reduction on a trace recorded on a TPU v5e
(``testdata/``), the corpus (deterministic per seed, the source's
ratios), the rules ``BENCHMARK.json`` keeps, the plain reference against
the program at a small size, and whole runs of each cell at a small size
with the chip check skipped: sound, and with the timed path broken
underneath or the bfloat16 control in its place, where ``correct`` has
to come out false.  Nothing here loads
the TPU library at import.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
PKG = ROOT / "chipbench"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {c["name"]: c for c in BENCH["workloads"]}
TRACE = PKG / "testdata" / "hepth_mmp_v5e.xplane.pb.gz"  # one MMP resolution, HEPTH scale 0.5
SMALL = 0.2  # scale of the whole runs on the CPU

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + list(CELLS)
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["config"] for c in CELLS.values()] + [c["traffic"] for c in CELLS.values()]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_every_cell_names_files_that_exist():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    for cell in CELLS.values():
        cfg = json.loads((PKG / "configs" / f"{cell['config']}.json").read_text())
        assert (PKG / "drivers" / f"{cfg['kind']}.py").is_file()
        assert (PKG / "traffic" / f"{cell['traffic']}.json").is_file()
        assert cell["chips"] == cfg["chips"] == 1
    for m in BENCH["per_layer"]:
        assert (PKG / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_per_layer_cells_report_the_metric_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    reported = {name: {n for n, m in e2e.items() if "workloads" not in m or name in m["workloads"]}
                for name in CELLS}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m["workloads"]:
            assert m["moves"] in reported[cell], (m["name"], cell)
    for name, got in reported.items():
        assert "setup_s" in got and len(got) >= 2
        assert any(name in m["workloads"] for m in BENCH["per_layer"])


# ---------------------------------------------------------------------------
# Traffic and window arithmetic
# ---------------------------------------------------------------------------


def test_corpus_is_deterministic_per_seed():
    from chipbench import corpus

    seed = 2**31 + 12345
    small = _small(json.loads((PKG / "configs" / "hepth_batch.json").read_text()))["corpus"]
    x, y = corpus.generate(small, seed), corpus.generate(small, seed)
    assert x.names == y.names and np.array_equal(x.edges, y.edges)
    assert np.array_equal(x.truth, y.truth) and np.array_equal(x.paper_of, y.paper_of)
    assert corpus.generate(small, seed + 1).names != x.names


def test_corpus_keeps_the_source_ratios():
    """The cell's corpus has the source's references per paper and per
    author (HEPTH: 58,515 references, 29,555 papers, 13,092 authors)."""
    from chipbench import corpus

    cfg = json.loads((PKG / "configs" / "hepth_batch.json").read_text())
    params = cfg["corpus"]
    c = corpus.generate(params, cfg["corpus_seed"])
    regular = c.paper_of < params["n_papers"]
    per_paper = regular.sum() / len(np.unique(c.paper_of[regular]))
    assert per_paper == pytest.approx(58515 / 29555, rel=0.02)
    assert params["n_papers"] / params["n_authors"] == pytest.approx(29555 / 13092, rel=0.01)
    full = cfg["full_size"]
    assert (full["n_authors"], full["n_papers"]) == (13092, 29555)


def test_surname_pool_finishes_past_two_syllables():
    from chipbench.corpus import _surname_pool

    small, _ = _surname_pool(np.random.default_rng(2**31 + 5), 3000)
    big, w = _surname_pool(np.random.default_rng(2**31 + 5), 22050)
    assert len(set(big)) == len(big) == len(w) == 22050
    assert big[:3000] == small  # sizes that two middle syllables hold draw as before


# ---------------------------------------------------------------------------
# Trace reduction, on a trace recorded on a TPU v5e
# ---------------------------------------------------------------------------


def test_trace_reduction_on_a_recorded_trace():
    import gzip

    from jax.profiler import ProfileData

    from chipbench import peaks, roofline, trace_reduce

    pd = ProfileData.from_serialized_xspace(gzip.decompress(TRACE.read_bytes()))
    r = trace_reduce.reduce_profile(pd)
    dev = [p for p in pd.planes if p.name == "/device:TPU:0"][0]
    ops = [e for line in dev.lines if line.name == "XLA Ops" for e in line.events]
    # busy: the union of the op intervals, never more than their sum or the window
    assert 0 < r.busy_s <= sum(e.duration_ns for e in ops) / 1e9 + 1e-9
    assert r.busy_s < r.window_s and r.chips == 1
    total_self = sum(r.op_seconds.values())
    assert total_self == pytest.approx(r.busy_s, rel=1e-6)
    k = roofline.kernel_calls(r, "sweep_matrix")
    assert len(k[0]) == 91 and k[1] == pytest.approx(0.008931613)
    for results, operands in k[0]:
        assert results[0][0] == "f32" and len(operands) == 3
        assert operands[2][1][-1] == operands[2][1][-2] == results[0][1][-1]
    p = peaks.peaks("TPU v5 lite")
    share, bound = roofline.roofline_share(r, "sweep_matrix", roofline.icm_sweep_flops,
                                           p["flops_per_s"], p["hbm_bytes_per_s"])
    assert 0 < share <= 100 and bound in ("compute", "memory")
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and b["idle_gaps"][0][0] == "no span open"
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_self_times_subtract_nested_events():
    from chipbench.trace_reduce import self_times, shapes, union_length

    ev = [(0, 10), (2, 3), (6, 2), (20, 5)]
    assert self_times(ev) == [5, 3, 2, 5]
    assert union_length([(0, 10), (2, 5), (12, 14)])[0] == 12
    res, ops = shapes("%k.1 = f32[4,1,8]{2,1,0:T(1,128)} custom-call(f32[4,1,8]{2,1,0} %a, "
                      "f32[4,8,8]{2,1,0:T(8,128)} %b), custom_call_target=\"tpu_custom_call\"")
    assert res == [("f32", (4, 1, 8))] and ops == [("f32", (4, 1, 8)), ("f32", (4, 8, 8))]


# ---------------------------------------------------------------------------
# The plain reference against the program, and whole runs at a small size
# ---------------------------------------------------------------------------


def _small(d: dict) -> dict:
    """A configuration cut to ``SMALL`` of its scale, its limits with it."""
    if "corpus" in d:
        for key in ("n_authors", "n_papers", "chain_motifs"):
            d["corpus"][key] = max(2, int(d["corpus"][key] * SMALL))
        d["limits"] = {k: int(v * SMALL) for k, v in d["limits"].items()}
    return d


@pytest.mark.parametrize("config,scheme", [("hepth_batch", "mmp"), ("hepth_batch", "smp")])
def test_reference_matches_the_program(config, scheme):
    from chipbench import corpus, reference
    from repro.core import pipeline
    from repro.core.mln import MLNMatcher
    from repro.core.parallel import make_em_mesh, run_parallel
    from repro.core.types import EntityTable, Relations

    cfg = _small(json.loads((PKG / "configs" / f"{config}.json").read_text()))
    c = corpus.generate(cfg["corpus"], 2**31 + 7)
    packed, gg, _ = pipeline.prepare(EntityTable(names=list(c.names)),
                                     Relations(edges={"coauthor": c.edges}))
    got = run_parallel(packed, MLNMatcher(), gg, scheme=scheme, mesh=make_em_mesh(1))
    inst = reference.instance(c.names, c.edges, cfg["matcher"])
    assert [tuple(int(e) for e in f) for f in packed.cover.full] == \
        [tuple(f) for f in reference.cover(c.names, c.edges, cfg["matcher"])[0]]
    assert np.array_equal(inst.gids, gg.gids)
    want = reference.fixpoint(inst, scheme, cfg["matcher"])
    assert np.array_equal(want, got.matches.gids) and len(want) > 0


def _run(monkeypatch, capsys, workload, seconds=4.0):
    """One harness run on the CPU at a small size; returns its result."""
    from chipbench import harness

    class Dev:
        platform, device_kind = "cpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 1}

    orig = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda p: _small(orig(p)))
    monkeypatch.setattr(harness, "require_devices", lambda chips: [Dev()] * chips)
    harness.main(["--workload", workload, "--seed", str(2**31 + 99),
                  "--seconds", str(seconds), "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _state_unchanged(monkeypatch):
    """Every resolution returns the matches it started from."""
    from repro.core import parallel
    from repro.core.types import MatchStore

    orig = parallel.run_parallel

    def stuck(*args, **kw):
        res = orig(*args, **kw)
        res.matches = kw.get("init_matches") or MatchStore()
        return res

    monkeypatch.setattr(parallel, "run_parallel", stuck)


def _half_left_out(monkeypatch):
    """Half of the batch is left out: a resolution gets the first half of
    the corpus."""
    from repro.core import pipeline
    from repro.core.types import EntityTable, Relations

    prepare = pipeline.prepare

    def half_prepare(entities, relations, **kw):
        n = len(entities.names) // 2
        e = relations.edges["coauthor"]
        return prepare(EntityTable(names=entities.names[:n]),
                       Relations(edges={"coauthor": e[(e < n).all(axis=1)]}), **kw)

    monkeypatch.setattr(pipeline, "prepare", half_prepare)


def _answer_altered(monkeypatch):
    """Answers altered where they are produced: a resolution drops a tenth
    of its matches, more than the limit, which tolerates the chip's float32
    ties."""
    from repro.core import parallel
    from repro.core.types import MatchStore

    orig = parallel.run_parallel

    def altered(*args, **kw):
        res = orig(*args, **kw)
        res.matches = MatchStore(res.matches.gids[len(res.matches.gids) // 10:])
        return res

    monkeypatch.setattr(parallel, "run_parallel", altered)


def _bf16_control(monkeypatch):
    """The control in the program's place: every resolution returns the
    plain reference's fixpoint computed in bfloat16."""
    from chipbench import corpus, reference
    from repro.core import parallel
    from repro.core.types import MatchStore

    made = {}
    generate = corpus.generate

    def keep(params, seed):
        made["corpus"] = generate(params, seed)
        return made["corpus"]

    monkeypatch.setattr(corpus, "generate", keep)
    m = json.loads((PKG / "configs" / "hepth_batch.json").read_text())["matcher"]
    orig = parallel.run_parallel

    def control(*args, **kw):
        res = orig(*args, **kw)
        if "gids" not in made:
            c = made["corpus"]
            inst = reference.instance(c.names, c.edges, m, bf16=True)
            made["gids"] = reference.fixpoint(inst, kw["scheme"], m, bf16=True)
        res.matches = MatchStore(made["gids"])
        return res

    monkeypatch.setattr(parallel, "run_parallel", control)


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_left_out, _answer_altered,
                                   _bf16_control],
                         ids=["sound", "state_unchanged", "half_left_out", "answer_altered",
                              "bf16_control"])
def test_a_run_is_correct_only_when_the_timed_path_is(monkeypatch, capsys, workload, fault):
    if fault is not None:
        fault(monkeypatch)
    out = _run(monkeypatch, capsys, workload)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks" and out["device"]["count"] == 1
    reported = {m["name"] for m in BENCH["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]}
    assert set(out["metrics"]) == reported
    assert all(v["value"] > 0 for v in out["metrics"].values())

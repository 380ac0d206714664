import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["REPRO_MIXED_DOT"] = "preferred"  # TPU math: bf16 dots, f32 accum

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is how the distribution config is proven coherent without real
hardware: 512 placeholder host devices stand in for 2 TPU v5e pods, and
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed for

  * the single-pod mesh  (data=16, model=16)      — 256 chips, and
  * the multi-pod mesh   (pod=2, data=16, model=16) — 512 chips,

for every assigned architecture × input-shape cell, plus the EM-round
cell (the paper's technique on the production mesh).  Each compile's
``memory_analysis()`` (fits in HBM?) and ``cost_analysis()`` (FLOPs /
bytes for the roofline) are captured to JSON under ``experiments/``;
``repro.launch.roofline`` consumes them.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both            # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --em                   # EM round cell
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import hlo_analysis
from repro.launch import sharding as shardlib
from repro.launch.mesh import make_production_mesh, pod_spec
from repro.models.param import abstract_params, filter_spec, param_count
from repro.models.registry import get_model
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_step, microbatched_specs

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def _mesh(multi_pod: bool):
    return make_production_mesh(multi_pod=multi_pod)


def _batch_abstract(api, shape):
    return dict(api.input_specs(shape))


def active_param_count(cfg, specs) -> int:
    """Params touched per token: total minus the (1 - k/E) unused experts."""
    total = param_count(specs)
    if not cfg.n_experts:
        return total
    f = cfg.moe_d_ff or cfg.d_ff
    per_expert = cfg.d_model * 2 * f + f * cfg.d_model
    if cfg.family == "hybrid":
        from repro.models.hybrid import _is_moe

        n_moe = sum(_is_moe(cfg, i) for i in range(cfg.n_layers))
    else:
        n_moe = cfg.n_layers
    unused = n_moe * (cfg.n_experts - cfg.experts_per_token) * per_expert
    return total - unused


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *,
               fsdp: str = "auto", microbatches: int | None = None,
               remat_group: int | None = None, donate: bool = True,
               tp: str = "on"):
    """Lower + compile one cell; return the metrics dict."""
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}

    mesh = _mesh(multi_pod)
    dsz = shardlib.data_axis_size(mesh) * (2 if multi_pod else 1)
    kind = shape.kind
    t0 = time.perf_counter()

    if kind == "train":
        from repro.models import layers as layerslib

        # Megatron-SP at layer boundaries was tried for the 32k-token
        # cells and REFUTED: GSPMD round-trips the resharding inside
        # every sublayer (flops x2, memory up for jamba) — see
        # EXPERIMENTS.md §Perf.  Off by default; kept as a knob.
        layerslib.SEQ_SHARD_BOUNDARY = os.environ.get("REPRO_SEQ_SHARD", "0") == "1"
        layerslib.DP_OVER_MODEL = tp == "off"
        rg = remat_group if remat_group is not None else shardlib.default_remat_group(cfg.n_layers)
        cfg = dataclasses.replace(cfg, remat_group=rg)
        api = get_model(cfg)
        specs = api.param_specs()
        if tp == "off":  # pure-DP layout: tensor axis becomes batch
            specs = shardlib.strip_model(specs)
        use_fsdp = fsdp == "on" or (fsdp == "auto")
        if use_fsdp:
            specs = shardlib.fsdp_params(specs, mesh)
        pshard = shardlib.param_shardings(specs, mesh)
        oshard = {"m": pshard, "v": pshard,
                  "step": NamedSharding(mesh, P())}
        params_abs = abstract_params(specs)
        opt_abs = {"m": params_abs, "v": params_abs,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
        if tp == "off":
            dsz *= dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        mb = microbatches if microbatches is not None else shardlib.pick_microbatches(
            shape.global_batch, dsz, shape.seq_len
        )
        batch_abs, batch_psp = microbatched_specs(
            _batch_abstract(api, shape), api.input_pspecs(shape), mb
        )
        if tp == "off":
            batch_psp = {k: shardlib.dp_over_model_spec(v) for k, v in batch_psp.items()}
        bshard = {
            name: NamedSharding(
                mesh,
                shardlib.drop_indivisible(
                    filter_spec(pod_spec(batch_psp[name], mesh), mesh),
                    batch_abs[name].shape,
                    mesh,
                ),
            )
            for name in batch_abs
        }
        step = make_train_step(api, OptConfig(), microbatches=mb)
        jitted = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1) if donate else (),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        extra = {"microbatches": mb, "remat_group": rg, "fsdp": use_fsdp,
                 "tp": tp}
    else:  # decode: single-token serve step against a seq_len KV cache
        api = get_model(cfg)
        specs = shardlib.cast_params(api.param_specs(), jnp.bfloat16)
        # big checkpoints must also shard weights over data to fit HBM
        use_fsdp = fsdp == "on" or (
            fsdp == "auto"
            and param_count(specs) * 2 / 16 > 8e9  # >8GB/chip at TP-16
        )
        if use_fsdp:
            specs = shardlib.fsdp_params(specs, mesh)
        pshard = shardlib.param_shardings(specs, mesh)
        cache_specs = api.cache_specs(shape.global_batch, shape.seq_len)
        cshard = shardlib.state_shardings(cache_specs, mesh)
        bshard = shardlib.input_shardings(api, shape, mesh)
        params_abs = abstract_params(specs)
        cache_abs = abstract_params(cache_specs)
        batch_abs = _batch_abstract(api, shape)
        jitted = jax.jit(
            api.decode,
            in_shardings=(pshard, cshard, bshard),
            out_shardings=(None, cshard),
            donate_argnums=(1,) if donate else (),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, cache_abs, batch_abs)
        extra = {"fsdp": use_fsdp}

    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    n_chips = int(np.prod(mesh.devices.shape))
    mem = compiled.memory_analysis()
    ana = hlo_analysis.analyze(
        compiled.as_text(), n_devices=n_chips, pod_boundary=256
    )

    n_params = param_count(specs)
    n_active = active_param_count(cfg, specs)
    tokens = shape.global_batch * (shape.seq_len if kind == "train" else 1)
    model_flops = (6 if kind == "train" else 2) * n_active * tokens

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "kind": kind, "n_chips": n_chips,
        "params": int(n_params), "active_params": int(n_active),
        "tokens_per_step": int(tokens), "model_flops": float(model_flops),
        # per-chip numbers from the loop-aware HLO analysis
        "hlo_flops": ana["flops"],
        "hlo_bytes": ana["bytes"],
        "mem": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
        },
        "collective_bytes": ana["collective_bytes"],
        "collective_wire_bytes": ana["collective_wire_bytes"],
        "collective_cross_pod_bytes": ana["collective_cross_pod_bytes"],
        "n_collectives": ana["n_collective_sites"],
        "collectives_by_kind": ana["collectives_by_kind"],
        "unknown_whiles": ana["unknown_whiles"],
        # f32 round-trips of bf16 buffers: a CPU-backend legalization
        # artifact absent on TPU; see EXPERIMENTS.md §Dry-run.
        "bf16_upcast_bytes": ana["bf16_upcast_bytes"],
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        **extra,
    }
    return rec


# ---------------------------------------------------------------------------
# The EM-round cell (the paper's technique on the production mesh)
# ---------------------------------------------------------------------------


def lower_em_cell(multi_pod: bool, *, k: int = 32, neighborhoods: int = 8192,
                  pairs: int = 128, universe: int = 1 << 20,
                  matcher_kind: str = "mln"):
    """Lower the round engine's programs for one bin at production scale.

    These are the programs ``run_parallel`` dispatches for a bin: its
    grounding (``_ground_bin_fn``, once per bin) and its full round
    (``build_bin_round_fn``: batched MLN MAP inference on every active
    neighborhood, sharded over all mesh axes, + the match-bitset
    all-reduce).  Each neighborhood is staged on ``pairs`` compact slots,
    the width of the DBLP cell's largest sub-bin; 8192 neighborhoods of
    k=32 is a DBLP-BIG-scale round (§6.3).  FLOPs, bytes, collectives and
    times are the two programs' sums; each memory field is the larger of
    the two (they run one after the other).
    """
    from repro.core.mln import PAPER_LEARNED
    from repro.core.parallel import BinRoundSpec, _ground_bin_fn, build_bin_round_fn

    prod = _mesh(multi_pod)
    # auto axes, so that jit partitions the grounding over the batch
    mesh = Mesh(prod.devices, prod.axis_names)
    axes = tuple(mesh.axis_names)
    n_chips = int(np.prod(mesh.devices.shape))
    B = max(neighborhoods, n_chips)
    Pc = pairs
    rows = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())

    def sds(shape, dt, sharding=rows):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    ground = _ground_bin_fn(matcher_kind, PAPER_LEARNED)  # rules ignore the weights
    ground_args = (
        sds((B, k), jnp.int32),         # entity_ids
        sds((B, k), jnp.bool_),         # entity_mask
        sds((B, k, k), jnp.bool_),      # coauthor
        sds((B, Pc), jnp.int8),         # sim_level
        sds((B, Pc), jnp.bool_),        # pair_mask
        sds((B, Pc), jnp.int16),        # slot_i
        sds((B, Pc), jnp.int16),        # slot_j
    )
    spec = BinRoundSpec(kind=matcher_kind, k=k, batch=B, num_pairs=Pc,
                        universe_size=universe)
    round_args = (
        *[sds(a.shape, a.dtype) for a in jax.eval_shape(ground, *ground_args)],
        sds((B, Pc), jnp.int32),        # uidx
        sds((B, Pc), jnp.bool_),        # pair_mask
        sds((B,), jnp.bool_),           # active rows
        sds((universe,), jnp.bool_, rep),  # m_bits
    )
    t_lower = t_compile = 0.0
    mems, anas = [], []
    for fn, args in ((ground, ground_args),
                     (build_bin_round_fn(spec, mesh, axes), round_args)):
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_lower += t1 - t0
        t_compile += time.perf_counter() - t1
        mems.append(compiled.memory_analysis())
        anas.append(hlo_analysis.analyze(
            compiled.as_text(), n_devices=n_chips, pod_boundary=256
        ))
    ana = {key: sum(a[key] for a in anas) for key in anas[0]
           if key != "collectives_by_kind"}
    by_kind: dict[str, float] = {}
    for a in anas:
        for kind, nbytes in a["collectives_by_kind"].items():
            by_kind[kind] = by_kind.get(kind, 0.0) + nbytes
    rec = {
        "arch": f"em_round_{matcher_kind}", "shape": f"k{k}_Pc{Pc}_B{B}",
        "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok",
        "kind": "em_round", "n_chips": n_chips,
        "params": 0, "active_params": 0, "tokens_per_step": B,
        # useful work: one (Pc,Pc)@(Pc,Pc) entailment matmul + sweeps per nb
        "model_flops": float(B * 2 * Pc * Pc * Pc),
        "hlo_flops": ana["flops"],
        "hlo_bytes": ana["bytes"],
        "mem": {
            key: max(int(getattr(m, field)) for m in mems)
            for key, field in (
                ("argument_bytes", "argument_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("temp_bytes", "temp_size_in_bytes"),
                ("alias_bytes", "alias_size_in_bytes"),
                ("code_bytes", "generated_code_size_in_bytes"),
            )
        },
        "collective_bytes": ana["collective_bytes"],
        "collective_wire_bytes": ana["collective_wire_bytes"],
        "collective_cross_pod_bytes": ana["collective_cross_pod_bytes"],
        "n_collectives": ana["n_collective_sites"],
        "collectives_by_kind": by_kind,
        "unknown_whiles": ana["unknown_whiles"],
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _save(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    )
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--em", action="store_true", help="run the EM-round cell")
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--tp", default="on", choices=["on", "off"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat-group", type=int, default=None)
    ap.add_argument("--out", default=os.environ.get("DRYRUN_OUT", "experiments/dryrun"))
    args = ap.parse_args()

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    n_ok = n_skip = n_fail = 0
    for multi_pod in meshes:
        if args.em:
            rec = lower_em_cell(multi_pod)
            _save(rec, args.out)
            print(f"[em_round {rec['mesh']}] ok "
                  f"flops={rec['hlo_flops']:.3e} coll={rec['collective_wire_bytes']:.3e}B "
                  f"compile={rec['compile_s']}s")
            n_ok += 1
            continue
        for arch in archs:
            for shape in shapes:
                tag = f"{arch} × {shape} × {'2x16x16' if multi_pod else '16x16'}"
                try:
                    rec = lower_cell(arch, shape, multi_pod, fsdp=args.fsdp,
                                     microbatches=args.microbatches,
                                     remat_group=args.remat_group, tp=args.tp)
                except Exception:
                    n_fail += 1
                    print(f"[{tag}] FAIL")
                    traceback.print_exc()
                    continue
                _save(rec, args.out)
                if rec["status"] == "skipped":
                    n_skip += 1
                    print(f"[{tag}] skipped: {rec['reason']}")
                else:
                    n_ok += 1
                    hbm = rec["mem"]["argument_bytes"] + rec["mem"]["temp_bytes"] + rec["mem"]["output_bytes"] - rec["mem"]["alias_bytes"]
                    print(f"[{tag}] ok mem/dev={hbm/2**30:.2f}GiB "
                          f"flops={rec['hlo_flops']:.3e} "
                          f"coll={rec['collective_wire_bytes']:.3e}B "
                          f"compile={rec['compile_s']}s")
    print(f"dry-run: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

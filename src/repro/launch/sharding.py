"""Launch-level sharding policy.

Models declare *logical* shardings over ``("data", "model")`` in their
PSpec trees; this module applies the launch policies on top:

* **FSDP** (``fsdp_params``): additionally shard every large parameter
  over the ``data`` axis (ZeRO-3 style).  GSPMD all-gathers the weight
  just-in-time per layer and reduce-scatters its gradient; optimizer
  state inherits the layout, so params+grads+Adam state are fully
  sharded over data×model.  Required to fit the 52B/72B/~100B configs
  on 16 GB v5e chips.
* **pod rewriting**: on a multi-pod mesh, batch-bearing dims shard over
  ``("pod", "data")``; parameters never shard over ``pod`` (pure DP,
  hierarchical gradient reduction: ICI reduce-scatter inside the pod,
  DCN all-reduce across pods).
* **divisibility guard** (``drop_indivisible``): axes whose shard count
  does not divide the dim are dropped (e.g. the ``long_500k`` batch of
  1 never shards over ``data``); GSPMD could pad, but explicit is
  cheaper and keeps the dry-run memory analysis honest.
* **launch heuristics**: microbatch count and remat group size per
  (arch × shape × mesh) cell.
"""

from __future__ import annotations

import dataclasses

import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import pod_spec
from repro.models.param import PSpec, filter_spec, spec_tree_map

FSDP_MIN_SIZE = 1 << 20  # params below 1M elements stay replicated over data


def _entry_axes(e):
    if e is None:
        return ()
    return tuple(e) if isinstance(e, (tuple, list)) else (e,)


def data_axis_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("data", 1))


def fsdp_spec(ps: PSpec, data_size: int) -> PSpec:
    """Shard one more dim of a large param over ``data`` (ZeRO-3)."""
    if ps.size < FSDP_MIN_SIZE or len(ps.shape) < 2:
        return ps
    if ps.init == "embed":
        # embedding tables stay out of FSDP: model-sharded tables break
        # the gather's propagation with an extra `data` axis; pure-DP
        # tables were tried vocab-sharded (hillclimb iter. 3) and
        # REFUTED — the unembed all-gathers cost more than the grad
        # all-reduce they save (EXPERIMENTS.md §Perf).
        return ps
    entries = list(ps.spec) + [None] * (len(ps.shape) - len(ps.spec))
    used = {a for e in entries for a in _entry_axes(e)}
    if "data" in used:
        return ps
    # Prefer the fan-in dim, then fan-out, then interior dims.  The
    # leading stacked-layer dim is skipped: lax.scan slices it per
    # iteration and a sharded slice axis would force a gather per layer.
    nd = len(ps.shape)
    order = [nd - 2, nd - 1] + list(range(1, nd - 2))
    for d in order:
        if entries[d] is None and ps.shape[d] % data_size == 0 and ps.shape[d] >= data_size:
            entries[d] = "data"
            return dataclasses.replace(ps, spec=P(*entries))
    return ps


def strip_model(tree):
    """Remove the `model` axis from every param spec (pure-DP layout).

    For small models TP-16 is the wrong point on the roofline: the
    megatron activation all-reduces dwarf the matmuls.  With `model`
    stripped, the launcher reuses the tensor axis as extra data
    parallelism (batch shards over ('data','model')) and params are
    FSDP-sharded over `data` only.
    """

    def fix_entry(e):
        if e == "model":
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a != "model")
            return kept if kept else None
        return e

    def f(ps: PSpec) -> PSpec:
        return dataclasses.replace(ps, spec=P(*(fix_entry(e) for e in ps.spec)))

    return spec_tree_map(f, tree)


def dp_over_model_spec(spec: P) -> P:
    """Rewrite batch specs 'data' -> ('data','model') (pure-DP layout)."""

    def fix(e):
        if e == "data":
            return ("data", "model")
        if isinstance(e, (tuple, list)):
            out = []
            for a in e:
                out.extend(["data", "model"] if a == "data" else [a])
            return tuple(out)
        return e

    return P(*(fix(e) for e in spec))


def fsdp_params(tree, mesh: Mesh):
    n = data_axis_size(mesh)
    return spec_tree_map(lambda ps: fsdp_spec(ps, n), tree)


def cast_params(tree, dtype):
    """Serve-time dtype override (params held in bf16 for decode)."""
    import jax.numpy as jnp

    def f(ps: PSpec) -> PSpec:
        if ps.dtype == jnp.float32:
            return dataclasses.replace(ps, dtype=dtype)
        return ps

    return spec_tree_map(f, tree)


def drop_indivisible(spec: P, shape, mesh: Mesh) -> P:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, e in zip(shape, entries):
        n = int(np.prod([sizes.get(a, 1) for a in _entry_axes(e)])) if e else 1
        out.append(e if (n == 1 or dim % n == 0) else None)
    return P(*out)


def input_shardings(api, shape, mesh: Mesh) -> dict:
    """NamedShardings for the input batch (pod-aware, divisibility-safe)."""
    sds = api.input_specs(shape)
    psp = api.input_pspecs(shape)
    out = {}
    for name, s in sds.items():
        sp = pod_spec(psp[name], mesh)
        sp = filter_spec(sp, mesh)
        sp = drop_indivisible(sp, s.shape, mesh)
        out[name] = NamedSharding(mesh, sp)
    return out


def state_shardings(tree, mesh: Mesh, *, pod_batch: bool = True):
    """NamedShardings for a PSpec state tree (e.g. the KV cache).

    ``pod_batch=True`` additionally shards 'data'-bearing dims over the
    pod axis (decode state is per-request, hence pure DP over pods).
    """

    def f(ps: PSpec):
        sp = pod_spec(ps.spec, mesh) if pod_batch else ps.spec
        sp = filter_spec(sp, mesh)
        sp = drop_indivisible(sp, ps.shape, mesh)
        return NamedSharding(mesh, sp)

    return spec_tree_map(f, tree)


def param_shardings(tree, mesh: Mesh):
    """NamedShardings for params (never sharded over pod)."""

    def f(ps: PSpec):
        sp = filter_spec(ps.spec, mesh)
        sp = drop_indivisible(sp, ps.shape, mesh)
        return NamedSharding(mesh, sp)

    return spec_tree_map(f, tree)


# ---------------------------------------------------------------------------
# Launch heuristics
# ---------------------------------------------------------------------------


def pick_microbatches(global_batch: int, data_shards: int, seq_len: int,
                      target_tokens: int = 8192) -> int:
    """Largest microbatch count keeping >= target tokens/device/microbatch.

    More microbatches => less live activation memory per grad-accum step
    but shorter matmuls; ~8k tokens per device per microbatch keeps the
    MXU well fed while bounding the remat working set.
    """
    b_loc = max(global_batch // max(data_shards, 1), 1)
    best = 1
    for mb in range(1, b_loc + 1):
        if b_loc % mb:
            continue
        if (b_loc // mb) * seq_len >= target_tokens:
            best = mb
    return best


def default_remat_group(n_layers: int) -> int:
    """Largest divisor of L that is <= ceil(sqrt(L)) (O(sqrt L) schedule)."""
    top = int(np.ceil(np.sqrt(n_layers)))
    for g in range(top, 1, -1):
        if n_layers % g == 0:
            return g
    return 1


# ---------------------------------------------------------------------------
# EM serving shards (LSH bucket-map partitioning)
# ---------------------------------------------------------------------------

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3


def bucket_shard(band: int, key: tuple[int, ...], n_shards: int) -> int:
    """Deterministic owner shard of one LSH bucket ``(band, key)``.

    FNV-1a over the band index and the key's minhash values — NOT
    Python's ``hash`` (salted per interpreter), so every process of a
    sharded service and every re-run of a test computes the same
    partition.  The partition is exhaustive and disjoint by
    construction: exactly one shard owns each bucket.
    """
    h = _FNV_OFFSET
    for v in (band, *key):
        v = int(v) & 0xFFFFFFFFFFFFFFFF
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * _FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
            v >>= 8
    return h % int(n_shards)


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """This process's slice of the sharded serving partition.

    ``n_shards`` is the process count of the serving mesh and
    ``shard_id`` this process's index; the LSH index stores and probes
    only the buckets :func:`bucket_shard` assigns to ``shard_id``, and
    per-probe candidate sets are merged by a cross-process union (the
    boundary-message merge at ingest quiescence points).
    """

    n_shards: int
    shard_id: int

    def __post_init__(self):
        if self.n_shards < 1 or not (0 <= self.shard_id < self.n_shards):
            raise ValueError(
                f"invalid shard spec: id {self.shard_id} of {self.n_shards}"
            )

    def owns(self, band: int, key: tuple[int, ...]) -> bool:
        return bucket_shard(band, key, self.n_shards) == self.shard_id


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n else 1


@dataclasses.dataclass
class ShardMerger:
    """Cross-process union of per-shard candidate-id sets.

    Callable hook for :class:`repro.stream.index.MinHashLSHIndex`: each
    process probes only its owned buckets, then the probe results are
    united over the mesh so every process sees the same candidate set
    the unsharded index would have produced (the partition is
    exhaustive, so the union is exact — and the caller sorts, so set
    order never leaks into downstream state).
    """

    mesh: Mesh

    def __post_init__(self):
        self._gather_fns: dict = {}
        self.merges = 0

    def _spans(self) -> bool:
        from repro.kernels.common import mesh_spans_processes

        return mesh_spans_processes(self.mesh)

    def _gather(self, local: np.ndarray, fill) -> np.ndarray:
        """All-gather equal-shape per-process row blocks (process order)."""
        import jax

        mesh = self.mesh
        axis = mesh.axis_names[0]
        devs_here = [
            d for d in mesh.devices.flat
            if d.process_index == jax.process_index()
        ]
        k = len(devs_here)
        pad = (-len(local)) % k
        if pad:
            local = np.concatenate(
                [local, np.full((pad,) + local.shape[1:], fill, local.dtype)]
            )
        per_dev = len(local) // k
        sharding = NamedSharding(mesh, P(axis))
        global_shape = (len(local) * (mesh.devices.size // k),) + local.shape[1:]
        shards = [
            jax.device_put(local[i * per_dev : (i + 1) * per_dev], d)
            for i, d in enumerate(devs_here)
        ]
        garr = jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards
        )
        key = (global_shape, local.dtype.str)
        fn = self._gather_fns.get(key)
        if fn is None:
            import jax.numpy as jnp  # noqa: F401 - jitted body below

            fn = self._gather_fns[key] = jax.jit(
                jax.shard_map(
                    lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True),
                    mesh=mesh, in_specs=(P(axis),), out_specs=P(),
                    check_vma=False,
                )
            )
        return np.asarray(fn(garr))

    def union(self, ids: set[int]) -> set[int]:
        """Union this shard's candidate ids across every process."""
        if not self._spans():
            return ids
        self.merges += 1
        local = np.fromiter(sorted(ids), np.int64, len(ids))
        counts = self._gather(np.array([len(local)], np.int64), 0)
        cap = _pow2(int(counts.max())) if counts.size else 1
        padded = np.full(cap, -1, np.int64)
        padded[: len(local)] = local
        merged = self._gather(padded, -1)
        return set(merged[merged >= 0].tolist())

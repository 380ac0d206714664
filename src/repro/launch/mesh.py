"""Production meshes + logical->physical spec mapping.

Single pod: ``(data=16, model=16)`` — 256 chips (TPU v5e pod).
Multi-pod: ``(pod=2, data=16, model=16)`` — 512 chips; the ``pod`` axis
is pure data parallelism (params replicated across pods, gradients
all-reduced hierarchically: reduce-scatter on ICI inside the pod, then
cross-pod on DCN).  Designed so ``pod`` scales to O(100) with no spec
changes — nothing but the batch is sharded over it.

Model code declares *logical* specs over ``("data", "model")``;
:func:`pod_spec` rewrites batch-bearing specs so that on a multi-pod
mesh the batch additionally shards over ``pod``.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def pod_spec(spec: P, mesh: Mesh) -> P:
    """Rewrite 'data' -> ('pod', 'data') when the mesh has a pod axis."""
    if "pod" not in mesh.axis_names:
        return spec

    def fix(entry):
        if entry == "data":
            return ("pod", "data")
        if isinstance(entry, (tuple, list)):
            out = []
            for e in entry:
                out.extend(["pod", "data"] if e == "data" else [e])
            return tuple(out)
        return entry

    return P(*(fix(e) for e in spec))


def data_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    """NamedSharding for an *input/state* spec (batch shards over pod)."""
    return NamedSharding(mesh, pod_spec(spec, mesh))


def param_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    """NamedSharding for a *parameter* spec (pod-replicated by design)."""
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# EM serving meshes (multi-process CPU/TPU sharded resolution)
# ---------------------------------------------------------------------------

_distributed_initialized = False


def init_em_distributed(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join (or skip) a ``jax.distributed`` service for sharded serving.

    Arguments default to the ``REPRO_SHARD_COORD`` / ``REPRO_SHARD_N`` /
    ``REPRO_SHARD_ID`` environment variables so subprocess workers (the
    CI mesh leg and ``benchmarks/shard_scaling.py``) need no plumbing.
    Returns False — without touching jax — when no coordinator is
    configured, so single-process callers can call this unconditionally.

    On CPU backends the cross-process collective client (gloo) must be
    selected *before* ``jax.distributed.initialize``; the option is
    ignored by the other backends.
    """
    global _distributed_initialized
    import os

    coordinator = coordinator or os.environ.get("REPRO_SHARD_COORD")
    if not coordinator:
        return False
    if _distributed_initialized:
        return True
    if num_processes is None:
        num_processes = int(os.environ.get("REPRO_SHARD_N", "1"))
    if process_id is None:
        process_id = int(os.environ.get("REPRO_SHARD_ID", "0"))
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _distributed_initialized = True
    return True


def em_service_mesh(n_shards: int | None = None) -> Mesh:
    """1-D ``("data",)`` mesh over the global device list.

    With ``jax.distributed`` initialized this spans every process
    (``process_count x local_devices`` shards); otherwise it is the
    local multi-device mesh ``core.parallel.make_em_mesh`` builds — the
    two entry points stay interchangeable so the serving stack can hand
    either to ``run_parallel``.
    """
    from repro.core.parallel import make_em_mesh

    return make_em_mesh(n_shards)

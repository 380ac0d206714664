"""Shared helpers for the Pallas TPU kernels.

Routing policy (``ops.py`` of every kernel), decided by
:func:`pallas_mode` from ``jax.default_backend()``:

* On TPU, run the compiled Pallas kernel — always.  ``REPRO_PALLAS``
  set to anything but ``compiled`` raises there: a chip run never
  falls back to the references or the interpreter.
* Elsewhere, run the pure-jnp reference in ``ref.py`` (identical math)
  so the whole framework works on CPU.  ``REPRO_PALLAS=interpret``
  runs the Pallas kernel bodies in interpret mode instead — this is how
  the CPU tests validate the kernels against the oracles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
import numpy as np


def pallas_mode() -> str:
    """'compiled' on TPU; 'interpret' or 'off' (the references) elsewhere."""
    env = os.environ.get("REPRO_PALLAS", "").lower()
    if jax.default_backend() == "tpu":
        if env not in ("", "compiled"):
            raise RuntimeError(
                f"REPRO_PALLAS={env!r} on a TPU backend: the chip runs the "
                "compiled kernels only; unset it"
            )
        return "compiled"
    if env in ("", "off"):
        return "off"
    if env == "interpret":
        return "interpret"
    raise ValueError(
        f"REPRO_PALLAS={env!r}: expected 'interpret' or 'off' off the TPU"
    )


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in a fixed directory.

    Called by entry points before their first compile, never on import.
    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when that is set
    nothing is changed.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: the directory is part of what a cached
    entry is found by, so it must not depend on a temporary name, a pid
    or the time.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = Path(__file__).resolve().parents[3]  # <checkout>/src/repro/kernels
    path = str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_axis(x, axis: int, to: int, fill=0.0):
    """Pad jnp/np array along axis to length `to`."""
    import jax.numpy as jnp

    cur = x.shape[axis]
    if cur == to:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - cur)
    return jnp.pad(x, pad, constant_values=fill)


def pick_tile(n: int, preferred: int = 128) -> int:
    """Block length along one array axis of ``n`` elements.

    Mosaic accepts a block only if each of its last two dims is
    divisible by 8 (sublanes) and 128 (lanes) respectively, or equals
    the array's dim.  An axis of at most ``preferred`` elements is one
    whole block, so it needs no padding; a longer axis is cut into
    ``preferred``-long blocks and padded to a multiple of that.
    ``preferred`` must be a multiple of 128.
    """
    return n if n <= preferred else preferred


def assert_allclose(a, b, rtol=1e-5, atol=1e-5, msg=""):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol, err_msg=msg)


# ---------------------------------------------------------------------------
# Mesh placement helpers (multi-device and multi-process sharded serving)
# ---------------------------------------------------------------------------


def mesh_spans_processes(mesh) -> bool:
    """True when the mesh covers devices from more than one JAX process.

    Across processes, every input to a global-mesh computation is built
    from host memory with an explicit ``NamedSharding`` so all processes
    agree on the layout, and row outputs the host reads are gathered
    back to replicated inside the program.
    """
    if mesh is None:
        return False
    try:
        return len({d.process_index for d in mesh.devices.flat}) > 1
    except Exception:  # pragma: no cover - exotic mesh types
        return False


def _placeable(x, mesh):
    """A process-local device array is not part of a global array, so
    uploads to a mesh spanning processes go from host memory."""
    return np.asarray(x) if mesh_spans_processes(mesh) else x


def put_replicated(x, mesh):
    """Place an array, host or device, fully replicated on every device
    of ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(_placeable(x, mesh), NamedSharding(mesh, PartitionSpec()))


def put_sharded(x, mesh, axis):
    """Place an array, host or device, on ``mesh`` split along its
    leading dim.

    The leading dimension must be divisible by the mesh size (callers
    pad batches with ``pad_mult``).  Each device receives only its own
    rows: an array committed to one device and handed to a ``shard_map``
    over several would be copied whole to every device instead.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec(axis, *([None] * (np.ndim(x) - 1)))
    return jax.device_put(_placeable(x, mesh), NamedSharding(mesh, spec))

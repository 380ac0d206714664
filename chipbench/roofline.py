"""Roofline share of a Pallas kernel from the traced window.

Operations and bytes of each call come from the operand and result
shapes that the trace's HLO text gives (``trace_reduce.shapes``).  The
share is the least time the kernel's calls could take on the chip, each
call the larger of operations over peak FLOP/s and bytes over peak HBM
bandwidth, over the kernel's measured device self time.  Bytes are what
a call must move at least: each operand read once, each result written
once.  A kernel's metric reader passes the kernel's name in the trace
and the function that counts one call's operations.
"""

from __future__ import annotations

import math

from chipbench.trace_reduce import nbytes


def icm_sweep_flops(results, operands) -> float:
    """``icm_sweep``: delta = u + X @ C with out (B, S, P) and C (B, P, P):
    a multiply and an add per term of the product, and the add of ``u``."""
    out = results[0][1]
    c = max(operands, key=nbytes)[1]
    return 2.0 * math.prod(out) * c[-2] + math.prod(out)


def kernel_calls(reduced, pattern: str):
    """Every traced call of the kernels whose name holds ``pattern``, as
    (results, operands), and their summed self time in seconds."""
    calls, seconds = [], 0.0
    for k in reduced.kernels.values():
        if pattern in k.name:
            calls += k.shapes
            seconds += k.seconds
    return calls, seconds


def roofline_share(reduced, pattern: str, flops_fn, peak_flops: float, peak_bw: float):
    """(share in %, 'compute' or 'memory', whichever bounds more of the
    calls' least time), or None if no such kernel ran in the trace."""
    calls, seconds = kernel_calls(reduced, pattern)
    if not calls or seconds <= 0:
        return None
    t_compute = t_memory = least = 0.0
    for results, operands in calls:
        f = flops_fn(results, operands) / peak_flops
        b = (sum(map(nbytes, operands)) + sum(map(nbytes, results))) / peak_bw
        t_compute += f
        t_memory += b
        least += max(f, b)
    return 100.0 * least / seconds, "compute" if t_compute >= t_memory else "memory"

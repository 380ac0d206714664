"""Roofline share of the ``icm_sweep`` kernel (``sweep_matrix`` in the
trace) in the traced slice: the least time its calls could take on the
chip (``chipbench/roofline.py``, peaks of ``chipbench/peaks.py``) over
their measured device self time."""

from chipbench.peaks import peaks
from chipbench.roofline import icm_sweep_flops, roofline_share


def read(run):
    if run.trace is None:
        return None
    p = peaks(run.device_kind)
    got = roofline_share(run.trace, "sweep_matrix", icm_sweep_flops,
                         p["flops_per_s"], p["hbm_bytes_per_s"])
    return None if got is None else got[0]

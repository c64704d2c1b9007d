"""Operations and bytes that the algorithms need, from their shapes.

These count what the algorithm requires, not what an implementation
does: a top-k batch scores every query against every row once and reads
the table and its norms once.  A training step's flops per triple belong
to its model and live in ``references/<model>.py``.  The scoring dots
run at ``Precision.HIGHEST``, about six bfloat16 passes, and these
counts still hold them against the bfloat16 peak: the compute ceiling of
those dots is about a sixth of what a share counts.
"""
from __future__ import annotations

from typing import Dict, Tuple


def topk_batches(q_rows_total: int, batches: int, n_rows: int,
                 dim: int) -> Tuple[float, float]:
    """(flops, bytes) of ``batches`` batches holding ``q_rows_total``
    query rows between them, padding rows included."""
    flops = 2.0 * q_rows_total * n_rows * dim
    return flops, float(batches) * n_rows * (dim + 1) * 4


def roofline_s(flops: float, nbytes: float, peaks: Dict, chips: int) -> Tuple[float, str]:
    """Least time the chips could take, and which bound sets it."""
    t_c = flops / (peaks["bf16_flops_per_s"] * chips)
    t_m = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

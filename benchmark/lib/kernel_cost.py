"""Operations and bytes of a kernel call, from its shapes.

The GF(2^8) bit-matrix kernel (encode: k rows in, m rows out; decode:
k rows in, k rows out) multiplies an (8 * rows_out, 8 * rows_in) 0/1
matrix by the bit planes of ``rows_in`` byte rows of ``lanes`` bytes:
2 * 8 rows_out * 8 rows_in * lanes integer operations, and at least
(rows_in + rows_out) * lanes bytes through HBM.  Lanes are the
unpadded bytes per row: padding shows as a lower share.
"""

from __future__ import annotations

from typing import Dict, Tuple


def gf_matmul_cost(rows_in: int, rows_out: int,
                   lanes: int) -> Tuple[float, float]:
    """(integer operations, HBM bytes) of one call."""
    ops = 2.0 * (8 * rows_out) * (8 * rows_in) * lanes
    nbytes = float(rows_in + rows_out) * lanes
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> Tuple[float, str]:
    """(percent of the least time the chip could take, and which term
    bounds it: "ops" or "bytes")."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    least = max(t_ops, t_bytes)
    return 100.0 * least / seconds, ("ops" if t_ops >= t_bytes
                                     else "bytes")

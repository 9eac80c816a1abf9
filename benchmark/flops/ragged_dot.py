"""Operations and bytes one grouped matrix product needs, from its shapes:
``rows`` sorted rows of width ``d_in``, each multiplied by its group's
``(d_in, d_out)`` matrix, one of ``groups`` (``lax.ragged_dot``; its two
transposes, the data gradient and the weight gradient, cost the same).

2 * rows * d_in * d_out FLOPs; it reads the rows and every group's matrix
once and writes the result rows.  The least the algorithm needs, so the share
of the roofline is a lower bound on how well the kernel does.
"""

from __future__ import annotations


def ops_bytes(*, rows: float, groups: int, d_in: int, d_out: int, itemsize: int) -> tuple[float, float]:
    return 2.0 * rows * d_in * d_out, float(itemsize * (rows * (d_in + d_out) + groups * d_in * d_out))

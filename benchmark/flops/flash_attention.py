"""Operations and bytes one flash-attention call needs, from its shapes.

Forward: QK^T and PV, 2 matmuls of 2*q*k*dh FLOPs per head, halved when
causal; it reads q, k, v and writes o once (plus the f32 log-sum-exp row the
backward needs).  Backward (the dq and the dk/dv kernels together): five
matmuls of the same size (recomputed scores, dP, dV, dQ, dK); it reads q, k,
v, o, do and the log-sum-exp, and writes dq, dk, dv.  These are the least the
algorithm needs, so the share of the roofline they give is a lower bound on
how well the kernel does.
"""

from __future__ import annotations


def ops_bytes(*, batch: int, heads: int, q_len: int, kv_len: int, head_dim: int,
              causal: bool, itemsize: int, backward: bool) -> tuple[float, float]:
    pair = 2.0 * batch * heads * q_len * kv_len * head_dim
    if causal:
        pair /= 2.0
    q = batch * heads * q_len * head_dim * itemsize
    kv = batch * heads * kv_len * head_dim * itemsize
    lse = batch * heads * q_len * 4
    if backward:
        return 5.0 * pair, float(3 * q + 2 * kv + lse + q + 2 * kv)
    return 2.0 * pair, float(q + 2 * kv + q + lse)

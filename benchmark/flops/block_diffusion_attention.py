"""Operations and bytes one block-diffusion attention call needs, from its
shapes: 2L positions (a noised copy, then the clean copy), grouped K/V
heads.

Forward: QK^T and PV on the live (query, key) pairs of the mask only
(``flops/sdar_moe.py::live_pairs``), 2 matmuls of 2 * pairs * dh FLOPs per
query head; it reads q once, k and v once at the K/V head count (a group's
query heads share them), and writes o and the f32 log-sum-exp row.
Backward (the dq and the dk/dv kernels together): five matmuls of the same
size; it reads q, k, v, o, do and the log-sum-exp and writes dq, dk, dv,
k-sized tensors at the K/V head count.  The least the algorithm needs, so
the share of the roofline is a lower bound on how well the kernel does.
"""

from __future__ import annotations

from .sdar_moe import live_pairs


def ops_bytes(*, batch: int, heads: int, kv_heads: int, seq_len: int, block: int,
              head_dim: int, itemsize: int, backward: bool) -> tuple[float, float]:
    pair = 2.0 * batch * heads * live_pairs(seq_len, block) * head_dim
    q = batch * heads * 2 * seq_len * head_dim * itemsize
    kv = batch * kv_heads * 2 * seq_len * head_dim * itemsize
    lse = batch * heads * 2 * seq_len * 4
    if backward:
        return 5.0 * pair, float(3 * q + 2 * kv + lse + q + 2 * kv)
    return 2.0 * pair, float(q + 2 * kv + q + lse)

"""Model FLOPs of a GPT-2 style decoder, from the configuration's shapes.

Counted: every matrix multiplication of the forward pass, times 3 for
forward + backward (the backward pass makes two matmuls for each one of the
forward).  Attention's QK^T and AV are counted, causal at half.  The output
head is counted.  Not counted: embedding look-ups, layer norms, GELU,
softmax, the optimizer, and anything recomputed.

Hand-worked, GPT-2 124M (d=768, 12 layers, V=50257, L=1024), per token:
  per layer   qkv 2*d*3d + proj 2*d*d + mlp 2*d*4d*2 = 24*d^2 = 14,155,776
  12 layers                                              = 169,869,312
  attention   QK^T + AV, full: 2 * 2*L*d; causal half    = 2*L*d = 1,572,864
  12 layers                                              =  18,874,368
  head        2*d*V                                      =  77,194,752
  forward                                                = 265,938,432
  x3                                                     = 797,815,296  (0.80 GFLOP/token)
"""

from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    d_ff = cfg.get("n_inner") or 4 * d
    dense = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * d_ff
    attention = 2 * seq_len * d          # QK^T + AV at causal half
    return float(layers * (dense + attention) + 2 * d * vocab)


def train_flops_per_sample(cfg: dict, shape: dict) -> float:
    """One sample is one sequence of ``shape["seq_len"]`` tokens."""
    seq_len = int(shape["seq_len"])
    return 3.0 * forward_flops_per_token(cfg, seq_len) * seq_len


def units_per_sample(cfg: dict, shape: dict) -> tuple[str, float]:
    return "tokens", float(shape["seq_len"])

"""Model FLOPs of SDAR-MoE trained by block diffusion, from the
configuration's shapes.

Counted as in ``flops/gpt2.py``: every matrix multiplication of the forward
pass, times 3 for forward + backward; nothing recomputed.  One sample is one
sequence of L clean tokens, which the model runs as P = 2L positions (a
noised copy and the clean copy).  Per position and layer: the q/k/v/o
projections, the router, and the HELD experts at their expected share of
the routed assignments (k * held / router width passes through one gated
expert: three matmuls) — or, given the share of the routed assignments that
landed on held experts in the measured window (``held_share``, from the
step's own counter), at what the dropless layer really multiplied: routing
at random weights is far from even, and a layer that drops nothing does
more or less than the expected share of the work.  Attention's QK^T and PV are counted on the LIVE
(query, key) pairs of the block-diffusion mask, exactly (``live_pairs``),
never P^2 / 2.  The head is counted on the L noisy positions.  Not counted:
embedding look-ups, norms, RoPE, softmax, routing and sorting, the optimizer.

Hand-worked, the cell's cut (d=2048, 32 q / 4 kv heads of 128, router 128
wide, 8 a token, 16 held, expert width 768, 6 layers, V=18,992, L=4096,
B=4), per sequence:
  q and o      2 * (2 * 2048 * 4096)                    =        33,554,432
  k and v      2 * (2 * 2048 * 512)                     =         4,194,304
  router       2 * 2048 * 128                           =           524,288
  experts      8 * 16/128 = 1 pass: 3 * 2 * 2048 * 768  =         9,437,184
  per position and layer                                =        47,710,208
  x 8192 positions                                      =   390,842,023,936
  live pairs   noisy-noisy 4096*4 = 16,384; noisy-clean 16 * 1024*1023/2 =
               8,380,416; clean-clean 16 * 1024*1025/2 = 8,396,800
                                                        =        16,793,600  (25.02 % of 8192^2)
  attention    4 * 128 * 32 * 16,793,600                =   275,146,342,400
  per layer                                             =   665,988,366,336
  x 6 layers                                            = 3,995,930,198,016
  head         2 * 2048 * 18,992 * 4096                 =   318,632,886,272
  forward                                               = 4,314,563,084,288
  x 3                                                   = 12,943,689,252,864  (12.94 TFLOP/sequence)
"""

from __future__ import annotations


def live_pairs(seq_len: int, block: int) -> int:
    """Live (query, key) pairs of the block-diffusion mask over 2L positions."""
    total = 0
    for start in range(0, seq_len, block):
        rows = min(block, seq_len - start)
        # a noisy row: its own block's noisy keys + every clean key before
        # the block; a clean row: the clean keys up to its block's end
        total += rows * (rows + start) + rows * (start + rows)
    return total


def forward_flops_per_sequence(cfg: dict, seq_len: int, held_share: float | None = None) -> float:
    d, layers, vocab = cfg["hidden_size"], cfg["layers"], cfg["vocab_size"]
    heads, kv_heads, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    overrides = cfg["system"]["overrides"]
    router, held, k = overrides["num_experts"], cfg["num_experts"], cfg["num_experts_per_tok"]
    dense = 2 * (2 * d * heads * dh) + 2 * (2 * d * kv_heads * dh) + 2 * d * router
    share = held / router if held_share is None else held_share
    experts = k * share * 3 * 2 * d * cfg["moe_intermediate_size"]
    attention = 4 * dh * heads * live_pairs(seq_len, overrides["block_length"])
    return float(layers * (2 * seq_len * (dense + experts) + attention) + 2 * d * vocab * seq_len)


def train_flops_per_sample(cfg: dict, shape: dict, held_share: float | None = None) -> float:
    """One sample is one sequence of ``shape["seq_len"]`` clean tokens."""
    return 3.0 * forward_flops_per_sequence(cfg, int(shape["seq_len"]), held_share)


def units_per_sample(cfg: dict, shape: dict) -> tuple[str, float]:
    return "tokens", float(shape["seq_len"])

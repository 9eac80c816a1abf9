"""Model FLOPs of the Qwen3-Next decoder under next-token training, from the
configuration's shapes.

Counted as in ``flops/gpt2.py``: every matrix multiplication of the forward
pass, times 3 for forward + backward; nothing recomputed.  Layer i is full
attention where ``(i + 1) % full_attention_interval == 0``, Gated DeltaNet
otherwise, and every layer has experts.  Per token:

- Gated DeltaNet: ``W_qkvz``, ``W_ba`` and ``W_out``; the depthwise
  convolution over ``[q | k | v]`` (K multiply-adds a channel); and for the
  delta rule the RECURRENCE's own count, ``S k``, the rank-one update and
  ``S q``, a multiply-add a state element each, 6 d_k d_v a position and
  value head, whatever algorithm computes it (the chunked form's products,
  its solve included, come to more, and are not what is counted);
- attention: ``W_q`` (query and gate), ``W_k``, ``W_v``, ``W_o`` and causal
  attention at half (q.k and p.v);
- experts: the router, the shared expert and its gate for every token, and
  the HELD routed experts at their expected share of the routed assignments
  (k * held / router width passes through one gated expert: three matmuls) —
  or, given the share of the routed assignments that landed on held experts
  in the measured window (``held_share``, from the step's own counter), at
  what the dropless layer really multiplied, as ``flops/instella_moe.py``
  does;
- the head on every position.

Not counted: embedding look-ups, norms, L2 norms, softmax, softplus, SiLU,
sigmoids, the gates' products with their inputs, routing and sorting, the
optimizer.

Hand-worked, the cell's cut (d=2048; Gated DeltaNet 16 key and 32 value
heads of 128, kernel 4; attention 16 / 2 heads of 256; experts 512 wide,
router 512 wide, 10 a token, 32 held, shared 512; layers LLLF; V=18,992;
T=8192), matmul parameters a token meets:
  L     W_qkvz, W_ba 2048*12,352 + W_out 4096*2048 = 33,685,504    x 3 = 101,056,512
  F     W_q 2048*8192 + W_k, W_v 2*2048*512 + W_o 4096*2048          =  27,262,976
  MoE   router 2048*512 = 1,048,576; shared 3*2048*512 = 3,145,728;
        gate 2048; experts 10 * 32/512 = 0.625 pass: 0.625 * 3*2048*512 = 1,966,080
        = 6,162,432                                                 x 4 =  24,649,728
  head  2048 * 18,992                                                  =  38,895,616
  matmul parameters a token                                            = 191,864,832
  x 2 (forward)                                                        = 383,729,664
  convolution  2 * 4 * 8192 = 65,536                             x 3   =     196,608
  delta rule   6 * 128 * 128 * 32 = 3,145,728                    x 3   =   9,437,184
  attention    q.k + p.v at causal half: 2 * 8192 * 16 * 256            =  67,108,864
  forward a token                                                      = 460,472,320
  x 3                                                                  = 1,381,416,960  (1.38 GFLOP/token)
  x 8192 tokens                                                        = 11,316,567,736,320 a sequence
of which the three Gated DeltaNet layers 5,203,889,750,016 (46.0 %).
"""

from __future__ import annotations


def layers(cfg: dict) -> str:
    """``L`` (Gated DeltaNet) or ``F`` (full attention) for each layer that runs."""
    interval = int(cfg["full_attention_interval"])
    return "".join("F" if (i + 1) % interval == 0 else "L" for i in range(int(cfg["layers"])))


def expert_blocks(cfg: dict) -> int:
    """Places a microbatch has an expert layer at: every layer."""
    return len(layers(cfg))


def forward_flops_per_token(cfg: dict, seq_len: int, held_share: float | None = None) -> float:
    d, vocab, run = cfg["hidden_size"], cfg["vocab_size"], layers(cfg)
    groups, heads = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    keys, values = groups * dk, heads * dv
    linear = (2 * (d * (2 * keys + 2 * values + 2 * heads) + values * d)
              + 2 * cfg["linear_conv_kernel_dim"] * (2 * keys + values) + 6 * heads * dk * dv)
    h, kv_heads, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = 2 * (2 * d * h * dh + 2 * d * kv_heads * dh + h * dh * d) + seq_len * h * 2 * dh    # causal at half
    router, held, k = cfg["system"]["overrides"]["num_experts"], cfg["num_experts"], cfg["num_experts_per_tok"]
    share = held / router if held_share is None else held_share
    experts = 2 * (d * router + 3 * d * cfg["shared_expert_intermediate_size"] + d
                   + k * share * 3 * d * cfg["moe_intermediate_size"])
    return float(run.count("L") * linear + run.count("F") * attention + len(run) * experts + 2 * d * vocab)


def train_flops_per_sample(cfg: dict, shape: dict, held_share: float | None = None) -> float:
    """One sample is one sequence of ``shape["seq_len"]`` tokens."""
    seq_len = int(shape["seq_len"])
    return 3.0 * forward_flops_per_token(cfg, seq_len, held_share) * seq_len


def units_per_sample(cfg: dict, shape: dict) -> tuple[str, float]:
    return "tokens", float(shape["seq_len"])

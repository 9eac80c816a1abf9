"""Model FLOPs of the ``nemotron_h`` tower under next-token training, from the
configuration's shapes.

Counted as in ``flops/gpt2.py``: every matrix multiplication of the forward
pass, times 3 for forward + backward; nothing recomputed.  The layers are the
first ``layers`` letters of ``hybrid_override_pattern``.  Per token:

- ``M``: the in- and out-projections; the depthwise convolution (K
  multiply-adds a channel); and for the scan the RECURRENCE's own count, a
  multiply-add a state element for the update and one for the read-out, 4 H P
  N a position, whatever algorithm computes it (the chunked form's products
  come to about 1.5 times that, and are not what is counted);
- ``*``: the four projections and causal attention at half (q.k and p.v);
- ``E``: the router, the shared expert for every token and the HELD routed
  experts at their expected share of the routed assignments (k * held / router
  width passes through one plain expert: two matmuls) — or, given the share of
  the routed assignments that landed on held experts in the measured window
  (``held_share``, from the step's own counter), at what the dropless layer
  really multiplied, as ``flops/instella_moe.py`` does;
- the head on every position.

Not counted: embedding look-ups, norms, softmax, softplus, SiLU, relu², the
gate, the D skip, routing and sorting, the optimizer.

Hand-worked, the cell's cut (d=2688; M: 64 heads of 64, 8 groups, state 128,
kernel 4; *: 32 / 2 heads of 128; E: experts 1856 wide, router 128 wide, 6 a
token, 8 held, shared 3712; layers MEMEM*EME; V=16,384; T=8192), matmul
parameters a token meets:
  M   W_in 2688*10,304 + W_out 4096*2688 = 38,707,200   x 4   = 154,828,800
  *   W_q, W_o 2*2688*4096 + W_k, W_v 2*2688*256              =  23,396,352
  E   router 2688*128 = 344,064; shared 2*2688*3712 = 19,955,712;
      experts 6 * 8/128 = 0.375 pass: 0.375 * 2*2688*1856 = 3,741,696
      = 24,041,472                                      x 4   =  96,165,888
  head  2688 * 16,384                                         =  44,040,192
  matmul parameters a token                                   = 318,431,232
  x 2 (forward)                                               = 636,862,464
  convolution  2 * 4 * 6144 = 49,152                    x 4   =     196,608
  scan         4 * 64 * 64 * 128 = 2,097,152            x 4   =   8,388,608
  attention    q.k + p.v at causal half: 2 * 8192 * 32 * 128  =  67,108,864
  forward a token                                             = 712,556,544
  x 3                                                         = 2,137,669,632  (2.14 GFLOP/token)
  x 8192 tokens                                               = 17,511,789,625,344 a sequence
of which the four M layers 7,821,135,446,016 (44.7 %).
"""

from __future__ import annotations


def layers(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][: int(cfg["layers"])]


def expert_blocks(cfg: dict) -> int:
    """Places a microbatch has an expert layer at."""
    return layers(cfg).count("E")


def forward_flops_per_token(cfg: dict, seq_len: int, held_share: float | None = None) -> float:
    d, vocab, run = cfg["hidden_size"], cfg["vocab_size"], layers(cfg)
    h, p, g, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    inner, streams = h * p, h * p + 2 * g * n
    mixer = 2 * (d * (inner + streams + h) + inner * d) + 2 * cfg["conv_kernel"] * streams + 4 * h * p * n
    heads, kv_heads, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = 2 * (2 * d * heads * dh + 2 * d * kv_heads * dh) + seq_len * heads * 2 * dh    # causal at half
    router, held, k = cfg["system"]["overrides"]["n_routed_experts"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    share = held / router if held_share is None else held_share
    experts = 2 * (d * router + 2 * d * cfg["moe_shared_expert_intermediate_size"]
                   + k * share * 2 * d * cfg["moe_intermediate_size"])
    return float(run.count("M") * mixer + run.count("*") * attention + run.count("E") * experts + 2 * d * vocab)


def train_flops_per_sample(cfg: dict, shape: dict, held_share: float | None = None) -> float:
    """One sample is one sequence of ``shape["seq_len"]`` tokens."""
    seq_len = int(shape["seq_len"])
    return 3.0 * forward_flops_per_token(cfg, seq_len, held_share) * seq_len


def units_per_sample(cfg: dict, shape: dict) -> tuple[str, float]:
    return "tokens", float(shape["seq_len"])

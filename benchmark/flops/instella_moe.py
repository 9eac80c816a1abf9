"""Model FLOPs of Instella-MoE (``deepseek_v3``) under its training objective,
from the configuration's shapes.

Counted as in ``flops/gpt2.py``: every matrix multiplication of the forward
pass, times 3 for forward + backward; nothing recomputed.  Per token and
block: MLA's five projections (W_q, W_kva, W_kvb, W_o and the output gate
W_g) and causal attention at half with q.k over nope + rope and p.v over the
value width; then the dense MLP (the leading layers), or the router, the
shared MLP for every token and the HELD routed experts at their expected
share of the routed assignments (k * held / router width passes through one
gated expert: three matmuls) — or, given the share of the routed assignments
that landed on held experts in the measured window (``held_share``, from the
step's own counter), at what the dropless layer really multiplied, as
``flops/sdar_moe.py`` does.  The MTP module is its projection, one more
expert block and the head a second time.  Both heads are counted on every
position.  Not counted: embedding look-ups, norms, RoPE, softmax, the gate's
sigmoid, routing and sorting, the balance term, the optimizer.

Hand-worked, the cell's cut (d=2048, 16 heads of 96 + 32 / 128, K/V latent
512, dense MLP 10,944, experts 1408 wide, router 64 wide, 6 a token, 8 held,
shared 2 x 1408, 1 dense + 4 expert layers + the MTP module, V=16,112,
T=8192), matmul parameters a token meets:
  attention   W_q 2048*2048 + W_kva 2048*544 + W_kvb 512*3584
              + W_o 2048*2048 + W_g 2048*2048          =  15,532,032
  x 6 blocks                                           =  93,192,192
  dense MLP   3 * 2048 * 10,944                        =  67,239,936
  shared MLP  3 * 2048 * 2816 = 17,301,504  x 5        =  86,507,520
  router      2048 * 64 = 131,072           x 5        =     655,360
  experts     6 * 8/64 = 0.75 pass: 0.75 * 3*2048*1408
              = 6,488,064                   x 5        =  32,440,320
  MTP proj    4096 * 2048                              =   8,388,608
  heads       2 * 2048 * 16,112                        =  65,994,752
  matmul parameters a token                            = 354,418,688
  x 2 (forward)                                        = 708,837,376
  attention   q.k + p.v at causal half: 2 * 8192 * 16 * 128 = 33,554,432
  x 6 blocks                                           = 201,326,592
  forward a token                                      = 910,163,968
  x 3                                                  = 2,730,491,904  (2.73 GFLOP/token)
  x 8192 tokens                                        = 22,368,189,677,568 a sequence
"""

from __future__ import annotations


def expert_blocks(cfg: dict) -> int:
    """Places a microbatch has an expert layer at: the trunk's expert layers
    and the MTP modules'."""
    return int(cfg["layers"]) - int(cfg["first_k_dense_replace"]) + int(cfg["num_nextn_predict_layers"])


def forward_flops_per_token(cfg: dict, seq_len: int, held_share: float | None = None) -> float:
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    heads, nope, rot, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, width = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    router, held, k = cfg["system"]["overrides"]["n_routed_experts"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    dense_layers, mtp = cfg["first_k_dense_replace"], cfg["num_nextn_predict_layers"]
    blocks = cfg["layers"] + mtp
    attention = (d * heads * (nope + rot) + d * (rank + rot) + rank * heads * (nope + dv)
                 + heads * dv * d + d * heads * dv)
    share = held / router if held_share is None else held_share
    expert_layer = d * router + 3 * d * cfg["n_shared_experts"] * width + k * share * 3 * d * width
    params = (blocks * attention + dense_layers * 3 * d * cfg["intermediate_size"]
              + expert_blocks(cfg) * expert_layer + mtp * 2 * d * d + (1 + mtp) * d * vocab)
    pairs = blocks * seq_len * heads * ((nope + rot) + dv)       # q.k + p.v, causal at half
    return float(2 * params + pairs)


def train_flops_per_sample(cfg: dict, shape: dict, held_share: float | None = None) -> float:
    """One sample is one sequence of ``shape["seq_len"]`` tokens."""
    seq_len = int(shape["seq_len"])
    return 3.0 * forward_flops_per_token(cfg, seq_len, held_share) * seq_len


def units_per_sample(cfg: dict, shape: dict) -> tuple[str, float]:
    return "tokens", float(shape["seq_len"])

"""Instella-MoE — a DeepSeek-V3-shaped decoder (``model_type`` ``deepseek_v3``,
amd/Instella-MoE-16B-A3B-Base): latent (MLA) attention with an output gate,
one leading dense layer, sigmoid-routed experts beside shared ones, the
far-skip residual, and a multi-token-prediction (MTP) module.

With ``N`` an RMSNorm with a learned scale and ``x`` a sublayer's input:

- **MLA** (no query latent): ``q = x W_q`` → H × (nope + rope);
  ``[c ; k_rope] = x W_kva`` (``c`` ``kv_lora_rank`` wide, ``k_rope`` one
  rotary key for all heads); ``[k_nope ; v] = N_kv(c) W_kvb``;
  ``k_h = [k_nope_h ; k_rope]``.  ``qk_layernorm``: an RMSNorm over each
  head's q and k (one learned scale each) before the rotation.  RoPE turns
  the LAST ``qk_rope_head_dim`` of q and k with YaRN's frequencies
  (:func:`yarn_inv_freq`; half-split pairing: the published interleaved
  pairing under a fixed permutation of ``W_q`` / ``W_kva`` columns).  Scores
  ``q·k × (nope + rope)^-0.5 × mscale²`` (:func:`softmax_scale`), causal.
  ``gated_attention``: ``o ← o ⊙ σ(x W_g)`` on the concatenated heads
  before ``W_o``.
- **Experts** (``models/moe.TopKMoe``, sigmoid scores, a selection-only bias,
  ``routed_scaling_factor``, the sequence-wise balance term) plus ONE
  SiLU-gated MLP of width ``n_shared_experts × moe_intermediate_size``
  that every token takes.  The first ``first_k_dense_replace`` layers have
  a SiLU-gated MLP of width ``intermediate_size`` instead.
- **Far-skip** (``farskip``): with sublayers ``f_1 … f_2L`` and ``r_0`` the
  embedding, ``r_1 = r_0 + f_1(N_1(r_0))`` and ``r_k = r_{k-1} +
  f_k(N_k(r_{k-2}))``: a sublayer reads the stream as it stood BEFORE its
  predecessor's output was added.  A block therefore takes and returns TWO
  streams, and per-block remat wraps the pair.  Off, it is the standard
  pre-norm block.
- **MTP** (DeepSeek-V3 §2.2, depth 1): ``h' = [N_h(h) ; N_e(Emb(x_{t+1}))]
  W_eh`` with ``h`` the trunk's last stream before the final norm, one more
  MoE block, a norm, the trunk's head; ``train/step.py`` reads
  ``lm_objective`` and adds its cross entropy on tokens two ahead.

Which of these forms the published config only names (``gated_attention``,
``qk_layernorm``, ``farskip``) is said in the benchmark's configuration file
under ``assumed``.  The config's fields are the published keys; what a chip
holds of the model stands beside them as in ``models/sdar.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import scope
from ..ops.attention import dot_product_attention
from .moe import TopKMoe
from .sdar import REMAT_SAVE, RMSNorm, rope

_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 40), ("mscale", 1),
         ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096), ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class InstellaMoeConfig:
    vocab_size: int = 128896
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 16
    qk_nope_head_dim: int = 96
    qk_rope_head_dim: int = 32
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64         # the router's width, never cut
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 8e6
    rope_scaling: Any = _YARN          # the published group; a dict is taken too
    gated_attention: bool = True
    qk_layernorm: bool = True
    farskip: bool = True
    num_nextn_predict_layers: int = 1
    # The objective's weights (train/step.py): λ on the MTP module's cross
    # entropy, α on the sequence-wise balance term.
    mtp_loss_weight: float = 0.3
    seq_aux_alpha: float = 1e-4
    # This chip's share of each layer's routed experts, as SdarConfig's.
    experts_held: tuple | None = None
    remat: bool = False

    def __post_init__(self):
        if self.experts_held is not None:      # JSON hands a list
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one MTP module or none")


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> jax.Array:
    """DeepSeek-V3's YaRN inverse frequencies for ``dim`` rotary dimensions,
    (dim/2,): pair i turns at ``theta^(-2i/dim)`` (extrapolated) below the
    correction dimension of ``beta_fast`` rotations over the original
    context, at that ÷ ``factor`` (interpolated) above ``beta_slow``'s, and
    at a linear blend between."""
    def correction_dim(rotations):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-pair / (dim // 2))
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(cfg: InstellaMoeConfig) -> float:
    """``(nope + rope)^-0.5 × mscale²``, ``mscale`` YaRN's over
    ``mscale_all_dim``; the factor on cos / sin is ``mscale /
    mscale_all_dim``'s ratio, 1 here, and not applied."""
    s = dict(cfg.rope_scaling)
    m = _yarn_mscale(s["factor"], s["mscale_all_dim"]) if s["mscale_all_dim"] else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _dense(n, name, dtype):
    return nn.Dense(n, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(stddev=0.02))


class MlaAttention(nn.Module):
    cfg: InstellaMoeConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        b, p, _ = x.shape
        h, dn, dr, dv, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                               cfg.v_head_dim, cfg.kv_lora_rank)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype, name=name)
        scaling = dict(cfg.rope_scaling)
        if scaling["mscale"] != scaling["mscale_all_dim"]:
            raise ValueError("a cos/sin factor other than 1 (mscale != mscale_all_dim) is not written")
        inv_freq = yarn_inv_freq(dr, cfg.rope_theta, scaling)

        def rotate(t):             # the last ``dr`` of every head
            return jnp.concatenate(
                [t[..., :dn], rope(t[..., dn:], positions, cfg.rope_theta, inv_freq)], axis=-1)

        with scope("attn/mla"):
            q = _dense(h * (dn + dr), "wq", self.dtype)(x).reshape(b, p, h, dn + dr)
            latent = _dense(rank + dr, "wkv_a", self.dtype)(x)
            kv = _dense(h * (dn + dv), "wkv_b", self.dtype)(norm("kv_norm")(latent[..., :rank]))
            kv = kv.reshape(b, p, h, dn + dv)
            k_rope = jnp.broadcast_to(latent[:, :, None, rank:], (b, p, h, dr))
            k, v = jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:]
            if cfg.qk_layernorm:
                q, k = norm("q_norm")(q), norm("k_norm")(k)
            q, k = rotate(q), rotate(k)
            q, k, v = (checkpoint_name(t, "attn_qkv") for t in (q, k, v))
        o = dot_product_attention(q, k, v, causal=True, scale=softmax_scale(cfg))
        with scope("attn/mla"):
            o = o.reshape(b, p, h * dv)
            if cfg.gated_attention:
                o = o * jax.nn.sigmoid(_dense(h * dv, "wg", self.dtype)(x))
            return _dense(cfg.hidden_size, "wo", self.dtype)(o)


class GatedMlp(nn.Module):
    """``(silu(x W_gate) ⊙ (x W_up)) W_down``, no bias."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.silu(_dense(self.width, "w_gate", self.dtype)(x)) * _dense(self.width, "w_up", self.dtype)(x)
        return _dense(x.shape[-1], "w_down", self.dtype)(h)


class InstellaBlock(nn.Module):
    """One attention and one feed-forward sublayer over the TWO residual
    streams ``(before, stream)``: ``stream`` as it stands and ``before`` as
    it stood one sublayer earlier; returns the pair one block on.  Under
    ``farskip`` each sublayer reads the older of its two; without it
    ``before`` is not read (the standard pre-norm block)."""

    cfg: InstellaMoeConfig
    dense_mlp: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, before, stream, positions):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype, name=name)
        attn_in = before if cfg.farskip else stream
        with scope("block/norm"):
            y = norm("ln1")(attn_in)
        y = MlaAttention(cfg, self.dtype, name="attn")(y, positions)
        with scope("block/norm"):
            mid = stream + y
            y = norm("ln2")(stream if cfg.farskip else mid)
        if self.dense_mlp:
            with scope("block/mlp"):
                y = GatedMlp(cfg.intermediate_size, self.dtype, name="mlp")(y)
            with scope("block/norm"):
                return mid, mid + y
        routed = TopKMoe(
            cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            scoring="sigmoid", selection_bias=True,
            routed_scaling_factor=cfg.routed_scaling_factor, seq_aux=True,
            dtype=self.dtype, name="moe",
        )(y)
        with scope("moe/shared"):
            shared = GatedMlp(cfg.n_shared_experts * cfg.moe_intermediate_size,
                              self.dtype, name="shared")(y)
        with scope("block/norm"):
            return mid, mid + routed + shared


class InstellaMoe(nn.Module):
    """(B, L) tokens → (B, L, vocab) logits; with ``mtp=True`` also the MTP
    module's logits (position t's row predicts token t + 2; its input pairs
    the trunk's stream at t with the embedding of token t + 1, id 0 past the
    end, as Megatron's roll does)."""

    cfg: InstellaMoeConfig
    dtype: Any = jnp.float32

    # What ``train/step.py``'s LM step reads to pick its objective.
    lm_objective = "next_token_mtp"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False, mtp: bool = False):
        cfg = self.cfg
        positions = jnp.arange(tokens.shape[1])
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype, name=name)
        embed = self.param(
            "embed", nn.initializers.normal(stddev=0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        ).astype(self.dtype)
        block_cls = InstellaBlock
        if cfg.remat:
            block_cls = nn.remat(
                InstellaBlock,
                policy=jax.checkpoint_policies.save_only_these_names(*REMAT_SAVE),
            )
        x = embed[tokens]
        before = x
        for i in range(cfg.num_hidden_layers):
            before, x = block_cls(cfg, i < cfg.first_k_dense_replace, self.dtype,
                                  name=f"block_{i}")(before, x, positions)
        with scope("block/norm"):
            hidden = norm("ln_final")(x)
        if return_hidden:
            return hidden
        head = _dense(cfg.vocab_size, "lm_head", self.dtype)
        with scope("train/head"):
            logits = head(hidden).astype(jnp.float32)
        if not cfg.num_nextn_predict_layers or not (mtp or self.is_initializing()):
            return logits
        with scope("train/mtp"):
            ahead = embed[jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))]
            y = _dense(cfg.hidden_size, "mtp_proj", self.dtype)(
                jnp.concatenate([norm("mtp_hnorm")(x), norm("mtp_enorm")(ahead)], axis=-1))
            _, y = block_cls(cfg, False, self.dtype, name="mtp_block")(y, y, positions)
            with scope("block/norm"):
                y = norm("mtp_final")(y)
            with scope("train/head"):      # the MTP head is a head: the inner name wins
                mtp_logits = head(y).astype(jnp.float32)
        return (logits, mtp_logits) if mtp else logits


def instella_moe_16b_a3b(cfg_overrides: dict | None = None, **kw) -> InstellaMoe:
    """Instella-MoE-16B-A3B-Base as published: 27 layers (the first dense,
    10,944 wide), hidden 2048, 16 MLA heads (96 + 32 / 128, K/V latent 512),
    64 sigmoid-routed experts of width 1408, 6 a token, beside 2 shared,
    one MTP module, vocabulary 128,896.  ``cfg_overrides`` patches
    InstellaMoeConfig fields (a chip's share, toy sizes)."""
    return InstellaMoe(cfg=InstellaMoeConfig(**(cfg_overrides or {})), **kw)

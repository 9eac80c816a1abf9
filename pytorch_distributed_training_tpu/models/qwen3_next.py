"""Qwen3-Next — a hybrid decoder (``model_type`` ``qwen3_next``,
Qwen/Qwen3-Next-80B-A3B-Instruct): Gated DeltaNet linear-attention layers and
gated full attention 3 : 1, every layer's feed-forward routed experts beside
a gated shared one.

With ``N(x) = x · rsqrt(mean(x²) + ε) · (1 + w)`` (zero-centred, ``w`` from
0, ε ``rms_norm_eps``): ``h_0 = Emb[x]``, ``h' = h + mixer(N(h))``,
``h_next = h' + moe(N(h'))``, logits ``= N_f(h_L) W_head`` (untied).  Layer
i's mixer is full attention where ``(i + 1) % full_attention_interval == 0``
and Gated DeltaNet otherwise.  No bias anywhere.

- **Gated DeltaNet** (G key heads and H value heads of d_k = d_v):
  ``[q | k | v | z] = x W_qkvz`` (G d_k | G d_k | H d_v | H d_v) and ``[b |
  a] = x W_ba`` (H | H).  ``[q | k | v] ← silu(causal depthwise conv_K([q |
  k | v]))``, no bias (``ops/causal_conv.causal_conv_silu``: the Pallas pair
  where ``conv_plan`` finds the shapes, reading ``[q | k | v]`` in place at
  column 0 of the projection).  ``q, k`` L2-normalised per head (ε 1e-6),
  ``q`` scaled by ``d_k^-½``; value head h reads key head ``h // (H / G)``.
  ``β = σ(b)``, ``g = -exp(A_log) · softplus(a + dt_bias)``.  ``S_t =
  e^{g_t} S_{t-1} (I - β_t k_t k_tᵀ) + β_t v_t k_tᵀ``, ``o_t = S_t q_t``
  (``ops/gated_delta.gated_delta_rule``, in chunks, the state float32).  ``o ← RMSNorm_{d_v}(o) · w_o · silu(z)`` a value head (``w_o``
  from 1, NOT zero-centred), ``out = o W_out``.
- **Gated attention** (heads of ``head_dim``, grouped K/V): ``[q | gate] = x
  W_q`` (a head's ``head_dim`` + ``head_dim``), ``k = x W_k``, ``v = x W_v``;
  ``q, k ← N(q), N(k)`` a head, zero-centred; rotate-half rotary on each
  head's FIRST ``partial_rotary_factor · head_dim`` lanes at θ
  ``rope_theta``; causal softmax at ``head_dim^-½``
  (``ops.attention.dot_product_attention``); ``o ← o ⊙ σ(gate)``; ``out = o
  W_o``.
- **Experts** (``models/moe.TopKMoe``): ``softmax(x W_r)`` over all
  ``num_experts``, top-``num_experts_per_tok``, renormalised
  (``norm_topk_prob``), SiLU-gated experts ``(silu(x W_g) ⊙ x W_u) W_d``,
  over the experts this chip holds; plus ``σ(x w_sg) · SwiGLU_shared(x)``, a
  shared expert of ``shared_expert_intermediate_size`` under a per-token
  sigmoid gate.

The fixed column orders of ``W_qkvz`` and ``W_ba`` (the published weights
interleave them by key group), the initialisation and the chunk are stated
under ``assumed`` in the benchmark's configuration file.  The config's fields
are the published keys; ``experts_held``, a sliced ``vocab_size`` and a
``num_hidden_layers`` of one pipeline stage are a chip's share, as in
``models/sdar.py``.  The published MTP module is not here.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import scope
from ..ops.attention import dot_product_attention
from ..ops.causal_conv import causal_conv_silu
from ..ops.gated_delta import gated_delta_rule
from ..ops.qk_norm_rope import rope, rope_inv_freq
from .layers import GatedMlp, dense
from .moe import TopKMoe
from .sdar import REMAT_SAVE, RMSNorm

_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts (decoder_sparse_step 1, mlp_only_layers []: every layer's)
    num_experts: int = 512             # the router's width, never cut
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    # This chip's share of each layer's routed experts, as SdarConfig's.
    experts_held: tuple | None = None
    remat: bool = False

    def __post_init__(self):
        if self.experts_held is not None:      # JSON hands a list
            object.__setattr__(self, "experts_held", tuple(self.experts_held))

    def full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A ~ U(0, 16)``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 0.0, 16.0))


def _l2_normalised(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + _L2_EPS)


class GatedDeltaNet(nn.Module):
    """``x`` (B, T, d) → (B, T, d): the linear-attention mixer of the module
    docstring.  β, the decays and the gated norm are float32 between the
    projections; the rule's products take the compute dtype."""

    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        groups, heads = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv, kernel = cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim
        keys, values = groups * dk, heads * dv
        bsz, t, _ = x.shape
        conv_w = self.param("conv_w", lambda key, shape: jax.random.uniform(
            key, shape, jnp.float32, -kernel ** -0.5, kernel ** -0.5), (kernel, 2 * keys + values))
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (heads,), jnp.float32)
        scale = self.param("norm", nn.initializers.ones, (dv,), jnp.float32)

        with scope("gdn/proj"):
            qkvz = dense(2 * keys + 2 * values, "in_proj_qkvz", self.dtype)(x)
            ba = dense(2 * heads, "in_proj_ba", self.dtype)(x)
        with scope("gdn/conv"):
            q, k, v = causal_conv_silu(qkvz, conv_w, jnp.zeros((2 * keys + values,), jnp.float32),
                                       splits=(keys, keys, values))
        with scope("gdn/delta"):
            q = (_l2_normalised(q.reshape(bsz, t, groups, dk)) * dk ** -0.5).astype(v.dtype)
            k = _l2_normalised(k.reshape(bsz, t, groups, dk)).astype(v.dtype)
            ba = ba.astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :heads])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., heads:] + dt_bias)
            o = gated_delta_rule(q, k, v.reshape(bsz, t, heads, dv), g, beta)
        with scope("gdn/gate"):
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_norm_eps) * scale
            z = qkvz[..., 2 * keys + values:].reshape(bsz, t, heads, dv)
            o = (o * nn.silu(z.astype(jnp.float32))).reshape(bsz, t, values).astype(self.dtype)
        with scope("gdn/proj"):
            return dense(cfg.hidden_size, "out_proj", self.dtype)(o)


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        bsz, t, _ = x.shape
        h, hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        rot = int(dh * cfg.partial_rotary_factor)
        positions, inv_freq = jnp.arange(t), rope_inv_freq(rot, cfg.rope_theta)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype, zero_centred=True, name=name)
        turn = lambda m: jnp.concatenate([rope(m[..., :rot], positions, inv_freq), m[..., rot:]], axis=-1)
        with scope("attn/proj"):
            q_gate = dense(h * 2 * dh, "q_proj", self.dtype)(x).reshape(bsz, t, h, 2 * dh)
            k = dense(hkv * dh, "k_proj", self.dtype)(x).reshape(bsz, t, hkv, dh)
            v = dense(hkv * dh, "v_proj", self.dtype)(x).reshape(bsz, t, hkv, dh)
            q, k = turn(norm("q_norm")(q_gate[..., :dh])), turn(norm("k_norm")(k))
            q, k, v = (checkpoint_name(m, "attn_qkv") for m in (q, k, v))
        o = dot_product_attention(q, k, v, causal=True)
        with scope("attn/proj"):
            o = o * jax.nn.sigmoid(q_gate[..., dh:].astype(jnp.float32)).astype(o.dtype)
            return dense(cfg.hidden_size, "o_proj", self.dtype)(o.reshape(bsz, t, h * dh))


class Qwen3NextLayer(nn.Module):
    """``h' = h + mixer(N(h))``, ``h' + moe(N(h'))``; the mixer is gated
    attention where ``full``, Gated DeltaNet otherwise."""

    cfg: Qwen3NextConfig
    full: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, self.dtype, zero_centred=True, name=name)
        with scope("block/norm"):
            y = norm("input_layernorm")(h)
        if self.full:
            y = GatedAttention(cfg, self.dtype, name="self_attn")(y)
        else:
            y = GatedDeltaNet(cfg, self.dtype, name="linear_attn")(y)
        with scope("block/norm"):
            h = h + y
            y = norm("post_attention_layernorm")(h)
        routed = TopKMoe(
            cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob, dtype=self.dtype, name="moe",
        )(y)
        with scope("moe/shared"):
            shared = GatedMlp(cfg.shared_expert_intermediate_size, self.dtype, name="shared_expert")(y)
            gate = jax.nn.sigmoid(dense(1, "shared_expert_gate", self.dtype)(y).astype(jnp.float32))
            shared = shared * gate.astype(shared.dtype)
        with scope("block/norm"):
            return h + routed + shared


class Qwen3Next(nn.Module):
    """(B, L) tokens → (B, L, vocab) logits.  Trains by plain next-token cross
    entropy (``train/step.py``'s default objective)."""

    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32

    # Leaves ``train/step.py`` hands over as stored, not in the compute
    # dtype: a value head's decay rate, behind a T-step product of decays.
    float32_params = ("A_log", "dt_bias")

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False):
        cfg = self.cfg
        embed = self.param(
            "embed", nn.initializers.normal(stddev=0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        layer_cls = Qwen3NextLayer
        if cfg.remat:
            layer_cls = nn.remat(
                Qwen3NextLayer,
                policy=jax.checkpoint_policies.save_only_these_names(*REMAT_SAVE),
            )
        h = embed.astype(self.dtype)[tokens]
        for i in range(cfg.num_hidden_layers):
            h = layer_cls(cfg, cfg.full_attention(i), self.dtype, name=f"block_{i}")(h)
        with scope("block/norm"):
            h = RMSNorm(cfg.rms_norm_eps, self.dtype, zero_centred=True, name="norm")(h)
        if return_hidden:
            return h
        with scope("train/head"):
            return dense(cfg.vocab_size, "lm_head", self.dtype)(h).astype(jnp.float32)


def qwen3_next_80b_a3b(cfg_overrides: dict | None = None, **kw) -> Qwen3Next:
    """Qwen3-Next-80B-A3B as published: 48 layers (Gated DeltaNet and gated
    attention 3 : 1), hidden 2048, Gated DeltaNet with 16 key and 32 value
    heads of 128 and a convolution of 4, attention with 16 / 2 heads of 256
    and rotary on 64 of them, 512 softmax-routed experts of width 512, 10 a
    token, beside a gated shared one of 512, vocabulary 151,936.
    ``cfg_overrides`` patches Qwen3NextConfig fields (a chip's share, toy
    sizes)."""
    return Qwen3Next(cfg=Qwen3NextConfig(**(cfg_overrides or {})), **kw)

"""SDAR-MoE — a block-diffusion language model on a Qwen3-MoE decoder.

``model_type`` ``sdar_moe`` (JetLM/SDAR-30B-A3B-Chat): pre-RMSNorm blocks
of grouped-query attention (per-head RMSNorm on q and k, rotate-half RoPE,
no bias anywhere) and a top-k mixture of SiLU-gated experts with no shared
expert and no dense layer, an untied head.  The model is trained and
sampled by diffusion over blocks: autoregressive across blocks of
``block_length`` tokens, masked diffusion inside one.

Training (the BD3-LM construction, Arriola et al. 2025) runs a sequence of
L tokens as 2L positions — a noised copy, then the clean copy, both at
positions 0 .. L-1 — under the block-diffusion mask
(``ops.attention.block_diffusion_mask``), and reads logits at the noisy
half only.  ``train/step.py`` finds the objective on the module
(``lm_objective``) and ``train/block_diffusion.py`` holds the noising and
the loss.  A plain call (``block_diffusion=False``) is the clean half
alone, each block seeing itself and the blocks before it: what a sampler's
prefill computes, here on the XLA path (initialisation and short lengths).

The config's fields are the published ``config.json``'s keys; what a chip
holds of the model is stated beside them: ``experts_held`` (a contiguous
range of the ``num_experts`` the router scores), a ``vocab_size`` that may
be a slice, ``num_hidden_layers`` that may be one pipeline stage's.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import scope
from ..ops.attention import _xla_masked_attention, dot_product_attention
from ..ops.pallas_attention import FLASH_RESIDUALS
from .moe import TopKMoe


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128            # the router's width, never cut
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    tie_word_embeddings: bool = False
    # This chip's share of each layer's experts: (first, count) of the
    # num_experts; None holds all.  The layer computes its own experts'
    # part of the result (models/moe.TopKMoe).
    experts_held: tuple | None = None
    # Block diffusion: tokens a block and the id a noised position takes
    # (train/block_diffusion.py); ``mask_token_id`` None is the vocabulary's
    # last row.
    block_length: int = 4
    mask_token_id: int | None = None
    # Rematerialize each block in the backward (jax.checkpoint), keeping
    # REMAT_SAVE below.
    remat: bool = False

    def __post_init__(self):
        if self.experts_held is not None:      # JSON hands a list
            object.__setattr__(self, "experts_held", tuple(self.experts_held))


# What a rematerialized block keeps for its backward, by ``checkpoint_name``
# (bytes a block at P positions, bf16): the flash kernels' output and
# log-sum-exp (P*H*dh*2 + P*H*4: the forward kernel then runs once) and q, k,
# v after their norm and RoPE (P*(H+2*Hkv)*dh*2: three projections and the
# float32 passes over them).
REMAT_SAVE = (*FLASH_RESIDUALS, "attn_qkv")


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float, inv_freq=None) -> jax.Array:
    """Rotate-half rotary embedding.  x: (B, P, H, D), positions: (P,).
    ``inv_freq`` (D/2,) replaces the plain ``theta`` ladder (a scaled one:
    ``models/instella_moe.yarn_inv_freq``)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half) if inv_freq is None else inv_freq
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]       # (P, D/2)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class SdarAttention(nn.Module):
    cfg: SdarConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions, block_diffusion):
        cfg = self.cfg
        b, p, _ = x.shape
        h, hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                                         kernel_init=nn.initializers.normal(stddev=0.02))
        with scope("attn/proj"):
            q = dense(h * dh, "wq")(x).reshape(b, p, h, dh)
            k = dense(hkv * dh, "wk")(x).reshape(b, p, hkv, dh)
            v = dense(hkv * dh, "wv")(x).reshape(b, p, hkv, dh)
            q = rope(RMSNorm(cfg.rms_norm_eps, self.dtype, name="q_norm")(q), positions, cfg.rope_theta)
            k = rope(RMSNorm(cfg.rms_norm_eps, self.dtype, name="k_norm")(k), positions, cfg.rope_theta)
            q, k, v = (checkpoint_name(t, "attn_qkv") for t in (q, k, v))
        if block_diffusion:
            with scope("attn/block_diffusion"):
                o = dot_product_attention(
                    q, k, v, block_diffusion=(p // 2, cfg.block_length),
                )
        else:
            blk = jnp.arange(p) // cfg.block_length
            with scope("attn/core"):
                o = _xla_masked_attention(q, k, v, blk[None, :] <= blk[:, None])
        with scope("attn/proj"):
            return dense(cfg.hidden_size, "wo")(o.reshape(b, p, h * dh))


class SdarBlock(nn.Module):
    cfg: SdarConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions, block_diffusion):
        cfg = self.cfg
        with scope("block/norm"):
            y = RMSNorm(cfg.rms_norm_eps, self.dtype, name="ln1")(x)
        y = SdarAttention(cfg, self.dtype, name="attn")(y, positions, block_diffusion)
        with scope("block/norm"):
            x = x + y
            y = RMSNorm(cfg.rms_norm_eps, self.dtype, name="ln2")(x)
        y = TopKMoe(
            cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            dtype=self.dtype, name="moe",
        )(y)
        with scope("block/norm"):
            return x + y


class SdarMoe(nn.Module):
    """(B, L) tokens → (B, L, vocab) logits; with ``block_diffusion=True``
    (B, 2L) tokens — noised copy, clean copy — → logits of the L noisy
    positions."""

    cfg: SdarConfig
    dtype: Any = jnp.float32

    # What ``train/step.py``'s LM step reads to pick its objective.
    lm_objective = "block_diffusion"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False,
                 block_diffusion: bool = False):
        cfg = self.cfg
        length = tokens.shape[1] // 2 if block_diffusion else tokens.shape[1]
        positions = jnp.arange(length)
        if block_diffusion:
            if tokens.shape[1] % 2 or length % cfg.block_length:
                raise ValueError(
                    f"block diffusion takes 2L positions, L a multiple of "
                    f"{cfg.block_length}; got {tokens.shape[1]}"
                )
            positions = jnp.concatenate([positions, positions])
        embed = self.param(
            "embed", nn.initializers.normal(stddev=0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        x = embed.astype(self.dtype)[tokens]
        block_cls = SdarBlock
        if cfg.remat:
            block_cls = nn.remat(
                SdarBlock, static_argnums=(3,),
                policy=jax.checkpoint_policies.save_only_these_names(*REMAT_SAVE),
            )
        for i in range(cfg.num_hidden_layers):
            x = block_cls(cfg, self.dtype, name=f"block_{i}")(x, positions, block_diffusion)
        if block_diffusion:
            x = x[:, :length]          # the head reads the noisy half only
        with scope("block/norm"):
            x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="ln_final")(x)
        if return_hidden:
            return x
        with scope("train/head"):
            if cfg.tie_word_embeddings:
                logits = jnp.einsum("bld,vd->blv", x, embed.astype(self.dtype))
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head",
                                  kernel_init=nn.initializers.normal(stddev=0.02))(x)
            return logits.astype(jnp.float32)


def sdar_30b_a3b(cfg_overrides: dict | None = None, **kw) -> SdarMoe:
    """SDAR-30B-A3B-Chat as published: 48 layers, hidden 2048, 32 / 4 heads
    of 128, 128 experts of width 768, 8 a token, vocabulary 151,936.
    ``cfg_overrides`` patches SdarConfig fields (a chip's share, toy sizes)."""
    return SdarMoe(cfg=SdarConfig(**(cfg_overrides or {})), **kw)

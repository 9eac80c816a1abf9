"""Vision Transformer (ViT-B/16) — BASELINE.json configs[2] model.

Not present in the reference tree (its only model is resnet18,
src/main.py:49); required by the BASELINE config "ViT-B/16 / ImageNet, DDP +
mixed precision (AMP→bf16)".  Architecture per Dosovitskiy et al. 2020:
16×16 conv patch embedding, learned position embeddings, CLS token, pre-LN
encoder blocks.  Attention routes through ``ops.dot_product_attention``,
whose measured dispatch picks the low-memory XLA attention (bf16 score
matmul + bf16-saved probabilities, the AMP-faithful path) at ViT's L=197,
below the flash kernel's measured L>=1024 win threshold — see
ops/attention.py; full-model: 894 vs 607 img/s (rounds 1-5, another
machine).  Compute
dtype is threaded for the bf16 (AMP-equivalent) policy.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from ..obs.trace import scope
from .layers import SelfAttention


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        d = x.shape[-1]
        x = nn.Dense(self.mlp_dim, dtype=self.dtype, name="fc1")(x)
        x = nn.gelu(x)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        x = nn.Dense(d, dtype=self.dtype, name="fc2")(x)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        return x


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    attn_layout: str = "auto"

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        with scope("block/norm"):
            y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        y = SelfAttention(
            self.num_heads, causal=False, dtype=self.dtype,
            attn_layout=self.attn_layout, name="attn",
        )(y)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        with scope("block/norm"):
            x = x + y
            y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        with scope("block/mlp"):
            y = MlpBlock(self.mlp_dim, dtype=self.dtype, dropout_rate=self.dropout_rate, name="mlp")(
                y, deterministic=deterministic
            )
        with scope("block/norm"):
            return x + y


class VisionTransformer(nn.Module):
    """ViT classifier over NHWC images."""

    num_classes: int = 1000
    patch_size: int = 16
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    # jax.checkpoint each encoder block in the backward (see
    # GPT2Config.remat for the memory/FLOPs trade).
    remat: bool = False
    # Attention activation-layout contract (models/layers.SelfAttention
    # .attn_layout) — the (B,H,L,Dh)-between-projections experiment
    # (rounds 1-5, another machine).  "bhld2" (head-major q/k/v
    # straight from the projection GEMMs, canonical bh-leading einsums,
    # head-consuming output projection) measured BEST at the batch-44
    # residency optimum: 1070.5 vs 1014-1039 img/s auto (MFU 0.556 vs
    # 0.53-0.54) and is the TPU default.  Param trees are identical
    # across both.
    attn_layout: str = "bhld2"

    @nn.compact
    def __call__(self, x, train: bool = True):
        b = x.shape[0]
        x = jnp.asarray(x, self.dtype)
        x = nn.Conv(
            self.hidden_dim,
            (self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            dtype=self.dtype,
            name="patch_embed",
        )(x)
        x = x.reshape(b, -1, self.hidden_dim)  # (B, N_patches, D)

        cls = self.param(
            "cls_token", nn.initializers.zeros, (1, 1, self.hidden_dim), jnp.float32
        )
        x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, self.hidden_dim)).astype(self.dtype), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, x.shape[1], self.hidden_dim),
            jnp.float32,
        )
        x = x + pos.astype(self.dtype)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=not train)

        block_cls = (
            nn.remat(EncoderBlock, static_argnums=(2,)) if self.remat
            else EncoderBlock
        )
        for i in range(self.depth):
            # deterministic positional: checkpoint static_argnums needs it.
            x = block_cls(
                self.num_heads,
                self.mlp_dim,
                dtype=self.dtype,
                dropout_rate=self.dropout_rate,
                attn_layout=self.attn_layout,
                name=f"block_{i}",
            )(x, not train)

        with scope("block/norm"):
            x = nn.LayerNorm(dtype=self.dtype, name="ln_final")(x)
        with scope("train/head"):
            cls_repr = x[:, 0]
            return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(cls_repr)


def vit_b16(num_classes: int = 1000, cfg_overrides: dict | None = None, **kw) -> VisionTransformer:
    """ViT-Base/16: 12 layers, 768 hidden, 12 heads, 3072 MLP (86M params).

    ``cfg_overrides`` patches constructor fields (smoke runs / scaling sweeps).
    """
    return VisionTransformer(num_classes=num_classes, **(cfg_overrides or {}), **kw)


def vit_s16(num_classes: int = 1000, cfg_overrides: dict | None = None, **kw) -> VisionTransformer:
    """ViT-Small/16: 12 layers, 384 hidden, 6 heads, 1536 MLP (22M params)."""
    cfg = {"hidden_dim": 384, "num_heads": 6, "mlp_dim": 1536,
           **(cfg_overrides or {})}
    return VisionTransformer(num_classes=num_classes, **cfg, **kw)


def vit_l16(num_classes: int = 1000, cfg_overrides: dict | None = None, **kw) -> VisionTransformer:
    """ViT-Large/16: 24 layers, 1024 hidden, 16 heads, 4096 MLP (304M params)."""
    cfg = {"hidden_dim": 1024, "depth": 24, "num_heads": 16, "mlp_dim": 4096,
           **(cfg_overrides or {})}
    return VisionTransformer(num_classes=num_classes, **cfg, **kw)

"""GPT-2 — BASELINE.json configs[3] model (124M / OpenWebText).

Not present in the reference tree (image classification only,
src/main.py:47-49); required by the BASELINE config "GPT-2 124M /
OpenWebText, DDP + gradient accumulation".  Decoder-only transformer per
Radford et al. 2019: learned position embeddings, pre-LN blocks, GELU MLP,
weight-tied LM head.  Causal attention routes through
``ops.dot_product_attention`` (Pallas flash kernel on TPU); the sequence
axis is kept explicit so the sequence-parallel paths (ring attention via
``parallel.ring_attention``, Ulysses all-to-all via ``parallel.ulysses``)
can shard it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from ..obs.trace import scope
from .layers import SelfAttention


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    hidden_dim: int = 768
    mlp_ratio: int = 4
    dropout_rate: float = 0.0
    tie_embeddings: bool = True
    # MoE variant: >0 swaps every odd block's MLP for a Switch-style top-1
    # MoE with this many experts (models/moe.MoeMlp: capacity-bounded, tokens
    # over capacity dropped), expert-parallel over the mesh's `expert` axis.
    # It is one of the file's two routers: the dropless top-k layer
    # (models/moe.TopKMoe) belongs to models/sdar.py and takes none of these.
    num_experts: int = 0
    moe_capacity_factor: float = 1.25
    # Token → expert-buffer formulation of the top-1 layer
    # (models/moe.MoeMlp.dispatch_mode): "einsum" = GShard (T,E,C) one-hots,
    # the form of THAT layer GSPMD shards over `expert`; "scatter" = row
    # scatter/gather, the fast form when its experts are NOT mesh-sharded
    # (identical selection — parity-tested).
    moe_dispatch: str = "einsum"
    # Rematerialize each block in the backward (jax.checkpoint): activation
    # memory drops from O(layers x L x d) to O(layers) block boundaries at
    # ~33% extra forward FLOPs — the HBM trade that makes long-context and
    # deep-model training fit (SURVEY.md §7 hard parts; identical math,
    # tested).
    remat: bool = False


class Block(nn.Module):
    cfg: GPT2Config
    dtype: Any = jnp.float32
    sp_mesh: Any = None  # sequence-parallel attention when set
    sp_mode: str = "ring"  # "ring" | "ulysses"
    decode: bool = False  # KV-cache autoregressive mode
    tp_mesh: Any = None  # TP-sharded decode (serving): kernel dispatch key
    kv_quant: str = "none"  # quantized paged KV storage (--serve-kv-dtype)

    @nn.compact
    def __call__(self, x, deterministic: bool = True, positions=None,
                 block_table=None, attn_mask=None):
        cfg = self.cfg
        with scope("block/norm"):
            y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        y = SelfAttention(
            cfg.num_heads, causal=True, dtype=self.dtype,
            sp_mesh=self.sp_mesh, sp_mode=self.sp_mode,
            decode=self.decode, tp_mesh=self.tp_mesh,
            kv_quant=self.kv_quant, name="attn",
        )(y, positions, block_table, attn_mask)
        y = nn.Dropout(cfg.dropout_rate)(y, deterministic=deterministic)
        with scope("block/norm"):
            x = x + y
            y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        with scope("block/mlp"):
            y = nn.Dense(cfg.hidden_dim * cfg.mlp_ratio, dtype=self.dtype, name="mlp_up")(y)
            y = nn.gelu(y)
            y = nn.Dense(cfg.hidden_dim, dtype=self.dtype, name="mlp_down")(y)
        y = nn.Dropout(cfg.dropout_rate)(y, deterministic=deterministic)
        with scope("block/norm"):
            return x + y


class GPT2(nn.Module):
    """Decoder-only LM: (B, L) int tokens → (B, L, vocab) logits.

    ``sp_mesh``: hand a Mesh with ``sequence > 1`` to run every block's
    attention sequence-parallel (long-context path, CLI
    ``--sequence-parallel``); ``sp_mode`` picks ring (K/V rotation, any
    head count) or ulysses (all-to-all head resharding, needs
    heads % sequence == 0; CLI ``--sequence-parallel-mode``).  Activations
    are length-sharded end to end either way.  Dense blocks only —
    combining with the MoE variant raises (MoE blocks have no SP plumbing
    yet, and silently mixing SP and full attention would forfeit the
    length-sharding memory win SP exists for).
    """

    cfg: GPT2Config
    dtype: Any = jnp.float32
    sp_mesh: Any = None
    sp_mode: str = "ring"
    # KV-cache decode mode (models/generate.py): initialize with a
    # full-length token array to size the caches, then apply one token at a
    # time with mutable=["cache"].
    decode: bool = False
    # TP-sharded decode (serve/engine.py tp_mesh=): marks the blocks as
    # running inside a tensor-parallel program so the fused decode kernels
    # route through their shard_map wrappers (models/layers.py); the XLA
    # paths are GSPMD-partitioned and ignore it.
    tp_mesh: Any = None
    # Quantized paged KV-cache storage (serve/engine.py kv_dtype=):
    # "int8"/"int4" size the decode cache variables at the stored width
    # plus per-position bf16 scales (models/layers.py) — the serving
    # engine's --serve-kv-dtype plumbing; "none" = native dtype.
    kv_quant: str = "none"

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False,
                 positions=None, block_table=None, attn_mask=None):
        """``return_hidden=True`` skips the LM head and returns the final
        hidden states (B, L, D) in compute dtype — the chunked-CE training
        path (``ops.losses.chunked_lm_cross_entropy``) computes the head
        matmul inside its scan so the (B, L, vocab) logits are never
        materialized.

        ``positions`` (decode mode only, serving path): (B,) int32 start
        position per row — each row's chunk embeds at its own positions and
        its K/V scatter to its own slot offsets (models/layers.py slot mode),
        replacing the shared scalar position counter.

        ``block_table`` (B, nb) int32 (decode slot mode only): per-row
        block tables routing the K/V scatter/gather through the paged
        cache pool (serve/kv_pool.PagedKVCachePool).  ``attn_mask``
        (B, C, L) bool: the slot-mode validity mask, computed once by the
        caller per tick and reused by every block (each layer otherwise
        re-derives the identical iota compare)."""
        cfg = self.cfg
        if self.sp_mesh is not None and cfg.num_experts > 0:
            raise ValueError(
                "sequence-parallel attention supports dense GPT-2 only "
                "(MoE blocks are not SP-wired)"
            )
        if self.decode and (cfg.num_experts > 0 or self.sp_mesh is not None):
            raise ValueError(
                "decode mode supports the dense single-device attention path "
                "(no MoE, no sp_mesh)"
            )
        b, l = tokens.shape

        wte = self.param(
            "wte", nn.initializers.normal(stddev=0.02), (cfg.vocab_size, cfg.hidden_dim), jnp.float32
        )
        wpe = self.param(
            "wpe", nn.initializers.normal(stddev=0.01), (cfg.max_seq_len, cfg.hidden_dim), jnp.float32
        )
        if positions is not None and not self.decode:
            raise ValueError("positions is a decode-mode (KV-cache) argument")
        if block_table is not None and positions is None:
            raise ValueError("block_table requires slot-mode positions")
        if self.decode:
            pos_var = self.variable(
                "cache", "position", lambda: jnp.zeros((), jnp.int32)
            )
            if self.is_initializing():
                x = (
                    wte[tokens].astype(self.dtype)
                    + wpe[jnp.arange(l)][None].astype(self.dtype)
                )
            elif positions is not None:
                # Per-row chunk positions (serving slots).  Clip only the
                # embedding GATHER: idle-slot sentinel rows (position >=
                # max_seq_len) compute garbage that the caller discards,
                # while their cache writes are dropped inside attention —
                # an unclipped gather would already clamp silently, the
                # clip just makes the contract explicit.
                cols = jnp.clip(
                    positions[:, None] + jnp.arange(l)[None],
                    0, cfg.max_seq_len - 1,
                )
                x = wte[tokens].astype(self.dtype) + wpe[cols].astype(self.dtype)
            else:
                pos = pos_var.value + jnp.arange(l)
                pos_var.value = pos_var.value + l
                x = (
                    wte[tokens].astype(self.dtype)
                    + wpe[pos][None].astype(self.dtype)
                )
        else:
            x = wte[tokens].astype(self.dtype) + wpe[:l][None].astype(self.dtype)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=not train)

        block_cls = Block
        moe_cls = None
        if cfg.remat:
            # static_argnums: `deterministic` is a Python bool the traced
            # checkpoint must treat as static, not a tracer.
            block_cls = nn.remat(Block, static_argnums=(2,))
        for i in range(cfg.num_layers):
            if cfg.num_experts > 0 and i % 2 == 1:
                from .moe import MoeBlock

                if moe_cls is None:
                    moe_cls = (
                        nn.remat(MoeBlock, static_argnums=(2,))
                        if cfg.remat else MoeBlock
                    )
                # deterministic passed positionally: jax.checkpoint's
                # static_argnums (under nn.remat) sees positional args only.
                x = moe_cls(
                    num_heads=cfg.num_heads,
                    num_experts=cfg.num_experts,
                    mlp_dim=cfg.hidden_dim * cfg.mlp_ratio,
                    capacity_factor=cfg.moe_capacity_factor,
                    dropout_rate=cfg.dropout_rate,
                    dtype=self.dtype,
                    dispatch_mode=cfg.moe_dispatch,
                    name=f"block_{i}",
                )(x, not train)
            else:
                x = block_cls(
                    cfg, dtype=self.dtype, sp_mesh=self.sp_mesh,
                    sp_mode=self.sp_mode,
                    decode=self.decode, tp_mesh=self.tp_mesh,
                    kv_quant=self.kv_quant, name=f"block_{i}",
                )(x, not train, positions, block_table, attn_mask)

        with scope("block/norm"):
            x = nn.LayerNorm(dtype=self.dtype, name="ln_final")(x)
        if return_hidden:
            return x
        with scope("train/head"):
            if cfg.tie_embeddings:
                logits = jnp.einsum("bld,vd->blv", x, wte.astype(self.dtype))
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")(x)
            return logits.astype(jnp.float32)


def gpt2_124m(cfg_overrides: dict | None = None, **kw) -> GPT2:
    """GPT-2 small: 12 layers, 768 hidden, 12 heads, 50257 vocab (124M params).

    ``cfg_overrides`` patches GPT2Config fields (smoke runs / scaling sweeps).
    """
    return GPT2(cfg=GPT2Config(**(cfg_overrides or {})), **kw)


def gpt2_medium(cfg_overrides: dict | None = None, **kw) -> GPT2:
    """GPT-2 medium: 24 layers, 1024 hidden, 16 heads (355M params)."""
    cfg = {"num_layers": 24, "hidden_dim": 1024, "num_heads": 16,
           **(cfg_overrides or {})}
    return GPT2(cfg=GPT2Config(**cfg), **kw)


def gpt2_large(cfg_overrides: dict | None = None, **kw) -> GPT2:
    """GPT-2 large: 36 layers, 1280 hidden, 20 heads (774M params)."""
    cfg = {"num_layers": 36, "hidden_dim": 1280, "num_heads": 20,
           **(cfg_overrides or {})}
    return GPT2(cfg=GPT2Config(**cfg), **kw)


def gpt2_xl(cfg_overrides: dict | None = None, **kw) -> GPT2:
    """GPT-2 XL: 48 layers, 1600 hidden, 25 heads (1.56B params)."""
    cfg = {"num_layers": 48, "hidden_dim": 1600, "num_heads": 25,
           **(cfg_overrides or {})}
    return GPT2(cfg=GPT2Config(**cfg), **kw)

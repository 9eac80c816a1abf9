"""Shared transformer building blocks used by ViT and GPT-2."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from ..obs.trace import scope

def _use_decode_kernel(batch: int) -> bool:
    """Shared dispatch for the fused Pallas decode-attention kernel (both
    the lockstep and the serving slot path — one rule, so a threshold
    change cannot desynchronize them).  The kernel's grid is one
    sequential program per batch row, so LARGE batches invert the trade
    (16.1k vs the XLA path's 33.5k tok/s at batch 128) — hence the
    b <= 64 gate, TPU-only (off-TPU the kernel would run in interpret
    mode — far slower than XLA).  PDT_DECODE_ATTN=xla|pallas overrides
    for A/Bs; it is read at TRACE time, so flipping it in-process needs
    jax.clear_caches() before the next generate()/engine build."""
    import os

    forced = os.environ.get("PDT_DECODE_ATTN", "").lower()
    if forced:
        return forced == "pallas"
    return jax.default_backend() == "tpu" and batch <= 64


# Widest chunk the fused multi-query decode kernels take (the speculative
# verify step's k+1 tokens per slot): past this the (C, L) score tile
# stops being launch-bound and the ragged XLA gather path wins — prefill
# chunks (default 16) stay on that path.
_MAX_FUSED_DECODE_CHUNK = 8


class _QkvToHeads(nn.Module):
    """Fused-QKV projection emitting q/k/v directly as (B, H, L, Dh).

    Same parameters as ``nn.Dense(3*features)`` named "qkv" (kernel
    (D, 3D) + bias), but each of q/k/v comes out of its own einsum whose
    output is already head-major — the relayout rides the GEMM epilogue
    instead of standing as a post-hoc transpose of the packed (B, L, 3D)
    activation.  Layout experiment counterpart to ``_ProjFromHeads``.
    """

    features: int
    num_heads: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        d = self.features
        h = self.num_heads
        dh = d // h
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (d, 3 * d), jnp.float32
        )
        bias = self.param("bias", nn.initializers.zeros, (3 * d,), jnp.float32)
        # Same dtype promotion as nn.Dense(dtype=...): input and params
        # all cast to the module dtype (fall back to x's when unset).
        dtype = self.dtype or x.dtype
        x = x.astype(dtype)
        kq, kk, kv = (
            kernel[:, :d], kernel[:, d:2 * d], kernel[:, 2 * d:]
        )
        bq, bk, bv = bias[:d], bias[d:2 * d], bias[2 * d:]

        def proj(w, b_):
            w = w.reshape(d, h, dh).astype(dtype)
            out = jnp.einsum("bld,dhe->bhle", x, w)
            return out + b_.reshape(h, 1, dh).astype(dtype)[None]

        return proj(kq, bq), proj(kk, bk), proj(kv, bv)


class _ProjFromHeads(nn.Module):
    """Output projection consuming (B, H, L, Dh) directly.

    Declares the SAME parameters as ``nn.Dense(features)`` on the flattened
    (B, L, H*Dh) input — kernel (H*Dh, features) + bias, default Dense
    inits — so checkpoints are interchangeable with the default attention
    path; only the contraction layout differs (einsum over (h, d) with the
    kernel viewed as (H, Dh, features), skipping the (B, L, H, Dh)
    relayout of the attention output).
    """

    features: int
    dtype: Any = None

    @nn.compact
    def __call__(self, o):
        b, h, l, dh = o.shape
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (h * dh, self.features),
            jnp.float32,
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        # Same dtype promotion as nn.Dense(dtype=...).
        dtype = self.dtype or o.dtype
        o = o.astype(dtype)
        wp = kernel.reshape(h, dh, self.features).astype(dtype)
        return (
            jnp.einsum("bhld,hdf->blf", o, wp)
            + bias.astype(dtype)[None, None]
        )


class SelfAttention(nn.Module):
    """Fused-QKV multi-head self-attention over (B, L, D).

    Routes through ``ops.dot_product_attention`` so the Pallas flash kernel
    is selected on TPU; ``causal`` picks the GPT-style masked variant.

    ``sp_mesh``: a Mesh whose ``sequence`` axis is > 1 switches the
    attention core to sequence parallelism; ``sp_mode`` picks the
    decomposition:

    - ``"ring"`` (default): K/V shards rotate over ICI
      (``parallel/ring_attention.py``) — works for any head count,
      scales to extreme lengths.
    - ``"ulysses"``: all-to-all head resharding
      (``parallel/ulysses.py``) — two all-to-alls per attention instead
      of (n-1) ppermutes; needs ``num_heads`` divisible by the
      ``sequence`` axis.

    Either way activations stay sharded on the length dim — the
    long-context path, selectable per model instead of only as a
    standalone op.

    ``decode``: autoregressive KV-cache mode (the flax ``cache`` collection
    pattern).  Initialize with a full-length input to size the cache, then
    apply one token at a time with ``mutable=["cache"]``: K/V land at
    ``cache_index`` and the single query attends over the filled prefix —
    O(L) per token instead of O(L^2) re-prefill.

    Decode mode also accepts per-row ``positions`` (B,) int32 — the serving
    path (serve/): each batch row is an independent cache *slot* whose chunk
    starts at its own position, so ragged live sequences coexist in one
    jitted step.  K/V scatter to ``positions[b] + j`` per row (rows whose
    position is past the cache length are DROPPED — the idle-slot sentinel),
    the chunk attends causally over its own row's filled prefix, and inputs
    may be chunks of any static length (batched/chunked prefill), not just
    one token.

    ``block_table`` (B, nb) int32 switches slot mode to the PAGED cache
    layout (serve/kv_pool.PagedKVCachePool): the cache variables hold
    ``(num_blocks, H, block_size, Dh)`` physical blocks and logical
    position ``p`` of row ``b`` lives at block ``block_table[b, p // bs]``
    offset ``p % bs``.  A table entry == num_blocks is the unallocated/
    idle sentinel (writes drop, reads clamp-and-mask).

    ``attn_mask`` (B, C, L) bool: the slot-mode ragged/causal validity,
    computed ONCE per tick by the caller (serve/engine.py) and reused by
    every layer instead of each layer re-deriving the same iota compare.

    ``tp_mesh``: a Mesh whose ``tensor`` axis is > 1 marks this module as
    running inside a TENSOR-PARALLEL-sharded decode program
    (serve/engine.py ``tp_mesh=``): params carry ``tp_rules_for`` layouts
    and the KV cache is sharded on the heads axis.  The XLA attention
    paths need nothing — GSPMD partitions them from the operand layouts —
    but the fused Pallas decode kernels are opaque to the partitioner, so
    kernel dispatch routes through their shard_map wrappers
    (ops/pallas_attention.*_tp; attention is head-local, each device runs
    the unmodified program on its head shard) and falls back to the XLA
    path when ``tensor`` does not divide the head count.
    """

    num_heads: int
    causal: bool = False
    dtype: Any = None
    sp_mesh: Any = None
    sp_mode: str = "ring"
    decode: bool = False
    tp_mesh: Any = None
    # Quantized KV-cache storage (--serve-kv-dtype, paged slot mode
    # only): "int8" / "int4" store the decode cache as quantized payload
    # plus a bf16 scale per (position, head) — extra ``cached_*_scale``
    # cache variables — encoded at the write scatter and dequantized at
    # the read (inside the paged Pallas kernels, or in the XLA gather
    # path).  "none" is the native-dtype status quo.
    kv_quant: str = "none"
    # "auto" routes through ops.dot_product_attention's measured dispatch.
    # "bhld2" keeps activations (B, H, L, Dh) end-to-end between the qkv and
    # output projections: q/k/v come head-major straight from the
    # projection GEMMs — the layout XLA's batched-dot canonicalization
    # wants (batch dims b,h leading) — the score/combine einsums run
    # canonically with zero internal relayouts, and the output projection
    # consumes (B, H, L, Dh) directly by contracting (h, d) against the
    # reshaped proj kernel (~10 GB/step of dot-canonicalization relayout
    # traffic at ViT batch 128; rounds 1-5, another machine).  XLA
    # non-causal path only (ViT); param tree is identical to "auto".
    attn_layout: str = "auto"

    @nn.compact
    def __call__(self, x, positions=None, block_table=None, attn_mask=None):
        from ..comm.mesh import AXIS_SEQUENCE
        from ..ops import dot_product_attention

        if positions is not None and not self.decode:
            raise ValueError("positions is a decode-mode (KV-cache) argument")
        if block_table is not None and positions is None:
            raise ValueError("block_table requires slot-mode positions")

        b, l, d = x.shape
        head_dim = d // self.num_heads
        if self.attn_layout not in ("auto", "bhld2"):
            raise ValueError(
                f"unknown attn_layout {self.attn_layout!r} (auto|bhld2)"
            )
        if (
            self.attn_layout == "bhld2"
            and not self.decode
            and not self.causal
            and self.sp_mesh is None
        ):
            with scope("attn/proj"):
                q3, k3, v3 = _QkvToHeads(
                    features=d, num_heads=self.num_heads, dtype=self.dtype,
                    name="qkv",
                )(x)
            return self._bhld_core(q3, k3, v3, d)
        with scope("attn/proj"):
            qkv = nn.Dense(3 * d, dtype=self.dtype, name="qkv")(x)
        # Both split forms select the IDENTICAL elements (q is columns
        # 0..d-1 either way: axis 2 of the (3, H, Dh) reshape is the
        # slowest-varying of the packed columns), so the choice is pure
        # layout co-optimization with the attention dispatch: last-axis
        # column spans feed the native-(B, L, H*D) flash kernels without
        # relayout (GPT-2 L=1024: 142.5k -> 147.7k tok/s), while the XLA
        # path fuses the axis-2 form better (ViT L=197 batch 44: 943 vs
        # 872 img/s).  Parameters are compatible across the switch.
        from ..ops.attention import flash_preferred

        with scope("attn/proj"):
            if not self.decode and flash_preferred(
                l, l, head_dim, self.num_heads, itemsize=qkv.dtype.itemsize
            ):
                q = qkv[..., :d].reshape(b, l, self.num_heads, head_dim)
                k = qkv[..., d:2 * d].reshape(b, l, self.num_heads, head_dim)
                v = qkv[..., 2 * d:].reshape(b, l, self.num_heads, head_dim)
            else:
                qkv = qkv.reshape(b, l, 3, self.num_heads, head_dim)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.decode:
            out = self._decode_attend(q, k, v, positions, block_table, attn_mask)
        elif (
            self.sp_mesh is not None
            and self.sp_mesh.shape.get(AXIS_SEQUENCE, 1) > 1
        ):
            if self.sp_mode == "ring":
                from ..parallel import ring_self_attention

                out = ring_self_attention(
                    q, k, v, self.sp_mesh, causal=self.causal
                )
            elif self.sp_mode == "ulysses":
                from ..parallel import ulysses_attention

                out = ulysses_attention(
                    q, k, v, self.sp_mesh, causal=self.causal
                )
            else:
                raise ValueError(
                    f"unknown sp_mode {self.sp_mode!r} (ring|ulysses)"
                )
        else:
            out = dot_product_attention(q, k, v, causal=self.causal)
        with scope("attn/proj"):
            out = out.reshape(b, l, d)
            return nn.Dense(d, dtype=self.dtype, name="proj")(out)

    def _bhld_core(self, q, k, v, d):
        """Canonical (b, h)-leading attention + head-consuming projection
        (``attn_layout="bhld2"``).  Both attention einsums have batch
        dims (b, h) leading — the canonical form XLA's batched-dot
        lowering wants, so no internal relayouts are emitted — and the
        output projection contracts (h, d) straight off the attention
        output via the proj kernel reshaped (H, Dh, D).  bf16 inputs take
        the same bf16-probs low-memory softmax as the XLA attention path
        (ops.attention._softmax_lowp)."""
        from ..ops.attention import _softmax_lowp

        head_dim = q.shape[-1]
        scale = head_dim ** -0.5
        with scope("attn/core"):
            if q.dtype == jnp.bfloat16:
                logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * jnp.asarray(
                    scale, q.dtype
                )
                weights = _softmax_lowp(logits)
            else:
                logits = jnp.einsum(
                    "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
                ) * scale
                weights = nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)
        with scope("attn/proj"):
            return _ProjFromHeads(features=d, dtype=self.dtype, name="proj")(o)

    def _tp(self):
        """The tensor-parallel mesh when TP-sharded serving is active
        (``tensor`` axis > 1), else None — the dispatch key for routing
        decode kernels through their shard_map wrappers."""
        from ..comm.mesh import AXIS_TENSOR

        m = self.tp_mesh
        if m is not None and m.shape.get(AXIS_TENSOR, 1) > 1:
            return m
        return None

    def _tp_kernels_ok(self, tp, num_heads: int) -> bool:
        """Whether kernel dispatch is legal here: always off-TP; on a TP
        mesh only when the tensor axis divides the heads (otherwise the
        XLA ragged path runs, partitioned by GSPMD)."""
        if tp is None:
            return True
        from ..ops.pallas_attention import tp_supports_decode_kernels

        return tp_supports_decode_kernels(tp, num_heads)

    def _decode_attend(self, q, k, v, positions=None, block_table=None,
                       attn_mask=None):
        """Attention against the KV cache.

        At ``init`` the (B, L, H, Dh) input sizes the cache and plain causal
        attention supplies the output.  At ``apply``:

        - ``positions=None``: the input must be one token, appended at the
          shared scalar ``cache_index`` (models/generate.py's lockstep scan).
        - ``positions`` (B,) int32: per-row slot mode (serve/) — the length-l
          chunk of row ``b`` lands at ``positions[b]..positions[b]+l-1`` and
          each query attends its own row's prefix, so rows at different
          sequence lengths share one step.  A position >= cache length makes
          the row's write a dropped scatter (idle-slot sentinel); its output
          is garbage by contract and must be discarded by the caller.
        - ``block_table`` additionally: paged slot mode — the cache
          collection holds a (num_blocks, H, block_size, Dh) block pool
          (installed by serve/kv_pool.PagedKVCachePool; the init-time
          contiguous skeleton is replaced before first apply) and row
          positions route through the table.
        """
        from ..ops import dot_product_attention

        b, l, h, dh = q.shape
        quant = (
            self.kv_quant if self.kv_quant not in (None, "none") else None
        )
        # Cache layout is (B, H, L, Dh) — heads ahead of length.  The
        # per-tick score/combine contractions are then batched over leading
        # (b, h) with a contiguous (L, Dh) tile per head, which the TPU
        # executes 2x faster than the (B, L, H, Dh) layout's interleaved
        # heads (measured 89.5 → 45.1 µs per layer at B=32/L=256,
        # rounds 1-5, another machine; decode attention is the largest tick
        # component, 12×87 µs ≈ half the step before this).
        #
        # Quantized storage (kv_quant): the SAME layout at the stored
        # width — int8 payload (or nibble-packed uint8 at Dh//2) plus a
        # bf16 scale per (position, head) in sibling ``cached_*_scale``
        # variables.  The skeleton these shapes produce at init is what
        # serve/kv_pool.BlockPool turns into quantized physical blocks.
        cks = cvs = None
        if quant is not None:
            if quant not in ("int8", "int4"):
                raise ValueError(
                    f"kv_quant {quant!r} not in ('none', 'int8', 'int4')"
                )
            if quant == "int4" and dh % 2:
                raise ValueError(
                    f"int4 KV packing needs an even head_dim, got {dh}"
                )
            stored_dh = dh // 2 if quant == "int4" else dh
            stored_dt = jnp.uint8 if quant == "int4" else jnp.int8
            ck = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, h, k.shape[1], stored_dh), stored_dt,
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, h, v.shape[1], stored_dh), stored_dt,
            )
            cks = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (b, h, k.shape[1]), jnp.bfloat16,
            )
            cvs = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (b, h, v.shape[1]), jnp.bfloat16,
            )
        else:
            ck = self.variable(
                "cache", "cached_key", jnp.zeros, (b, h, k.shape[1], dh),
                k.dtype,
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros, (b, h, v.shape[1], dh),
                v.dtype,
            )
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if self.is_initializing():
            return dot_product_attention(q, k, v, causal=self.causal)
        if positions is not None:
            if block_table is not None:
                return self._paged_attend(
                    q, k, v, positions, block_table, ck, cv, attn_mask,
                    cks, cvs, quant,
                )
            if quant is not None:
                raise ValueError(
                    "kv_quant stores PAGED blocks — contiguous slot mode "
                    "has no per-block scales (pass block_table)"
                )
            return self._slot_attend(q, k, v, positions, ck, cv, attn_mask)
        if quant is not None:
            raise ValueError(
                "kv_quant is a serving (paged slot-mode) feature — the "
                "lockstep decode cache stays native"
            )
        if l != 1:
            raise ValueError(
                f"decode mode consumes one token per call, got length {l}"
            )
        i = idx.value
        ck.value = lax.dynamic_update_slice(
            ck.value, jnp.transpose(k, (0, 2, 1, 3)), (0, 0, i, 0)
        )
        cv.value = lax.dynamic_update_slice(
            cv.value, jnp.transpose(v, (0, 2, 1, 3)), (0, 0, i, 0)
        )
        idx.value = i + 1
        if _use_decode_kernel(b):
            # Fused decode kernel: scores + masked softmax + combine for
            # all heads of a batch row in ONE Pallas program
            # (ops.pallas_attention.decode_attention).  The small-batch
            # decode tick is kernel-launch-count-bound, not
            # bandwidth-bound (GEN_ROOFLINE (deleted: not measured on the
            # current machine)), so collapsing the
            # ~6-8 XLA fusions this math otherwise lowers to is what
            # moves end-to-end throughput: measured 10.2k → 12.4k tok/s
            # at batch 32 (+22%), 11.8k → 14.5k at 64.  Dispatch rule
            # (batch gate, TPU-only, PDT_DECODE_ATTN override):
            # _use_decode_kernel.
            from ..ops.pallas_attention import decode_attention

            out = decode_attention(q[:, 0], ck.value, cv.value, i)
            return out[:, None].astype(q.dtype)
        max_len = ck.value.shape[2]
        # (B, H, 1, L) scores over the cache; positions past i masked out.
        # K/V are consumed in their stored dtype with fp32 MXU accumulation
        # (preferred_element_type) — an explicit .astype(f32) here would
        # materialize fp32 copies of the FULL cache every tick.  Scale
        # folds in after the einsum, in fp32, same as the flash kernel's
        # score path.
        scale = dh ** -0.5
        scores = jnp.einsum(
            "bqhd,bhkd->bhqk", q, ck.value,
            preferred_element_type=jnp.float32,
        ) * scale
        valid = (jnp.arange(max_len) <= i)[None, None, None, :]
        scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
        probs = nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bhkd->bqhd", probs.astype(cv.value.dtype), cv.value,
            preferred_element_type=jnp.float32,
        )
        return out.astype(q.dtype)

    def _slot_attend(self, q, k, v, positions, ck, cv, attn_mask=None):
        """Per-row-position cache write + ragged-mask attention (serve/).

        q/k/v: (B, C, H, Dh) chunk; ``positions``: (B,) int32 start position
        per row.  mode="drop" on the scatter is load-bearing: a sentinel
        position >= max_len (idle slot) must write NOTHING — clamping would
        silently corrupt the last cache row of live neighbors' slots.
        """
        b, c, h, dh = q.shape
        max_len = ck.value.shape[2]
        rows = jnp.arange(b)[:, None]
        cols = positions[:, None] + jnp.arange(c)[None, :]
        # Advanced indices (rows, cols) around the head slice: the indexed
        # result is (B, C, H, Dh) — exactly k/v's layout, no transpose.
        ck.value = ck.value.at[rows, :, cols].set(k, mode="drop")
        cv.value = cv.value.at[rows, :, cols].set(v, mode="drop")
        tp = self._tp()
        if (
            c == 1 and _use_decode_kernel(b)
            and self._tp_kernels_ok(tp, h)
        ):
            # Same fused kernel as the lockstep path — the per-row index
            # variant: row b's program masks its own prefix 0..positions[b].
            # Under TP the heads-sharded shard_map wrapper runs it.
            if tp is not None:
                from ..ops.pallas_attention import decode_attention_tp

                out = decode_attention_tp(
                    q[:, 0], ck.value, cv.value, positions, mesh=tp
                )
            else:
                from ..ops.pallas_attention import decode_attention

                out = decode_attention(q[:, 0], ck.value, cv.value, positions)
            return out[:, None].astype(q.dtype)
        if (
            c <= _MAX_FUSED_DECODE_CHUNK and _use_decode_kernel(b)
            and self._tp_kernels_ok(tp, h)
        ):
            # Speculative-verify chunk (k+1 tokens per slot): the fused
            # multi-query variant — query j of row b masks its own prefix
            # 0..positions[b]+j, still one program per row.
            if tp is not None:
                from ..ops.pallas_attention import decode_attention_multi_tp

                out = decode_attention_multi_tp(
                    q, ck.value, cv.value, positions, mesh=tp
                )
            else:
                from ..ops.pallas_attention import decode_attention_multi

                out = decode_attention_multi(q, ck.value, cv.value, positions)
            return out.astype(q.dtype)
        return self._ragged_attend(
            q, ck.value, cv.value, cols, max_len, attn_mask
        )

    def _ragged_attend(self, q, kk, vv, cols, max_len, attn_mask):
        """(B, H, C, L) scores over gathered/contiguous cache K/V; query j
        of row b (global position cols[b, j]) sees keys 0..cols[b, j] —
        causal within the chunk AND ragged across rows in one mask,
        supplied precomputed (``attn_mask``, one compute per tick shared by
        all layers) or derived here for direct layer-level callers.  Same
        stored-dtype operands + fp32 accumulation trade as the scalar path.
        """
        dh = q.shape[-1]
        scale = dh ** -0.5
        scores = jnp.einsum(
            "bqhd,bhkd->bhqk", q, kk,
            preferred_element_type=jnp.float32,
        ) * scale
        if attn_mask is not None:
            valid = attn_mask[:, None]  # (B, 1, C, L) over heads
        else:
            valid = (
                jnp.arange(max_len)[None, None, None, :]
                <= cols[:, None, :, None]
            )
        scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
        probs = nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bhkd->bqhd", probs.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32,
        )
        return out.astype(q.dtype)

    def _paged_attend(self, q, k, v, positions, block_table, ck, cv,
                      attn_mask=None, cks=None, cvs=None, quant=None):
        """Block-table cache write + ragged attention (serve/ paged mode).

        q/k/v: (B, C, H, Dh) chunk; cache: (num_blocks, H, block_size, Dh)
        physical blocks; ``block_table``: (B, nb) int32, entry num_blocks =
        unallocated/idle sentinel.  Logical position p of row b writes to
        block ``table[b, p // bs]`` offset ``p % bs`` — mode="drop" plus
        the sentinel entry make idle rows and not-yet-allocated trailing
        chunk columns write NOTHING (the paged analogue of the contiguous
        sentinel position).

        ``quant`` ("int8"|"int4"): the pool's write path IS this scatter —
        the chunk's K/V are encoded here (``comm.compress.quantize_kv``,
        per-position-per-head bf16 scales into ``cks``/``cvs``) so every
        downstream consumer of the blocks (decode reads, COW copies,
        host-tier spills, handoffs) moves only the compressed bytes.  The
        read side dequantizes INSIDE the fused Pallas kernels (the XLA
        gather path dequantizes the gathered window — the off-TPU
        fallback).
        """
        b, c, h, dh = q.shape
        n_blocks, _, bs, _ = ck.value.shape
        nb = block_table.shape[1]
        cols = positions[:, None] + jnp.arange(c)[None, :]  # (B, C) logical
        rows = jnp.arange(b)[:, None]
        # A column past the table span (idle-sentinel rows; a final
        # prefill chunk's trailing padding) must resolve to the DROPPING
        # block id, never clamp onto the row's last real block — a clamped
        # padding write would wrap ``off`` back into valid positions of
        # that block and corrupt live K/V.
        tbl_idx = cols // bs
        blk = jnp.where(
            tbl_idx < nb,
            block_table[rows, jnp.minimum(tbl_idx, nb - 1)],
            n_blocks,
        )
        off = cols % bs
        if quant is not None:
            from ..comm.compress import quantize_kv

            k_store, k_sc = quantize_kv(k, quant)  # (B,C,H,Dh'), (B,C,H)
            v_store, v_sc = quantize_kv(v, quant)
            cks.value = cks.value.at[blk, :, off].set(k_sc, mode="drop")
            cvs.value = cvs.value.at[blk, :, off].set(v_sc, mode="drop")
        else:
            k_store, v_store = k, v
        # Advanced indices (blk, off) around the head slice: the indexed
        # result is (B, C, H, Dh') — exactly the stored chunk's layout.
        ck.value = ck.value.at[blk, :, off].set(k_store, mode="drop")
        cv.value = cv.value.at[blk, :, off].set(v_store, mode="drop")
        safe_table = jnp.minimum(block_table, n_blocks - 1)
        tp = self._tp()
        quant_kw = {}
        if quant is not None:
            quant_kw = dict(
                k_scale=cks.value, v_scale=cvs.value, quant=quant
            )
        if (
            c == 1 and _use_decode_kernel(b)
            and self._tp_kernels_ok(tp, h)
        ):
            # Fused paged kernel: block-table-indexed K/V loads via scalar
            # prefetch, same per-row-index contract as the vector-index
            # variant (ops.pallas_attention.paged_decode_attention).
            if tp is not None:
                from ..ops.pallas_attention import paged_decode_attention_tp

                out = paged_decode_attention_tp(
                    q[:, 0], ck.value, cv.value, safe_table, positions,
                    mesh=tp, **quant_kw,
                )
            else:
                from ..ops.pallas_attention import paged_decode_attention

                out = paged_decode_attention(
                    q[:, 0], ck.value, cv.value, safe_table, positions,
                    **quant_kw,
                )
            return out[:, None].astype(q.dtype)
        if (
            c <= _MAX_FUSED_DECODE_CHUNK and _use_decode_kernel(b)
            and self._tp_kernels_ok(tp, h)
        ):
            # Speculative-verify chunk through the paged pool: same
            # scalar-prefetched table indirection, C queries per program.
            if tp is not None:
                from ..ops.pallas_attention import (
                    paged_decode_attention_multi_tp,
                )

                out = paged_decode_attention_multi_tp(
                    q, ck.value, cv.value, safe_table, positions, mesh=tp,
                    **quant_kw,
                )
            else:
                from ..ops.pallas_attention import paged_decode_attention_multi

                out = paged_decode_attention_multi(
                    q, ck.value, cv.value, safe_table, positions, **quant_kw
                )
            return out.astype(q.dtype)
        from ..ops.pallas_attention import MAX_FUSED_PREFILL_CHUNK

        if (
            c <= MAX_FUSED_PREFILL_CHUNK and _use_decode_kernel(b)
            and self._tp_kernels_ok(tp, h)
        ):
            # Fused CHUNKED PREFILL: the paged decode grid generalized to
            # the prefill chunk width (online softmax across the row's
            # blocks, causal/ragged mask, prefix-skip via the per-row
            # start position) — with this both serving phases run fused
            # (ops.pallas_attention.paged_prefill_attention).
            if tp is not None:
                from ..ops.pallas_attention import (
                    paged_prefill_attention_tp,
                )

                out = paged_prefill_attention_tp(
                    q, ck.value, cv.value, safe_table, positions, mesh=tp,
                    **quant_kw,
                )
            else:
                from ..ops.pallas_attention import paged_prefill_attention

                out = paged_prefill_attention(
                    q, ck.value, cv.value, safe_table, positions, **quant_kw
                )
            return out.astype(q.dtype)
        # Gather each row's K/V through its table into the contiguous
        # (B, H, nb*bs, Dh) read window, then the shared ragged attend —
        # clamped sentinel entries read garbage the mask never admits.
        # Quantized pools dequantize the gathered window here (the
        # off-TPU fallback; the fused kernels above dequantize per block
        # tile in VMEM instead).
        def through_table(blocks):
            g = blocks[safe_table]               # (B, nb, H, bs, Dh')
            g = jnp.transpose(g, (0, 2, 1, 3, 4))
            return g.reshape(b, h, nb * bs, g.shape[-1])

        kk, vv = through_table(ck.value), through_table(cv.value)
        if quant is not None:
            from ..comm.compress import dequantize_kv

            def scales_through(sc):
                g = sc[safe_table]               # (B, nb, H, bs)
                g = jnp.transpose(g, (0, 2, 1, 3))
                return g.reshape(b, h, nb * bs)

            kk = dequantize_kv(kk, scales_through(cks.value), quant)
            vv = dequantize_kv(vv, scales_through(cvs.value), quant)
        return self._ragged_attend(
            q, kk, vv, cols, nb * bs, attn_mask,
        )

"""Flash attention as a Pallas TPU kernel.

Online-softmax blockwise attention (Dao et al.) tiled for the MXU: the
(L×L) score matrix never materializes in HBM; running max/denominator and the
f32 output accumulator live in VMEM scratch across the kv-block grid
dimension (the innermost, sequentially-executed one on TPU).

No counterpart exists in the reference (no attention at all — SURVEY.md §5
"long-context" row); this is the kernel behind ViT-B/16 and GPT-2
(BASELINE.json configs[2]/[3]) and the building block the ring-attention
sequence-parallel path reuses per shard.

Backward pass: ``jax.custom_vjp`` with saved logsumexp; the kernels
recompute p/ds per tile (one fused kernel for dq, dk and dv wherever a
head's dk/dv fit VMEM, dq over kv blocks and dk/dv over q blocks beyond) —
the (L×L) score matrix never materializes in the backward either.
Perf claims rest on FULL-MODEL A/Bs (a round-4 sweep on another machine:
flash wins from L=1024 up while the low-memory XLA path wins below; not
measured on the current machine).  Default blocks are 1024x1024, that
sweep's optimum.  O(L) memory where XLA materializes the
(L x L) scores.

Layout: public API takes (batch, length, heads, head_dim); the kernel tiles
over (batch, heads, q_blocks, kv_blocks) on a (B, H, L, D) transpose.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30

# Every ``pl.pallas_call`` below passes one of these as ``name``: the HLO
# instruction (and so the device trace's event) is then ``%<name>.<n>``.
# By role, not by variant (single-tile, native-layout, grouped, ...), so a
# metric on a name keeps meaning the same work when an implementation is
# swapped; a test walks this file and refuses a call without one.
KERNEL_NAMES = (
    "flash_fwd",          # every forward variant
    "flash_bwd",          # a fused backward (dq, dk, dv from one call)
    "flash_bwd_dq",       # the split backward's two halves (key rows past
    "flash_bwd_dkv",      # the fused backward's fit)
    "flash_bd_fwd",       # the tabled pair under the block-diffusion mask: names
    "flash_bd_bwd",       # of their own, so a causal kernel's metric never reads them
    "decode_attn",        # contiguous cache, one token a row
    "decode_multi_attn",  # contiguous cache, a C-token chunk (verify)
    "paged_decode_attn",  # paged pool, one token a row
    "paged_verify_attn",  # paged pool, the speculative k+1 chunk
    "paged_prefill_attn",  # paged pool, a prefill chunk
)


# ``checkpoint_name``s of the multi-tile forward's output and log-sum-exp.
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _live_block(qi, ki, *, causal, causal_offset, kv_len, block_q, block_k,
                bd=None):
    """Predicate for kv/q tile pairs with any unmasked entry, or None when
    every tile is live.  Shared by the forward and both backward kernels so
    mask variants stay in lockstep."""
    live = None
    if causal:
        live = ki * block_k <= qi * block_q + block_q - 1 + causal_offset
    if bd is not None:
        live = _bd_live_block(qi, ki, block_q, block_k, *bd)
    if kv_len is not None:
        key_live = ki * block_k < kv_len
        live = key_live if live is None else live & key_live
    return live


# The block-diffusion mask (BD3-LM's training mask, Arriola et al. 2025):
# positions 0 .. L-1 hold the noised copy of a sequence, L .. 2L-1 the clean
# copy, and position j of either half lies in block j // B.  A noisy query
# sees the noisy keys of its own block and the clean keys of EARLIER blocks;
# a clean query sees the clean keys of its own and earlier blocks, and never
# a noisy key.  ``bd`` is the static pair (L, B) everywhere below.


def _bd_live_block(qi, ki, block_q, block_k, seq_len, block_len):
    """Whether tile (qi, ki) holds any live (query, key) pair of the
    block-diffusion mask.  A tile may straddle the noisy/clean boundary
    (short padded sequences), so each half of each side is tested."""
    q0, k0 = qi * block_q, ki * block_k
    q1, k1 = q0 + block_q - 1, k0 + block_k - 1
    blk = lambda pos: jnp.minimum(pos, seq_len - 1) // block_len
    q_noisy, k_noisy = q0 < seq_len, k0 < seq_len
    q_clean, k_clean = q1 >= seq_len, k1 >= seq_len
    # block ranges of each side's halves (meaningful only where the half exists)
    qn_lo, qn_hi = q0 // block_len, blk(q1)
    kn_lo, kn_hi = k0 // block_len, blk(k1)
    qc_hi = blk(q1 - seq_len)
    kc_lo = jnp.maximum(k0 - seq_len, 0) // block_len
    same = q_noisy & k_noisy & (kn_lo <= qn_hi) & (qn_lo <= kn_hi)
    earlier = q_noisy & k_clean & (kc_lo < qn_hi)
    clean = q_clean & k_clean & (kc_lo <= qc_hi)
    return same | earlier | clean


def _when_live(compute, qi, ki, mask, bd=None, compute_ranges=None, subs=None):
    """Run a tile's ``compute`` where the tile is live under ``mask`` (the
    keywords of ``_live_block`` but ``bd``).  A live tile whose every pair
    is live — half of them under the block-diffusion mask at L = 4096, 28 of
    36 under the causal one at 8192 — takes ``compute(masked=False)``: no
    mask is built and none applied.  A tile of a diagonal class
    (``_sub_classes``) takes ``compute_ranges(sub, ranges)``, straight-line
    code over the ``_bd_sub_ranges`` of its q sub-blocks and nothing past
    the mask's frontier.  Whatever is left is computed whole under the
    mask."""
    live = _live_block(qi, ki, bd=bd, **mask)
    if live is None:        # no mask and no padded key: ``compute`` builds none
        compute()
        return
    if bd is not None:
        full = _bd_full_block(qi, ki, mask["block_q"], mask["block_k"], *bd)
    else:
        full = _causal_full_block(qi, ki, **mask)
    pl.when(live & full)(functools.partial(compute, masked=False))
    rest = live & jnp.logical_not(full)
    for hit, sub, ranges in _sub_classes(qi, ki, mask, bd, subs):
        pl.when(hit)(functools.partial(compute_ranges, sub, ranges))
        rest &= jnp.logical_not(hit)
    pl.when(rest)(compute)


def _causal_full_block(qi, ki, *, causal, causal_offset, kv_len, block_q,
                       block_k):
    """Whether EVERY pair of tile (qi, ki) is live under the causal (or the
    empty) mask: the tile's last key is one its first query row sees, and
    none of its keys is padding."""
    full = True
    if causal:
        full = (ki + 1) * block_k - 1 <= qi * block_q + causal_offset
    if kv_len is not None:
        full &= (ki + 1) * block_k <= kv_len
    return full


def _bd_full_block(qi, ki, block_q, block_k, seq_len, block_len):
    """Whether EVERY pair of tile (qi, ki) is live: clean keys only, all of
    them in blocks the tile's first query row already sees (no padded key:
    a padded sequence's last clean tile is never full, its ``kv_len`` mask
    stays on).  Such a tile runs without building the mask."""
    q0, k0 = qi * block_q, ki * block_k
    q1, k1 = q0 + block_q - 1, k0 + block_k - 1
    kc_hi = (k1 - seq_len) // block_len
    k_clean = (k0 >= seq_len) & (k1 < 2 * seq_len)
    noisy_q = (q1 < seq_len) & (kc_hi < q0 // block_len)
    clean_q = (q0 >= seq_len) & (kc_hi <= (q0 - seq_len) // block_len)
    return k_clean & (noisy_q | clean_q)


# Rows of a q sub-block (and columns of its masked range) in the classes of
# diagonal tile below, as (frontier, same-block), for the multi-tile forward
# and the fused backward.  Chosen on the chip (PERF.md §6, PR 31): the forward
# is bound by the VPU's passes over masked scores and wants its frontier
# narrow; the backward by the MXU, whose products want 256 rows.  The causal
# mask's diagonal tile is a frontier tile and reads the same (PR 33).
_SUBS = {"fwd": (128, 256), "bwd": (256, 128)}


def _bd_sub_class(frontier, qi, ki, block_q, block_k, seq_len, block_len, sub):
    """Whether tile (qi, ki) belongs to a class of diagonal tile whose live
    pairs lie in static ranges of ``sub`` columns (``_bd_sub_ranges``), or
    None where the static numbers rule the class out: tiles that are not
    square, shorter than two sub-blocks or no multiple of one, or blocks that
    straddle sub-blocks.  Such a tile is live, never full, and holds no
    padded key.

    - ``frontier``: the diagonal tiles of the noisy→clean and the
      clean→clean quarter, a block-causal lower triangle each.  Only where
      the halves are whole tiles (``seq_len`` a multiple of the tile: no
      padded length).
    - otherwise the same-block class: a diagonal tile of the noisy→noisy
      quarter that lies wholly in the noisy half, live in ``block_len``
      squares along its diagonal alone.
    """
    if (
        block_q != block_k or block_q % sub or block_q < 2 * sub
        or sub % block_len
    ):
        return None
    if not frontier:
        return (qi == ki) & ((qi + 1) * block_q <= seq_len)
    if seq_len % block_q:
        return None
    half = seq_len // block_q
    return (ki >= half) & ((ki - half == qi) | (ki == qi))


def _bd_sub_ranges(frontier, block, sub):
    """``[[(lo, hi, masked), ..], ..]``: the key columns of the tile that q
    sub-block ``r`` (rows ``[r * sub, (r + 1) * sub)``) visits.  Every class
    masks the ``sub`` columns on the diagonal; a frontier tile's sub-block
    sees the columns before them whole."""
    ranges = []
    for r in range(block // sub):
        diagonal = (r * sub, (r + 1) * sub, True)
        ranges.append(
            [(0, r * sub, False), diagonal] if frontier and r else [diagonal]
        )
    return ranges


def _causal_sub_class(qi, ki, *, causal, causal_offset, kv_len, block_q,
                      block_k, sub):
    """Whether tile (qi, ki) is a diagonal tile of the causal mask that
    ``_bd_sub_ranges``' frontier form describes (the frontier class at block
    length 1: q sub-block r sees the columns before its own whole and its
    own ``sub`` under the triangle), or None where the static numbers rule
    it out: no causal mask, queries that do not end where the keys do, tiles
    that are not square, shorter than two sub-blocks or no multiple of one.
    Such a tile holds no padded key."""
    if (
        not causal or causal_offset or block_q != block_k
        or block_q % sub or block_q < 2 * sub
    ):
        return None
    hit = qi == ki
    if kv_len is not None:
        hit &= (ki + 1) * block_k <= kv_len
    return hit


def _sub_classes(qi, ki, mask, bd, subs):
    """``(hit, sub, ranges)`` of each class of diagonal tile that the call's
    mask (``mask``: the keywords of ``_live_block`` but ``bd``) and the
    static numbers allow — the tile predicate, the sub-block rows and the
    class's ``_bd_sub_ranges`` — at the kernel's ``subs = (frontier sub,
    same-block sub)`` (``_SUBS``; None for a kernel that knows no class)."""
    if subs is None:
        return
    block_q, block_k = mask["block_q"], mask["block_k"]
    if bd is None:      # the causal diagonal: a frontier tile
        hits = [(True, subs[0], _causal_sub_class(qi, ki, sub=subs[0], **mask))]
    else:
        hits = [
            (frontier, sub,
             _bd_sub_class(frontier, qi, ki, block_q, block_k, *bd, sub))
            for frontier, sub in zip((True, False), subs)
        ]
    for frontier, sub, hit in hits:
        if hit is not None:
            yield hit, sub, _bd_sub_ranges(frontier, block_q, sub)


def _range_mask(q0, k0, rows, cols, *, causal_offset, bd):
    """(rows, cols) boolean tile of the call's mask — block-diffusion, else
    causal — whose first row is position ``q0`` and first column ``k0``."""
    if bd is not None:
        return _bd_mask(q0, k0, rows, cols, *bd)
    q = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    k = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    return q + causal_offset >= k


def _bd_mask(q0, k0, block_q, block_k, seq_len, block_len):
    """(block_q, block_k) boolean tile of the block-diffusion mask whose
    first row is position ``q0`` and first column position ``k0``.  Block
    ids are taken on a column and a row vector, so the full tile costs two
    compares and an or."""
    q = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    q_noisy, k_noisy = q < seq_len, k < seq_len
    if block_len & (block_len - 1) == 0:
        shift = block_len.bit_length() - 1
        blk = lambda pos: pos >> shift      # a vector shift, not a division
    else:
        blk = lambda pos: pos // block_len
    qb = blk(jnp.where(q_noisy, q, q - seq_len))
    kb = blk(jnp.where(k_noisy, k, k - seq_len))
    big = jnp.int32(2**30)
    # clean keys up to the query's last visible clean block
    earlier = jnp.where(k_noisy, big, kb) <= jnp.where(q_noisy, qb - 1, qb)
    # noisy keys of a noisy query's own block
    same = jnp.where(k_noisy, kb, -1) == jnp.where(q_noisy, qb, -2)
    return earlier | same


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    causal: bool,
    causal_offset: int,
    scale: float,
    block_q: int,
    block_k: int,
    kv_len: int | None,
    bd: tuple | None = None,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked=True):
        q = q_ref[0, 0]  # (block_q, d)
        k = k_ref[0, 0]  # (block_k, d)
        v = v_ref[0, 0]  # (block_k, d)
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale != 1.0:   # the block-diffusion mask hands in a scaled q
            s = s * scale  # (block_q, block_k)

        mask = None
        if causal and masked:
            # Bottom-right-aligned causal mask (matches _xla_attention and the
            # VJP backward): query row i attends keys j <= i + (k_len - q_len).
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = q_ids + causal_offset >= k_ids
        if bd is not None and masked:
            mask = _bd_mask(qi * block_q, ki * block_k, block_q, block_k, *bd)
        if kv_len is not None and masked:
            # Pad-and-mask support (ViT's L=197 and friends): keys at or past
            # the original kv length are padding and must not contribute.
            k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            kmask = k_ids < kv_len
            mask = kmask if mask is None else mask & kmask
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, 0:1]  # (block_q, 1)
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        if mask is not None:
            # In a fully-masked row m_new == _NEG_INF, so exp(s - m_new) is 1,
            # not 0 — zero the masked entries so l counts only visible keys
            # (keeps the l==0 finalize guard honest for q_len > k_len rows).
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    def _compute_ranges(sub, ranges):
        # A diagonal tile under the block-diffusion mask: q sub-block r takes
        # its own key ranges, with one max over them and the rows' running
        # one.  Written phase by phase over ALL sub-blocks — scores, maxima,
        # probabilities, p v, stores — and not sub-block by sub-block: the
        # sub-blocks' chains of product, lane reduction and exp are short
        # and independent, and in this order they overlap (PERF.md §6, PR 31).
        strips = [slice(r * sub, (r + 1) * sub) for r in range(len(ranges))]
        lane_chunks = lambda x: [
            x[:, j:j + _LANES] for j in range(0, x.shape[1], _LANES)
        ]
        scores = []
        for rows, parts in zip(strips, ranges):
            q = q_ref[0, 0, rows, :]
            scores.append([
                jax.lax.dot_general(
                    q, k_ref[0, 0, lo:hi, :],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) for lo, hi, _ in parts
            ])
        m_prev = [m_scr[rows, 0:1] for rows in strips]
        masks, m_new = [], []
        for r, parts in enumerate(ranges):
            masks.append([
                _range_mask(qi * block_q + r * sub, ki * block_k + lo, sub,
                            hi - lo, causal_offset=causal_offset, bd=bd)
                if masked else None
                for lo, hi, masked in parts
            ])
            for i, mask in enumerate(masks[r]):
                s = scores[r][i] if scale == 1.0 else scores[r][i] * scale
                scores[r][i] = s if mask is None else jnp.where(mask, s, _NEG_INF)
            # lanes first, across the ranges: one lane reduction a sub-block
            widest = functools.reduce(
                jnp.maximum, [c for s in scores[r] for c in lane_chunks(s)]
            )
            m_new.append(jnp.maximum(
                m_prev[r], jnp.max(widest, axis=1, keepdims=True)
            ))
        probs, alpha, l_new = [], [], []
        for r, rows in enumerate(strips):
            probs.append([])
            sums = []
            for s, mask in zip(scores[r], masks[r]):
                p = jnp.exp(s - m_new[r])
                if mask is not None:
                    # as in ``_compute``: a row that has seen no key yet
                    p = jnp.where(mask, p, 0.0)
                sums.extend(lane_chunks(p))
                probs[r].append(p.astype(v_ref.dtype))
            alpha.append(jnp.exp(m_prev[r] - m_new[r]))
            l_new.append(
                alpha[r] * l_scr[rows, 0:1] + jnp.sum(
                    functools.reduce(jnp.add, sums), axis=1, keepdims=True
                )
            )
        acc = [acc_scr[rows, :] * alpha[r] for r, rows in enumerate(strips)]
        for r, parts in enumerate(ranges):
            for p, (lo, hi, _) in zip(probs[r], parts):
                acc[r] += jax.lax.dot_general(
                    p, v_ref[0, 0, lo:hi, :],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
        for r, rows in enumerate(strips):
            acc_scr[rows, :] = acc[r]
            m_scr[rows, :] = jnp.broadcast_to(m_new[r], (sub, m_scr.shape[1]))
            l_scr[rows, :] = jnp.broadcast_to(l_new[r], (sub, l_scr.shape[1]))

    _when_live(
        _compute, qi, ki,
        dict(causal=causal, causal_offset=causal_offset, kv_len=kv_len,
             block_q=block_q, block_k=block_k),
        bd, _compute_ranges, _SUBS["fwd"],
    )

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        # Guard fully-masked rows (l==0 cannot happen with causal q>=k, but
        # keeps the kernel total-function for future mask variants).
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _single_tile_mask(qi, block_q, k_len, *, causal, causal_offset, kv_len):
    """(block_q, k_len) boolean mask for a whole-key-row tile, or None when
    nothing is masked.  Shared by both one-tile forward kernels so mask
    variants stay in lockstep (the forward analog of ``_bwd_block``)."""
    mask = None
    shape = (block_q, k_len)
    if causal:
        q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_ids = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = q_ids + causal_offset >= k_ids
    if kv_len is not None:
        k_ids = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        kmask = k_ids < kv_len
        mask = kmask if mask is None else mask & kmask
    return mask


def _fwd_tile(q, k, v, mask, scale):
    """Direct (non-online) softmax attention for one whole-key-row tile:
    returns (o_f32, lse_f32_column).  The l==0 guard keeps fully-masked
    rows at zero output instead of a uniform distribution."""
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jax.lax.dot_general(
        (p / l_safe).astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return o, m + jnp.log(l_safe)


def _fwd_kernel_single(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    *,
    causal: bool,
    causal_offset: int,
    scale: float,
    block_q: int,
    block_k: int,
    kv_len: int | None,
):
    """One-tile forward: the whole key row fits a single kv block, so the
    online-softmax machinery (VMEM scratch, alpha rescales, the final
    divide pass) collapses to one direct softmax — the small-L fast path.
    Grid: (b, h, q_blocks)."""
    qi = pl.program_id(2)
    mask = _single_tile_mask(
        qi, block_q, k_ref.shape[2], causal=causal,
        causal_offset=causal_offset, kv_len=kv_len,
    )
    o, lse = _fwd_tile(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], mask, scale)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    # 8-lane LSE: the multi-tile kernel broadcasts its LSE across 128
    # lanes (a 64x-inflated HBM write, ~30 us at the GPT-2 L=512 shape);
    # 8 is the narrowest legal trailing block dim (full last dimension),
    # a 16x cut for free.
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd_single(q, k, v, causal, scale, block_q, interpret,
                      causal_offset, kv_len):
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    block_q = min(block_q, q_len)
    grid = (b, h, q_len // block_q)
    kernel = functools.partial(
        _fwd_kernel_single,
        causal=causal,
        causal_offset=k_len - q_len if causal_offset is None else causal_offset,
        scale=scale,
        block_q=block_q,
        block_k=k_len,
        kv_len=kv_len,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, k_len, d), lambda b_, h_, qi: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, k_len, d), lambda b_, h_, qi: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, qi: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, q_len, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, q_len, 8), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


def _fwd_kernel_single_nlhd(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    *,
    causal: bool,
    causal_offset: int,
    scale: float,
    block_q: int,
    num_heads: int,
    head_dim: int,
    kv_len: int | None,
):
    """Heads-fused one-tile forward over the NATIVE (B, L, H*D) layout.

    The (B, H, L, D) kernels force (B, L, H, D) -> (B, H, L, D) boundary
    transposes in the surrounding program — measured as the residual
    full-model gap to the XLA path below L=1024 (rounds 1-5, another
    machine).  This kernel instead takes q/k/v as
    (B, L, H*D) — a FREE reshape of the model's (B, L, H, D) — and loops
    the heads inside the tile, slicing 64-wide column groups out of VMEM.
    Grid: (b, q_blocks); the whole key row sits in one tile (the small-L
    regime where the transposes dominate).
    """
    qi = pl.program_id(1)
    k_len = k_ref.shape[1]
    mask = _single_tile_mask(
        qi, block_q, k_len, causal=causal, causal_offset=causal_offset,
        kv_len=kv_len,
    )
    for h in range(num_heads):
        lo = h * head_dim
        q = q_ref[0, :, lo:lo + head_dim]  # (block_q, d)
        k = k_ref[0, :, lo:lo + head_dim]  # (k_len, d)
        v = v_ref[0, :, lo:lo + head_dim]
        o, lse = _fwd_tile(q, k, v, mask, scale)
        o_ref[0, :, lo:lo + head_dim] = o.astype(o_ref.dtype)
        lse_ref[0, :, h] = lse[:, 0]


def _flash_fwd_single_nlhd(q, k, v, causal, scale, block_q, interpret,
                           causal_offset, kv_len, num_heads):
    """Launcher for the heads-fused forward. q/k/v: (B, L, H*D)."""
    b, q_len, hd = q.shape
    k_len = k.shape[1]
    d = hd // num_heads
    block_q = min(block_q, q_len)
    grid = (b, q_len // block_q)
    kernel = functools.partial(
        _fwd_kernel_single_nlhd,
        causal=causal,
        causal_offset=k_len - q_len if causal_offset is None else causal_offset,
        scale=scale,
        block_q=block_q,
        num_heads=num_heads,
        head_dim=d,
        kv_len=kv_len,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b_, qi: (b_, qi, 0)),
            pl.BlockSpec((1, k_len, hd), lambda b_, qi: (b_, 0, 0)),
            pl.BlockSpec((1, k_len, hd), lambda b_, qi: (b_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b_, qi: (b_, qi, 0)),
            pl.BlockSpec((1, block_q, num_heads), lambda b_, qi: (b_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, q_len, hd), q.dtype),
            jax.ShapeDtypeStruct((b, q_len, num_heads), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _bwd_kernel_single_nlhd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, *, causal, causal_offset,
                            scale, num_heads, head_dim, kv_len):
    """Heads-fused one-tile backward over (B, L, H*D) (grid: b).

    Same 5-matmul-per-head structure as ``_bwd_kernel_single``; the head
    loop reuses one (q_len, k_len) mask across heads and writes the three
    grads into 64-wide column groups of the native layout."""
    q_len = q_ref.shape[1]
    k_len = k_ref.shape[1]
    for h in range(num_heads):
        lo = h * head_dim
        q = q_ref[0, :, lo:lo + head_dim]
        k = k_ref[0, :, lo:lo + head_dim]
        v = v_ref[0, :, lo:lo + head_dim]
        do = do_ref[0, :, lo:lo + head_dim]
        lse = lse_ref[0, :, h][:, None]
        delta = delta_ref[0, :, h][:, None]
        p, ds = _bwd_block(
            q, k, v, do, lse, delta, 0, 0,
            causal=causal, causal_offset=causal_offset, scale=scale,
            block_q=q_len, block_k=k_len, kv_len=kv_len,
        )
        ds_c = ds.astype(k.dtype)
        dq_ref[0, :, lo:lo + head_dim] = jax.lax.dot_general(
            ds_c, k, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)
        dk_ref[0, :, lo:lo + head_dim] = jax.lax.dot_general(
            ds_c, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dk_ref.dtype)
        dv_ref[0, :, lo:lo + head_dim] = jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dv_ref.dtype)


def _flash_bwd_nlhd(q, k, v, out, lse, do, causal, scale, interpret,
                    causal_offset, kv_len, num_heads):
    b, q_len, hd = q.shape
    k_len = k.shape[1]
    d = hd // num_heads
    # delta_h = sum_d do*out per head: (B, L, H).
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
            b, q_len, num_heads, d
        ),
        axis=-1,
    )
    kernel = functools.partial(
        _bwd_kernel_single_nlhd,
        causal=causal,
        causal_offset=causal_offset,
        scale=scale,
        num_heads=num_heads,
        head_dim=d,
        kv_len=kv_len,
    )
    qspec = pl.BlockSpec((1, q_len, hd), lambda b_: (b_, 0, 0))
    kspec = pl.BlockSpec((1, k_len, hd), lambda b_: (b_, 0, 0))
    hspec = pl.BlockSpec((1, q_len, num_heads), lambda b_: (b_, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[qspec, kspec, kspec, qspec, hspec, hspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="flash_bwd",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_nlhd(q, k, v, causal, scale, block_q, interpret, causal_offset,
                kv_len, num_heads):
    out, _ = _flash_fwd_single_nlhd(
        q, k, v, causal, scale, block_q, interpret, causal_offset, kv_len,
        num_heads,
    )
    return out


def _flash_nlhd_vjp_fwd(q, k, v, causal, scale, block_q, interpret,
                        causal_offset, kv_len, num_heads):
    out, lse = _flash_fwd_single_nlhd(
        q, k, v, causal, scale, block_q, interpret, causal_offset, kv_len,
        num_heads,
    )
    return out, (q, k, v, out, lse)


def _flash_nlhd_vjp_bwd(causal, scale, block_q, interpret, causal_offset,
                        kv_len, num_heads, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_nlhd(
        q, k, v, out, lse, do, causal, scale, interpret,
        causal_offset, kv_len, num_heads,
    )


_flash_nlhd.defvjp(_flash_nlhd_vjp_fwd, _flash_nlhd_vjp_bwd)


# ---------------------------------------------------------------------------
# Grouped-heads native-layout kernels: the k_len 513..1024 band (and any
# width/length the whole-heads kernels cannot fit in VMEM).
#
# The whole-heads single-tile kernels above blow the ~16 MB scoped-VMEM
# budget at k_len 1024 (all H heads' k/v rows plus per-head (L, L) f32
# intermediates in one grid cell).  These variants tile BOTH the heads
# (Hg-head groups, lane-aligned 128-element column slices of the
# (B, L, H*D) layout) and the query length (dk/dv accumulate in VMEM scratch
# across q blocks), so GPT-2's L = 1024 runs without the (B, L, H, D) <->
# (B, H, L, D) boundary transposes.  The key row of a head group stays in
# VMEM whole.  Without ``causal`` a q block takes it as ONE tile; under
# ``causal`` a q block takes the row's prefix up to its own frontier
# (``_causal_spans``), and only the columns the diagonal (or ``kv_len``)
# crosses build a mask.  What each form reads on the chip, and the forms
# that were tried: PERF.md, sections 5 and 6.
# ---------------------------------------------------------------------------


_VMEM_BUDGET = 11 * 2**20  # conservative: the 16 MB scoped limit minus slack

# Scoped VMEM the fused backward may take: two float32 (Lk, d) accumulators,
# the (Lk, d) dk / dv output blocks and four (block_q, block_k) float32
# tiles are past the 16 MB default at 8192 keys.
_FUSED_BWD_VMEM = 64 * 2**20


def _nlhd_single_fits(q_len, k_len, hd_all, itemsize):
    """Whether the whole-heads single-tile pair fits the VMEM budget.

    Backward is the binding side: grid (b,) holds q/k/v/do/dq/dk/dv
    whole-row tiles plus per-head s/p/dp/ds f32 intermediates in one cell.
    Wide-attention models (large H*D) overflow here even at short L and
    must take the grouped path instead.
    """
    fwd = (2 * k_len + 2 * min(q_len, 512)) * hd_all * itemsize \
        + 2 * min(q_len, 512) * k_len * 4
    bwd = (3 * q_len + 4 * k_len) * hd_all * itemsize \
        + 4 * q_len * k_len * 4
    return fwd <= _VMEM_BUDGET and bwd <= _VMEM_BUDGET


def _fused_bwd_fits(k_len, head_dim, itemsize, block_q, block_k):
    """Whether ``_bwd_fused_kernel`` fits ``_FUSED_BWD_VMEM`` at this key row:
    it keeps a K/V head's dk and dv whole — two float32 (k_len, d)
    accumulators and the double-buffered (k_len, d) output blocks — beside
    its four float32 score tiles and the double-buffered q / do / dq and
    k / v blocks.  8192 x 128 in bfloat16 at 1024-tiles takes 35 MB."""
    head = 2 * k_len * head_dim * 4 + 2 * 2 * k_len * head_dim * itemsize
    tiles = 4 * block_q * block_k * 4
    blocks = (
        2 * (3 * block_q + 2 * block_k) * head_dim * itemsize
        + 2 * 2 * block_q * _LANES * 4      # lse, delta: a column pads to the lanes
        + block_q * head_dim * 4            # dq's accumulator
    )
    return head + tiles + blocks <= _FUSED_BWD_VMEM


# The tallest q block of the causal form.  A q block computes every pair
# above the diagonal inside its own rows, and re-reads (and transposes) the
# key prefix once a block: 256 rows read fastest at 1024 x 1024 (PERF.md §6).
_CAUSAL_BLOCK_Q = 256


def _nlhd_group_config(q_len, k_len, num_heads, head_dim, itemsize,
                       causal=False):
    """(heads_per_group, block_q_fwd, block_q_bwd) for the grouped kernels,
    or None when no configuration fits the VMEM budget.

    Group column slices must start at 128-element lane boundaries, so
    heads_per_group * head_dim % 128 == 0 (whole groups are exempt).
    Prefers the largest group (best k/v reuse), then the largest blocks, up
    to ``_CAUSAL_BLOCK_Q`` under ``causal``.  The estimates hold for both
    forms: the last causal q block's tiles are as wide as the whole row.
    """
    def fwd_est(bq, hg):
        hd = hg * head_dim
        return (2 * k_len * hd + 2 * bq * hd) * itemsize + 2 * bq * k_len * 4

    def bwd_est(bq, hg):
        hd = hg * head_dim
        return (
            (3 * bq * hd + 4 * k_len * hd) * itemsize
            + 2 * k_len * hd * 4          # dk/dv f32 scratch
            + 4 * bq * k_len * 4          # s/p/dp/ds tiles
        )

    # Candidate q blocks must tile q_len exactly — a non-divisor block
    # truncates the grid and silently skips trailing query rows.
    tallest = min(q_len, _CAUSAL_BLOCK_Q if causal else 512)
    bqs = [b for b in (512, 256, 128) if b <= tallest and q_len % b == 0]
    if not bqs:
        bqs = [q_len]
    for hg in range(num_heads, 0, -1):
        if num_heads % hg:
            continue
        if hg != num_heads and (hg * head_dim) % 128:
            continue
        bq_f = next((b for b in bqs if fwd_est(b, hg) <= _VMEM_BUDGET), None)
        bq_b = next((b for b in bqs if bwd_est(b, hg) <= _VMEM_BUDGET), None)
        if bq_f is not None and bq_b is not None:
            return hg, bq_f, bq_b
    return None


def _causal_spans(q_len, k_len, block_q, causal_offset, kv_len):
    """``[(first, last, full, visit)]``: q blocks ``first .. last`` see the
    key columns ``[0, visit)`` and need no mask over ``[0, full)``; both are
    multiples of 128.  Static: the launcher's shapes decide it."""
    keys = k_len if kv_len is None else kv_len
    spans = []
    for qi in range(q_len // block_q):
        # keys the block's first row sees; its last row sees block_q - 1 more
        first = qi * block_q + causal_offset + 1
        last = first + block_q - 1
        width = (
            min(max(first, 0), keys) // _LANES * _LANES,
            -(-min(max(last, 0), keys) // _LANES) * _LANES,
        )
        if spans and spans[-1][2:] == width:
            spans[-1] = (spans[-1][0], qi, *width)
        else:
            spans.append((qi, qi, *width))
    return spans


# kernel name -> key columns visited ÷ (q rows × k_len) in the launch traced
# last.  The grouped pair: 1.0 for a whole-row tile, the causal prefixes'
# share otherwise.  The tabled pair: its live tiles, the diagonal classes at
# their sub-ranges.
_visited_pair_share: dict[str, float] = {}


def flash_visited_pair_share() -> dict[str, float]:
    """The ``flash_visited_pair_share[kernel=..]`` gauges' values."""
    return dict(_visited_pair_share)


def _note_visited_share(kernel, spans, q_len, k_len, block_q):
    _visited_pair_share[kernel] = 1.0 if spans is None else sum(
        (last - first + 1) * block_q * visit
        for first, last, _, visit in spans
    ) / (q_len * k_len)


def _note_tabled_visited_share(kernel, nq, nk, subs, **mask):
    """The multi-tile launchers' share (the tabled pair's, the plain
    forward's and the split backward's), by the kernels' own tile predicates
    (``_live_block``, ``_sub_classes``) on the whole grid at trace time: a
    live tile whole, a diagonal one of a class at its sub-ranges (``subs``
    as the kernel takes them; the split backward knows no class)."""
    import numpy as np

    bd = mask.pop("bd", None)
    block_q, block_k = mask["block_q"], mask["block_k"]
    qi = np.arange(nq, dtype=np.int32)[:, None]
    ki = np.arange(nk, dtype=np.int32)[None, :]
    with jax.ensure_compile_time_eval():
        live = _live_block(qi, ki, bd=bd, **mask)
        visited = np.broadcast_to(
            True if live is None else np.asarray(live), (nq, nk)
        ) * float(block_q * block_k)
        for hit, sub, ranges in _sub_classes(qi, ki, mask, bd, subs):
            visited[np.broadcast_to(np.asarray(hit), (nq, nk))] = sub * sum(
                hi - lo for parts in ranges for lo, hi, _ in parts
            )
    _visited_pair_share[kernel] = float(
        visited.sum() / (nq * block_q * nk * block_k)
    )


def _in_each_span(qi, spans, body):
    """Run ``body(parts)`` in the branch of the span that holds q block
    ``qi``; ``parts`` are its ``(lo, hi, masked)`` key column ranges."""
    for first, last, full, visit in spans:
        parts = [(lo, hi, masked)
                 for lo, hi, masked in ((0, full, False), (full, visit, True))
                 if hi > lo]
        pl.when((qi >= first) & (qi <= last))(functools.partial(body, parts))


def _fwd_kernel_grouped(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                        causal_offset, scale, block_q, heads_per_group,
                        head_dim, kv_len):
    """Grouped-heads one-tile-k forward without the causal mask (grid: b,
    head_groups, q_blocks)."""
    qi = pl.program_id(2)
    mask = _single_tile_mask(
        qi, block_q, k_ref.shape[1], causal=False,
        causal_offset=causal_offset, kv_len=kv_len,
    )
    for j in range(heads_per_group):
        lo = j * head_dim
        o, lse = _fwd_tile(
            q_ref[0, :, lo:lo + head_dim],
            k_ref[0, :, lo:lo + head_dim],
            v_ref[0, :, lo:lo + head_dim],
            mask, scale,
        )
        o_ref[0, :, lo:lo + head_dim] = o.astype(o_ref.dtype)
        lse_ref[0, 0, :, j] = lse[:, 0]


def _fwd_kernel_grouped_causal(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                               causal_offset, scale, block_q, spans,
                               heads_per_group, head_dim, kv_len):
    """Grouped-heads causal forward (grid: b, head_groups, q_blocks): a
    direct softmax over the key row's prefix up to the q block's frontier,
    taken in an unmasked and a masked range of columns."""
    qi = pl.program_id(2)

    def prefix(parts):
        for j in range(heads_per_group):
            cols = slice(j * head_dim, (j + 1) * head_dim)
            q = q_ref[0, :, cols]
            m = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
            tiles = []
            for lo, hi, masked in parts:
                s = jax.lax.dot_general(
                    q, k_ref[0, lo:hi, cols],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                mask = None
                if masked:
                    q_ids = qi * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    k_ids = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                    mask = q_ids + causal_offset >= k_ids
                    if kv_len is not None:
                        mask &= k_ids < kv_len
                    s = jnp.where(mask, s, _NEG_INF)
                m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                tiles.append((s, mask, slice(lo, hi)))
            l = jnp.zeros((block_q, 1), jnp.float32)
            o = jnp.zeros((block_q, head_dim), jnp.float32)
            for s, mask, rows in tiles:
                p = jnp.exp(s - m)
                if mask is not None:
                    # a row that sees no key keeps m at _NEG_INF, where
                    # exp(s - m) is 1: zero it, so l counts visible keys only
                    p = jnp.where(mask, p, 0.0)
                l += jnp.sum(p, axis=1, keepdims=True)
                v = v_ref[0, rows, cols]
                o += jax.lax.dot_general(
                    p.astype(v.dtype), v,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, cols] = (o / l_safe).astype(o_ref.dtype)
            lse_ref[0, 0, :, j] = (m + jnp.log(l_safe))[:, 0]

    _in_each_span(qi, spans, prefix)


def _bwd_kernel_grouped(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                        causal_offset, scale, block_q, heads_per_group,
                        head_dim, kv_len, spans=None):
    """Grouped-heads backward, q-blocked (grid: b, head_groups, q_blocks).

    dq writes per q block; dk/dv accumulate in f32 VMEM scratch across the
    (innermost) q-block dimension and flush on its last iteration.  Without
    ``spans`` the key row is one tile; with them (the causal form) a q block
    takes the prefix up to its frontier and adds into those rows only."""
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)
    k_len = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def rows(parts):
        for j in range(heads_per_group):
            lo = j * head_dim
            q = q_ref[0, :, lo:lo + head_dim]
            dq = jnp.zeros((block_q, head_dim), jnp.float32) if not parts \
                else None       # a q block that sees no key
            tiles = []
            for k_lo, k_hi, masked in parts:
                k = k_ref[0, k_lo:k_hi, lo:lo + head_dim]
                v = v_ref[0, k_lo:k_hi, lo:lo + head_dim]
                if not tiles:
                    do = do_ref[0, :, lo:lo + head_dim]
                    lse = lse_ref[0, 0, :, j][:, None]
                    delta = delta_ref[0, 0, :, j][:, None]
                # a range's columns count from k_lo: the mask shifts with it
                p, ds = _bwd_block(
                    q, k, v, do, lse, delta, qi, 0,
                    causal=masked and spans is not None,
                    causal_offset=causal_offset - k_lo, scale=scale,
                    block_q=block_q, block_k=k_hi - k_lo,
                    kv_len=kv_len - k_lo if masked and kv_len is not None
                    else None,
                )
                ds_c = ds.astype(k.dtype)
                part = jax.lax.dot_general(
                    ds_c, k, dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dq = part if dq is None else dq + part
                tiles.append((slice(k_lo, k_hi), ds_c, p))
            dq_ref[0, :, lo:lo + head_dim] = dq.astype(dq_ref.dtype)
            for k_rows, ds_c, p in tiles:
                dk_scr[k_rows, lo:lo + head_dim] += jax.lax.dot_general(
                    ds_c, q, dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dv_scr[k_rows, lo:lo + head_dim] += jax.lax.dot_general(
                    p.astype(do.dtype), do,
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

    if spans is None:
        rows([(0, k_len, True)])
    else:
        _in_each_span(qi, spans, rows)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# The two launchers are jitted on their own: a model's layers hand them the
# same shapes, so the kernel is traced and lowered to Mosaic once a program
# and not once a layer (XLA inlines the calls again).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_fwd_grouped(q, k, v, causal, scale, interpret, causal_offset,
                       kv_len, num_heads, cfg):
    b, q_len, hd_all = q.shape
    k_len = k.shape[1]
    d = hd_all // num_heads
    hg, bq, _ = cfg
    ng = num_heads // hg
    hd = hg * d
    causal_offset = k_len - q_len if causal_offset is None else causal_offset
    tile = dict(causal_offset=causal_offset, scale=scale, block_q=bq,
                heads_per_group=hg, head_dim=d, kv_len=kv_len)
    spans = None
    if causal:
        spans = _causal_spans(q_len, k_len, bq, causal_offset, kv_len)
        kernel = functools.partial(
            _fwd_kernel_grouped_causal, spans=spans, **tile
        )
    else:
        kernel = functools.partial(_fwd_kernel_grouped, **tile)
    _note_visited_share("flash_fwd", spans, q_len, k_len, bq)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, ng, q_len // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b_, g, qi: (b_, qi, g)),
            pl.BlockSpec((1, k_len, hd), lambda b_, g, qi: (b_, 0, g)),
            pl.BlockSpec((1, k_len, hd), lambda b_, g, qi: (b_, 0, g)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b_, g, qi: (b_, qi, g)),
            pl.BlockSpec((1, 1, bq, hg), lambda b_, g, qi: (b_, g, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, q_len, hd_all), q.dtype),
            jax.ShapeDtypeStruct((b, ng, q_len, hg), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_bwd_grouped(q, k, v, out, lse, do, causal, scale, interpret,
                       causal_offset, kv_len, num_heads, cfg):
    b, q_len, hd_all = q.shape
    k_len = k.shape[1]
    d = hd_all // num_heads
    hg, _, bq = cfg
    ng = num_heads // hg
    hd = hg * d
    # delta per head, laid out to match the lse blocks: (B, nG, L, Hg).
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
            b, q_len, ng, hg, d
        ),
        axis=-1,
    ).transpose(0, 2, 1, 3)
    spans = None
    if causal:
        spans = _causal_spans(q_len, k_len, bq, causal_offset, kv_len)
    kernel = functools.partial(
        _bwd_kernel_grouped,
        causal_offset=causal_offset,
        scale=scale,
        block_q=bq,
        heads_per_group=hg,
        head_dim=d,
        kv_len=kv_len,
        spans=spans,
    )
    _note_visited_share("flash_bwd", spans, q_len, k_len, bq)
    qspec = pl.BlockSpec((1, bq, hd), lambda b_, g, qi: (b_, qi, g))
    kspec = pl.BlockSpec((1, k_len, hd), lambda b_, g, qi: (b_, 0, g))
    hspec = pl.BlockSpec((1, 1, bq, hg), lambda b_, g, qi: (b_, g, qi, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b, ng, q_len // bq),
        in_specs=[qspec, kspec, kspec, qspec, hspec, hspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((k_len, hd), jnp.float32),
            pltpu.VMEM((k_len, hd), jnp.float32),
        ],
        name="flash_bwd",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_nlhd_grouped(q, k, v, causal, scale, interpret, causal_offset,
                        kv_len, num_heads, cfg):
    out, _ = _flash_fwd_grouped(
        q, k, v, causal, scale, interpret, causal_offset, kv_len, num_heads,
        cfg,
    )
    return out


def _flash_nlhd_grouped_vjp_fwd(q, k, v, causal, scale, interpret,
                                causal_offset, kv_len, num_heads, cfg):
    out, lse = _flash_fwd_grouped(
        q, k, v, causal, scale, interpret, causal_offset, kv_len, num_heads,
        cfg,
    )
    return out, (q, k, v, out, lse)


def _flash_nlhd_grouped_vjp_bwd(causal, scale, interpret, causal_offset,
                                kv_len, num_heads, cfg, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_grouped(
        q, k, v, out, lse, do, causal, scale, interpret, causal_offset,
        kv_len, num_heads, cfg,
    )


_flash_nlhd_grouped.defvjp(_flash_nlhd_grouped_vjp_fwd,
                           _flash_nlhd_grouped_vjp_bwd)


def _takes_tabled(k_len, head_dim, itemsize, block_q, block_k, bd):
    """Whether a transposed call takes the tabled pair: the block-diffusion
    mask always (the only kernels that read K/V at their own head count), any
    other mask where the key row is several tiles and the fused backward
    fits.  ``flash_plan`` answers with it, ``_flash_fwd`` / ``_flash_bwd``
    follow it."""
    return bd is not None or (
        k_len > block_k
        and _fused_bwd_fits(k_len, head_dim, itemsize, block_q, block_k)
    )


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               causal_offset=None, kv_len=None, bd=None):
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    if _takes_tabled(k_len, d, q.dtype.itemsize, block_q, block_k, bd):
        return _flash_tabled_fwd(
            q, k, v, causal, scale, block_q, block_k, interpret,
            causal_offset, kv_len, bd,
        )
    if q_len % block_q or k_len % block_k:
        raise ValueError(f"seq lens ({q_len},{k_len}) not divisible by blocks ({block_q},{block_k})")
    if k_len <= block_k:
        # Whole key row in one tile: the online-softmax machinery buys
        # nothing, and dropping it (plus the narrow LSE) measured
        # 220 -> 62 us on the GPT-2 L=512 microbatch shape — past the XLA
        # fused attention (77 us; rounds 1-5, another machine).
        return _flash_fwd_single(
            q, k, v, causal, scale, block_q, interpret, causal_offset,
            kv_len,
        )

    grid = (b, h, q_len // block_q, k_len // block_k)
    mask = dict(
        causal=causal,
        causal_offset=k_len - q_len if causal_offset is None else causal_offset,
        block_q=block_q,
        block_k=block_k,
        kv_len=kv_len,
    )
    _note_tabled_visited_share("flash_fwd", *grid[2:], _SUBS["fwd"], **mask)
    kernel = functools.partial(_fwd_kernel, scale=scale, **mask)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, q_len, d), q.dtype),
            # 8 lanes, not 128: the narrowest legal trailing dim — the LSE
            # is logically a column; 128 lanes was a 64x-inflated write.
            jax.ShapeDtypeStruct((b, h, q_len, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


def _bwd_block(q, k, v, do, lse, delta, qi, ki, *, causal, causal_offset,
               scale, block_q, block_k, kv_len=None, bd=None, masked=True):
    """Recompute p and ds for one (q_block, kv_block) tile.

    q/do: (bq, d); k/v: (bk, d) — in their INPUT dtype (bf16 on the AMP
    path): the MXU runs bf16 x bf16 -> f32 at full rate but decomposes f32
    matmuls ~4x slower, so the recompute matmuls keep bf16 operands and
    f32 accumulation (``preferred_element_type``), the same trade the
    XLA low-memory path makes with its bf16 probs (ops/attention.py).
    lse/delta: (bq, 1) f32 column vectors (the trailing unit dim satisfies
    the TPU block-shape rules).  Returns (p, ds), each (bq, bk) f32 — the
    tiles both backward kernels are built from; callers cast them to the
    input dtype for their own second-stage matmuls.
    """
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    rescale = scale != 1.0    # the block-diffusion mask hands in a scaled q
    if rescale:
        s = s * scale
    p = jnp.exp(s - lse)
    if causal and masked:
        q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # Explicit zero (not -inf then exp): a fully-masked row has lse ≈
        # _NEG_INF and exp(s - lse) would be 1 there, leaking gradient.
        p = jnp.where(q_ids + causal_offset >= k_ids, p, 0.0)
    if bd is not None and masked:
        p = jnp.where(
            _bd_mask(qi * block_q, ki * block_k, block_q, block_k, *bd), p, 0.0
        )
    if kv_len is not None and masked:
        k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.where(k_ids < kv_len, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    if rescale:
        ds = ds * scale
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, causal, causal_offset, scale, block_q, block_k,
                   kv_len=None):
    """Accumulates dq over kv blocks (grid: b, h, q_blocks, kv_blocks)."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        _, ds = _bwd_block(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0], delta_ref[0, 0], qi, ki,
            causal=causal, causal_offset=causal_offset, scale=scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
        )
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, 0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = _live_block(
        qi, ki, causal=causal, causal_offset=causal_offset, kv_len=kv_len,
        block_q=block_q, block_k=block_k,
    )
    if live is not None:
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal, causal_offset,
                    scale, block_q, block_k, kv_len=None):
    """Accumulates dk/dv over q blocks (grid: b, h, kv_blocks, q_blocks)."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    num_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        p, ds = _bwd_block(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0], delta_ref[0, 0], qi, ki,
            causal=causal, causal_offset=causal_offset, scale=scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0, 0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0, 0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = _live_block(
        qi, ki, causal=causal, causal_offset=causal_offset, kv_len=kv_len,
        block_q=block_q, block_k=block_k,
    )
    if live is not None:
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_kernel_single(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, *, causal, causal_offset,
                       scale, block_q, block_k, kv_len=None):
    """Fused one-tile backward (grid: b, h) for lengths within one block.

    The split dq / dkv kernels each recompute the (s, p, dp) tile — 7
    matmuls total; with the whole row in one tile, a single kernel
    recomputes once and emits all three grads in 5 matmuls, with no
    accumulator scratch or finalize passes."""
    p, ds = _bwd_block(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
        lse_ref[0, 0], delta_ref[0, 0], 0, 0,
        causal=causal, causal_offset=causal_offset, scale=scale,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    ds_c = ds.astype(k_ref.dtype)
    dq_ref[0, 0] = jax.lax.dot_general(
        ds_c, k_ref[0, 0], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)
    dk_ref[0, 0] = jax.lax.dot_general(
        ds_c, q_ref[0, 0], dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dk_ref.dtype)
    dv_ref[0, 0] = jax.lax.dot_general(
        p.astype(do_ref.dtype), do_ref[0, 0],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)


def _flash_bwd_single(q, k, v, lse, delta, do, causal, scale, interpret,
                      causal_offset, kv_len):
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    kernel = functools.partial(
        _bwd_kernel_single,
        causal=causal,
        causal_offset=causal_offset,
        scale=scale,
        block_q=q_len,
        block_k=k_len,
        kv_len=kv_len,
    )
    qspec = pl.BlockSpec((1, 1, q_len, d), lambda b_, h_: (b_, h_, 0, 0))
    kspec = pl.BlockSpec((1, 1, k_len, d), lambda b_, h_: (b_, h_, 0, 0))
    colspec = pl.BlockSpec((1, 1, q_len, 1), lambda b_, h_: (b_, h_, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[qspec, kspec, kspec, qspec, colspec, colspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="flash_bwd",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _live_table(nq, nk, **mask):
    """Block table ``kv_of[qi, ki]`` for the K/V index maps of the tabled
    multi-tile kernels: a dead tile's block index repeats a live
    neighbour's, so the pipeline issues no copy for a tile the kernel skips
    (three quarters of the tiles under the block-diffusion mask, 28 of 64
    under the causal one at 8192).  The
    tile predicate is the kernels' own (``_live_block``), evaluated here on
    the whole grid at trace time."""
    import numpy as np

    with jax.ensure_compile_time_eval():
        live = _live_block(
            np.arange(nq, dtype=np.int32)[:, None],
            np.arange(nk, dtype=np.int32)[None, :], **mask,
        )
    table = np.tile(np.arange(nk, dtype=np.int32), (nq, 1))
    if live is None:
        return table
    for qi, row in enumerate(np.broadcast_to(np.asarray(live), (nq, nk))):
        cols = np.flatnonzero(row)
        if cols.size:
            # a dead tile: the nearest live one at or after it, else the last
            at = np.searchsorted(cols, np.arange(nk))
            table[qi] = cols[np.minimum(at, cols.size - 1)]
    return table


# Jitted on their own, as the grouped launchers are: a model's layers trace
# and lower the pair once a program.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_tabled_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      causal_offset, kv_len, bd):
    """The multi-tile forward under any mask (``_takes_tabled``), named
    ``flash_bd_fwd`` under the block-diffusion mask and ``flash_fwd``
    otherwise.  q: (B, H, Lq, D); k, v: (B, Hkv, Lk, D) with H a multiple of
    Hkv: query head h reads K/V head h // (H / Hkv) through the block index,
    never a repeated copy in HBM."""
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    group = h // k.shape[1]
    block_q, block_k = min(block_q, q_len), min(block_k, k_len)
    if q_len % block_q or k_len % block_k:
        raise ValueError(f"seq lens ({q_len},{k_len}) not divisible by blocks ({block_q},{block_k})")
    mask = dict(
        causal=causal,
        causal_offset=k_len - q_len if causal_offset is None else causal_offset,
        kv_len=kv_len, block_q=block_q, block_k=block_k, bd=bd,
    )
    nq, nk = q_len // block_q, k_len // block_k
    kv_of = _live_table(nq, nk, **mask)
    _note_tabled_visited_share(
        "flash_bd_fwd" if bd is not None else "flash_fwd", nq, nk,
        _SUBS["fwd"], **mask
    )
    q_index = lambda b_, h_, qi, ki, tbl: (b_, h_, qi, 0)
    kv_index = lambda b_, h_, qi, ki, tbl: (b_, h_ // group, tbl[qi, ki], 0)
    kernel = functools.partial(_fwd_kernel, scale=scale, **mask)
    out, lse = pl.pallas_call(
        lambda tbl, *refs: kernel(*refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_q, 8), q_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, q_len, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, q_len, 8), jnp.float32),
        ],
        name="flash_bd_fwd" if bd is not None else "flash_fwd",
        interpret=interpret,
    )(jnp.asarray(kv_of), q, k, v)
    return out, lse[..., 0]


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                      causal, causal_offset, scale, block_q, block_k,
                      kv_len=None, bd=None):
    """dq, dk and dv from ONE recomputation of each live tile (grid: b, K/V
    head, query head of its group, q_blocks, kv_blocks).

    The split backward recomputes a tile's scores, probabilities and dp
    twice (7 matmuls a tile); here they are computed once (5).  dq
    accumulates over the kv blocks of one q block; dk and dv accumulate over
    EVERYTHING that visits their K/V head — all its query heads' q blocks —
    in float32 scratch that holds the head's whole key length, and leave
    for HBM once, at the head's last tile."""
    g, qi, ki = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    num_g, num_q, num_k = pl.num_programs(2), pl.num_programs(3), pl.num_programs(4)

    @pl.when((g == 0) & (qi == 0) & (ki == 0))
    def _init_head():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(ki == 0)
    def _init_rows():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked=True):
        p, ds = _bwd_block(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0], delta_ref[0, 0], qi, ki,
            causal=causal, causal_offset=causal_offset, scale=scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len, bd=bd,
            masked=masked,
        )
        ds_c = ds.astype(k_ref.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds_c, k_ref[0, 0], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        keys = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dv_scr[keys, :] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0, 0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[keys, :] += jax.lax.dot_general(
            ds_c, q_ref[0, 0], dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _compute_ranges(sub, ranges):
        # A diagonal tile under the block-diffusion mask: five matmuls a
        # visited range, into its q sub-block's rows of dq and its keys' rows
        # of the head's dk / dv.  Phase by phase over all sub-blocks, as the
        # forward's: p and ds of every range, then dq, then dk / dv.
        tiles = []
        for r, parts in enumerate(ranges):
            rows = slice(r * sub, (r + 1) * sub)
            q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
            lse, delta = lse_ref[0, 0, rows, :], delta_ref[0, 0, rows, :]
            tiles.append([])
            for lo, hi, masked in parts:
                k = k_ref[0, 0, lo:hi, :]
                # a masked range is ``sub`` columns at a multiple of ``sub``:
                # the mask's origin in ``_bwd_block``'s own units
                p, ds = _bwd_block(
                    q, k, v_ref[0, 0, lo:hi, :], do, lse, delta,
                    qi * (block_q // sub) + r, ki * (block_k // sub) + lo // sub,
                    causal=causal, causal_offset=causal_offset, scale=scale,
                    block_q=sub, block_k=sub, bd=bd, masked=masked,
                )
                tiles[r].append(
                    (lo, hi, q, do, k, p.astype(do.dtype), ds.astype(k.dtype))
                )
        for r, row in enumerate(tiles):
            dq_scr[r * sub:(r + 1) * sub, :] += sum(
                jax.lax.dot_general(
                    ds, k, dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) for _, _, _, _, k, _, ds in row
            )
        for lo, hi, q, do, _, p, ds in (t for row in tiles for t in row):
            keys = pl.ds(pl.multiple_of(ki * block_k + lo, sub), hi - lo)
            dv_scr[keys, :] += jax.lax.dot_general(
                p, do, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_scr[keys, :] += jax.lax.dot_general(
                ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _when_live(
        _compute, qi, ki,
        dict(causal=causal, causal_offset=causal_offset, kv_len=kv_len,
             block_q=block_q, block_k=block_k),
        bd, _compute_ranges, _SUBS["bwd"],
    )

    @pl.when(ki == num_k - 1)
    def _finalize_rows():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when((g == num_g - 1) & (qi == num_q - 1) & (ki == num_k - 1))
    def _finalize_head():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash_tabled_bwd(q, k, v, lse, delta, do, causal, scale, block_q,
                      block_k, interpret, causal_offset, kv_len, bd):
    """The backward behind ``_flash_tabled_fwd``: one fused kernel
    (``_bwd_fused_kernel``; ``flash_bd_bwd`` under the block-diffusion mask,
    ``flash_bwd`` otherwise) over grid (b, K/V head, its query heads,
    q blocks, kv blocks).  K/V are read through the block index of their own
    head, and dk / dv come out at the K/V head count."""
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    group = h // k.shape[1]
    mask = dict(
        causal=causal,
        causal_offset=k_len - q_len if causal_offset is None else causal_offset,
        kv_len=kv_len, block_q=block_q, block_k=block_k, bd=bd,
    )
    nq, nk = q_len // block_q, k_len // block_k
    kv_of = _live_table(nq, nk, **mask)
    _note_tabled_visited_share(
        "flash_bd_bwd" if bd is not None else "flash_bwd", nq, nk,
        _SUBS["bwd"], **mask
    )

    q_index = lambda b_, n_, g_, qi, ki, tbl: (b_, n_ * group + g_, qi, 0)
    kv_index = lambda b_, n_, g_, qi, ki, tbl: (b_, n_, tbl[qi, ki], 0)
    head_index = lambda b_, n_, g_, qi, ki, tbl: (b_, n_, 0, 0)
    q_spec = pl.BlockSpec((1, 1, block_q, d), q_index)
    k_spec = pl.BlockSpec((1, 1, block_k, d), kv_index)
    row_spec = pl.BlockSpec((1, 1, block_q, 1), q_index)
    head_spec = pl.BlockSpec((1, 1, k_len, d), head_index)
    kernel = functools.partial(_bwd_fused_kernel, scale=scale, **mask)
    return pl.pallas_call(
        lambda tbl, *refs: kernel(*refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // group, group, nq, nk),
            in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
            out_specs=[q_spec, head_spec, head_spec],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((k_len, d), jnp.float32),
                pltpu.VMEM((k_len, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_FUSED_BWD_VMEM),
        name="flash_bd_bwd" if bd is not None else "flash_bwd",
        interpret=interpret,
    )(jnp.asarray(kv_of), q, k, v, do, lse, delta)


def _flash_bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k, interpret,
               causal_offset=None, kv_len=None, bd=None):
    """Blockwise backward: never materializes the (L, L) score matrix.

    The tabled pair's fused kernel where ``_takes_tabled`` says so, the
    one-tile fused kernel where the call is one tile, and otherwise two
    kernels (the standard flash-attention backward split, for key rows past
    the fused kernel's fit): dq accumulates over kv blocks with q outermost;
    dk/dv accumulate over q blocks with kv outermost.  p/ds tiles are
    recomputed from q/k/lse per block.
    """
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    # Column-vector layout (B, H, Q, 1): the trailing unit dim keeps the last
    # two block dims TPU-legal ((block_q, 1) — full trailing dimension).
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )
    lse = lse[..., None]

    if _takes_tabled(k_len, d, q.dtype.itemsize, block_q, block_k, bd):
        return _flash_tabled_bwd(
            q, k, v, lse, delta, do, causal, scale, block_q, block_k,
            interpret, causal_offset, kv_len, bd,
        )
    if q_len <= block_q and k_len <= block_k:
        # One-tile case: the fused kernel recomputes (s, p, dp) once for
        # all three grads instead of once per split kernel.
        return _flash_bwd_single(
            q, k, v, lse, delta, do, causal, scale, interpret,
            k_len - q_len if causal_offset is None else causal_offset,
            kv_len,
        )

    mask = dict(
        causal=causal,
        causal_offset=k_len - q_len if causal_offset is None else causal_offset,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    _note_tabled_visited_share(
        "flash_bwd", q_len // block_q, k_len // block_k, None, **mask
    )
    common = dict(mask, scale=scale)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_, ki, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b, h, q_len // block_q, k_len // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, q_len, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # kv-outer grid: index maps see (b, h, ki, qi).
    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ki, qi: (b_, h_, qi, 0))
    k_spec2 = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, ki, qi: (b_, h_, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(b, h, k_len // block_k, q_len // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, k_len, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, k_len, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret,
           causal_offset=None, kv_len=None, bd=None):
    out, _ = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, interpret, causal_offset,
        kv_len, bd,
    )
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   causal_offset=None, kv_len=None, bd=None):
    out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, interpret, causal_offset,
        kv_len, bd,
    )
    # Named so that a rematerialized block can keep them (``FLASH_RESIDUALS``
    # in a ``save_only_these_names`` policy): the backward then reads o and
    # the log-sum-exp instead of running the forward kernel a second time.
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, causal_offset,
                   kv_len, bd, res, do):
    q, k, v, out, lse = res
    return _flash_bwd(
        q, k, v, out, lse, do, causal, scale, block_q, block_k, interpret,
        causal_offset, kv_len, bd,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


class FlashPlan(NamedTuple):
    """What ``flash_attention`` does with one call's static facts."""

    q_len: int  # padded to the 128-lane tile
    k_len: int
    block_q: int
    block_k: int
    kind: str  # "single" | "grouped" | "transposed" | "tabled"
    # "grouped" only: (heads_per_group, block_q_fwd, block_q_bwd)
    group: tuple[int, int, int] | None


def flash_plan(
    q_len: int,
    k_len: int,
    num_heads: int,
    kv_heads: int,
    head_dim: int,
    itemsize: int,
    *,
    causal: bool,
    block_diffusion: tuple[int, int] | None,
    block_q: int = 1024,
    block_k: int = 1024,
) -> FlashPlan:
    """Which kernels a call takes, from its shapes alone: the one place
    that pads to the lane tile, picks blocks and asks the VMEM fits.
    ``flash_attention`` runs the plan; ``ops.attention.flash_preferred``
    reads its ``kind``, so a producer that picks the flash-favored qkv
    split cannot meet a call that relays out anyway.

    - ``single``: the whole-heads native-(B, L, H*D) pair, a free reshape
      of the operands: padded k_len and q_len <= 512 where every head's
      rows and (L, L) tiles fit VMEM in one grid cell.
    - ``grouped``: the grouped-heads native pair, which tiles heads and
      query length: padded k_len <= 1024 (the GPT-2 L = 1024 band), long q
      over a short key row, or widths ``single`` cannot fit.
    - ``tabled``: (B, H, L, D) operands and a key row of several tiles,
      under any mask (block-diffusion, causal with any offset, padded keys,
      none): the tile predicate skips dead tiles, a block table keeps them
      from being copied, a tile that is wholly live builds no mask, and ONE
      fused backward keeps a head's dk / dv in VMEM (``_fused_bwd_fits``).
      The block-diffusion mask always plans it: these are the only kernels
      that read K/V at their own head count.
    - ``transposed``: (B, H, L, D) operands otherwise; ``_flash_fwd`` /
      ``_flash_bwd`` take the single-tile kernels where the key row is one
      block, and the plain multi-tile forward with the split backward where
      the key row is past the fused backward's fit.
    """
    q_len += (-q_len) % _LANES
    k_len += (-k_len) % _LANES

    def pick_block(length: int, preferred: int) -> int:
        for b in (preferred, 256, 128):
            if length % min(b, length) == 0:
                return b
        return _LANES  # padded lengths are multiples of 128 by construction

    block_q = pick_block(q_len, block_q)
    block_k = pick_block(k_len, block_k)
    plan = functools.partial(FlashPlan, q_len, k_len, block_q, block_k)
    if block_diffusion is not None:
        return plan("tabled", None)
    if kv_heads == num_heads:  # the native pairs know one head count
        if (
            k_len <= min(block_k, 512)
            and q_len <= 512
            and _nlhd_single_fits(q_len, k_len, num_heads * head_dim, itemsize)
        ):
            return plan("single", None)
        if k_len <= min(block_k, 1024):
            group = _nlhd_group_config(
                q_len, k_len, num_heads, head_dim, itemsize, causal
            )
            if group is not None:
                return plan("grouped", group)
    tabled = _takes_tabled(
        k_len, head_dim, itemsize, min(block_q, q_len), min(block_k, k_len),
        None,
    )
    return plan("tabled" if tabled else "transposed", None)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array:
    """Flash attention. q/k/v: (B, L, H, D) → (B, L, H, D).

    Which kernels run is ``flash_plan``'s answer, from the shapes alone.
    ``block_diffusion=(L, B)`` applies the block-diffusion training mask
    over 2L positions (noised copy, then clean copy, blocks of B; see
    ``_bd_mask``) and takes the tabled multi-tile pair under the names
    ``flash_bd_fwd`` / ``flash_bd_bwd``; a causal or unmasked call whose key
    row is several tiles takes the same pair as ``flash_fwd`` /
    ``flash_bwd``.  ``k``/``v`` may carry fewer heads than ``q``
    (grouped-query attention: H a multiple of their head count): the tabled
    pair reads them in place through the block index under any mask
    (Nemotron-H's 32 query heads over 2 K/V heads at 8192 keys); a plan of
    another kind takes kernels that know one head count, and K/V are
    repeated to H heads for it.

    Sequence lengths need not be lane-aligned: non-multiples of 128 (e.g.
    ViT-B/16's L = 197) are zero-padded to the next multiple, padded keys
    are masked inside the kernel (static ``kv_len``), and the padded query
    rows are sliced off — AD through the pad handles the gradient slicing.

    ``interpret=None`` auto-enables the Pallas interpreter off-TPU so the
    same kernel is testable on the CPU mesh harness.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, q_len, h, d = q.shape
    k_len = k.shape[1]
    if block_diffusion is not None and causal:
        raise ValueError("block_diffusion replaces the causal mask")
    if h % k.shape[2]:
        raise ValueError(
            f"{h} query heads are not a multiple of {k.shape[2]} K/V heads"
        )
    plan = flash_plan(
        q_len, k_len, h, k.shape[2], d, q.dtype.itemsize, causal=causal,
        block_diffusion=block_diffusion, block_q=block_q, block_k=block_k,
    )
    if k.shape[2] != h and plan.kind != "tabled":
        k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    pad_q, pad_k = plan.q_len - q_len, plan.k_len - k_len
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # Causal alignment follows the ORIGINAL lengths; kv_len masks padded keys.
    causal_offset = k_len - q_len
    kv_len = k_len if pad_k else None
    if plan.kind in ("single", "grouped"):
        q2, k2, v2 = (x.reshape(b, x.shape[1], h * d) for x in (q, k, v))
        if plan.kind == "single":
            out = _flash_nlhd(
                q2, k2, v2, causal, scale, plan.block_q, interpret,
                causal_offset, kv_len, h,
            )
        else:
            out = _flash_nlhd_grouped(
                q2, k2, v2, causal, scale, interpret, causal_offset,
                kv_len, h, plan.group,
            )
        out = out.reshape(b, plan.q_len, h, d)
    else:
        if block_diffusion is not None:
            # The scale goes onto q once (a (P, d) pass XLA fuses into q's
            # producer), not onto every (block_q, block_k) score tile.  q is
            # rounded to its dtype a second time by this.  The other masks
            # keep the scale on the float32 scores: at Instella's scale (no
            # power of two) the second rounding showed in the reference
            # check's q / k norm gradients for 0.05 ms a forward call
            # (PERF.md §6, PR 33).
            q, scale = q * jnp.asarray(scale, q.dtype), 1.0
        # (B, L, H, D) → (B, H, L, D) for blocking.
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        out = _flash(
            qt, kt, vt, causal, scale, plan.block_q, plan.block_k, interpret,
            causal_offset, kv_len, block_diffusion,
        )
        out = jnp.swapaxes(out, 1, 2)
    return out[:, :q_len] if pad_q else out


def _decode_kernel(i_ref, q_ref, k_ref, v_ref, o_ref, *, scale):
    """Fused single-token decode attention for one batch row, all heads.

    One program computes scores → masked softmax → combine for every head
    of its batch element in one VMEM residency: the XLA lowering of the
    same math spans ~6-8 fused kernels per layer, and at decode's tiny
    per-op sizes the per-kernel launch overhead — not bandwidth — is the
    binding cost (GEN_ROOFLINE (deleted: not measured on the current machine)
    accounting).  q: (H, Dh); k/v:
    (H, L, Dh); the filled prefix is positions 0..i inclusive, where i is
    this batch row's entry of the prefetched index vector — a shared scalar
    in lockstep decode (models/generate.py), per-row slot positions in the
    continuous-batching engine (serve/engine.py).
    """
    i = i_ref[pl.program_id(0)]
    num_heads = q_ref.shape[1]
    # Per-head 2D dots, unrolled: Mosaic does not lower batched
    # dot_general (batch dims in the dimension numbers fail to parse);
    # H tiny matmuls inside ONE program is exactly the point — the
    # alternative is H x 6-8 separate XLA kernels.
    outs = []
    for head in range(num_heads):
        qh = q_ref[0, head][None]                      # (1, Dh)
        kh = k_ref[0, head]                            # (L, Dh)
        vh = v_ref[0, head]
        s = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # (1, L)
        idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx <= i, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)                 # f32
        o = jax.lax.dot_general(
            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # (1, Dh)
        outs.append(o)
    o_ref[0] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    index: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token KV-cache attention, one fused kernel per batch row.

    q: (B, H, Dh) — the current token's heads; k_cache/v_cache:
    (B, H, L, Dh) (the decode cache layout, models/layers.py); ``index``:
    the position just written (attend over 0..index) — a scalar shared by
    every row (lockstep decode), or an (B,) int32 vector of per-row
    positions (ragged serving slots; an out-of-range entry simply unmasks
    the whole stale row — the idle-slot sentinel whose output the engine
    discards).  Returns (B, H, Dh).  Falls back to the caller's XLA path
    off-TPU unless the interpreter is requested.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, h, l, dh = k_cache.shape
    scale = scale if scale is not None else dh ** -0.5
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, h, l, dh), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, l, dh), lambda i, *_: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda i, *_: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        name="decode_attn",
        interpret=interpret,
    )(index, q, k_cache, v_cache)


def _decode_kernel_multi(i_ref, q_ref, k_ref, v_ref, o_ref, *, scale):
    """Multi-query decode attention for one batch row, all heads.

    The speculative-verify generalization of ``_decode_kernel``: q is a
    C-token chunk (the pending token + up to C-1 drafted tokens, written
    to the cache at positions i..i+C-1 before this attention runs), and
    query j attends keys 0..i+j — causal WITHIN the chunk, ragged across
    rows via the per-row prefetched index, so k drafted tokens cost one
    cache read per tick instead of k.  q/o: (H, C, Dh) HEAD-MAJOR — each
    head's (C, Dh) chunk is its own tile; indexing the head out of a
    (C, H, Dh) block is a strided second-minor access Mosaic cannot lay
    out, so the launcher transposes outside.  k/v: (H, L, Dh).
    """
    i = i_ref[pl.program_id(0)]
    num_heads = q_ref.shape[1]
    for head in range(num_heads):
        qh = q_ref[0, head]                            # (C, Dh)
        kh = k_ref[0, head]                            # (L, Dh)
        vh = v_ref[0, head]
        s = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # (C, L)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(col <= i + row, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)                 # f32
        o = jax.lax.dot_general(
            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # (C, Dh)
        o_ref[0, head] = o.astype(o_ref.dtype)


def decode_attention_multi(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    index: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Multi-token KV-cache attention, one fused kernel per batch row.

    q: (B, C, H, Dh) — a C-token chunk per row whose K/V are already
    written at positions ``index[b]..index[b]+C-1``; k_cache/v_cache:
    (B, H, L, Dh); ``index``: (B,) int32 FIRST query position per row
    (query j of row b attends 0..index[b]+j; an out-of-range entry
    unmasks the whole stale row — the idle-slot sentinel whose output the
    engine discards).  Returns (B, C, H, Dh).  The variable-tokens-per-
    tick face of ``decode_attention`` — the serving engine's speculative
    verify step scores k+1 positions per slot in one program per row.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, h, l, dh = k_cache.shape
    c = q.shape[1]
    scale = scale if scale is not None else dh ** -0.5
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,))
    q_spec = pl.BlockSpec((1, h, c, dh), lambda i, *_: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, h, l, dh), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, l, dh), lambda i, *_: (i, 0, 0, 0)),
        ],
        out_specs=q_spec,
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel_multi, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c, dh), q.dtype),
        name="decode_multi_attn",
        interpret=interpret,
    )(index, jnp.swapaxes(q, 1, 2), k_cache, v_cache)
    return jnp.swapaxes(out, 1, 2)


def _kv_planes(raw, quant, dtype):
    """One stored KV tile (rows, Dh') → its matmul operand plane(s) in
    the query dtype, UNSCALED: the per-row scale multiplies the score
    tile instead (see ``_paged_decode_kernel_multi``), so the payload
    never needs the (rows,) → (rows, 1) scale relayout Mosaic has no
    lowering for.  int8 → one (rows, Dh) plane; int4 → the even- and
    odd-column planes (low nibble = even column, two's-complement: the
    ``comm.compress.encode_int4`` convention) — interleaving them back
    is a lane shuffle, so the launcher de-interleaves q and re-
    interleaves the output outside the kernel instead.  int8 and int4
    values are exact in bf16."""
    if quant is None:
        return (raw,)
    if quant == "int8":
        return (raw.astype(jnp.float32).astype(dtype),)
    if quant == "int4":
        byte = raw.astype(jnp.int32)
        return tuple(
            jnp.where(n > 7, n - 16, n).astype(jnp.float32).astype(dtype)
            for n in (byte & 0xF, byte >> 4)
        )
    raise ValueError(f"unknown kv quant {quant!r} (int8|int4)")


def _head_row(tile, head):
    """Row ``head`` of an (H, n) f32 tile as (1, n), by mask-and-reduce:
    a static one-row slice at an unaligned sublane offset is a relayout,
    where/sum over sublanes is not."""
    rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(rows == head, tile, 0.0), axis=0, keepdims=True)


def _paged_kv_specs(h, block_size, dh, quant):
    """BlockSpecs for the paged K/V operands (+ scale columns when
    quantized), all routed through the scalar-prefetched block table —
    shared by the paged launchers so the indirection cannot drift."""
    kv = pl.BlockSpec(
        (1, h, block_size, dh),
        lambda bi, j, i_ref, t_ref: (t_ref[bi, j], 0, 0, 0),
    )
    specs = [kv, kv]
    if quant:
        sc = pl.BlockSpec(
            (1, h, block_size),
            lambda bi, j, i_ref, t_ref: (t_ref[bi, j], 0, 0),
        )
        specs += [sc, sc]
    return specs


def _paged_decode_kernel(i_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale, block_size):
    """Paged single-token decode attention: one batch row, one physical
    KV block per grid step, all heads.

    The (b, j) program sees the j-th LOGICAL block of row b — Pallas
    fetched the physical block ``tbl[b, j]`` via the scalar-prefetched
    block table in the BlockSpec index map, so the kernel body never
    touches the indirection.  Online softmax (running max / denominator /
    f32 accumulator in VMEM scratch, per head) folds the blocks of the
    row's prefix together across the sequentially-executed inner grid
    dimension, exactly the _fwd_kernel recurrence at q_len = 1.
    """
    b_idx = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    i = i_ref[b_idx]
    num_heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        # Per-head 2D dots, unrolled — same Mosaic constraint and same
        # launch-count argument as _decode_kernel.
        for head in range(num_heads):
            qh = q_ref[0, head][None]                  # (1, Dh)
            kh = k_ref[0, head]                        # (block_size, Dh)
            vh = v_ref[0, head]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # (1, block_size)
            pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            live = pos <= i
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_scr[head:head + 1, 0:1]         # (1, 1)
            l_prev = l_scr[head:head + 1, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # A fully-dead block has m_new == _NEG_INF and exp(s - m_new)
            # == 1 — zero masked entries so l counts only visible keys.
            p = jnp.where(live, p, 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[head:head + 1, :] = (
                acc_scr[head:head + 1, :] * alpha
                + jax.lax.dot_general(
                    p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            m_scr[head:head + 1, :] = jnp.broadcast_to(
                m_new, (1, m_scr.shape[1])
            )
            l_scr[head:head + 1, :] = jnp.broadcast_to(
                l_new, (1, l_scr.shape[1])
            )

    # Blocks wholly past the row's prefix contribute nothing — skip the
    # math (their HBM fetch already happened via the clamped table entry).
    pl.when(j * block_size <= i)(_compute)

    @pl.when(j == num_j - 1)
    def _finalize():
        l = l_scr[:, 0:1]                              # (H, 1)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    block_table: jax.Array,
    index: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    quant: str | None = None,
) -> jax.Array:
    """Single-token KV-cache attention over the PAGED block pool.

    q: (B, H, Dh); k_blocks/v_blocks: (num_blocks, H, block_size, Dh) —
    the serve/kv_pool.PagedKVCachePool layout (heads ahead of length,
    same as the contiguous decode cache); ``block_table``: (B, nb) int32
    physical-block ids per logical block, PRE-CLAMPED to [0, num_blocks)
    by the caller (models/layers.py clamps its sentinel entries — a
    clamped entry's garbage keys sit past ``index`` and are masked);
    ``index``: (B,) int32 position just written per row (attend over
    0..index; an out-of-range entry unmasks the whole stale row — the
    idle-slot sentinel whose output the engine discards).

    ``quant`` ("int8"|"int4", --serve-kv-dtype): the blocks hold the
    QUANTIZED payload (int8, or nibble-packed uint8 at Dh//2) and
    ``k_scale``/``v_scale`` carry the (num_blocks, H, block_size) bf16
    scales; dequantization happens per tile inside the kernel, so the
    full-precision K/V never exist in HBM.  Quantized pools run the
    multi-query kernel at C = 1 (one implementation of the in-kernel
    dequantization).

    Grid is (B, nb) with the block dimension innermost (sequential on
    TPU): each program loads ONE physical block, selected by the
    scalar-prefetched table inside the BlockSpec index map — the
    gather-free indirection that makes the paged layout cost the same
    HBM traffic as the contiguous kernel.  Returns (B, H, Dh).  Falls
    back to the caller's XLA gather path off-TPU unless the interpreter
    is requested.
    """
    if quant:
        return _paged_multi_call(
            q[:, None], k_blocks, v_blocks, block_table, index, scale=scale,
            interpret=interpret, k_scale=k_scale, v_scale=v_scale,
            quant=quant, name="paged_decode_attn",
        )[:, 0]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_blocks, h, block_size, dh = k_blocks.shape
    b, nb = block_table.shape
    scale = scale if scale is not None else dh ** -0.5
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,))
    block_table = jnp.asarray(block_table, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda bi, j, i_ref, t_ref: (bi, 0, 0)),
            *_paged_kv_specs(h, block_size, dh, None),
        ],
        out_specs=pl.BlockSpec(
            (1, h, dh), lambda bi, j, i_ref, t_ref: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, block_size=block_size,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        name="paged_decode_attn",
        interpret=interpret,
    )(index, block_table, q, k_blocks, v_blocks)


def _paged_decode_kernel_multi(i_ref, tbl_ref, q_ref, k_ref, v_ref, *rest,
                               scale, block_size, quant=None):
    """Multi-query paged decode attention: one batch row, one physical KV
    block per grid step, all heads of a C-token chunk.

    The C>1 generalization of ``_paged_decode_kernel`` — the ONE grid
    both the speculative verify step (C = k+1) and the fused chunked
    prefill (C = prefill chunk) run on: query j of row b sits at
    position ``i + j`` (i per-row prefetched) and attends keys 0..i+j —
    causal within the chunk, ragged across rows, online-softmax across
    the row's blocks (a prefix-cache hit simply starts ``i`` past the
    cached blocks — the prefix-skip path reads them like any other
    block).

    q/o: (H, P, C, Dh/P) HEAD-MAJOR, so each head's chunk (and each
    head's scratch, (H, C, ·)) is a whole tile reached by a leading
    index — indexing a head out of a (C, H, Dh) block is a strided
    second-minor access Mosaic cannot lay out.  P is the number of
    operand planes ``_kv_planes`` yields (2 for int4, else 1); the
    launcher splits q's columns to match.

    ``quant``: stored-payload refs plus per-(head, position) bf16 scale
    refs.  The scales live on the LANE axis of their (H, block_size)
    tile, which is the key axis of the (C, block_size) score tile — so
    K's scale multiplies the scores and V's scale multiplies the
    probabilities, ``(q·kᵀ)·s_k`` and ``(p·s_v)·v``, the same values as
    dequantizing the payload rows without moving a scale across axes.
    """
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b_idx = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    i = i_ref[b_idx]
    num_heads, planes, c = q_ref.shape[1:4]
    nt = (((1,), (1,)), ((), ()))                      # a @ b.T
    nn = (((1,), (0,)), ((), ()))                      # a @ b

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        if quant:
            k_scales = ks_ref[0].astype(jnp.float32)   # (H, block_size)
            v_scales = vs_ref[0].astype(jnp.float32)
        for head in range(num_heads):
            k_planes = _kv_planes(k_ref[0, head], quant, q_ref.dtype)
            v_planes = _kv_planes(v_ref[0, head], quant, q_ref.dtype)
            s = sum(
                jax.lax.dot_general(
                    q_ref[0, head, part], k_planes[part], nt,
                    preferred_element_type=jnp.float32,
                )
                for part in range(planes)
            ) * scale                                  # (C, block_size)
            if quant:
                s = s * _head_row(k_scales, head)
            pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            live = pos <= i + row
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_scr[head, :, 0:1]               # (C, 1)
            l_prev = l_scr[head, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # A fully-dead row has m_new == _NEG_INF and exp(s - m_new)
            # == 1 — zero masked entries so l counts only visible keys.
            p = jnp.where(live, p, 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * _head_row(v_scales, head)
            p = p.astype(v_planes[0].dtype)
            for part in range(planes):
                acc_scr[head, part] = (
                    acc_scr[head, part] * alpha
                    + jax.lax.dot_general(
                        p, v_planes[part], nn,
                        preferred_element_type=jnp.float32,
                    )
                )
            m_scr[head] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[head] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    # A block wholly past even the LAST query's prefix contributes
    # nothing — skip the math.
    pl.when(j * block_size <= i + c - 1)(_compute)

    @pl.when(j == num_j - 1)
    def _finalize():
        for head in range(num_heads):
            l = l_scr[head, :, 0:1]                    # (C, 1)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            for part in range(planes):
                o_ref[0, head, part] = (
                    acc_scr[head, part] / l_safe
                ).astype(o_ref.dtype)


def _paged_multi_call(q, k_blocks, v_blocks, block_table, index, *,
                      scale, interpret, k_scale, v_scale, quant, name):
    """Shared launcher for the multi-query paged kernel: the speculative-
    verify chunk (``paged_decode_attention_multi``), the fused chunked
    prefill (``paged_prefill_attention``) and quantized single-token
    decode run the SAME kernel body on the same (B, nb) grid, each under
    its own role ``name`` (one of :data:`KERNEL_NAMES`)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_blocks, h, block_size, dh_stored = k_blocks.shape
    b, c, _, dh = q.shape
    nb = block_table.shape[1]
    planes = 2 if quant == "int4" else 1
    scale = scale if scale is not None else dh ** -0.5
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,))
    block_table = jnp.asarray(block_table, jnp.int32)
    # (B, C, H, Dh) → (B, H, P, C, Dh/P): head-major, and column d lands
    # in plane d % P — int4's even/odd split, the identity at P = 1.
    q_planes = jnp.transpose(
        q.reshape(b, c, h, dh // planes, planes), (0, 2, 4, 1, 3)
    )
    operands = [q_planes, k_blocks, v_blocks]
    if quant:
        operands += [k_scale, v_scale]
    q_spec = pl.BlockSpec(
        (1, h, planes, c, dh // planes),
        lambda bi, j, i_ref, t_ref: (bi, 0, 0, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            q_spec, *_paged_kv_specs(h, block_size, dh_stored, quant),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, c, _LANES), jnp.float32),
            pltpu.VMEM((h, c, _LANES), jnp.float32),
            pltpu.VMEM((h, planes, c, dh // planes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel_multi, scale=scale,
            block_size=block_size, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_planes.shape, q.dtype),
        name=name,
        interpret=interpret,
    )(index, block_table, *operands)
    return jnp.transpose(out, (0, 3, 1, 4, 2)).reshape(b, c, h, dh)


def paged_decode_attention_multi(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    block_table: jax.Array,
    index: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    quant: str | None = None,
) -> jax.Array:
    """Multi-token KV-cache attention over the PAGED block pool.

    q: (B, C, H, Dh) — a C-token chunk per row whose K/V are already
    scattered through the row's block table at logical positions
    ``index[b]..index[b]+C-1``; k_blocks/v_blocks:
    (num_blocks, H, block_size, Dh) (quantized payload + ``k_scale``/
    ``v_scale`` under ``quant``, as in :func:`paged_decode_attention`);
    ``block_table``: (B, nb) int32 PRE-CLAMPED to [0, num_blocks);
    ``index``: (B,) int32 FIRST query position per row (query j attends
    0..index[b]+j).  Returns (B, C, H, Dh) — the variable-tokens-per-
    tick face of ``paged_decode_attention`` for the engine's speculative
    verify step.  Same (B, nb) grid and scalar-prefetched table
    indirection as the single-query kernel; the chunk rides in one block
    fetch per step.
    """
    return _paged_multi_call(
        q, k_blocks, v_blocks, block_table, index, scale=scale,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale, quant=quant,
        name="paged_verify_attn",
    )


# Widest prefill chunk the fused kernel takes: past this the flattened
# (H*C, ·) scratch and the q tile stop fitting the VMEM budget at the
# flagship head counts, and the per-(C, block) score tiles are large
# enough that the XLA gather path's batched matmuls win anyway.
MAX_FUSED_PREFILL_CHUNK = 64


def paged_prefill_attention(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    block_table: jax.Array,
    index: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    quant: str | None = None,
) -> jax.Array:
    """Fused CHUNKED-PREFILL attention over the paged block pool — the
    flash-style prefill kernel that closes the serving kernel gap: the
    paged decode grid generalized to C>1 queries, with online softmax
    across the row's KV blocks and the causal/ragged mask.

    q: (B, C, H, Dh) — one prefill chunk per slot, already scattered
    into the row's blocks at positions ``index[b]..index[b]+C-1``
    (serve/engine.py writes before attending, so the chunk attends its
    own keys too); ``index``: (B,) int32 chunk START position per row —
    a prefix-cache hit simply starts past the cached blocks (the
    prefix-skip path: the skipped blocks are read like any others, never
    recomputed), and an idle row rides at the sentinel with its output
    discarded.  Query j of row b attends keys ``0..index[b]+j`` —
    causal within the chunk, ragged across rows.  Trailing chunk
    columns past the row's real tokens are padding whose output the
    engine's ``last_idx`` gather discards.  ``quant``: stored int8/int4
    payload + bf16 scales, dequantized inside the kernel.

    Shares its kernel body and (B, nb) scalar-prefetched grid with
    ``paged_decode_attention_multi`` (C ≤ k+1, the verify step); this
    entry lifts the chunk width to ``MAX_FUSED_PREFILL_CHUNK`` so the
    default 16-token prefill chunk runs fused — with it, BOTH serving
    phases run Pallas kernels end to end.
    """
    if q.shape[1] > MAX_FUSED_PREFILL_CHUNK:
        raise ValueError(
            f"prefill chunk {q.shape[1]} exceeds the fused kernel's "
            f"VMEM-bounded width {MAX_FUSED_PREFILL_CHUNK} — the caller "
            "(models/layers.py) routes wider chunks to the XLA path"
        )
    return _paged_multi_call(
        q, k_blocks, v_blocks, block_table, index, scale=scale,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale, quant=quant,
        name="paged_prefill_attn",
    )


# --------------------------------------------------------------------- #
# Tensor-parallel wrappers: the decode kernels under shard_map
# --------------------------------------------------------------------- #


def tp_supports_decode_kernels(mesh, num_heads: int) -> bool:
    """Whether the fused decode kernels can run on this TP mesh: the
    ``tensor`` axis must divide the head count (each shard runs the SAME
    per-row program on its own heads).  When it does not, the caller
    (models/layers.py) stays on the XLA ragged path and lets GSPMD
    partition it — slower, never wrong."""
    from ..comm.mesh import AXIS_TENSOR

    return num_heads % mesh.shape.get(AXIS_TENSOR, 1) == 0


def _tp_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map a head-local kernel over the ``tensor`` axis.  Attention
    is head-local, so no collective appears inside: each device runs the
    unmodified Pallas program on its head shard of q/K/V — the manual-
    partitioning escape hatch GSPMD needs because it cannot see inside a
    ``pallas_call`` (the XLA paths partition automatically; the kernels
    do not)."""
    from ..compat import shard_map

    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def decode_attention_tp(q, k_cache, v_cache, index, *, mesh,
                        interpret=None):
    """``decode_attention`` with heads sharded over ``mesh``'s ``tensor``
    axis: q (B, H, Dh) and the (B, H, L, Dh) cache split at H, the per-row
    index replicated.  Head count must divide the axis
    (``tp_supports_decode_kernels``)."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR

    h = P(None, AXIS_TENSOR)
    hc = P(None, AXIS_TENSOR, None, None)
    return _tp_shard_map(
        functools.partial(decode_attention, interpret=interpret),
        mesh, in_specs=(h, hc, hc, P(None)), out_specs=h,
    )(q, k_cache, v_cache, jnp.asarray(index, jnp.int32).reshape(-1))


def decode_attention_multi_tp(q, k_cache, v_cache, index, *, mesh,
                              interpret=None):
    """``decode_attention_multi`` (q (B, C, H, Dh)) under the same
    head-sharded shard_map as :func:`decode_attention_tp`."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR

    ch = P(None, None, AXIS_TENSOR, None)
    hc = P(None, AXIS_TENSOR, None, None)
    return _tp_shard_map(
        functools.partial(decode_attention_multi, interpret=interpret),
        mesh, in_specs=(ch, hc, hc, P(None)), out_specs=ch,
    )(q, k_cache, v_cache, jnp.asarray(index, jnp.int32).reshape(-1))


def _paged_tp_call(fn, mesh, q_spec, q, k_blocks, v_blocks, block_table,
                   index, interpret, k_scale, v_scale, quant):
    """Shared head-sharded shard_map for the paged kernels: the
    (num_blocks, H, ...) pool (and, quantized, its scale columns) split
    at H over ``tensor``; block table and per-row index replicated
    (host-fed control state every shard routes by)."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR

    hc = P(None, AXIS_TENSOR, None, None)
    hs = P(None, AXIS_TENSOR, None)
    table = jnp.asarray(block_table, jnp.int32)
    index = jnp.asarray(index, jnp.int32).reshape(-1)
    if quant:
        wrapped = _tp_shard_map(
            lambda q_, k_, v_, ks_, vs_, t_, i_: fn(
                q_, k_, v_, t_, i_, interpret=interpret,
                k_scale=ks_, v_scale=vs_, quant=quant,
            ),
            mesh,
            in_specs=(q_spec, hc, hc, hs, hs, P(None, None), P(None)),
            out_specs=q_spec,
        )
        return wrapped(q, k_blocks, v_blocks, k_scale, v_scale, table,
                       index)
    wrapped = _tp_shard_map(
        functools.partial(fn, interpret=interpret),
        mesh, in_specs=(q_spec, hc, hc, P(None, None), P(None)),
        out_specs=q_spec,
    )
    return wrapped(q, k_blocks, v_blocks, table, index)


def paged_decode_attention_tp(q, k_blocks, v_blocks, block_table, index,
                              *, mesh, interpret=None, k_scale=None,
                              v_scale=None, quant=None):
    """``paged_decode_attention`` with the (num_blocks, H, block_size,
    Dh) pool split at H over ``tensor``; the block table and per-row index
    stay replicated (host-fed control state every shard routes by).
    Quantized pools split the scale columns on the same heads axis."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR

    return _paged_tp_call(
        paged_decode_attention, mesh, P(None, AXIS_TENSOR), q, k_blocks,
        v_blocks, block_table, index, interpret, k_scale, v_scale, quant,
    )


def paged_decode_attention_multi_tp(q, k_blocks, v_blocks, block_table,
                                    index, *, mesh, interpret=None,
                                    k_scale=None, v_scale=None,
                                    quant=None):
    """``paged_decode_attention_multi`` (q (B, C, H, Dh)) under the same
    head-sharded shard_map as :func:`paged_decode_attention_tp`."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR

    return _paged_tp_call(
        paged_decode_attention_multi, mesh,
        P(None, None, AXIS_TENSOR, None), q, k_blocks, v_blocks,
        block_table, index, interpret, k_scale, v_scale, quant,
    )


def paged_prefill_attention_tp(q, k_blocks, v_blocks, block_table, index,
                               *, mesh, interpret=None, k_scale=None,
                               v_scale=None, quant=None):
    """``paged_prefill_attention`` (q (B, C, H, Dh)) under the same
    head-sharded shard_map as :func:`paged_decode_attention_tp` —
    attention is head-local, so the fused chunked prefill runs
    unmodified on each device's head shard."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR

    return _paged_tp_call(
        paged_prefill_attention, mesh,
        P(None, None, AXIS_TENSOR, None), q, k_blocks, v_blocks,
        block_table, index, interpret, k_scale, v_scale, quant,
    )

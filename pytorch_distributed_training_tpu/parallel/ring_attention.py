"""Ring attention: sequence-parallel exact attention over a mesh axis.

No long-context support of any kind exists in the reference (SURVEY.md §5
"long-context" row), but it is first-class here: sequences too long for one
chip's HBM are sharded over the ``sequence`` mesh axis and attention is
computed exactly by rotating K/V shards around the ring with ``ppermute``
(Liu et al. 2023, blockwise ring attention), overlapping each hop's transfer
with the local block's compute on the neighbor-connected ICI torus.

Numerics: flash-style online softmax — each ring step updates a running
(max, sum, unnormalized-out) triple in f32, so the result matches full
attention to accumulation order regardless of how many hops the ring has.

Built on ``lax.scan`` (not ``fori_loop``) so reverse-mode AD works.  The
scan body is wrapped in ``jax.checkpoint``, so the backward rematerializes
each hop's attention probabilities instead of storing them — the dominant
O((L/n)^2 per hop, O(L^2/n) total) residual.  The K/V shard handed around
the ring is still part of the scan carry, so each device retains O(L) of
K/V through the backward (a fully O(L/n) backward needs a hand-written
reverse ring à la Liu et al. — a possible future kernel; the quadratic
term is the one that matters at long context).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..comm.mesh import AXIS_SEQUENCE, BATCH_AXES
from ..compat import pcast, shard_map, typeof

_NEG_INF = -1e30  # finite mask value: avoids (-inf) - (-inf) = nan in the online max


def _block(q, k, v, q_off, k_off, *, causal: bool, scale: float):
    """One q-shard × k-shard attention block → (unnormalized out, max, sum).

    q: (B, Lq, H, D); k/v: (B, Lk, H, D); offsets are the shards' global
    sequence positions, needed to orient the causal mask across the ring.
    """
    lq, lk = q.shape[1], k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        q_pos = q_off + jnp.arange(lq)
        k_pos = k_off + jnp.arange(lk)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                      # (B, H, Lq)
    p = jnp.exp(logits - m[..., None])
    if causal:
        # Fully-masked rows (ring hops strictly after this q shard) have
        # m == _NEG_INF and p == 1 everywhere; zero them so l stays 0 and the
        # hop contributes nothing.
        p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)                           # (B, H, Lq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Exact attention over sequence shards; call inside shard_map/pjit.

    q/k/v: the local (B, L_local, H, D) shard of a globally (B, L, H, D)
    tensor sharded on dim 1 over ``axis_name``.  ``axis_size`` must be the
    static size of that mesh axis (mesh sizes are compile-time constants, so
    callers pass ``mesh.shape[axis]``).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    l_loc = q.shape[1]
    my = lax.axis_index(axis_name)
    q_off = my * l_loc
    # Each scan step: attend to the currently-held k/v shard, then pass it to
    # the previous ring neighbor (so we receive from the next — after i hops
    # we hold shard (my + i) mod n).
    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        k_off = ((my + i) % axis_size) * l_loc
        o_b, m_b, l_b = _block(q, k_cur, v_cur, q_off, k_off, causal=causal, scale=scale)
        m_new = jnp.maximum(m, m_b)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_b - m_new)
        l = l * alpha + l_b * beta
        o = o * alpha.transpose(0, 2, 1)[..., None] + o_b * beta.transpose(0, 2, 1)[..., None]
        # Last hop's permute is wasted but keeps the scan body uniform; XLA
        # overlaps the transfer with the next block's matmuls either way.
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o, m_new, l, k_next, v_next), None

    b, _, h, d = q.shape
    o0 = jnp.zeros((b, l_loc, h, d), jnp.float32)
    m0 = jnp.full((b, h, l_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, l_loc), jnp.float32)
    # Constant inits are device-invariant; the scan carry becomes varying the
    # moment it mixes with q/k/v, so pre-mark them (shard_map vma typing).
    vma = typeof(q).vma
    if vma:
        o0, m0, l0 = (pcast(x, tuple(vma), to="varying") for x in (o0, m0, l0))
    # checkpoint: rematerialize each hop's (B,H,Lq,Lk) probability block in
    # the backward rather than saving it (module docstring).
    (o, m, l, _, _), _ = lax.scan(
        jax.checkpoint(step), (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    # Fully-masked rows (none occur for causal self-attention, where position
    # i always sees itself) would have l == 0; guard the division anyway.
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = False,
    scale: float | None = None,
    axis_name: str = AXIS_SEQUENCE,
) -> jax.Array:
    """shard_map wrapper: globally-shaped (B, L, H, D) in and out.

    Batch dim rides the (data, fsdp) axes, sequence dim the ring axis, and
    the head dim the ``tensor`` axis — ring attention is per-head math, so
    Megatron-style TP (tensor-sharded QKV/proj producing head-sharded
    q/k/v) composes with the ring for free: each (sequence, tensor) device
    ring-rotates only its own heads' K/V shards.  With ``tensor == 1``
    heads stay local; with ``mesh.shape[axis_name] == 1`` this degrades to
    ordinary single-chip attention (one ring hop).
    """
    from ..comm.mesh import AXIS_TENSOR

    if q.shape[2] % mesh.shape[AXIS_TENSOR]:
        raise ValueError(
            f"heads ({q.shape[2]}) not divisible by the tensor axis "
            f"({mesh.shape[AXIS_TENSOR]})"
        )
    spec = P(BATCH_AXES, axis_name, AXIS_TENSOR, None)
    inner = functools.partial(
        ring_attention,
        axis_name=axis_name,
        axis_size=mesh.shape[axis_name],
        causal=causal,
        scale=scale,
    )
    fn = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)

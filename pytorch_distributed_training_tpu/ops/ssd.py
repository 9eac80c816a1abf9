"""The state-space recurrence of a Mamba-2 mixer, computed in chunks (SSD:
Dao & Gu 2024, "Transformers are SSMs", the state-space dual form).

Per head, with ``S`` a (P, N) state that starts at zero, a scalar step
``Δ_t > 0`` and a scalar ``A < 0``:

    S_t = exp(Δ_t A) · S_{t-1} + Δ_t · x_t ⊗ B_t          y_t = S_t C_t

``B`` and ``C`` are shared by the ``H / G`` heads of a group.  Position by
position that is T dependent steps over a state no matrix unit sees.  In
chunks of ``Q`` positions it is four batched matrix products and one short
loop:

- inside a chunk ``y_t = Σ_{s<=t} (C_t·B_s) L_ts Δ_s x_s`` with ``L_ts =
  exp(Σ_{s<r<=t} Δ_r A)``: the (Q, Q) scores of a group, masked and decayed
  per head, times the chunk's ``Δ x``;
- a chunk's own state ``Σ_s exp(Σ_{s<r<=Q} Δ_r A) Δ_s x_s ⊗ B_s``;
- the state ENTERING each chunk, by the recurrence over chunks (T / Q steps
  of an elementwise update);
- what the entering state adds: ``exp(Σ_{r<=t} Δ_r A) · S_in C_t``.

The log-decays, their running sums, every ``exp`` and the carried state are
float32 whatever ``x``'s dtype; the products take their operands in ``x``'s
dtype and accumulate in float32, as the flash kernels do.  Plain ``lax`` /
``jnp``: the backward is ``jax.grad``'s (a chunk's (Q, Q) decay block a head
is what it keeps: T·Q·H float32, 268 MB at 8192 × 128 × 64, inside a
rematerialized layer).  A Pallas pair is ROADMAP R5.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ssd_chunked(x, dt, a, b, c, *, chunk: int):
    """x (B, T, H, P); dt (B, T, H) float32, the steps after their softplus;
    a (H,) float32, negative; b, c (B, T, G, N) with H a multiple of G →
    y (B, T, H, P) in ``x``'s dtype, from a zero state.  T must be a
    multiple of ``chunk``: a ragged tail would be a second program shape,
    and every caller's lengths are the loader's."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk or h % g:
        raise ValueError(f"T={t} is no multiple of the chunk {chunk}, or H={h} none of G={g}")
    nc, r, dtype = t // chunk, h // g, x.dtype
    f32 = jnp.float32
    dot = lambda spec, lhs, rhs: jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                                            preferred_element_type=f32)

    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.astype(f32).reshape(bsz, nc, chunk, g, r)
    b, c = (m.reshape(bsz, nc, chunk, g, n) for m in (b, c))
    # log-decay of every step and its running sum inside the chunk, heads
    # before positions: (B, nc, G, R, Q)
    cum = jnp.cumsum(jnp.moveaxis(dt * a.astype(f32).reshape(g, r), 2, -1), axis=-1)
    xdt = x.astype(f32) * dt[..., None]                                   # Δ_s x_s

    # inside a chunk: the group's scores under each head's decay
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = dot("bclgn,bcsgn->bcgls", c, b)
    y = dot("bcgrls,bcsgrp->bclgrp", scores[:, :, :, None] * decay, xdt)

    # a chunk's own state, the state entering each chunk, and what it adds
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), -1, 2)            # (B, nc, Q, G, R)
    own = dot("bcsgn,bcsgrp->bcgrpn", b, xdt * to_end[..., None])

    def carry(state, chunk_):
        kept, new = chunk_
        return kept[..., None, None] * state + new, state

    _, entering = lax.scan(carry, jnp.zeros(own.shape[:1] + own.shape[2:], f32),
                           (jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0), jnp.moveaxis(own, 1, 0)))
    since_start = jnp.moveaxis(jnp.exp(cum), -1, 2)                       # (B, nc, Q, G, R)
    y = y + dot("bclgn,bcgrpn->bclgrp", c, jnp.moveaxis(entering, 0, 1)) * since_start[..., None]
    return y.reshape(bsz, t, h, p).astype(dtype)

"""The gated delta rule of a Gated DeltaNet layer (Yang, Kautz & Hatamizadeh
2024, "Gated Delta Networks"), computed in chunks.

Per value head, with ``S`` a (d_k, d_v) state that starts at zero, a key
``k_t`` and a query ``q_t`` of d_k, a value ``v_t`` of d_v, a log-decay
``g_t <= 0`` and a write strength ``β_t`` in (0, 1):

    S_t = e^{g_t} (I - β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ          o_t = S_tᵀ q_t

(the state written as keys by values, so that ``o_t = q_t S_t`` a row).
Position by position that is T dependent rank-one updates.  In chunks of C
positions, with ``γ_i = Σ_{j<=i} g_j`` inside a chunk (every decay below is
``e`` to a difference ``γ_i - γ_j`` with ``i >= j``, or to ``γ_i`` itself,
so no exponent is ever positive and ``e^{-γ}`` is never formed):

- ``L_ij = β_i (k_i·k_j) e^{γ_i - γ_j}`` for ``j < i``; the WY form's
  ``[U | W] = (I + L)^{-1} [β ⊙ V | β ⊙ e^{γ} ⊙ K]``, by one triangular solve
  a chunk and head (on the TPU XLA's ``InvertDiagBlocksLowerTriangular``
  custom call, 1.4 ms a span of 16 chunks × 32 heads and about a quarter of
  the Qwen3-Next cell's step; the same elimination by blockwise doubling in
  HIGHEST-precision products was slower still: PERF.md section 6);
- the state ENTERING each chunk, by the recurrence over chunks: ``V' = U - W
  S`` and ``S ← e^{γ_C} S + (e^{γ_C - γ} ⊙ K)ᵀ V'`` (T / C dependent steps of
  two products);
- the chunk's output ``O = (e^{γ} ⊙ Q) S + [(Q Kᵀ) ⊙ e^{γ_i - γ_j} ⊙
  tril] V'``, for every chunk at once from the states that entered them.

The decays, β, the solve and the carried state are float32 whatever the
inputs' dtype; the products take their operands in ``v``'s dtype and
accumulate in float32, as ``ops/ssd.py``'s do.  Keys and queries come in at
their own head count and value head h reads key head ``h // (H / G)``.

The chunks are taken ``SPAN_CHUNKS`` at a time under ``jax.checkpoint``, the
state carried from span to span: a backward keeps a span's state and
recomputes the span's (C, C) blocks, solves and states one span at a time.
Whole, at 8192 positions of 32 heads of 128, those are 3.4 GB alive in one
layer's backward, which a v5e's 16 GB beside a 10 GB training state cannot
hold (PERF.md section 4).

One form today, ``gated_delta_plan``'s only answer: ``xla``, plain ``lax`` /
``jnp`` with ``jax.grad``'s backward (a chunk's (C, C) blocks a head and the
state entering each chunk are what it keeps), in chunks of ``CHUNK``.  The
plan is the one place a kernel's shapes would be asked for, so that a Pallas
form slots in behind ``gated_delta_rule`` without a change to the model.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
CHUNK = 64                            # positions a chunk (the published chunked form's)
SPAN_CHUNKS = 16                      # chunks a checkpointed span holds


class GatedDeltaPlan(NamedTuple):
    """What ``gated_delta_rule`` does with one call's static facts."""

    kind: str         # "xla" (no kernel is written yet)
    chunk: int        # positions a chunk


def gated_delta_plan(seq_len: int, key_heads: int, value_heads: int, key_dim: int,
                     value_dim: int) -> GatedDeltaPlan:
    """Which form a call takes, from its shapes alone."""
    return GatedDeltaPlan("xla", CHUNK)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int | None = None):
    """q, k (B, T, G, d_k) — the query already scaled —, v (B, T, H, d_v) with
    H a multiple of G, g (B, T, H) the log-decays (<= 0) and beta (B, T, H) →
    o (B, T, H, d_v) in ``v``'s dtype, from a zero state.  ``chunk`` is the
    plan's unless given.  A length that is no multiple of the span is padded
    at the end with positions that write nothing and keep the state (β 0,
    g 0)."""
    bsz, t, groups, _ = q.shape
    heads = v.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} value heads are no multiple of {groups} key heads")
    chunk = chunk or gated_delta_plan(t, groups, heads, q.shape[-1], v.shape[-1]).chunk
    span = min(chunk * SPAN_CHUNKS, -(-t // chunk) * chunk)
    pad = -t % span
    if pad:
        q, k, v, g, beta = (jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2)) for m in (q, k, v, g, beta))

    @jax.checkpoint
    def one_span(state, inputs):
        return _gated_delta_xla(*inputs, chunk, state)

    spans = lambda m: jnp.moveaxis(m.reshape(bsz, -1, span, *m.shape[2:]), 1, 0)
    _, o = lax.scan(one_span, jnp.zeros((bsz, heads, q.shape[-1], v.shape[-1]), _F32),
                    tuple(spans(m) for m in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(bsz, -1, heads, v.shape[-1])[:, :t]


def _gated_delta_xla(q, k, v, g, beta, chunk, state):
    """The ``xla`` form of the module docstring over one span, from ``state``
    (B, H, d_k, d_v) float32 → (the state after it, the span's output)."""
    bsz, t, groups, dk = q.shape
    heads, dv, dtype = v.shape[2], v.shape[3], v.dtype
    nc, per = t // chunk, heads // groups
    dot = lambda spec, lhs, rhs: jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                                            preferred_element_type=_F32)
    # (B, H, nc, C, ...): value head h next to key head h // per
    by_head = lambda m: jnp.moveaxis(m.reshape(bsz, nc, chunk, *m.shape[2:]), 3, 1)
    q, k = (jnp.repeat(by_head(m), per, axis=1) for m in (q, k))
    v = by_head(v)
    gamma = jnp.cumsum(by_head(g.astype(_F32)), axis=-1)                  # (B, H, nc, C)
    beta = by_head(beta.astype(_F32))

    below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay_below = jnp.exp(jnp.where(below, diff, -jnp.inf))
    decay_seen = jnp.exp(jnp.where(seen, diff, -jnp.inf))

    # the WY form: one unit-lower triangular solve a chunk and head, float32
    lower = beta[..., :, None] * dot("bhcid,bhcjd->bhcij", k, k) * decay_below
    rhs = jnp.concatenate([beta[..., None] * v.astype(_F32),
                           (beta * jnp.exp(gamma))[..., None] * k.astype(_F32)], axis=-1)
    solved = lax.linalg.triangular_solve(lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    to_end = (jnp.exp(gamma[..., -1:] - gamma)[..., None] * k.astype(_F32))     # e^{γ_C - γ} ⊙ K

    def carry(state, at):
        u_c, w_c, k_c, kept = at
        new = u_c - dot("bhcd,bhde->bhce", w_c, state)                    # V'
        return kept[..., None, None] * state + dot("bhcd,bhce->bhde", k_c, new), (new, state)

    chunks = lambda m: jnp.moveaxis(m, 2, 0)
    state, (new, entering) = lax.scan(
        carry, state, (chunks(u), chunks(w), chunks(to_end), chunks(jnp.exp(gamma[..., -1]))))
    new, entering = jnp.moveaxis(new, 0, 2), jnp.moveaxis(entering, 0, 2)

    o = dot("bhcid,bhcde->bhcie", jnp.exp(gamma)[..., None] * q.astype(_F32), entering)
    o = o + dot("bhcij,bhcje->bhcie", dot("bhcid,bhcjd->bhcij", q, k) * decay_seen, new)
    return state, jnp.moveaxis(o, 1, 3).reshape(bsz, t, heads, dv).astype(dtype)

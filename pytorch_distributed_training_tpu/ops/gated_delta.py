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
  ``[U | W] = (I + L)^{-1} [β ⊙ V | β ⊙ e^{γ} ⊙ K]``, one unit-lower solve a
  chunk and head in float32 (the plan's ``solve``, below);
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

Two forms, ``gated_delta_plan``'s choice from the shapes and the backend
alone, in chunks of ``CHUNK``; the blocks ``L`` and the right-hand side are
the same ``lax`` / ``jnp`` code in both, with ``jax.grad``'s backward:

- ``pallas``: two Mosaic pairs, each under one ``jax.custom_vjp``.  The
  solve (``gated_delta_solve_fwd``, ``gated_delta_solve_bwd``) runs over a
  span's chunk-heads, 128 a grid step.  The forward forms ``T = (I + L)^{-1}`` in
  VMEM by forward substitution with each chunk-head on a lane of its own, so
  the 63 dependent row steps are plain float32 multiply-adds over 128
  independent systems: ``L`` comes in rows-major (``(C, N, C)``, a
  relayout XLA fuses into the blocks' producer) and is turned onto the
  lanes a row at a time on the XLU; the rows are summed in place a block of
  8 at a time, each block's share only as wide as the block (``T`` is lower
  triangular), in loops whose bodies Mosaic lowers once (straight-line code
  for every pair of rows cost each program 1.3 s more of lowering, which no
  cache keeps).  ``T`` goes back to one (C, C) matrix a chunk-head by strided
  stores, and ``T · rhs`` runs on the MXU at
  ``HIGHEST`` precision, as XLA's expander runs it after its
  ``InvertDiagBlocksLowerTriangular`` custom call (1.4 ms a span of 16
  chunks × 32 heads on a v5e, about a quarter of the Qwen3-Next cell's step;
  blockwise doubling in XLA products was slower still: PERF.md section 6).
  It writes the solution and ``T``, the backward's residual (made again
  under the span's ``jax.checkpoint`` with everything else).  The backward
  is two products a chunk-head and no solve: ``d_rhs = Tᵀ d`` and ``d_L =
  -strict_lower(d_rhs · solvedᵀ)``, at ``HIGHEST`` too.  A Mosaic call is
  opaque to the partitioner: under a mesh of several devices each pair runs
  a shard inside a ``shard_map``, the batch over the batch axes and the heads
  over ``tensor`` where each divides (``ops/attention._kernel_partition``,
  as the flash pair; the chunk pair splits the key heads, a key head's value
  heads staying together).
  The recurrence over the span's chunks and their output
  (``gated_delta_chunk_fwd``, ``gated_delta_chunk_bwd``) take the solve's
  ``[U | W]`` as it lies, the queries and keys at their key heads (the
  layer's (B, T, G·d_k), value head h reading key head h // (H / G) by the
  block's lanes) and ``γ``, and write ``O`` as (B, T, H·d_v).  Grid (batch,
  a block of value heads: whole key heads, ``_CHUNK_HEADS`` value heads, so
  that their dependent chains interleave), a step's blocks the whole span;
  the chunks are walked by a ``pl.loop`` with each head's (d_k, d_v) state
  float32 in VMEM.  Per chunk and head ``V' = U - W S``, ``O = (e^{γ} ⊙ Q)
  S + ((Q Kᵀ) ⊙ E) V'`` and ``S ← e^{γ_C} S + (e^{γ_C - γ} ⊙ K)ᵀ V'``: the
  two products with S are one over ``[W; e^{γ} ⊙ Q]`` and the two with V'
  one over ``[A; K̃ᵀ]``, ``Kᵀ`` turned once a key head and the decays formed
  in whichever layout a product takes.  The forward writes nothing beyond
  ``O`` and the state after the span, whether a backward follows or not
  (a residual of the entering states would be 33.5 MB a span written by
  every forward call, three a span in the cell's step against one
  backward): the backward's first walk makes the states entering each chunk
  and each ``V'`` again in VMEM from the inputs (5 MB for a step's four
  heads at the cell), its second walks the chunks from the last with the
  state's cotangent in VMEM and writes the cotangents of q, k (a key head's
  value heads summed), ``[U | W]``, ``γ`` and the entering state.  Precision is the parent ``jnp`` form's: every
  product accumulates in float32 and takes in ``q``'s dtype the operands the
  parent casts (the float32 cotangents ``dS'``, ``dV'`` and ``dA ⊙ E``
  stay float32 at the MXU's default precision, as the parent's transposed
  products take them); ``γ``, every decay, ``V'``, ``S`` and ``dS`` are
  float32.
- ``xla``: ``lax.linalg.triangular_solve`` and its autodiff, and the
  recurrence as a ``lax.scan`` over chunks with the output from the states
  that entered them (``jax.grad``'s backward keeps a chunk's (C, C) blocks a
  head and those states), where a head's keys or values are no multiple of
  128 or the backend is neither the TPU nor the CPU's interpreter; the toy
  sizes, and the tests' second opinion.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ..compat import shard_map
from .attention import _kernel_partition
from .ssd import _exact

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST      # the precision of XLA's own products round its solve
_LANES = 128
_SUBLANES = 8
CHUNK = 64                            # positions a chunk (the published chunked form's)
SPAN_CHUNKS = 16                      # chunks a checkpointed span holds

# Every ``pl.pallas_call`` below carries one of these as ``name`` (the HLO
# instruction, and so the device trace's event, is ``%<name>.<n>``;
# ``obs/cost.mosaic_kernels`` counts them).
KERNEL_NAMES = ("gated_delta_solve_fwd", "gated_delta_solve_bwd", "gated_delta_chunk_fwd", "gated_delta_chunk_bwd")

_FWD_HEADS = _LANES                   # chunk-heads a forward grid step: one a lane
_BWD_HEADS = 64                       # chunk-heads a backward grid step
_UNROLL = 4                           # chunk-heads a step of the product loops: the MXU's slots 85 % full, 55 % at one
_VMEM_BYTES = 64 * 2**20              # the forward's blocks twice over (52 MB) and its two (C, C, 128) scratches


class GatedDeltaPlan(NamedTuple):
    """What ``gated_delta_rule`` does with one call's static facts."""

    kind: str           # "pallas" | "xla"
    chunk: int          # positions a chunk
    interpret: bool     # the kernels under Pallas's interpreter: the CPU


# kind -> call sites traced with it since the process started (the
# ``gated_delta_plan[kind=..]`` gauges)
_plans_traced: dict[str, int] = {}


def gated_delta_plans_traced() -> dict[str, int]:
    """The ``gated_delta_plan[kind=pallas|xla]`` gauges' values."""
    return dict(_plans_traced)


def gated_delta_plan(seq_len: int, key_heads: int, value_heads: int, key_dim: int, value_dim: int, *,
                     chunk: int | None = None, backend: str | None = None) -> GatedDeltaPlan:
    """Which form a call takes, from its shapes and the backend alone: the
    kernels are written for chunks of ``CHUNK`` and heads whose keys and
    values are whole lane tiles.  ``chunk`` is ``CHUNK`` unless given;
    ``backend`` is ``jax.default_backend()`` unless given: the kernels run
    on the TPU and, interpreted, on the CPU."""
    backend = jax.default_backend() if backend is None else backend
    chunk = chunk or CHUNK
    if backend in ("tpu", "cpu") and chunk == CHUNK and key_dim % _LANES == 0 and value_dim % _LANES == 0:
        return GatedDeltaPlan("pallas", chunk, backend == "cpu")
    return GatedDeltaPlan("xla", chunk, False)


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
    plan = gated_delta_plan(t, groups, heads, q.shape[-1], v.shape[-1], chunk=chunk)
    _plans_traced[plan.kind] = _plans_traced.get(plan.kind, 0) + 1
    chunk = plan.chunk
    solve, chunks = _solve_xla, None
    if plan.kind == "pallas":
        solve = functools.partial(_solve_pallas, interpret=plan.interpret)
        chunks = functools.partial(_chunks_pallas, interpret=plan.interpret)
    span = min(chunk * SPAN_CHUNKS, -(-t // chunk) * chunk)
    pad = -t % span
    if pad:
        q, k, v, g, beta = (jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2)) for m in (q, k, v, g, beta))

    @jax.checkpoint
    def one_span(state, inputs):
        return _gated_delta_xla(*inputs, chunk, state, solve, chunks)

    spans = lambda m: jnp.moveaxis(m.reshape(bsz, -1, span, *m.shape[2:]), 1, 0)
    _, o = lax.scan(one_span, jnp.zeros((bsz, heads, q.shape[-1], v.shape[-1]), _F32),
                    tuple(spans(m) for m in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(bsz, -1, heads, v.shape[-1])[:, :t]


def _gated_delta_xla(q, k, v, g, beta, chunk, state, solve, chunks=None):
    """The chunked rule of the module docstring over one span, from
    ``state`` (B, H, d_k, d_v) float32 → (the state after it, the span's
    output); ``solve(lower, rhs)`` is ``(I + lower)^{-1} rhs`` a chunk-head;
    ``chunks(q, k, solved, gamma, state)``, where given, is the recurrence
    and the output from the solution (``_chunks_pallas``), in place of the
    ``lax.scan`` below."""
    bsz, t, groups, dk = q.shape
    heads, dv, dtype = v.shape[2], v.shape[3], v.dtype
    nc, per = t // chunk, heads // groups
    dot = lambda spec, lhs, rhs: jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                                            preferred_element_type=_F32)
    at_key_heads = q, k
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
    solved = solve(lower, rhs)
    if chunks is not None:
        return chunks(*(m.astype(dtype) for m in at_key_heads), solved, gamma, state)
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


def _solve_xla(lower, rhs):
    """The ``xla`` form's solve: XLA's triangular solve and its autodiff."""
    return lax.linalg.triangular_solve(lower, rhs, left_side=True, lower=True, unit_diagonal=True)


def _dot(lhs, rhs, contract=((1,), (0,)), precision=_HIGHEST):
    """A product accumulated in float32, at ``HIGHEST`` unless told:
    ``contract`` names the lhs and rhs dimension summed over ((0, 0): lhs
    transposed; (1, 1): rhs transposed)."""
    return lax.dot_general(lhs, rhs, (contract, ((), ())), preferred_element_type=_F32, precision=precision)


def _substitute(lower_t, inverse_t):
    """``T = (I + L)^{-1}`` of 128 chunk-heads, one a lane: ``lower_t`` and
    ``inverse_t`` (C, C, 128) float32 hold ``L[i, j]`` and ``T[i, m]`` at
    ``[i, j]`` / ``[i, m]``.  Row i of ``T`` is ``e_i - Σ_{j<i} L[i, j]
    T[j]``, summed in place block by block of 8 j: once a block's rows are
    final, every row from the block's first on takes off their share.  A row
    of the block itself takes its share from the rows before it alone,
    because ``L[i, j]`` is 0 for ``j >= i``; and row j of ``T`` is zero past
    column j, so a block's share touches no column past the block.  The rows
    are walked by a ``pl.loop`` a block, its body traced and lowered once."""
    chunk, _, lanes = lower_t.shape
    column = lax.broadcasted_iota(jnp.int32, (chunk, lanes), 0)

    @pl.loop(0, chunk)
    def _unit(i):
        inverse_t[i] = jnp.where(column == i, 1.0, 0.0).astype(_F32)

    for jb in range(chunk // _SUBLANES):
        at_j = pl.ds(jb * _SUBLANES, _SUBLANES)
        width = pl.ds(0, (jb + 1) * _SUBLANES)

        @pl.loop(jb * _SUBLANES, chunk)
        def _share(i, at_j=at_j, width=width):
            l_i = lower_t[i, at_j, :]                                       # L[i, j] of the block's 8 j
            rows = inverse_t[at_j, width, :]                                # their rows of T
            even = inverse_t[i, width, :]
            odd = jnp.zeros_like(even)                                      # two sums: half the dependent chain
            for u in range(0, _SUBLANES, 2):
                even = even - l_i[u:u + 1] * rows[u]
                odd = odd - l_i[u + 1:u + 2] * rows[u + 1]
            inverse_t[i, width, :] = even + odd


def _fwd_kernel(lower_ref, rhs_ref, solved_ref, inverse_ref, lower_t, inverse_t):
    """A grid step's 128 chunk-heads: ``lower_ref`` (C, 128, C) holds
    ``L[i]`` of each chunk-head as its row, ``rhs_ref`` (128, C, W); writes
    the solution and ``T`` a chunk-head (``inverse_ref`` (128·C, C), row
    ``c·C + i``)."""
    chunk, heads = lower_ref.shape[:2]

    @pl.loop(0, chunk)
    def _onto_lanes(i):
        lower_t[i] = lower_ref[i].T

    _substitute(lower_t, inverse_t)

    @pl.loop(0, chunk)
    def _off_lanes(i):
        inverse_ref[pl.ds(i, heads, stride=chunk), :] = inverse_t[i].T

    @pl.loop(0, heads, step=_UNROLL)
    def _product(h0):
        for h in (h0 + u for u in range(_UNROLL)):
            solved_ref[h] = _dot(inverse_ref[pl.ds(pl.multiple_of(h * chunk, chunk), chunk), :], rhs_ref[h])


def _bwd_kernel(inverse_ref, solved_ref, d_ref, drhs_ref, dlower_ref):
    """A grid step's chunk-heads: ``d_rhs = Tᵀ d``, ``d_L = -strict_lower(d_rhs
    · solvedᵀ)``."""
    heads, chunk = inverse_ref.shape[:2]
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    below = rows > lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    @pl.loop(0, heads, step=_UNROLL)
    def _heads(h0):
        for h in (h0 + u for u in range(_UNROLL)):
            drhs = _dot(inverse_ref[h], d_ref[h], ((0,), (0,)))
            drhs_ref[h] = drhs
            dlower_ref[h] = jnp.where(below, -_dot(drhs, solved_ref[h], ((1,), (1,))), 0.0)


# Jitted on their own, as the other launchers are: a model's call sites
# trace and lower the pair once a program.
@functools.partial(jax.jit, static_argnums=2)
def _solve_fwd(lower, rhs, interpret):
    """lower (N, C, C) and rhs (N, C, W) float32, N a multiple of 128 → the
    solution (N, C, W) and ``T`` (N, C, C)."""
    n, chunk, _ = lower.shape
    width = rhs.shape[-1]
    heads = _FWD_HEADS
    solved, inverse = pl.pallas_call(
        _fwd_kernel,
        grid=(n // heads,),
        in_specs=[pl.BlockSpec((chunk, heads, chunk), lambda s: (0, s, 0)),
                  pl.BlockSpec((heads, chunk, width), lambda s: (s, 0, 0))],
        out_specs=[pl.BlockSpec((heads, chunk, width), lambda s: (s, 0, 0)),
                   pl.BlockSpec((heads * chunk, chunk), lambda s: (s, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, chunk, width), _F32), jax.ShapeDtypeStruct((n * chunk, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk, heads), _F32), pltpu.VMEM((chunk, chunk, heads), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_BYTES),
        name="gated_delta_solve_fwd",
        interpret=interpret,
    )(jnp.swapaxes(lower, 0, 1), rhs)
    return solved, inverse.reshape(n, chunk, chunk)


@functools.partial(jax.jit, static_argnums=3)
def _solve_bwd(inverse, solved, d, interpret):
    """The cotangents of lower and rhs from the solution's ``d``."""
    n, chunk, width = solved.shape
    heads = _BWD_HEADS
    block = lambda cols: pl.BlockSpec((heads, chunk, cols), lambda s: (s, 0, 0))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(n // heads,),
        in_specs=[block(chunk), block(width), block(width)],
        out_specs=[block(width), block(chunk)],
        out_shape=[jax.ShapeDtypeStruct((n, chunk, width), _F32), jax.ShapeDtypeStruct((n, chunk, chunk), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_BYTES),
        name="gated_delta_solve_bwd",
        interpret=interpret,
    )(inverse, solved, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _solve_pair(lower, rhs, interpret):
    return _solve_fwd(lower, rhs, interpret)[0]


def _solve_pair_fwd(lower, rhs, interpret):
    solved, inverse = _solve_fwd(lower, rhs, interpret)
    return solved, (inverse, solved)


def _solve_pair_bwd(interpret, saved, d):
    inverse, solved = saved
    drhs, dlower = _solve_bwd(inverse, solved, d, interpret)
    return dlower, drhs


_solve_pair.defvjp(_solve_pair_fwd, _solve_pair_bwd)


def _solve_pallas(lower, rhs, *, interpret):
    """The ``pallas`` form's solve over lower (B, H, nc, C, C) and rhs (B,
    H, nc, C, W) float32."""
    def kernels(lower, rhs):                  # the call's operands, or one device's shard of them
        *lead, chunk, width = rhs.shape
        n = lower.size // (chunk * chunk)
        pad = -n % _FWD_HEADS                 # chunk-heads past the last: L 0, rhs 0
        flat = lambda m: jnp.pad(m.reshape(n, chunk, -1), ((0, pad), (0, 0), (0, 0)))
        return _solve_pair(flat(lower), flat(rhs), interpret)[:n].reshape(*lead, chunk, width)

    partition = _kernel_partition(lower.shape[0], lower.shape[1])
    if partition is None:
        return kernels(lower, rhs)
    mesh, spec = partition                    # (batch axes, None, tensor, None)
    heads = PartitionSpec(spec[0], spec[2], None, None, None)
    return shard_map(kernels, mesh=mesh, in_specs=(heads, heads), out_specs=heads, check_vma=False)(lower, rhs)


# ---- the recurrence over a span's chunks and their output: the chunk pair ----

_CHUNK_HEADS = 4                      # value heads a grid step of the chunk pair: their dependent chains interleave
_NT, _TN = ((1,), (1,)), ((0,), (0,))


def _heads_a_step(groups: int, heads: int) -> int:
    """Value heads a grid step of the chunk pair: whole key heads' worth
    (``heads // groups`` each), as many as ``_CHUNK_HEADS`` holds."""
    per = heads // groups
    return per * max(m for m in range(1, groups + 1) if groups % m == 0 and per * m <= max(_CHUNK_HEADS, per))


class _Decays(NamedTuple):
    """A chunk-head's decays, from its ``γ`` along the lanes (1, C) and down
    the rows (C, 1), and their transposes (``_t``: positions along the
    lanes), formed as they are and not turned; no exponent is ever
    positive."""

    since: jax.Array        # (C, d_k) e^{γ_i}, each row one value
    to_end: jax.Array       # (C, d_k) e^{γ_C - γ_i}
    seen: jax.Array         # (C, C) E_ij = e^{γ_i - γ_j} on i >= j, 0 above
    kept: jax.Array         # (d_k, d_v) e^{γ_C}
    since_t: jax.Array      # (d_k, C)
    to_end_t: jax.Array     # (d_k, C)
    seen_t: jax.Array       # (C, C) Eᵀ


def _decays(row, column, dk, dv):
    chunk = row.shape[1]
    rows, cols = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), a) for a in (0, 1))
    down, along = jnp.broadcast_to(column, (chunk, chunk)), jnp.broadcast_to(row, (chunk, chunk))
    wide, tall = (lambda m: jnp.broadcast_to(m, (chunk, dk))), (lambda m: jnp.broadcast_to(m, (dk, chunk)))
    last = jnp.broadcast_to(row[:, chunk - 1:], (1, chunk))
    return _Decays(wide(jnp.exp(column)), wide(jnp.exp(column[chunk - 1:] - column)),
                   jnp.exp(jnp.where(rows >= cols, down - along, -jnp.inf)),
                   # e^{γ_C} along the lanes first: Mosaic broadcasts a scalar along one axis at a time
                   jnp.broadcast_to(jnp.exp(jnp.broadcast_to(column[chunk - 1:], (1, dv))), (dk, dv)),
                   tall(jnp.exp(row)), tall(jnp.exp(last - row)), jnp.exp(jnp.where(rows <= cols, along - down, -jnp.inf)))


class _Step(NamedTuple):
    """What a grid step reads of chunk ``c``: ``rows``, the chunk's
    positions; ``gamma`` (heads, C), each value head's ``γ`` along the
    lanes, and ``down``, the same down the rows (C, heads)."""

    rows: object
    gamma: jax.Array
    down: jax.Array

    @classmethod
    def at(cls, c, gamma_ref):
        chunk = gamma_ref.shape[4]
        gamma = gamma_ref[0, 0, c]
        return cls(pl.ds(pl.multiple_of(c * chunk, chunk), chunk), gamma, gamma.T)


def _chunk_fwd_kernel(q_ref, k_ref, solved_ref, gamma_ref, state_ref, o_ref, out_ref):
    """Grid (batch, a block of value heads): the span's chunks in order, a
    ``pl.loop``, each head's float32 state in ``out_ref`` (VMEM until the
    step ends).  Per chunk and head: ``V' = U - W S``, ``O = (e^{γ} ⊙ Q) S +
    ((Q Kᵀ) ⊙ E) V'``, ``S ← e^{γ_C} S + (e^{γ_C - γ} ⊙ K)ᵀ V'``, the
    products' operands in ``q``'s dtype (the parent ``jnp`` form's); the
    two products with S run as one over ``[W; e^{γ} ⊙ Q]`` and the two with
    V' as one over ``[(Q Kᵀ) ⊙ E; (e^{γ_C - γ} ⊙ K)ᵀ]``, so that each of
    S and V' goes into the MXU once a chunk."""
    heads, nc, chunk = solved_ref.shape[1:4]
    dk, dv = out_ref.shape[2:]
    per = heads * dk // q_ref.shape[2]
    dtype = q_ref.dtype
    mm = lambda lhs, rhs, contract=((1,), (0,)): _dot(lhs.astype(dtype), rhs.astype(dtype), contract, None)
    out_ref[...] = state_ref[...]

    @pl.loop(0, nc)
    def _chunk(c):
        step = _Step.at(c, gamma_ref)
        for r in range(heads):
            lanes = pl.ds((r // per) * dk, dk)
            q, k = q_ref[0, step.rows, lanes], k_ref[0, step.rows, lanes]
            if r % per == 0:
                scores = mm(q, k, _NT)                                          # Q Kᵀ, a key head's
                keys_t = k.astype(_F32).T                                       # Kᵀ (d_k, C)
            decay = _decays(step.gamma[r:r + 1], step.down[:, r:r + 1], dk, dv)
            solved, state = solved_ref[0, r, c], out_ref[0, r]
            by_state = mm(jnp.concatenate([solved[:, dv:], decay.since * q.astype(_F32)]), state)
            new = solved[:, :dv] - by_state[:chunk]                             # V' = U - W S
            by_new = mm(jnp.concatenate([scores * decay.seen, keys_t * decay.to_end_t]), new)
            o_ref[0, step.rows, pl.ds(r * dv, dv)] = (by_state[chunk:] + by_new[:chunk]).astype(o_ref.dtype)
            out_ref[0, r] = decay.kept * state + by_new[chunk:]


def _chunk_bwd_kernel(q_ref, k_ref, solved_ref, gamma_ref, state_ref, do_ref, dout_ref,
                      dq_ref, dk_ref, dsolved_ref, dgamma_ref, dstate_ref, entering, values):
    """The same grid.  A first ``pl.loop`` walks the chunks in order and
    keeps the state entering each in ``entering`` (nc, heads, d_k, d_v)
    float32 and each ``V'`` in ``values`` (nc, heads, C, d_v) in the
    products' dtype — the forward's only residual is its inputs —, a second
    walks them from the last with the state's cotangent ``dS'`` in
    ``dstate_ref``:

        dV' = Aᵀ dO + K̃ dS'        dU = dV'        dW = -dV' Sᵀ
        dS  = Qdᵀ dO + e^{γ_C} dS' - Wᵀ dV'
        dQ  = e^{γ} ⊙ (dO Sᵀ) + (dA ⊙ E) K       dK = e^{γ_C - γ} ⊙ (V' dS'ᵀ) + (dA ⊙ E)ᵀ Q

    with ``Qd = e^{γ} ⊙ Q``, ``K̃ = e^{γ_C - γ} ⊙ K``, ``A = (Q Kᵀ) ⊙ E``
    and ``dA = dO V'ᵀ``; a key head's ``dA ⊙ E`` is summed over its value
    heads before its two products.  ``dγ_i = Σ_d dQd ⊙ Qd - Σ_d dK̃ ⊙ K̃ +
    Σ_j M_ij - Σ_j M_ji`` with ``M = dA ⊙ A`` below the diagonal (the
    diagonal's share is in both sums), and the last position also takes
    ``Σ dK̃ ⊙ K̃ + e^{γ_C} Σ dS' ⊙ S``: the sums over the lanes come out down
    the rows and are turned onto the lanes with the step's other heads'.
    Operands the parent's ``jax.grad`` takes in ``q``'s dtype are cast to
    it; the float32 cotangents ``dS'``, ``dV'`` and ``dA ⊙ E`` stay
    float32, as in the parent's transposed products."""
    heads, nc, chunk = solved_ref.shape[1:4]
    dk, dv = dstate_ref.shape[2:]
    per = heads * dk // q_ref.shape[2]
    dtype = q_ref.dtype
    mm = lambda lhs, rhs, contract=((1,), (0,)): _dot(lhs, rhs, contract, None)
    rows_i, cols_i = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), a) for a in (0, 1))
    last = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    entering[0] = state_ref[0]

    @pl.loop(0, nc)
    def _forward(c):
        step = _Step.at(c, gamma_ref)
        for r in range(heads):
            if r % per == 0:
                keys_t = k_ref[0, step.rows, pl.ds((r // per) * dk, dk)].astype(_F32).T
            decay = _decays(step.gamma[r:r + 1], step.down[:, r:r + 1], dk, dv)
            solved, state = solved_ref[0, r, c], entering[c, r]
            new = (solved[:, :dv] - mm(solved[:, dv:].astype(dtype), state.astype(dtype))).astype(dtype)
            values[c, r] = new

            @pl.when(c < nc - 1)
            def _carry():
                entering[c + 1, r] = decay.kept * state + mm((keys_t * decay.to_end_t).astype(dtype), new)

    dstate_ref[...] = dout_ref[...]

    @pl.loop(0, nc)
    def _reverse(back):
        c = nc - 1 - back
        step = _Step.at(c, gamma_ref)
        down, along = [], []                  # each head's dγ: the sums over lanes (C, 1), over rows (1, C)
        for i in range(heads // per):
            lanes = pl.ds(i * dk, dk)
            q, k = q_ref[0, step.rows, lanes], k_ref[0, step.rows, lanes]
            scores, scores_t = mm(q, k, _NT), mm(k, q, _NT)                     # Q Kᵀ and K Qᵀ
            queries_t = q.astype(_F32).T
            dq = dk_sum = dp_sum = None
            for r in range(i * per, (i + 1) * per):
                decay = _decays(step.gamma[r:r + 1], step.down[:, r:r + 1], dk, dv)
                state, dleaving, new_c = entering[c, r], dstate_ref[0, r], values[c, r]
                state_c, w_c = state.astype(dtype), solved_ref[0, r, c, :, dv:].astype(dtype)
                a = scores * decay.seen
                qd, kt = decay.since * q.astype(_F32), decay.to_end * k.astype(_F32)
                do = do_ref[0, step.rows, pl.ds(r * dv, dv)]
                # [Aᵀ dO; Qdᵀ dO], both transposes formed directly
                by_do = mm(jnp.concatenate([scores_t * decay.seen_t, queries_t * decay.since_t]).astype(dtype), do)
                dnew = by_do[:chunk] + mm(kt.astype(dtype), dleaving)              # dV'
                by_state = mm(jnp.concatenate([do.astype(_F32), dnew]), state_c, _NT)  # [dO Sᵀ; dV' Sᵀ]
                dqd = by_state[:chunk]
                dkt = mm(new_c, dleaving, _NT)                                    # V' dS'ᵀ
                dstate_ref[0, r] = by_do[chunk:] + decay.kept * dleaving - mm(w_c, dnew, _TN)
                dsolved_ref[0, r, c] = jnp.concatenate([dnew, -by_state[chunk:]], axis=1)
                da = jnp.where(rows_i >= cols_i, mm(do, new_c, _NT), 0.0)
                dp = da * decay.seen
                dq_r, dk_r = decay.since * dqd, decay.to_end * dkt
                dq, dk_sum, dp_sum = ((dq_r, dk_r, dp) if dq is None else (dq + dq_r, dk_sum + dk_r, dp_sum + dp))
                moved = jnp.where(rows_i > cols_i, da * a, 0.0)
                down.append(jnp.sum(dqd * qd - dkt * kt, axis=1, keepdims=True) + jnp.sum(moved, axis=1, keepdims=True))
                total = jnp.sum(dkt * kt, keepdims=True) + decay.kept[:1, :1] * jnp.sum(dleaving * state, keepdims=True)
                along.append(jnp.where(last, total, 0.0) - jnp.sum(moved, axis=0, keepdims=True))
            dq_ref[0, step.rows, lanes] = (dq + mm(dp_sum, k)).astype(dq_ref.dtype)
            dk_ref[0, step.rows, lanes] = (dk_sum + mm(dp_sum, q, _TN)).astype(dk_ref.dtype)
        dgamma_ref[0, 0, c] = jnp.concatenate(down, axis=1).T + jnp.concatenate(along)


def _chunk_specs(q, solved, state):
    """The grid, value heads a step, and block specs of a (batch, block of
    value heads) grid step: keys and queries (B, T, G·d_k) the step's key
    heads, outputs (B, T, H·d_v) its value heads, the per-head operands (B,
    H, …) whole past the head, and ``γ`` (B, H / step, nc, step, C) its
    block's."""
    bsz, t, _ = q.shape
    heads, nc, chunk = solved.shape[1:4]
    dk, dv = state.shape[2:]
    step = _heads_a_step(q.shape[2] // dk, heads)
    key_heads = step * q.shape[2] // (dk * heads)
    keys = pl.BlockSpec((1, t, key_heads * dk), lambda b, j: (b, 0, j))
    values = pl.BlockSpec((1, t, step * dv), lambda b, j: (b, 0, j))
    per_head = lambda m: pl.BlockSpec((1, step) + m.shape[2:], lambda b, j: (b, j) + (0,) * (m.ndim - 2))
    gamma = pl.BlockSpec((1, 1, nc, step, chunk), lambda b, j: (b, j, 0, 0, 0))
    return (bsz, heads // step), step, keys, values, per_head, gamma


def _gamma_blocks(gamma, step):
    """(B, H, nc, C) → (B, H / step, nc, step, C): a grid step's heads of a
    chunk in one (step, C) block."""
    bsz, heads, nc, chunk = gamma.shape
    return jnp.swapaxes(gamma.reshape(bsz, heads // step, step, nc, chunk), 2, 3)


_CHUNK_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_BYTES)


# Jitted on their own, as the solve's launchers are.
@functools.partial(jax.jit, static_argnums=5)
def _chunk_fwd(q, k, solved, gamma, state, interpret):
    """q, k (B, T, G·d_k) in the products' dtype; solved (B, H, nc, C, d_v +
    d_k), gamma (B, H, nc, C) and state (B, H, d_k, d_v) float32 → o (B, T,
    H·d_v) in q's dtype and the state after the span."""
    grid, step, keys, values, per_head, gammas = _chunk_specs(q, solved, state)
    heads, dv = solved.shape[1], state.shape[3]
    return pl.pallas_call(
        _chunk_fwd_kernel,
        grid=grid,
        in_specs=[keys, keys, per_head(solved), gammas, per_head(state)],
        out_specs=[values, per_head(state)],
        out_shape=[jax.ShapeDtypeStruct(q.shape[:2] + (heads * dv,), q.dtype), jax.ShapeDtypeStruct(state.shape, _F32)],
        compiler_params=_CHUNK_PARAMS,
        name="gated_delta_chunk_fwd",
        interpret=interpret,
    )(q, k, solved, _gamma_blocks(gamma, step), state)


@functools.partial(jax.jit, static_argnums=7)
def _chunk_bwd(q, k, solved, gamma, state, do, dout, interpret):
    """The cotangents of ``_chunk_fwd``'s five operands from those of its
    two results."""
    grid, step, keys, values, per_head, gammas = _chunk_specs(q, solved, state)
    nc = solved.shape[2]
    blocks = _gamma_blocks(gamma, step)
    dq, dk, dsolved, dgamma, dstate = pl.pallas_call(
        _chunk_bwd_kernel,
        grid=grid,
        in_specs=[keys, keys, per_head(solved), gammas, per_head(state), values, per_head(state)],
        out_specs=[keys, keys, per_head(solved), gammas, per_head(state)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(solved.shape, _F32), jax.ShapeDtypeStruct(blocks.shape, _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((nc, step) + state.shape[2:], _F32),
                        pltpu.VMEM((nc, step, solved.shape[3], state.shape[3]), q.dtype)],
        compiler_params=_CHUNK_PARAMS,
        name="gated_delta_chunk_bwd",
        interpret=interpret,
    )(q, k, solved, blocks, state, do, dout)
    return dq, dk, dsolved, jnp.swapaxes(dgamma, 2, 3).reshape(gamma.shape), dstate


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunk_pair(q, k, solved, gamma, state, interpret):
    return _chunk_fwd(q, k, solved, gamma, state, interpret)


def _chunk_pair_fwd(q, k, solved, gamma, state, interpret):
    return _chunk_fwd(q, k, solved, gamma, state, interpret), (q, k, solved, gamma, state)


def _chunk_pair_bwd(interpret, saved, cotangents):
    return _chunk_bwd(*saved, *cotangents, interpret)


_chunk_pair.defvjp(_chunk_pair_fwd, _chunk_pair_bwd)


def _chunks_pallas(q, k, solved, gamma, state, *, interpret):
    """The ``pallas`` form's recurrence and output over a span: q, k (B, T,
    G, d_k) at their key heads in the products' dtype, solved (B, H, nc, C,
    d_v + d_k) the solve's ``[U | W]``, gamma (B, H, nc, C) and state (B,
    H, d_k, d_v) float32 → (the state after the span, o (B, T, H, d_v))."""
    def kernels(q, k, solved, gamma, state):  # the call's operands, or one device's shard of them
        wide = lambda m: m.reshape(*m.shape[:2], -1)
        o, state = _chunk_pair(wide(q), wide(k), solved, gamma, state, interpret)
        return state, o.reshape(*o.shape[:2], state.shape[1], -1)

    partition = _kernel_partition(q.shape[0], q.shape[2])
    if partition is None:
        return kernels(q, k, solved, gamma, state)
    mesh, spec = partition                    # (batch axes, None, tensor, None): a key head's value heads stay together
    per_head = lambda ndim: PartitionSpec(spec[0], spec[2], *(None,) * (ndim - 2))
    return shard_map(kernels, mesh=mesh, in_specs=(spec, spec, per_head(5), per_head(4), per_head(4)),
                     out_specs=(per_head(4), spec), check_vma=False)(q, k, solved, gamma, state)

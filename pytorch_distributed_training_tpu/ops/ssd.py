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
dtype and accumulate in float32, as the flash kernels do.

Two forms of the same arithmetic, ``ssd_plan``'s choice from the shapes and
the backend alone:

- ``pallas``: a Mosaic pair under one ``jax.custom_vjp`` (``ssd_fwd``,
  ``ssd_bwd``), one grid step a (batch, group, chunk) with the chunks in
  sequence.  The (Q, Q) scores and each head's decay block live in VMEM and
  the group's carried state is a float32 scratch that outlives the chunk
  axis; ``Δ x`` is formed inside.  Heads stand side by side in slabs of a
  lane tile (two heads of 64 columns; a group of one head is a slab of its
  own): a head's product takes the slab's operand whole and keeps its own
  lanes, so no load, store or product works on half a tile.  Running sums and sums over a head's lanes are products
  with 0 / 1 matrices (``_exact``: the float32 side as three bf16 parts in
  one pass of the MXU), not cross-lane reductions.  What goes to HBM besides
  ``y`` is the state ENTERING each chunk, the backward's one residual beside
  the inputs (T/Q · H·P·N float32, 134 MB at 8192 / 128 · 64·64·128, alive
  inside one rematerialized layer's backward; a forward no backward follows
  writes it too: one program, not two).  The backward walks the
  chunks in reverse with the state's cotangent in VMEM and recomputes a
  chunk's blocks from the inputs; the log-decays' gradient leaves it in the
  two layouts its sums come out in, and the launcher adds them and forms
  ``ddt`` and ``dA`` in float32.  A Mosaic call is opaque to the partitioner,
  so under a mesh of several devices the pair runs a shard inside a
  ``shard_map``: the batch over the batch axes, the groups over ``tensor``,
  each where it divides (``ops/attention._kernel_partition``, as the flash
  pair).
- ``xla``: plain ``lax`` / ``jnp`` with ``jax.grad``'s backward (a chunk's
  (Q, Q) decay block a head is what it keeps: T·Q·H float32), where a shape
  is not one the kernels were written for — heads of 64, a state of 128, one
  head or an even number a group: Nemotron-H's — or the backend is neither
  the TPU nor the CPU's interpreter; the toy sizes, and the tests' second
  opinion.
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

_LANES = 128
_HEAD_DIM = 64                        # the kernels' head: two side by side fill a lane tile
_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST        # float32 products against 0 / 1 matrices

# Every ``pl.pallas_call`` below carries one of these as ``name``, by role:
# the HLO instruction, and so the device trace's event, is ``%<name>.<n>``
# (``obs/cost.mosaic_kernels`` counts them; ``ssm.mixer_share.train`` reads
# ``^%\w*ssd_``).
KERNEL_NAMES = ("ssd_fwd", "ssd_bwd")

# scoped VMEM the pair may ask for: blocks twice over (the pipeline's two
# buffers), the carried state, and a head's (Q, Q) temporaries
_VMEM_BYTES = 32 * 1024 * 1024


class SsdPlan(NamedTuple):
    """What ``ssd_chunked`` does with one call's static facts."""

    kind: str         # "pallas" | "xla"
    why: str          # the first reason a shape was refused, or ""
    interpret: bool   # the kernels under Pallas's interpreter: the CPU


# kind -> call sites traced with it since the process started (the
# ``ssd_plan[kind=..]`` gauges)
_plans_traced: dict[str, int] = {}


def ssd_plans_traced() -> dict[str, int]:
    """The ``ssd_plan[kind=pallas|xla]`` gauges' values."""
    return dict(_plans_traced)


def _per_slab(per_group: int) -> int:
    """Heads side by side in a slab of the kernels: the two that fill a lane
    tile, or the group's one."""
    return min(per_group, _LANES // _HEAD_DIM)


def _vmem_estimate(chunk: int, per_group: int, itemsize: int) -> int:
    """The backward's blocks (it holds the most), in bytes."""
    slab = chunk * per_group * _HEAD_DIM
    blocks = 3 * slab * itemsize + 4 * chunk * _LANES * itemsize          # x, dy, dx; b, c, db, dc
    blocks += per_group * _HEAD_DIM * _LANES * 4                          # the entering state
    blocks += 6 * chunk * _LANES * 4                                      # steps and log-decays, lane-padded
    carried = per_group * _HEAD_DIM * _LANES * 4 + chunk * _LANES * 4
    temporaries = 12 * chunk * chunk * 4 + 16 * chunk * _LANES * 4
    return 2 * blocks + carried + temporaries


def ssd_plan(seq_len: int, heads: int, groups: int, head_dim: int, state: int, chunk: int,
             itemsize: int, *, backend: str | None = None) -> SsdPlan:
    """Which form a call takes, from its shapes and the backend alone: the
    one place that asks the tiles and the VMEM fit.  The kernels are written
    for Nemotron-H's mixer and no wider: heads of 64 columns in slabs of two,
    a state of one lane tile.  ``backend`` is ``jax.default_backend()``
    unless given: the kernels run on the TPU and, interpreted, on the CPU."""
    backend = jax.default_backend() if backend is None else backend
    per_group = heads // max(groups, 1)
    for ok, why in (
        (backend in ("tpu", "cpu"), f"backend {backend}"),
        (groups > 0 and heads % groups == 0 and seq_len % chunk == 0, "ragged"),
        (chunk % _LANES == 0, f"chunk {chunk} is no multiple of the {_LANES}-lane tile"),
        (state == _LANES, f"state {state} is not the {_LANES}-lane tile"),
        (head_dim == _HEAD_DIM, f"heads of {head_dim}, not {_HEAD_DIM}"),
        # (one head is half a lane tile: whole only where it is the array's whole width)
        (per_group % 2 == 0 or heads == 1, f"{per_group} heads a group do not fill slabs of two"),
        (3 * per_group <= _LANES, f"{per_group} heads a group: the backward sums three lanes a head"),
        (_vmem_estimate(chunk, per_group, itemsize) <= _VMEM_BYTES, "VMEM"),
    ):
        if not ok:
            return SsdPlan("xla", why, False)
    return SsdPlan("pallas", "", backend == "cpu")


def _dot(lhs, rhs, contract=((1,), (0,)), precision=None):
    """A product with float32 accumulation: ``contract`` names the lhs and
    rhs dimension summed over ((1, 1): rhs transposed; (0, 0): lhs)."""
    return lax.dot_general(lhs, rhs, dimension_numbers=(contract, ((), ())),
                           preferred_element_type=_F32, precision=precision)


_NT, _TN = ((1,), (1,)), ((0,), (0,))


def _exact(lhs, rhs):
    """The product of a float32 array and a 0 / 1 matrix (bf16) to float32's
    own precision in ONE pass of the MXU: the float32 side as three bf16
    parts (8 + 8 + 8 bits of mantissa) side by side along the contraction,
    the 0 / 1 side repeated.  Running sums and sums over a head's lanes are
    such products: no cross-lane reduction, no ``reduce-window``."""
    def parts(v):
        hi = v.astype(jnp.bfloat16)
        rest = v - hi.astype(_F32)
        mid = rest.astype(jnp.bfloat16)
        return [hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)]

    if lhs.dtype == _F32:
        return _dot(jnp.concatenate(parts(lhs), axis=1), jnp.concatenate([rhs] * 3, axis=0))
    return _dot(jnp.concatenate([lhs] * 3, axis=1), jnp.concatenate(parts(rhs), axis=0))


class _Chunk(NamedTuple):
    """What forward and backward both rebuild of a (batch, group, chunk)."""

    seen: jax.Array         # (Q, Q) s <= t
    down: jax.Array         # (Q, R) the log-decays' running sums, positions down the rows
    along: jax.Array        # (R, Q) the same sums, positions along the lanes
    scores: jax.Array       # (Q, Q) C_t · B_s, the group's
    kept: jax.Array         # (R, N) exp(cum_Q), along the state's lanes


def _grid(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _one(hit):
    return jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)


def _chunk_blocks(lac_ref, lar_ref, b_ref, c_ref):
    """The running sums as triangular products, the scores, and every
    head's decay over the whole chunk."""
    q, per_group = lac_ref.shape[2:]
    rows, cols = _grid((q, q), 0), _grid((q, q), 1)
    down = _exact(_one(rows >= cols), lac_ref[0, 0])                                # Σ_{s<=t}
    along = _dot(lar_ref[0, 0], (rows <= cols).astype(_F32), precision=_EXACT)
    # Mosaic broadcasts along one axis at a time: the whole chunk's decay as rows of the state's width
    kept = jnp.exp(jnp.broadcast_to(along[:, q - 1:q], (per_group, b_ref.shape[2])))
    return _Chunk(rows >= cols, down, along, _dot(c_ref[0], b_ref[0], _NT), kept)


def _decay(chunk, r):
    """Head r's (Q, Q) block exp(cum_t - cum_s), zero above the diagonal."""
    q = chunk.seen.shape[0]
    return jnp.exp(jnp.where(chunk.seen, jnp.broadcast_to(chunk.down[:, r:r + 1], (q, q)) - chunk.along[r:r + 1, :],
                             -jnp.inf))


def _by_head(values, shape, axis):
    """One array of ``shape`` that is ``values[i]`` on head i's stretch of
    ``axis``: a slab's one head, or its two side by side."""
    if len(values) == 1:
        return jnp.broadcast_to(values[0], shape)
    first, second = values
    return jnp.where(_grid(shape, axis) < _HEAD_DIM, first, second)


class _Slab(NamedTuple):
    """``per_slab`` heads side by side: a lane tile of columns (two heads of
    64), so that no load, store or product works on half a tile."""

    heads: range
    at: slice               # the slab's columns of x, its rows of the state
    x: jax.Array            # (Q, W) float32
    dt: jax.Array           # (Q, W) each head's steps on its lanes
    xdt: jax.Array          # (Q, W) Δ x
    since_start: jax.Array  # (Q, W) exp(cum_t)
    to_end: jax.Array       # (Q, W) exp(cum_Q - cum_t)
    kept: jax.Array         # (W, N) exp(cum_Q) on each head's rows of the state
    pick: object            # per-head (Q, W) results -> the slab's


def _slab_blocks(j, chunk, x_ref, dtc_ref):
    q, per_slab = x_ref.shape[1], _per_slab(dtc_ref.shape[3])
    width = per_slab * _HEAD_DIM
    heads = range(j * per_slab, (j + 1) * per_slab)
    at = slice(j * width, (j + 1) * width)
    pick = lambda values: _by_head(values, (q, width), 1)
    column = lambda m, r: jnp.broadcast_to(m[:, r:r + 1], (q, width))
    down = pick([column(chunk.down, r) for r in heads])
    dt = pick([column(dtc_ref[0, 0], r) for r in heads])
    x = x_ref[0, :, at].astype(_F32)
    kept = _by_head([chunk.kept[r:r + 1] for r in heads], (width, chunk.kept.shape[1]), 0)
    if per_slab > 1:
        to_end = jnp.exp(down[q - 1:q] - down)
    else:       # one head: Mosaic would fold the row's and the column's broadcast into one it cannot do
        to_end = column(jnp.exp(chunk.down[q - 1:q] - chunk.down), heads[0])
    return _Slab(heads, at, x, dt, x * dt, jnp.exp(down), to_end, kept, pick)


def _fwd_kernel(x_ref, dtc_ref, lac_ref, lar_ref, b_ref, c_ref, y_ref, enter_ref, state):
    """Grid (batch, group, chunk), the chunks in sequence: ``state`` (R·P, N)
    float32 is the group's carried state; the state entering the chunk goes
    out too, for the backward."""
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    chunk = _chunk_blocks(lac_ref, lar_ref, b_ref, c_ref)
    for j in range(lac_ref.shape[3] // _per_slab(lac_ref.shape[3])):
        slab = _slab_blocks(j, chunk, x_ref, dtc_ref)
        entering = state[slab.at]                                                   # (W, N)
        enter_ref[0, 0, 0, slab.at] = entering
        xdt = slab.xdt.astype(dtype)
        # a head's weights times the whole slab's Δx: its own lanes are its y, the others are dropped
        y = slab.pick([_dot((chunk.scores * _decay(chunk, r)).astype(dtype), xdt) for r in slab.heads])
        y += slab.since_start * _dot(c_ref[0], entering.astype(dtype), _NT)
        y_ref[0, :, slab.at] = y.astype(dtype)
        state[slab.at] = slab.kept * entering + _dot((slab.xdt * slab.to_end).astype(dtype), b_ref[0], _TN)


def _bwd_kernel(x_ref, dtc_ref, lac_ref, lar_ref, b_ref, c_ref, enter_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dlac_ref, dlar_ref, ddx_ref, dstate, sums):
    """The same grid with the chunk axis walked from the last chunk:
    ``dstate`` (R·P, N) float32 is the cotangent of the state LEAVING the
    chunk.  ``dlac`` (Q, R) and ``dlar`` (R, Q) are two parts of the
    log-decays' gradient, in the layouts their sums come out in (the launcher
    adds them); ``ddx`` (Q, R) is ``Σ_p d(Δx) x``.

    A step's log-decay ``a_u`` scales every decay from the start exp(cum_t)
    with t >= u, every decay to the end with s < u, the kept state, and
    every weight L_ts with s < u <= t: Σ_{t>=u} of the weights' row sums
    less Σ_{s>=u} of their column sums.  ``sums`` (Q, 128) float32 holds,
    a head a lane, what is summed from t on in lanes [0, R), what is summed
    before u in [R, 2R), and ``ddx`` in [2R, 3R)."""
    dtype = x_ref.dtype
    q, per_group = lac_ref.shape[2:]
    per_slab = _per_slab(per_group)
    width = per_slab * _HEAD_DIM
    sums[...] = jnp.zeros_like(sums)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    chunk = _chunk_blocks(lac_ref, lar_ref, b_ref, c_ref)
    b, c = b_ref[0], c_ref[0]
    rows, cols = _grid((q, q), 0), _grid((q, q), 1)
    head_lane, head_column = _grid((1, _LANES), 1), _grid((q, _LANES), 1)
    dscores = jnp.zeros((q, q), _F32)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    for j in range(per_group // per_slab):
        slab = _slab_blocks(j, chunk, x_ref, dtc_ref)
        xdt = slab.xdt.astype(dtype)
        dy = dy_ref[0, :, slab.at]
        dy32, lane = dy.astype(_F32), _grid(dy.shape, 1)
        entering, leaving = enter_ref[0, 0, 0, slab.at], dstate[slab.at]            # (W, N) float32
        entering_c, leaving_c = entering.astype(dtype), leaving.astype(dtype)

        # inside the chunk: y = (scores ⊙ L)(Δx), a head at a time
        dxdts = []
        for i, r in enumerate(slab.heads):
            decay = _decay(chunk, r)
            weights = chunk.scores * decay
            mine = (lane >= i * _HEAD_DIM) & (lane < (i + 1) * _HEAD_DIM)
            dweights = _dot(jnp.where(mine, dy, jnp.zeros_like(dy)), xdt, _NT)      # (Q, Q), over the head's lanes
            dxdts.append(_dot(weights.astype(dtype), dy, _TN))                      # (Q, W)
            dscores += dweights * decay
            # a step moves the weights BELOW the diagonal only: the diagonal, the largest entries, stays out of
            # both sums and not just out of their difference
            moved = jnp.where(rows > cols, dweights * weights, 0.0)
            dlar_ref[0, 0, r:r + 1, :] = -jnp.sum(moved, axis=0, keepdims=True)
            sums[...] += jnp.where(head_column == r, jnp.sum(moved, axis=1, keepdims=True), 0.0)
        dxdt = slab.pick(dxdts)

        # what the entering state adds: y += exp(cum) ⊙ (C S_inᵀ)
        reach = slab.since_start * dy32                                             # (Q, W)
        dfrom_start = reach * _dot(c, entering_c, _NT)
        dc += _dot(reach.astype(dtype), entering_c)
        dentering = _dot(reach.astype(dtype), c, _TN)                               # (W, N)

        # the state leaving: kept · S_in + ((Δx) ⊙ to_end)ᵀ B
        dcarried = _dot(b, leaving_c, _NT) * slab.to_end                            # (Q, W)
        db += _dot((slab.xdt * slab.to_end).astype(dtype), leaving_c)
        dxdt += dcarried
        dx_ref[0, :, slab.at] = (dxdt * slab.dt).astype(dtype)

        # the three sums over a head's lanes in one product: lane l of the slab is head j·per_slab + l // P,
        # and each sum lands on its third of the lanes
        to_head = _by_head(list(slab.heads), (width, _LANES), 0)
        onto = jnp.concatenate([_one(_grid((width, _LANES), 1) == to_head + k * per_group) for k in range(3)], axis=0)
        sums[...] += _exact(jnp.concatenate([dfrom_start, dcarried * slab.xdt, dxdt * slab.x], axis=1), onto)
        # the kept state's decay is every step's: its gradient goes to the last row, which every u reaches
        rows_sum = _exact(leaving * entering, jnp.ones((entering.shape[1], _LANES), jnp.bfloat16))   # (W, 128)
        for i, r in enumerate(slab.heads):
            dkept = jnp.sum(rows_sum[i * _HEAD_DIM:(i + 1) * _HEAD_DIM], axis=0, keepdims=True)     # (1, 128)
            kept = jnp.exp(jnp.broadcast_to(chunk.along[r:r + 1, q - 1:q], (1, _LANES)))
            sums[q - 1:q, :] += jnp.where(head_lane == r, dkept * kept, 0.0)
        dstate[slab.at] = slab.kept * leaving + dentering

    dlar_ref[0, 0] = _dot(dlar_ref[0, 0], (rows >= cols).astype(_F32), precision=_EXACT)            # Σ_{s>=u}
    total = sums[...]
    third = lambda k: (head_column >= k * per_group) & (head_column < (k + 1) * per_group)
    dlac = _exact(jnp.concatenate([_one(rows <= cols), _one(rows > cols)], axis=1),                 # Σ_{t>=u}, Σ_{s<u}
                  jnp.concatenate([jnp.where(third(0), total, 0.0), jnp.where(third(1), total, 0.0)], axis=0))
    dlac_ref[0, 0] = dlac[:, :per_group] + dlac[:, per_group:2 * per_group]
    ddx_ref[0, 0] = total[:, 2 * per_group:3 * per_group]
    db_ref[0] = (db + _dot(dscores.astype(dtype), c, _TN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dscores.astype(dtype), b)).astype(dc_ref.dtype)


def _layouts(dt, a, groups):
    """The steps (B, G, T, R) and their log-decays in that layout and with
    the positions along the lanes (B, G, R, T): 2 MB each at the cell's
    sizes, against the 134 MB a float32 ``Δ x`` was."""
    bsz, t, h = dt.shape
    dtc = jnp.moveaxis(dt.reshape(bsz, t, groups, h // groups), 2, 1)
    lac = dtc * a.reshape(1, groups, 1, h // groups)
    return dtc, lac, jnp.swapaxes(lac, 2, 3)


def _specs(per_group, chunk, chunk_of):
    """Block specs of a (batch, group, chunk) grid step whose chunk is
    ``chunk_of(ci)``: x-like (B, T, H·P), the steps' two layouts, b-like
    (B, T, G·N), the entering states (B, G, T/Q, R·P, N)."""
    slab = pl.BlockSpec((1, chunk, per_group * _HEAD_DIM), lambda bi, gi, ci: (bi, chunk_of(ci), gi))
    down = pl.BlockSpec((1, 1, chunk, per_group), lambda bi, gi, ci: (bi, gi, chunk_of(ci), 0))
    along = pl.BlockSpec((1, 1, per_group, chunk), lambda bi, gi, ci: (bi, gi, 0, chunk_of(ci)))
    shared = pl.BlockSpec((1, chunk, _LANES), lambda bi, gi, ci: (bi, chunk_of(ci), gi))
    states = pl.BlockSpec((1, 1, 1, per_group * _HEAD_DIM, _LANES),
                          lambda bi, gi, ci: (bi, gi, chunk_of(ci), 0, 0))
    return slab, down, along, shared, states


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                               vmem_limit_bytes=_VMEM_BYTES)


# Jitted on their own, as the flash launchers are: a model's mixers trace and
# lower the pair once a program, whatever retraces the step.
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _ssd_fwd(x, dt, a, b, c, groups, chunk, interpret):
    """x (B, T, H·P); dt (B, T, H) and a (H,) float32; b, c (B, T, G·N) →
    y like x and the state entering every chunk (B, G, T/Q, R·P, N)."""
    bsz, t, _ = x.shape
    per_group = dt.shape[2] // groups
    slab, down, along, shared, states = _specs(per_group, chunk, lambda ci: ci)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, groups, t // chunk),
        in_specs=[slab, down, down, along, shared, shared],
        out_specs=[slab, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, groups, t // chunk, per_group * _HEAD_DIM, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((per_group * _HEAD_DIM, _LANES), _F32)],
        compiler_params=_PARAMS,
        name="ssd_fwd",
        interpret=interpret,
    )(x, *_layouts(dt, a, groups), b, c)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _ssd_bwd(x, dt, a, b, c, entering, dy, groups, chunk, interpret):
    """The cotangents of ``_ssd_fwd``'s five operands from ``dy``."""
    bsz, t, heads = dt.shape
    per_group, nc = heads // groups, t // chunk
    slab, down, along, shared, states = _specs(per_group, chunk, lambda ci: nc - 1 - ci)
    dtc, lac, lar = _layouts(dt, a, groups)
    dx, db, dc, dlac, dlar, ddx = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, groups, nc),
        in_specs=[slab, down, down, along, shared, shared, states, slab],
        out_specs=[slab, shared, shared, down, along, down],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), jax.ShapeDtypeStruct(dtc.shape, _F32),
                   jax.ShapeDtypeStruct(lar.shape, _F32), jax.ShapeDtypeStruct(dtc.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((per_group * _HEAD_DIM, _LANES), _F32), pltpu.VMEM((chunk, _LANES), _F32)],
        compiler_params=_PARAMS,
        name="ssd_bwd",
        interpret=interpret,
    )(x, dtc, lac, lar, b, c, entering, dy)
    # a = Δ A: both gradients from the log-decays', summed in float32
    dla = jnp.moveaxis(dlac + jnp.swapaxes(dlar, 2, 3), 1, 2).reshape(bsz, t, heads)
    ddt = dla * a + jnp.moveaxis(ddx, 1, 2).reshape(bsz, t, heads)
    return dx, ddt, jnp.sum(dla * dt, axis=(0, 1)), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd_pallas(x, dt, a, b, c, groups, chunk, interpret):
    return _ssd_fwd(x, dt, a, b, c, groups, chunk, interpret)[0]


def _ssd_pallas_fwd(x, dt, a, b, c, groups, chunk, interpret):
    y, entering = _ssd_fwd(x, dt, a, b, c, groups, chunk, interpret)
    return y, (x, dt, a, b, c, entering)


def _ssd_pallas_bwd(groups, chunk, interpret, saved, dy):
    return _ssd_bwd(*saved, dy, groups, chunk, interpret)


_ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


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
    plan = ssd_plan(t, h, g, p, n, chunk, x.dtype.itemsize)
    _plans_traced[plan.kind] = _plans_traced.get(plan.kind, 0) + 1
    if plan.kind == "xla":
        return _ssd_xla(x, dt, a, b, c, chunk)

    def kernels(x, dt, a, b, c):              # the call's operands, or one device's shard of them
        wide = lambda m: m.reshape(*m.shape[:2], -1)
        y = _ssd_pallas(wide(x), dt.astype(_F32), a.astype(_F32), wide(b), wide(c), b.shape[2], chunk, plan.interpret)
        return y.reshape(x.shape)

    # groups split over ``tensor`` where they divide; a group's heads stay together
    partition = _kernel_partition(bsz, g)
    if partition is None:
        return kernels(x, dt, a, b, c)
    mesh, spec = partition                    # (batch axes, None, tensor, None)
    return shard_map(kernels, mesh=mesh, in_specs=(spec, PartitionSpec(*spec[:3]), PartitionSpec(spec[2]), spec, spec),
                     out_specs=spec, check_vma=False)(x, dt, a, b, c)


def _ssd_xla(x, dt, a, b, c, chunk):
    """The ``xla`` form of the module docstring."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
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

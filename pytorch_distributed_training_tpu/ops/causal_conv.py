"""The Mamba-2 mixer's causal depthwise convolution and the SiLU after it,
over the time axis of a (B, T, C) stream:

    y[t, c] = silu(bias[c] + Σ_{j<K} w[j, c] · x[t - K + 1 + j, c])

with zeros before the sequence.  The stream is read and written in its own
dtype (bf16 in a mixed-precision step); the sum, the SiLU and, in the
backward, the pre-activation's cotangent are float32, as are ``w``, ``bias``
and their gradients.

Two forms of the same arithmetic, ``conv_plan``'s choice from the shapes and
the backend alone:

- ``pallas``: a Mosaic pair under one ``jax.custom_vjp`` (``causal_conv_fwd``,
  ``causal_conv_bwd``) on a grid (batch, channel tiles, time tiles), the time
  tiles in sequence.  A step walks its ``(T_tile, C_tile)`` block in chunks
  of (32, 128) that stay in vector registers (a ``pl.loop`` over the rows,
  traced and lowered once: as straight-line code the tile's 64 chunks cost
  every process 25 s of tracing and Mosaic lowering, which no compile cache
  holds): a chunk is widened to float32,
  the K taps are its sublanes rotated, each vreg's first rows taken from the
  vreg before (the 8 rows BEFORE the tile from a VMEM scratch that outlives
  the time axis), and ``silu(p) = h + h tanh(h)`` at ``h = p / 2`` comes
  from halved weights with one transcendental: one read and one write of the
  stream in HBM.  The backward walks the time tiles in REVERSE, recomputes
  the pre-activation from ``x`` (no residual but the inputs; the rows before
  a tile come as a 16-row block of their own, the walk having not been
  there), keeps the NEXT tile's first rows of ``dpre = dy · silu'(pre)`` in
  VMEM for ``dx[t] = Σ_j w[j] · dpre[t + K - 1 - j]``, and sums ``dw`` and
  ``dbias`` over the time tiles in a float32 scratch written once a channel
  tile: two reads, one write.  The stream may be a span of columns of a
  WIDER array (the mixer's in-projection ``[z | xBC | dt]``) and the result
  may leave as several arrays (``x``, ``B``, ``C``): block index maps do
  both, so no slice round the calls is a copy.  A Mosaic call is opaque to
  the partitioner: under a mesh of several devices the pair runs a shard of
  the batch inside a ``shard_map`` (``ops/attention._kernel_partition``, as
  the flash pair and ``ops/ssd``; the channels stay whole: a split over
  ``tensor`` would cut ``x``, ``B`` and ``C`` at other columns than the
  in-projection's).
- ``xla``: ``jnp.pad`` and K shifted slices in float32 with ``jax.grad``'s
  backward, where the channels, the column spans or the length miss the
  tiles, K > 8, or the backend is neither the TPU nor the CPU's interpreter;
  the toy sizes, and the tests' second opinion.
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ..compat import shard_map
from .attention import _kernel_partition

_LANES = 128
_EDGE = 8                             # rows a carry holds: a float32 tile's sublanes, >= K - 1
_HALO = 16                            # rows of the block before a tile: a packed bf16 tile's sublanes
_F32 = jnp.float32

# Every ``pl.pallas_call`` below carries one of these as ``name`` (the HLO
# instruction, and so the device trace's event, is ``%<name>.<n>``;
# ``obs/cost.mosaic_kernels`` counts them).
KERNEL_NAMES = ("causal_conv_fwd", "causal_conv_bwd")

_TIME_TILES = (512, 256, 128)
_CHANNEL_TILES = (512, 256, 128)
_CHUNK_ROWS = 32                      # rows of a 128-lane chunk: 4 vector registers an array


class ConvPlan(NamedTuple):
    """What ``causal_conv_silu`` does with one call's static facts."""

    kind: str           # "pallas" | "xla"
    why: str            # the first reason a shape was refused, or ""
    time_tile: int
    channel_tile: int
    interpret: bool     # the kernels under Pallas's interpreter: the CPU


# kind -> call sites traced with it since the process started (the
# ``conv_plan[kind=..]`` gauges)
_plans_traced: dict[str, int] = {}


def conv_plans_traced() -> dict[str, int]:
    """The ``conv_plan[kind=pallas|xla]`` gauges' values."""
    return dict(_plans_traced)


def conv_plan(seq_len: int, channels: int, kernel: int, itemsize: int, *, offset: int = 0,
              splits: tuple[int, ...] | None = None, backend: str | None = None) -> ConvPlan:
    """Which form a call takes, from its shapes and the backend alone, and
    the pair's tiles: the widest channel tile that divides the stream's
    column offset in its array and every piece it leaves in, the longest
    time tile that divides the length.  ``backend`` is
    ``jax.default_backend()`` unless given: the kernels run on the TPU and,
    interpreted, on the CPU."""
    backend = jax.default_backend() if backend is None else backend
    spans = (offset, *(splits or (channels,)))
    time_tile = next((t for t in _TIME_TILES if seq_len % t == 0), 0)
    channel_tile = next((c for c in _CHANNEL_TILES if all(s % c == 0 for s in spans)), 0)
    for ok, why in (
        (backend in ("tpu", "cpu"), f"backend {backend}"),
        (1 <= kernel <= _EDGE, f"kernel {kernel}: a carry holds {_EDGE} rows"),
        (channels % _LANES == 0, f"{channels} channels are no multiple of the {_LANES}-lane tile"),
        (channel_tile > 0, f"column spans {spans} share no multiple of the {_LANES}-lane tile"),
        (time_tile > 0, f"length {seq_len} is no multiple of the {_TIME_TILES[-1]}-row time tile"),
        (itemsize in (2, 4), f"elements of {itemsize} bytes"),
    ):
        if not ok:
            return ConvPlan("xla", why, 0, 0, False)
    return ConvPlan("pallas", "", time_tile, channel_tile, backend == "cpu")


def causal_conv(x, w, bias):
    """The ``xla`` form's sum: x (B, T, C), w (K, C), bias (C,) → ``bias +
    Σ_j w[j] · x[t - K + 1 + j]`` with zeros before the sequence, float32."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(_F32), ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(w[j] * padded[:, j:j + t] for j in range(k))


def _conv_xla(wide, w, bias, offset, splits):
    """The ``xla`` form of the module docstring."""
    y = jax.nn.silu(causal_conv(wide[..., offset:offset + w.shape[1]], w, bias)).astype(wide.dtype)
    return tuple(jnp.split(y, list(itertools.accumulate(splits[:-1])), axis=-1))


def _rows_before(prev, tile, shift):
    """``tile`` (n, 8, L) read ``shift`` rows early, ``prev`` (1, 8, L) the 8
    rows before it: every vreg's sublanes rotated, the first ``shift`` rows
    of each taken from the vreg before."""
    turned = pltpu.roll(jnp.concatenate([prev, tile], axis=0), shift, axis=1)
    early = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) < shift
    return jnp.where(early, turned[:-1], turned[1:])


def _rows_after(tile, following, shift):
    """``tile`` (n, 8, L) read ``shift`` rows late, ``following`` (1, 8, L) the
    8 rows after it."""
    turned = pltpu.roll(jnp.concatenate([tile, following], axis=0), _EDGE - shift, axis=1)
    late = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) >= _EDGE - shift
    return jnp.where(late, turned[1:], turned[:-1])


def _taps(x_ref, w_ref, b_ref, r, at, prev):
    """Rows [r, r + _CHUNK_ROWS) of the lanes ``at`` widened to (n, 8, 128)
    float32, the K taps (tap j the chunk K - 1 - j rows early, the rows
    before it from ``prev`` (1, 8, 128)) and HALF the pre-activation: the
    kernels are handed ``w / 2`` and ``bias / 2`` (exact), because ``silu(p)
    = h + h tanh(h)`` at ``h = p / 2`` is one transcendental and three
    float32 operations a value."""
    k = w_ref.shape[0]
    tile = x_ref[0, pl.ds(r, _CHUNK_ROWS), at].astype(_F32).reshape(-1, _EDGE, _LANES)
    taps = [_rows_before(prev, tile, k - 1 - j) for j in range(k - 1)] + [tile]
    half = b_ref[:, at][None]
    for j in range(k):
        half = half + w_ref[j:j + 1, at][None] * taps[j]
    return tile, taps, half


def _lane_groups(lanes):
    return [slice(l, l + _LANES) for l in range(0, lanes, _LANES)]


def _in_piece(piece):
    first, last = piece
    ci = pl.program_id(1)
    return (ci >= first) & (ci < last)


def _fwd_kernel(pieces, x_ref, w_ref, b_ref, *rest):
    """Grid (batch, channel tile, time tile), the time tiles in sequence; a
    tile in chunks of _CHUNK_ROWS rows (a loop: the body is traced and
    lowered once), a chunk a lane tile at a time.  ``tail`` (8, C_tile)
    float32 is the 8 rows before the chunk: the chunk before's last, across
    the tiles too.  ``pieces``: the (first, one past the last) channel tile
    of each array the result leaves in; a step writes the block of the piece
    it lies in (the others' block indices stand still meanwhile:
    ``_piece_map``)."""
    *y_refs, tail = rest
    rows, lanes = x_ref.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        tail[...] = jnp.zeros_like(tail)

    def tile_into(y_ref):
        @pl.loop(0, rows // _CHUNK_ROWS)
        def _chunk(i):
            r = pl.multiple_of(i * _CHUNK_ROWS, _CHUNK_ROWS)
            for at in _lane_groups(lanes):
                tile, _, half = _taps(x_ref, w_ref, b_ref, r, at, tail[:, at][None])
                y = half + half * jnp.tanh(half)
                y_ref[0, pl.ds(r, _CHUNK_ROWS), at] = y.reshape(_CHUNK_ROWS, _LANES).astype(y_ref.dtype)
                tail[:, at] = tile[-1:].reshape(_EDGE, _LANES)

    for y_ref, piece in zip(y_refs, pieces):
        pl.when(_in_piece(piece))(functools.partial(tile_into, y_ref))


def _bwd_kernel(pieces, x_ref, before_ref, w_ref, b_ref, *rest):
    """The same grid with the time axis walked from the last tile, and a
    tile's chunks from its last: ``head`` (8, C_tile) float32 is the first
    rows of (twice) ``dpre`` of the chunk AFTER, across the tiles too;
    ``before_ref`` the 16 rows of ``x`` before the tile (zeros before the
    sequence); ``sums`` (K + 1, 8, C_tile) float32 holds ``dw``'s K rows
    and ``dbias``, each summed over rows 8 apart, over the time tiles."""
    dy_refs, (dx_ref, dw_ref, db_ref, head, sums) = rest[:len(pieces)], rest[len(pieces):]
    k = w_ref.shape[0]
    rows, lanes = x_ref.shape[1:]
    chunks = rows // _CHUNK_ROWS
    ti, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(ti == 0)
    def _start():
        head[...] = jnp.zeros_like(head)
        sums[...] = jnp.zeros_like(sums)

    def tile_from(dy_ref):
        @pl.loop(0, chunks)
        def _chunk(i):
            r = pl.multiple_of((chunks - 1 - i) * _CHUNK_ROWS, _CHUNK_ROWS)
            for at in _lane_groups(lanes):
                # the 8 rows before the chunk: the tile's own, or at its top the block before it (zeros at t < 0)
                inside = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(r - _HALO, 0), _HALO), _HALO), at].astype(_F32)
                outside = jnp.where(ti == last, 0.0, before_ref[0, :, at].astype(_F32))
                prev = jnp.where(r == 0, outside, inside)[_HALO - _EDGE:][None]
                _, taps, half = _taps(x_ref, w_ref, b_ref, r, at, prev)
                # silu'(p) = sigmoid(p) (1 + p (1 - sigmoid(p))) = (1 + t) (1 + h - h t) / 2 at t = tanh(h); the half
                # is the halved weights' in dx and the last step's in dw and dbias: ``dpre`` is TWICE the cotangent
                tanh = jnp.tanh(half)
                dy = dy_ref[0, pl.ds(r, _CHUNK_ROWS), at].astype(_F32).reshape(-1, _EDGE, _LANES)
                dpre = dy * ((1.0 + tanh) * (1.0 + (half - half * tanh)))
                following = head[:, at][None]
                dx = w_ref[k - 1:k, at][None] * dpre
                for j in range(k - 1):
                    dx = dx + w_ref[j:j + 1, at][None] * _rows_after(dpre, following, k - 1 - j)
                dx_ref[0, pl.ds(r, _CHUNK_ROWS), at] = dx.reshape(_CHUNK_ROWS, _LANES).astype(dx_ref.dtype)
                for j in range(k):
                    sums[j, :, at] += jnp.sum(dpre * taps[j], axis=0)
                sums[k, :, at] += jnp.sum(dpre, axis=0)
                head[:, at] = dpre[:1].reshape(_EDGE, _LANES)

    for dy_ref, piece in zip(dy_refs, pieces):
        pl.when(_in_piece(piece))(functools.partial(tile_from, dy_ref))

    @pl.when(ti == last)
    def _finish():
        dw_ref[0] = 0.5 * jnp.sum(sums[:k], axis=1)
        db_ref[0] = 0.5 * jnp.sum(sums[k], axis=0, keepdims=True)


def _pieces(splits, channel_tile):
    edges = [0, *itertools.accumulate(s // channel_tile for s in splits)]
    return tuple(zip(edges[:-1], edges[1:]))


def _piece_map(piece, time_of, tiles):
    """The block index map of one piece's array: the step's own block while
    its channel tile lies in the piece; before that the first block the walk
    will write and after it the last one it wrote, so the index stands still
    and Pallas neither fetches nor writes back a block no step filled."""
    first, last = piece

    def index(bi, ci, ti):
        inside, after = (ci >= first) & (ci < last), ci >= last
        time = jnp.where(inside, time_of(ti), jnp.where(after, time_of(tiles - 1), time_of(0)))
        return bi, time, jnp.clip(ci - first, 0, last - first - 1)
    return index


# (the channel tiles in sequence too: a piece's block index stands still only between neighbouring steps)
_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


# Jitted on their own, as the flash and ``ssd`` launchers are: a model's
# mixers trace and lower the pair once a program.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _conv_fwd(wide, w, bias, offset, splits, time_tile, channel_tile, interpret):
    """wide (B, T, W) with the stream at columns [offset, offset + C); w (K,
    C) and bias (C,) float32 → the result as ``len(splits)`` arrays."""
    bsz, t, _ = wide.shape
    k, channels = w.shape
    tiles, pieces = t // time_tile, _pieces(splits, channel_tile)
    block = (1, time_tile, channel_tile)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, pieces),
        grid=(bsz, channels // channel_tile, tiles),
        in_specs=[pl.BlockSpec(block, lambda bi, ci, ti: (bi, ti, offset // channel_tile + ci)),
                  pl.BlockSpec((k, channel_tile), lambda bi, ci, ti: (0, ci)),
                  pl.BlockSpec((1, channel_tile), lambda bi, ci, ti: (0, ci))],
        out_specs=[pl.BlockSpec(block, _piece_map(piece, lambda ti: ti, tiles)) for piece in pieces],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, width), wide.dtype) for width in splits],
        scratch_shapes=[pltpu.VMEM((_EDGE, channel_tile), _F32)],
        compiler_params=_PARAMS,
        name="causal_conv_fwd",
        interpret=interpret,
    )(wide, 0.5 * w, 0.5 * bias.reshape(1, channels))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _conv_bwd(wide, w, bias, dys, offset, splits, time_tile, channel_tile, interpret):
    """The stream's cotangent (B, T, C) in ``wide``'s dtype and those of
    ``w`` and ``bias`` in float32, from the pieces' cotangents."""
    bsz, t, _ = wide.shape
    k, channels = w.shape
    tiles, pieces = t // time_tile, _pieces(splits, channel_tile)
    at = offset // channel_tile
    back = lambda ti: tiles - 1 - ti
    block = (1, time_tile, channel_tile)
    per_tile = time_tile // _HALO
    dx, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, pieces),
        grid=(bsz, channels // channel_tile, tiles),
        in_specs=[pl.BlockSpec(block, lambda bi, ci, ti: (bi, back(ti), at + ci)),
                  pl.BlockSpec((1, _HALO, channel_tile),
                               lambda bi, ci, ti: (bi, jnp.maximum(back(ti) * per_tile - 1, 0), at + ci)),
                  pl.BlockSpec((k, channel_tile), lambda bi, ci, ti: (0, ci)),
                  pl.BlockSpec((1, channel_tile), lambda bi, ci, ti: (0, ci)),
                  *(pl.BlockSpec(block, _piece_map(piece, back, tiles)) for piece in pieces)],
        out_specs=[pl.BlockSpec(block, lambda bi, ci, ti: (bi, back(ti), ci)),
                   pl.BlockSpec((1, k, channel_tile), lambda bi, ci, ti: (bi, 0, ci)),
                   pl.BlockSpec((1, 1, channel_tile), lambda bi, ci, ti: (bi, 0, ci))],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, channels), wide.dtype),
                   jax.ShapeDtypeStruct((bsz, k, channels), _F32),
                   jax.ShapeDtypeStruct((bsz, 1, channels), _F32)],
        scratch_shapes=[pltpu.VMEM((_EDGE, channel_tile), _F32),
                        pltpu.VMEM((k + 1, _EDGE, channel_tile), _F32)],
        compiler_params=_PARAMS,
        name="causal_conv_bwd",
        interpret=interpret,
    )(wide, wide, 0.5 * w, 0.5 * bias.reshape(1, channels), *dys)
    return dx, jnp.sum(dw, axis=0), jnp.sum(db, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _conv_pallas(wide, w, bias, offset, splits, time_tile, channel_tile, interpret):
    return tuple(_conv_fwd(wide, w, bias, offset, splits, time_tile, channel_tile, interpret))


def _conv_pallas_fwd(wide, w, bias, offset, splits, time_tile, channel_tile, interpret):
    return _conv_pallas(wide, w, bias, offset, splits, time_tile, channel_tile, interpret), (wide, w, bias)


def _conv_pallas_bwd(offset, splits, time_tile, channel_tile, interpret, saved, dys):
    wide, w, bias = saved
    dx, dw, db = _conv_bwd(wide, w, bias, tuple(dys), offset, splits, time_tile, channel_tile, interpret)
    after = wide.shape[2] - offset - w.shape[1]
    return jnp.pad(dx, ((0, 0), (0, 0), (offset, after))), dw, db


_conv_pallas.defvjp(_conv_pallas_fwd, _conv_pallas_bwd)


def causal_conv_silu(wide, w, bias, *, offset: int = 0, splits: tuple[int, ...] | None = None):
    """``silu(causal_conv(stream, w, bias))`` in ``wide``'s dtype, where the
    stream is columns [offset, offset + C) of wide (B, T, W), w (K, C) and
    bias (C,) float32: a tuple of arrays ``splits`` wide each, side by side
    the whole result (one array of C unless given)."""
    bsz, t, _ = wide.shape
    k, channels = w.shape
    splits = tuple(splits or (channels,))
    if sum(splits) != channels or offset + channels > wide.shape[2]:
        raise ValueError(f"pieces {splits} of {channels} channels at column {offset} of {wide.shape[2]}")
    plan = conv_plan(t, channels, k, wide.dtype.itemsize, offset=offset, splits=splits)
    _plans_traced[plan.kind] = _plans_traced.get(plan.kind, 0) + 1
    if plan.kind == "xla":
        return _conv_xla(wide, w, bias, offset, splits)

    def kernels(wide, w, bias):               # the call's operands, or one device's shard of the batch
        return _conv_pallas(wide, w.astype(_F32), bias.astype(_F32), offset, splits,
                            plan.time_tile, plan.channel_tile, plan.interpret)

    partition = _kernel_partition(bsz, 1)
    if partition is None:
        return kernels(wide, w, bias)
    mesh, spec = partition
    rows = PartitionSpec(spec[0], None, None)
    return shard_map(kernels, mesh=mesh, in_specs=(rows, PartitionSpec(), PartitionSpec()),
                     out_specs=(rows,) * len(splits), check_vma=False)(wide, w, bias)

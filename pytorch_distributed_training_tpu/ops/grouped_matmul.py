"""The held experts' matrix products over a pass's SORTED rows
(``models/moe.held_experts``): row ``r`` of ``lhs`` belongs to the group
(expert) whose run of ``group_sizes`` it falls in, and

    gmm    out[r]  = lhs[r] · W[group(r)]            (or · W[group(r)]^T: the data gradient)
    tgmm   dW[e]   = Σ_{r in group e} lhs[r]^T rhs[r]   (the weight gradient, a pass at a time)

Rows past the last group belong to no one: ``gmm`` leaves them unwritten
(as ``lax.ragged_dot`` does), ``tgmm`` does not read them.  Operands in the
rows' dtype (bf16 in a mixed-precision step), every sum float32.

Two forms of the same arithmetic, ``grouped_plan``'s choice from the shapes
and the backend alone:

- ``pallas``: three Mosaic kernels (``KERNEL_NAMES``: the names are roles)
  and, for the plain product, one ``jax.custom_vjp``.  ``group_sizes``
  becomes a table of VISITS, scalar-prefetched: a row tile is visited once
  for each group with rows in it, in sorted order, so the grid's visit axis
  is ``tiles + groups - 1`` long at most and the visits past the last live
  row do nothing — their block indices stand still on the last visit's, so
  nothing is copied: the work follows the LIVE rows.  A group's matrix is a
  whole block whose index is the group: it stays in VMEM across that group's
  tiles and is read from HBM once a pass.  Inside a visit the tile goes in
  runs of ``_SUB_ROWS`` rows, a run with none of the group's rows skipped and
  one that straddles a boundary stored (``gmm``) or read (``tgmm``) under a
  row mask, so a boundary costs a run and not a tile.

  ``gmm`` takes SEVERAL products over the same rows — of one left operand
  or of one each — and an ``epilogue`` that turns a run's float32 products
  and the rows' own operands into the results while the run is in VMEM
  (``grouped_products``): an expert's gate, activation and router weight,
  and in the backward their derivatives and a sum a row, never go to HBM
  between a product and what follows it, nor run over a pass's dead rows.
  The data gradient is the same kernel contracting over the matrix's OTHER
  axis (the block is the untransposed stack's: no ``W^T`` exists).

  ``tgmm`` keeps the group's ``(d_in, d_out)`` float32 sum in VMEM across
  the group's tiles and, at the group's last row, rounds it into the group's
  block of the stack gradient (``input_output_aliases``: the stack is in the
  matrices' dtype and every block is written once).  Sorted rows end group
  by group, so between two passes of ``held_experts``' loop only ONE group
  is unfinished: its float32 block is the carry (copied by hand from and to
  HBM, when there is one), not the whole stack; a group with no rows in the
  pass is not touched, and no stack-wide add, zero fill or cast follows.

  The COMBINE (``add_rows``: a pass's rows added to their tokens' rows, and
  in the backward the rows' gradients to the tokens') multiplies nothing but
  walks the same sorted rows: the tokens' float32 sums stay in VMEM a column
  tile at a time while the pass's live row tiles stream by, where XLA's
  scatter-add takes a row at a time from HBM, dead rows too.

  A Mosaic call is opaque to the partitioner: under a mesh of several
  devices each device runs the whole product inside a ``shard_map`` (sorted
  rows have no batch axis to split; ``ops/attention._kernel_partition``'s
  reason).
- ``xla``: ``lax.ragged_dot`` (on a TPU XLA's own grouped matmul) and its
  transposes, the epilogue on whole arrays, where a width is no multiple of
  the lane tile (Nemotron-H's 1856, the toy sizes), the rows are no multiple
  of the row tile, a group's matrix does not fit VMEM, or the backend is
  neither the TPU nor the CPU's interpreter; and the tests' second opinion.
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
_F32 = jnp.float32

# Every ``pl.pallas_call`` below carries one of these as ``name``, by role.
# The HLO instruction, and so the device trace's event, is ``%<name>.<n>``:
# the hyphens are XLA's own spelling of ``lax.ragged_dot``'s Mosaic call
# (``%ragged-dot-none``), which ``kernel.ragged_dot_roofline.train`` and
# ``moe.grouped_matmul_share.train`` read (``^%ragged-dot[\w.\-]* = …``) and
# ``obs/cost.mosaic_kernels`` counts.
KERNEL_NAMES = ("ragged-dot-held-fwd", "ragged-dot-held-refwd", "ragged-dot-held-dgrad", "ragged-dot-held-wgrad",
                "held-rows-add")
# the forward's products; the backward's second run of them; products with the stacks' other axis; the stacks'
# gradients; and the combine, which multiplies nothing and so stays out of the products' name
FWD, REFWD, DGRAD, WGRAD, ADD = KERNEL_NAMES

_ROW_TILES = (512, 256, 128)          # rows of a visit's block: the longest that divides the pass
_SUB_ROWS = 128                       # rows of one product inside a visit: what a boundary costs
# scoped VMEM the kernels ask for, and what their blocks (twice over: the
# pipeline's two buffers) and temporaries may take of it
_VMEM_BYTES = 64 * 2**20
_VMEM_BLOCKS = 48 * 2**20


class GroupedPlan(NamedTuple):
    """What the grouped products do with one call's static facts."""

    kind: str           # "pallas" | "xla"
    why: str            # the first reason a shape was refused, or ""
    row_tile: int
    interpret: bool     # the kernels under Pallas's interpreter: the CPU


# kind -> products traced with it since the process started (the
# ``grouped_plan[kind=..]`` gauges)
_plans_traced: dict[str, int] = {}


def grouped_plans_traced() -> dict[str, int]:
    """The ``grouped_plan[kind=pallas|xla]`` gauges' values."""
    return dict(_plans_traced)


def _lane_tiles(width: int) -> list[int]:
    """The multiples of the lane tile that divide ``width``, widest first."""
    return [t for t in range(width, 0, -_LANES) if width % t == 0] if width % _LANES == 0 else []


def _column_tile(row_tile: int, lhs_widths, product_widths, d_out: int, wide: int, itemsize: int) -> int:
    """``gmm``'s widest tile of result columns whose blocks fit (each twice:
    the pipeline's two buffers): the row tile of every left operand whole in
    its width, a ``(width, tile)`` block of every product's matrix, ``wide``
    ``(row_tile, tile)`` blocks (the results and the rows' own operands of
    that width), and a run's float32 products and results."""
    for tile in _lane_tiles(d_out):
        blocks = 2 * itemsize * (row_tile * sum(lhs_widths) + sum(product_widths) * tile + wide * row_tile * tile)
        if blocks + 4 * _SUB_ROWS * tile * (len(product_widths) + wide) <= _VMEM_BLOCKS:
            return tile
    return 0


def _stack_tiles(row_tile: int, d_in: int, d_out: int, itemsize: int, stack_itemsize: int | None = None) -> tuple[int, int]:
    """``tgmm``'s largest ``(d_in, d_out)`` tile of a group's gradient whose
    blocks fit: the float32 block it sums in and a run's float32 product,
    the rounded block that leaves for the stack and both operands' row tiles
    (two buffers each)."""
    out = itemsize if stack_itemsize is None else stack_itemsize
    fits = [
        (tk, tn) for tk in _lane_tiles(d_in) for tn in _lane_tiles(d_out)
        if (2 * 4 + 2 * out) * tk * tn + 2 * itemsize * row_tile * (tk + tn) <= _VMEM_BLOCKS
    ]
    return max(fits, key=lambda t: (t[0] * t[1], t[1]), default=(0, 0))


def _layer_fits(row_tile: int, width: int, inner: int, itemsize: int) -> bool:
    """Whether the widest calls an expert layer makes fit with their columns
    whole (``models/moe.py``; a product is planned alike whichever way it
    goes through the layer): two products of one operand into the experts'
    ``inner`` width with five more blocks that wide, two products of two
    operands back into the model's ``width``, and a group's gradient block."""
    return (_column_tile(row_tile, (width,), (width, width), inner, 5, itemsize) == inner
            and _column_tile(row_tile, (inner, inner), (inner, inner), width, 1, itemsize) == width
            and all(_stack_tiles(row_tile, width, inner, itemsize)))


def _planned_from(rows: int, dtype, backend, shapes_fit) -> GroupedPlan:
    """The plan of one kernel family: ``pallas`` where the backend is the TPU
    (or, interpreted, the CPU), the elements bf16 or float32, the rows whole
    row tiles, and ``shapes_fit(row_tile)``'s ``(holds, why not)`` all hold;
    else ``xla`` with the first reason."""
    backend = jax.default_backend() if backend is None else backend
    row_tile = next((t for t in _ROW_TILES if rows > 0 and rows % t == 0), 0)
    for ok, why in (
        (backend in ("tpu", "cpu"), f"backend {backend}"),
        (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)), f"elements of {jnp.dtype(dtype).name}"),
        (row_tile > 0, f"{rows} rows are no multiple of the {_ROW_TILES[-1]}-row tile"),
        *(shapes_fit(row_tile) if row_tile else ()),
    ):
        if not ok:
            return GroupedPlan("xla", why, 0, False)
    return GroupedPlan("pallas", "", row_tile, backend == "cpu")


def grouped_plan(rows: int, d_in: int, d_out: int, groups: int, dtype, *,
                 backend: str | None = None) -> GroupedPlan:
    """Which form the products of one pass take — ``rows`` sorted rows, the
    ``groups`` matrices ``(d_in, d_out)`` and their transposes — from the
    shapes and the backend alone, and the rows' tile.  ``backend`` is
    ``jax.default_backend()`` unless given: the kernels run on the TPU and,
    interpreted, on the CPU."""
    itemsize = jnp.dtype(dtype).itemsize
    return _planned_from(rows, dtype, backend, lambda row_tile: (
        (groups >= 1, f"{groups} groups"),
        (d_in % _LANES == 0 and d_out % _LANES == 0,
         f"widths {d_in} x {d_out} are no multiples of the {_LANES}-lane tile"),
        (_layer_fits(row_tile, d_in, d_out, itemsize) or _layer_fits(row_tile, d_out, d_in, itemsize),
         f"a group's {d_in} x {d_out} matrix leaves no block that fits VMEM"),
    ))


def _visits(sizes, tiles: int, row_tile: int):
    """The scalar-prefetched table of a pass: ``(group, tile, starts, ends,
    count)``.  Visit ``s < count`` multiplies the rows of ``group[s]`` that
    lie in row tile ``tile[s]``; the groups in ascending order, a group's
    tiles in ascending order, a tile that holds rows of several groups once
    for each.  ``starts`` / ``ends`` are each group's first row and one past
    its last.  The visits from ``count`` on (``tiles + groups - 1`` is the
    most there can be) repeat the last one's indices."""
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    per = jnp.where(sizes > 0, (ends - 1) // row_tile - starts // row_tile + 1, 0)   # tiles a group has rows in
    through = jnp.cumsum(per)                                                        # visits through each group's last
    count = through[-1]
    at = jnp.minimum(jnp.arange(tiles + groups - 1, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(through, at, side="right"), groups - 1).astype(jnp.int32)
    tile = jnp.clip(starts[group] // row_tile + at - (through[group] - per[group]), 0, tiles - 1)
    return group, tile.astype(jnp.int32), starts, ends, count[None]


def _runs(step, group_of, tile_of, starts, ends, rows, visit):
    """The runs of ``_SUB_ROWS`` rows of visit ``step`` that hold rows of its
    group: ``visit(r, mine)`` for each, ``r`` the run's first row in the
    tile and ``mine`` (run, 1) which of its rows are the group's.  A loop:
    the body is traced and lowered once."""
    sub = min(_SUB_ROWS, rows)
    g = group_of[step]
    base = tile_of[step] * rows
    lo, hi = starts[g] - base, ends[g] - base       # the group's rows in the tile's numbering

    @pl.loop(0, rows // sub)
    def _run(i):
        r = pl.multiple_of(i * sub, sub)

        @pl.when((r < hi) & (r + sub > lo))
        def _live():
            at = r + lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            visit(pl.ds(r, sub), (at >= lo) & (at < hi))


def _gmm_kernel(lhs_of, extras, epilogue, transposed, group_of, tile_of, starts, ends, count, *refs):
    """Grid (column tiles, visits).  ``refs``: the left operands' row tiles;
    a block of the visit's group's matrix for each product — ``(1, d_in,
    tile)``, or transposed ``(1, tile, d_in)`` and contracted over its last
    axis —, product ``p`` of operand ``lhs_of[p]``; ``extras`` blocks of the
    rows' own operands; the results.  A run's float32 products and its rows'
    operands go through ``epilogue`` and each result is stored under the
    group's rows: the tile's blocks stay in VMEM across its visits (the
    index does not change), so every group's rows are there when they are
    written back."""
    lhs_refs, refs = refs[:max(lhs_of) + 1], refs[max(lhs_of) + 1:]
    w_refs, extra_refs, out_refs = refs[:len(lhs_of)], refs[len(lhs_of):len(lhs_of) + extras], refs[len(lhs_of) + extras:]
    step = pl.program_id(1)

    @pl.when(step < count[0])
    def _visit():
        def run(at, mine):
            rows = [ref[at, :] for ref in lhs_refs]
            products = [lax.dot_general(rows[l], w[0], (((1,), (1 if transposed else 0,)), ((), ())),
                                        preferred_element_type=_F32) for l, w in zip(lhs_of, w_refs)]
            results = epilogue(products, [ref[at, :].astype(_F32) for ref in extra_refs])
            for ref, result in zip(out_refs, results):
                ref[at, :] = jnp.where(mine, result, ref[at, :].astype(_F32)).astype(ref.dtype)

        _runs(step, group_of, tile_of, starts, ends, lhs_refs[0].shape[0], run)


def _wgrad_kernel(group_of, tile_of, starts, ends, count, flags, lhs_ref, rhs_ref, stack_in, carry_in,
                  stack_ref, carry_out, acc, sem):
    """Grid (``d_in`` tiles, ``d_out`` tiles, visits).  ``acc`` (tk, tn)
    float32 in VMEM is the visit's group's block of the gradient: zero at the
    group's first visit — the CARRIED block where the pass's first group
    began in the pass before (``flags[0]``) — then every run's ``lhs^T rhs``
    (the rows of other groups, and past the last, zeroed on both sides: what
    ``gmm`` left unwritten may be anything).  At the group's last visit it is
    rounded into ``stack_ref``, the group's block of the stack (written back
    when the group changes: a group with no rows in the pass has no visit
    and keeps what the aliased stack held); where the pass's last group goes
    on in the next pass (``flags[1]``) the float32 block leaves as the carry
    too.  The carry lives in HBM and is copied by hand, the two times it is
    wanted."""
    step = pl.program_id(2)
    g, last = group_of[step], count[0] - 1
    tk, tn = acc.shape
    block = (pl.ds(pl.multiple_of(pl.program_id(0) * tk, tk), tk), pl.ds(pl.multiple_of(pl.program_id(1) * tn, tn), tn))

    def copy(src, dst):
        move = pltpu.make_async_copy(src, dst, sem)
        move.start()
        move.wait()

    # (a pass with no row has no visit, and the block its indices rest on is written back all the same: as it was)
    pl.when((step == 0) & (last < 0))(lambda: copy(stack_in.at[g].at[block], stack_ref.at[0]))

    @pl.when(step <= last)
    def _visit():
        @pl.when((step == 0) | (group_of[jnp.maximum(step - 1, 0)] != g))
        def _start():
            acc[...] = jnp.zeros_like(acc)

        pl.when((step == 0) & (flags[0] == 1))(lambda: copy(carry_in.at[block], acc))

        def run(at, mine):
            lhs, rhs = lhs_ref[at, :], rhs_ref[at, :]
            acc[...] += lax.dot_general(
                jnp.where(mine, lhs, jnp.zeros_like(lhs)), jnp.where(mine, rhs, jnp.zeros_like(rhs)),
                (((0,), (0,)), ((), ())), preferred_element_type=_F32)

        _runs(step, group_of, tile_of, starts, ends, lhs_ref.shape[0], run)

        @pl.when((step == last) | (group_of[jnp.minimum(step + 1, group_of.shape[0] - 1)] != g))
        def _finish():
            stack_ref[0] = acc[...].astype(stack_ref.dtype)

        pl.when((step == last) & (flags[1] == 1))(lambda: copy(acc, carry_out.at[block]))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_BYTES)


def _product_alone(products, extras):
    return products


# Jitted on their own, as the flash, ``ssd`` and convolution launchers are: a
# model's expert layers trace and lower each kernel once a program.
@functools.partial(jax.jit, static_argnames=("lhs_of", "epilogue", "outs", "transposed", "row_tile", "name", "interpret"))
def _gmm_call(lhs, ws, extras, sizes, *, lhs_of, epilogue, outs, transposed, row_tile, name, interpret):
    """lhs: the left operands, (R, d_in) each; ws: a stack for each product,
    (G, d_in, d_out) or transposed (G, d_out, d_in), product ``p`` of
    ``lhs[lhs_of[p]]``; extras: the rows' own operands, (R, d_out) or (R, 1);
    sizes (G,) int32; ``epilogue(products, extras)`` → the results, float32
    (run, d_out) or (run, 1) as ``outs`` says: a ``(dtype name, narrow)``
    each → the results, the rows past the last group unwritten."""
    rows = lhs[0].shape[0]
    d_out = ws[0].shape[1] if transposed else ws[0].shape[2]
    wide = sum(not narrow for _, narrow in outs) + sum(e.shape[1] == d_out for e in extras)
    tile = _column_tile(row_tile, [l.shape[1] for l in lhs], [lhs[l].shape[1] for l in lhs_of], d_out, wide,
                        lhs[0].dtype.itemsize)
    if tile != d_out and wide != len(outs) + len(extras):
        raise ValueError(f"{name}: a result a row wants the {d_out} columns whole, and {tile} fit")
    tiles = rows // row_tile
    by_row = lambda width: pl.BlockSpec((row_tile, tile if width == d_out else width),
                                        lambda n, s, group, at, *_: (at[s], n if width == d_out else 0))
    w_spec = lambda w: (pl.BlockSpec((1, tile, w.shape[2]), lambda n, s, group, *_: (group[s], n, 0)) if transposed else
                        pl.BlockSpec((1, w.shape[1], tile), lambda n, s, group, *_: (group[s], 0, n)))
    results = pl.pallas_call(
        functools.partial(_gmm_kernel, lhs_of, len(extras), epilogue, transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(d_out // tile, tiles + ws[0].shape[0] - 1),
            in_specs=[*(pl.BlockSpec((row_tile, l.shape[1]), lambda n, s, group, at, *_: (at[s], 0)) for l in lhs),
                      *(w_spec(w) for w in ws), *(by_row(e.shape[1]) for e in extras)],
            out_specs=[by_row(1 if narrow else d_out) for _, narrow in outs],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, 1 if narrow else d_out), jnp.dtype(kind)) for kind, narrow in outs],
        compiler_params=_params("parallel", "arbitrary"),
        name=name,
        interpret=interpret,
    )(*_visits(sizes, tiles, row_tile), *lhs, *ws, *extras)
    return tuple(results)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _wgrad_call(lhs, rhs, sizes, stack, carry, flags, row_tile, interpret):
    """lhs (R, d_in), rhs (R, d_out), sizes (G,) int32, stack (G, d_in,
    d_out), carry (d_in, d_out) float32, flags (2,) int32 → ``(stack,
    carry)`` in their buffers: :func:`grouped_weight_grad`'s."""
    rows, d_in = lhs.shape
    d_out = rhs.shape[1]
    tk, tn = _stack_tiles(row_tile, d_in, d_out, lhs.dtype.itemsize, stack.dtype.itemsize)
    tiles = rows // row_tile
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _wgrad_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(d_in // tk, d_out // tn, tiles + stack.shape[0] - 1),
            in_specs=[pl.BlockSpec((row_tile, tk), lambda k, n, s, group, at, *_: (at[s], k)),
                      pl.BlockSpec((row_tile, tn), lambda k, n, s, group, at, *_: (at[s], n)),
                      anywhere, anywhere],
            out_specs=[pl.BlockSpec((1, tk, tn), lambda k, n, s, group, *_: (group[s], k, n)), anywhere],
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct(stack.shape, stack.dtype), jax.ShapeDtypeStruct(carry.shape, _F32)],
        input_output_aliases={8: 0, 9: 1},    # after the table's five, the flags and the two operands
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name=WGRAD,
        interpret=interpret,
    )(*_visits(sizes, tiles, row_tile), flags, lhs, rhs, stack, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, w, sizes, transposed, row_tile, interpret):
    return _gmm_call((lhs,), (w,), (), sizes, lhs_of=(0,), epilogue=_product_alone, outs=((lhs.dtype.name, False),),
                     transposed=transposed, row_tile=row_tile, name=DGRAD if transposed else FWD,
                     interpret=interpret)[0]


def _gmm_fwd(lhs, w, sizes, transposed, row_tile, interpret):
    return _gmm(lhs, w, sizes, transposed, row_tile, interpret), (lhs, w, sizes)


def _gmm_bwd(transposed, row_tile, interpret, saved, d_out):
    lhs, w, sizes = saved
    d_lhs = _gmm(d_out, w, sizes, not transposed, row_tile, interpret)
    pair = (d_out, lhs) if transposed else (lhs, d_out)
    d_w, _ = _wgrad_call(*pair, sizes, jnp.zeros_like(w), jnp.zeros(w.shape[1:], _F32), jnp.zeros((2,), jnp.int32),
                         row_tile, interpret)
    return d_lhs, d_w, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _whole_on_every_device(kernels, *operands):
    """``kernels(*operands)``, inside a ``shard_map`` with everything whole
    where the trace is under a mesh of several devices."""
    partition = _kernel_partition(1, 1)
    if partition is None:
        return kernels(*operands)
    whole = PartitionSpec()
    return shard_map(kernels, mesh=partition[0], in_specs=(whole,) * len(operands), out_specs=whole,
                     check_vma=False)(*operands)


def _planned(rows, d_in, d_out, groups, dtype):
    plan = grouped_plan(rows, d_in, d_out, groups, dtype)
    _plans_traced[plan.kind] = _plans_traced.get(plan.kind, 0) + 1
    return plan


def grouped_matmul(lhs, w, sizes, *, transposed: bool = False):
    """``out[r] = lhs[r] · w[group(r)]`` over sorted rows: lhs (R, d_in), w
    (G, d_in, d_out), sizes (G,) int32 with ``sum(sizes) <= R`` → (R, d_out)
    in ``lhs``'s dtype; the rows past the last group hold anything.
    ``transposed``: w is (G, d_out, d_in) and each row meets its group's
    matrix transposed (the data gradient of the plain product).
    Differentiable in ``lhs`` and ``w``."""
    d_in, d_out = (w.shape[2], w.shape[1]) if transposed else w.shape[1:]
    plan = _planned(lhs.shape[0], d_in, d_out, w.shape[0], lhs.dtype)
    if plan.kind == "xla":
        return lax.ragged_dot(lhs, jnp.swapaxes(w, 1, 2) if transposed else w, sizes)
    return _whole_on_every_device(
        lambda lhs, w, sizes: _gmm(lhs, w.astype(lhs.dtype), sizes.astype(jnp.int32), transposed,
                                   plan.row_tile, plan.interpret), lhs, w, sizes)


def grouped_products(lhs, ws, sizes, epilogue, outs, *, extras=(), lhs_of=None, transposed: bool = False,
                     name: str = FWD):
    """Several grouped products over the same sorted rows and what follows
    them row by row, in one pass over the rows: ``products[p] =
    lhs[lhs_of[p]] · ws[p][group]`` (``lhs_of``: all of the first operand
    unless given) in float32, then ``epilogue(products, extras)`` with
    ``extras`` the rows' own operands ((R, d_out) or (R, 1), handed over in
    float32) → a tuple of results, each (R, d_out) or — ``narrow`` — (R, 1),
    in the dtypes ``outs`` names: a ``(dtype name, narrow)`` each.  The
    ``epilogue`` must be the SAME object call after call (it is a static
    argument of the jitted launcher).  Not differentiable: the caller's
    backward is its own (``models/moe.py``).  Where the plan is ``xla`` the
    products are ``lax.ragged_dot``'s and the epilogue runs on whole arrays."""
    lhs_of = tuple(lhs_of or (0,) * len(ws))
    d_in, d_out = (ws[0].shape[2], ws[0].shape[1]) if transposed else ws[0].shape[1:]
    plan = _planned(lhs[0].shape[0], d_in, d_out, ws[0].shape[0], lhs[0].dtype)
    if plan.kind == "xla":
        products = [lax.ragged_dot(lhs[l], jnp.swapaxes(w, 1, 2) if transposed else w, sizes,
                                   preferred_element_type=_F32) for l, w in zip(lhs_of, ws)]
        results = epilogue(products, [e.astype(_F32) for e in extras])
        return tuple(r.astype(kind) for r, (kind, _) in zip(results, outs))
    count = (len(lhs), len(ws))

    def kernels(sizes, *operands):
        lhs, ws, extras = operands[:count[0]], operands[count[0]:sum(count)], operands[sum(count):]
        return _gmm_call(tuple(lhs), tuple(w.astype(lhs[0].dtype) for w in ws), tuple(extras), sizes.astype(jnp.int32),
                         lhs_of=lhs_of, epilogue=epilogue, outs=tuple(outs), transposed=transposed,
                         row_tile=plan.row_tile, name=name, interpret=plan.interpret)

    return _whole_on_every_device(kernels, sizes, *lhs, *ws, *extras)


_BY_GROUP = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def grouped_weight_grad(lhs, rhs, sizes, stack, carry, continued, continues):
    """One pass of the weight gradient ``dW[e] = Σ_{r in group e} lhs[r]^T
    rhs[r]`` over sorted rows that come a pass at a time: lhs (R, d_in), rhs
    (R, d_out), sizes (G,) int32 the pass's rows of each group → ``(stack,
    carry)``.

    A group's sum is whole when its rows end, and sorted rows end group by
    group: so the block of every group with rows in the pass is WRITTEN
    (rounded once from its float32 sum) into ``stack`` (G, d_in, d_out), in
    the matrices' dtype — the other groups' blocks stay as they were, zero
    until a pass reaches them — and only the one group that straddles two
    passes is carried, as ``carry`` (d_in, d_out) float32: ``continued``
    (scalar, bool) says the pass's first group began in the pass before and
    starts from ``carry``; ``continues`` that its last group goes on in the
    next, which makes this pass hand its float32 sum on (its block of the
    stack is then the next pass's to overwrite).  No stack-wide sum, zero
    fill in float32 or cast follows the passes.  Where the plan is
    ``pallas`` both results are their operands' buffers."""
    plan = _planned(lhs.shape[0], lhs.shape[1], rhs.shape[1], stack.shape[0], lhs.dtype)
    flags = jnp.stack([continued, continues]).astype(jnp.int32)
    if plan.kind == "xla":
        has = sizes > 0
        sums = lax.ragged_dot_general(lhs, rhs, sizes, _BY_GROUP, preferred_element_type=_F32)
        first, last = jnp.argmax(has), sizes.shape[0] - 1 - jnp.argmax(has[::-1])
        at_first = (jnp.arange(sizes.shape[0]) == first)[:, None, None]
        sums = sums + jnp.where(at_first & (flags[0] == 1), carry, 0.0)
        return (jnp.where(has[:, None, None], sums.astype(stack.dtype), stack),
                jnp.where((flags[1] == 1) & has.any(), sums[last], carry))
    return _whole_on_every_device(
        lambda lhs, rhs, sizes, stack, carry, flags: _wgrad_call(
            lhs, rhs.astype(lhs.dtype), sizes.astype(jnp.int32), stack, carry.astype(_F32), flags,
            plan.row_tile, plan.interpret),
        lhs, rhs, sizes, stack, carry, flags)


_ADD_COLUMNS = (512, 256, 128)        # columns of the tokens a step of the combine holds in VMEM: the widest that divides
_ADD_GROUP = 16                       # rows read together: a packed bf16 tile's sublanes


def _add_rows_kernel(row_tile, token_of, n_live, rows_ref, into_hbm, out_ref, acc, sem):
    """Grid (column tiles, row tiles).  ``acc`` (T, columns) float32 holds
    the tokens' sums for one column tile across the row tiles; a live row
    tile is read ``_ADD_GROUP`` rows at a time and each row added to its
    token's row of ``acc``, one after another (two rows of a tile may be the
    same token's).  After the last tile what was there before (``into_hbm``,
    copied by hand into the result's block) is added and the block rounded."""
    tile, last = pl.program_id(1), pl.num_programs(1) - 1
    base = tile * row_tile
    columns = pl.ds(pl.multiple_of(pl.program_id(0) * acc.shape[1], acc.shape[1]), acc.shape[1])

    @pl.when(tile == 0)
    def _start():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(base < n_live[0])
    def _live():
        @pl.loop(0, row_tile // _ADD_GROUP)
        def _group(i):
            first = pl.multiple_of(i * _ADD_GROUP, _ADD_GROUP)
            rows = rows_ref[pl.ds(first, _ADD_GROUP), :].astype(_F32)
            for j in range(_ADD_GROUP):
                r = base + first + j
                at = pl.ds(token_of[r], 1)
                acc[at, :] = acc[at, :] + jnp.where(r < n_live[0], rows[j:j + 1, :], 0.0)

    @pl.when(tile == last)
    def _finish():
        move = pltpu.make_async_copy(into_hbm.at[:, columns], out_ref, sem)
        move.start()
        move.wait()
        out_ref[...] = (out_ref[...].astype(_F32) + acc[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _add_rows_call(into, token_of, rows, n_live, row_tile, interpret):
    tokens, width = into.shape
    columns = next(c for c in _ADD_COLUMNS if width % c == 0)
    tiles = rows.shape[0] // row_tile
    live_tile = lambda c, t, token_of, n: (jnp.minimum(t, jnp.maximum(n[0] - 1, 0) // row_tile), c)
    return pl.pallas_call(
        functools.partial(_add_rows_kernel, row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(width // columns, tiles),
            in_specs=[pl.BlockSpec((row_tile, columns), live_tile), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, columns), lambda c, t, *_: (0, c)),
            scratch_shapes=[pltpu.VMEM((tokens, columns), _F32), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(into.shape, into.dtype),
        input_output_aliases={3: 0},          # after the rows' tokens, the live count and the rows
        compiler_params=_params("parallel", "arbitrary"),
        name=ADD,
        interpret=interpret,
    )(token_of.astype(jnp.int32), jnp.reshape(n_live, (1,)).astype(jnp.int32), rows, into)


def add_rows_plan(tokens: int, rows: int, width: int, dtype, *, backend: str | None = None) -> GroupedPlan:
    """Which form :func:`add_rows` takes, from the shapes and the backend
    alone: the kernel where the rows are whole row tiles, the width whole
    lane tiles, and a column tile of every token's float32 sum fits VMEM
    beside the result's two buffers."""
    columns = next((c for c in _ADD_COLUMNS if width % c == 0), 0)
    return _planned_from(rows, dtype, backend, lambda row_tile: (
        (columns > 0, f"width {width} is no multiple of the {_LANES}-lane tile"),
        (tokens % 8 == 0, f"{tokens} tokens are no multiple of a tile's 8 sublanes"),
        (tokens * columns * (4 + 2 * jnp.dtype(dtype).itemsize) + 2 * row_tile * columns * 4 <= _VMEM_BLOCKS,
         f"{tokens} tokens' sums leave no column tile that fits VMEM"),
    ))


def add_rows(into, token_of, rows, n_live):
    """``into.at[token_of].add(rows)`` over the first ``n_live`` of a pass's
    sorted rows (the others may hold anything): into (T, d), token_of (R,)
    int32, rows (R, d) → (T, d), in ``into``'s buffer where the plan is
    ``pallas``.  XLA's scatter-add takes the rows one by one from HBM, 90 ns
    a row of a v5e live or dead; here the tokens' sums stay in VMEM a column
    tile at a time and the pass's dead tiles are neither read nor walked."""
    plan = add_rows_plan(into.shape[0], rows.shape[0], rows.shape[1], into.dtype)
    if plan.kind == "xla":
        live = (jnp.arange(rows.shape[0]) < n_live)[:, None]
        return into.at[token_of].add(jnp.where(live, rows, 0).astype(into.dtype))
    return _whole_on_every_device(
        lambda into, token_of, rows, n_live: _add_rows_call(into, token_of, rows.astype(into.dtype), n_live,
                                                            plan.row_tile, plan.interpret),
        into, token_of, rows, n_live)

"""Mixture-of-Experts layers: two routers, one file.

Absent from the reference (SURVEY.md §2c "EP" row) — provided because the
mesh reserves an ``expert`` axis and a complete framework fills it.

- :class:`MoeMlp` / :class:`MoeBlock` (``gpt2_moe``): Switch-Transformer
  top-1 routing (Fedus et al. 2021) into capacity-bounded buffers, tokens
  over capacity DROPPED.  Two formulations of the same selection: GShard
  one-hot einsums (experts on a leading axis that shards over the mesh's
  ``expert`` axis — GSPMD turns dispatch/combine into all-to-alls) and a
  row scatter/gather for experts that are not mesh-sharded.  Static shapes
  throughout.
- :class:`TopKMoe` (``sdar_moe``, ``instella_moe``, ``nemotron_h``): top-k
  routing over the published router width (softmax scores, or DeepSeek-V3's
  sigmoid scores with a selection-only bias) with normalised weights and NO
  capacity: every assignment to an expert this chip holds is computed.  An
  expert is gated (three matrices, ``act(x·Wgate) ⊙ (x·Wup)`` then ``Wdown``:
  SDAR's and Instella's, SiLU) or plain (two matrices, ``act(x·Wup)·Wdown``:
  Nemotron-H's, ``relu²``), as the layer is constructed.  The layer is told which contiguous
  range of experts it holds (``experts_held``), routes over all of them,
  and returns its own experts' part of the result — what expert
  parallelism asks of a chip before the exchange, and nothing standing in
  for the absent chips.  Assignments are sorted by expert and multiplied
  under a loop whose trip count follows the assignments there are (no
  static bound, nothing to overflow), ``ROWS_CHUNK`` sorted rows a pass as
  grouped matrix products — by ``ops/grouped_matmul.py``'s plan either its
  Pallas kernels (both widths multiples of the lane tile: SDAR's 2048 / 768,
  Instella's 2048 / 1408), which are told where each expert's rows lie, skip
  the pass's dead tiles, run the gate, the activation and the router weight
  in the products' epilogues, read a stack as it lies for the data gradient,
  write each expert's weight gradient once and add the rows to their tokens
  from VMEM, or ``lax.ragged_dot`` and XLA's scatter-add (on a TPU XLA's own
  Mosaic grouped matmul: any other width, the toy sizes) — or,
  where the layer is constructed with an ``expert_window``, as plain
  products: one a window of that many sorted rows and expert with rows in
  it (Nemotron-H: its 1856-wide experts miss the lane tile, and a plain
  product over a few hundred rows runs near the matrix unit's pace where
  ``ragged_dot`` does not).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.trace import scope
from ..ops.grouped_matmul import (
    DGRAD, REFWD, add_rows, grouped_matmul, grouped_plan, grouped_products, grouped_weight_grad,
)


def _constrain_for_ep(x: jax.Array, spec: P) -> jax.Array:
    """Apply a sharding constraint only when running under a mesh whose
    ``expert`` axis is real.

    Token-side constraints re-shard the token dim over (data, fsdp,
    expert) so the dispatch/combine einsums lower to all-to-alls over the
    expert axis (each expert shard exchanges only its token slice — the
    MaxText-style EP placement) instead of all-gathering EVERY token to
    every expert shard, which is what GSPMD picks when tokens stay sharded
    over the batch axes alone (measured: 18 all-gathers, 0 all-to-alls on
    a data=2 x expert=4 AOT compile).  Bare-P constraints require a mesh
    context (the framework's ``with mesh:``) and its axis names; outside
    one — single-chip runs, foreign meshes — the constraint must become a
    no-op, and the only reliable probe across jit/AOT tracing is to
    attempt it (``get_abstract_mesh`` does not reflect the legacy context
    manager).
    """
    # Inside a shard_map body (e.g. the MoE block as a pipeline stage) the
    # mesh axes are manual and naming them raises at trace time too.
    try:
        return lax.with_sharding_constraint(x, spec)
    except (RuntimeError, ValueError, KeyError):
        return x


def _top1_route(logits: jax.Array, capacity: int):
    """Shared router math. logits: (T, E) → (expert_idx, slot, gate, aux).

    ``slot`` is the token's position within its expert's capacity buffer —
    its rank among tokens routed to that expert (cumsum over the one-hot) —
    or -1 when the token overflows capacity and is dropped (standard Switch
    behavior).  Both dispatch formulations (einsum and scatter) derive from
    this one routing so their token selection is identical by construction.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                     # (T,)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (T, E)
    gate = jnp.sum(probs * onehot, axis=-1)                     # (T,)

    # Load-balancing aux loss (Switch eq. 4): E * Σ_e fraction_e · prob_e.
    fraction = jnp.mean(onehot, axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(fraction * prob_mean)

    position = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # (T, E), -1 if unrouted
    in_capacity = (position >= 0) & (position < capacity)
    slot = jnp.where(in_capacity, position, -1.0).max(axis=-1).astype(jnp.int32)
    return expert_idx, slot, gate, aux_loss


def _top1_dispatch(logits: jax.Array, capacity: int):
    """GShard one-hot formulation. logits: (T, E) → dispatch (T, E, C),
    combine (T, E, C), aux.

    The (T, E, C) one-hots make dispatch/combine dense einsums — the
    formulation GSPMD turns into expert-axis all-to-alls when experts are
    mesh-sharded — at the cost of O(T·E·C) bytes and O(T·E·C·D) matmul
    FLOPs per einsum.  On meshes without a real expert axis the scatter
    formulation (``_top1_scatter_indices`` + ``MoeMlp(dispatch_mode=
    "scatter")``) computes the same selection in O(T·D).
    """
    t, e = logits.shape
    expert_idx, slot, gate, aux_loss = _top1_route(logits, capacity)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (T, E)
    pos_onehot = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)  # (T, C)
    keep = (slot >= 0).astype(jnp.float32)                      # (T,)
    dispatch = onehot[:, :, None] * pos_onehot[:, None, :] * keep[:, None, None]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux_loss


def _top1_scatter_indices(logits: jax.Array, capacity: int):
    """Scatter/gather formulation. logits: (T, E) → (flat (T,), gate (T,),
    keep (T,), aux).

    Each (expert, slot) capacity cell receives at most one token, so the
    GShard dispatch einsum ``td,tec->ecd`` is a row-scatter in disguise and
    the combine einsum a row-gather: ``flat = expert·C + slot`` indexes the
    flattened (E·C, D) expert buffers, with dropped tokens pointed one past
    the end.  Replacing the einsums with scatter-add/gather removes both
    the (T, E, C) one-hot bytes and their O(T·E·C·D) matmul FLOPs — at
    T=4096, E=8, C=640, D=768 that is ~32 GFLOP per einsum per layer of
    pure dispatch overhead, ~30% of the routed step FLOPs.
    """
    expert_idx, slot, gate, aux_loss = _top1_route(logits, capacity)
    keep = (slot >= 0).astype(jnp.float32)
    e = logits.shape[-1]
    flat = jnp.where(slot >= 0, expert_idx * capacity + slot, e * capacity)
    return flat.astype(jnp.int32), gate, keep, aux_loss


class MoeMlp(nn.Module):
    """Drop-in MLP replacement: (B, L, D) → (B, L, D) through E experts.

    ``capacity_factor`` scales each expert's buffer relative to the even
    split T/E; dropped tokens pass through the residual unchanged (their
    combine weights are zero).  The aux load-balancing loss is stashed with
    ``self.sow`` under the "losses" collection.

    ``dispatch_mode`` picks the token → expert-buffer formulation:

    - ``"einsum"`` (default): GShard (T, E, C) one-hot einsums — the
      EP-shardable path (GSPMD lowers the t↔e resharding to expert-axis
      all-to-alls under a mesh with a real ``expert`` axis).
    - ``"scatter"``: row scatter-add / gather through flat (E·C, D)
      buffers — identical token selection (both modes derive from
      ``_top1_route``), no (T, E, C) tensors and no dispatch matmul
      FLOPs.  The fast path when experts are NOT mesh-sharded (single
      chip, or EP degree 1): GSPMD handles data-dependent scatter across
      shards poorly, so EP meshes should keep "einsum".
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    dispatch_mode: str = "einsum"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.dispatch_mode not in ("einsum", "scatter"):
            raise ValueError(
                f"dispatch_mode must be 'einsum' or 'scatter', got "
                f"{self.dispatch_mode!r}"
            )
        b, l, d = x.shape
        t = b * l
        e = self.num_experts
        capacity = max(int(self.capacity_factor * t / e), 1)
        tokens = x.reshape(t, d)

        router = nn.Dense(e, dtype=jnp.float32, name="router")
        w_up = self.param(
            "w_up", nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e, d, self.mlp_dim), jnp.float32,
        )
        w_down = self.param(
            "w_down", nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e, self.mlp_dim, d), jnp.float32,
        )

        if self.dispatch_mode == "scatter":
            flat, gate, keep, aux_loss = _top1_scatter_indices(
                router(tokens), capacity
            )
            self.sow("losses", "moe_aux_loss", aux_loss)
            self.sow("moe_stats", "drop_rate", 1.0 - jnp.sum(keep) / t)
            # Scatter token rows into the flat (E·C, D) buffers; dropped
            # tokens target the sentinel row e*capacity, sliced off before
            # the expert matmuls.  Indices are unique among kept tokens
            # (each cell holds ≤1 token), so the add never actually sums.
            buf = jnp.zeros((e * capacity + 1, d), self.dtype)
            buf = buf.at[flat].add(tokens.astype(self.dtype))
            expert_in = buf[: e * capacity].reshape(e, capacity, d)
            h = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(self.dtype))
            h = nn.gelu(h)
            expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
            # Combine = gather each token's cell back, weighted by its
            # gate; the sentinel index fills 0 for dropped tokens.
            rows = jnp.take(
                expert_out.reshape(e * capacity, d), flat, axis=0,
                mode="fill", fill_value=0,
            )
            out = rows * (gate * keep).astype(self.dtype)[:, None]
            return out.reshape(b, l, d).astype(x.dtype)

        dispatch, combine, aux_loss = _top1_dispatch(router(tokens), capacity)
        self.sow("losses", "moe_aux_loss", aux_loss)
        # Token-drop rate (capacity overflow): every kept token contributes
        # exactly one 1 to dispatch.  Sown into its own collection —
        # "losses" entries are summed INTO the training loss, a metric here
        # would corrupt it.  Surfaced per step as metrics["moe_drop_rate"]
        # (train/step.py).
        self.sow("moe_stats", "drop_rate", 1.0 - jnp.sum(dispatch) / t)

        # (E, C, D) expert inputs; experts run as one batched matmul whose
        # leading axis shards over the mesh's `expert` axis.  The token dim
        # is constrained over (data, fsdp, expert) around the dispatch /
        # combine so the t <-> e resharding lowers to expert-axis
        # all-to-alls (see _constrain_for_ep).
        tokens = _constrain_for_ep(tokens, P(("data", "fsdp", "expert"), None))
        expert_in = jnp.einsum(
            "td,tec->ecd", tokens.astype(self.dtype), dispatch.astype(self.dtype)
        )
        expert_in = _constrain_for_ep(expert_in, P("expert", None, None))
        h = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        expert_out = _constrain_for_ep(expert_out, P("expert", None, None))
        out = jnp.einsum(
            "ecd,tec->td", expert_out, combine.astype(self.dtype)
        )
        out = _constrain_for_ep(out, P(("data", "fsdp", "expert"), None))
        return out.reshape(b, l, d).astype(x.dtype)


class MoeBlock(nn.Module):
    """Pre-LN transformer block with an MoE MLP (GPT-2 block variant).

    Residual dropout mirrors the dense ``gpt2.Block`` so MoE and dense
    blocks regularize identically.
    """

    num_heads: int
    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dropout_rate: float = 0.0
    dtype: Any = jnp.float32
    dispatch_mode: str = "einsum"

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        from .layers import SelfAttention

        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        y = SelfAttention(self.num_heads, causal=True, dtype=self.dtype, name="attn")(y)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        y = MoeMlp(
            self.num_experts, self.mlp_dim,
            capacity_factor=self.capacity_factor, dtype=self.dtype,
            dispatch_mode=self.dispatch_mode, name="moe",
        )(y)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return x + y


def topk_route(logits: jax.Array, k: int, norm_topk_prob: bool = True, *,
               scoring: str = "softmax", bias: jax.Array | None = None,
               scale: float = 1.0):
    """Router math of the top-k layer.  logits: (T, E) → (weights (T, k)
    f32, expert ids (T, k) int32, scores (T, E) f32).  ``scoring``
    ``"softmax"``: softmax in f32 over ALL E outputs; ``"sigmoid"``
    (DeepSeek-V3's): each output's own sigmoid.  The k experts are the k
    largest scores — of ``scores + bias`` when a selection ``bias`` (E,) is
    given (``noaux_tc``: it decides WHICH experts, takes no gradient, and
    never enters a weight) — and the weights are their unbiased scores,
    renormalised to sum to one when ``norm_topk_prob``, times ``scale``."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
    squash = jax.nn.softmax if scoring == "softmax" else jax.nn.sigmoid
    scores = squash(logits.astype(jnp.float32))
    if bias is None:
        weights, experts = lax.top_k(scores, k)
    else:
        _, experts = lax.top_k(scores + lax.stop_gradient(bias.astype(jnp.float32)), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), scores


def sequence_balance(scores: jax.Array, experts: jax.Array) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance term (``seq_aux``), before its
    coefficient: scores (B, L, E) f32 and chosen experts (B, L, k) →
    ``mean_b Σ_i f_i P_i`` with ``f_i = E / (k L) · #{t : i chosen at t}``
    (a count: no gradient) and ``P_i = mean_t s_{i,t} / Σ_j s_{j,t}``, over
    ALL E outputs: the router is whole on every chip."""
    e, (_, length, k) = scores.shape[-1], experts.shape
    chosen = jnp.sum(
        experts[..., None] == jnp.arange(e, dtype=experts.dtype), axis=(1, 2),
        dtype=jnp.float32,
    )                                                      # (B, E)
    f = chosen * (e / (k * length))
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def group_held_assignments(experts: jax.Array, first: int, held: int):
    """Sort the (T, k) assignments by expert, those on the held range
    ``first .. first + held - 1`` first.

    Returns ``(order, counts)``: ``order`` (T·k,) indexes the flattened
    assignments, held ones first, grouped by expert in ascending order
    (stable: by token inside a group); ``counts`` (held,) int32 is each
    held expert's number of assignments, so the first ``sum(counts)``
    entries of ``order`` are the held assignments."""
    local = experts.reshape(-1) - first
    is_held = (local >= 0) & (local < held)
    group = jnp.where(is_held, local, held)       # absent experts sort last
    order = jnp.argsort(group, stable=True)
    # (held, T·k): the long axis on the lanes
    counts = jnp.sum(
        jnp.arange(held, dtype=group.dtype)[:, None] == group[None, :],
        axis=1, dtype=jnp.int32,
    )
    return order, counts


# Sorted rows one pass of ``held_experts``' loop takes in ``TopKMoe``: the
# expected P·k·held/E of SDAR's chip share at 8192 positions (8192 rows) fits
# one pass twice over; a layer the routing favours takes more passes.
ROWS_CHUNK = 16384


def relu2(x):
    """``relu(x)²`` (``mlp_hidden_act`` ``relu2``)."""
    return jnp.square(nn.relu(x))


ACTIVATIONS = {"silu": nn.silu, "relu2": relu2}


def _expert_chunk(x, w_rows, product, act, *ws):
    """One pass's sorted rows through their experts: x (C, d), the rows'
    router weights (C,), ``product(lhs, w)`` the pass's matrix product.  Three
    ``ws`` ``(w_gate, w_up, w_down)`` are gated experts, two ``(w_up,
    w_down)`` plain ones."""
    *w_in, w_down = ws
    h = ACTIVATIONS[act](product(x, w_in[0]))
    if len(w_in) == 2:
        h = h * product(x, w_in[1])
    return product(h, w_down) * w_rows[:, None].astype(x.dtype)


# Where the grouped products are the kernels' (``ops/grouped_matmul.py``), what
# follows a product row by row runs in the kernel that made it, on the run of
# rows still in VMEM and in float32: the activation and the gate, the router
# weight, and in the backward their derivatives.  Each is a product's
# ``epilogue(products, extras)``; made once an activation, because the jitted
# launcher knows an epilogue by the object it is.


@functools.lru_cache(maxsize=None)
def _into_experts(act):
    """``h = act(x·W_gate) ⊙ (x·W_up)``, or ``act(x·W_up)`` of one product."""
    return lambda products, _: (ACTIVATIONS[act](products[0]) * products[1] if len(products) == 2
                                else ACTIVATIONS[act](products[0]),)


def _weighted(products, extras):
    """``(h·W_down) ⊙ w``: the row's router weight."""
    return (products[0] * extras[0],)


@functools.lru_cache(maxsize=None)
def _into_experts_again(act):
    """The backward's second look at :func:`_into_experts`, with ``d_h =
    d_y·W_down^T`` (before the router weight) and the weight ``w`` the rows'
    own: → ``(h ⊙ w, d_gate [, d_up], <d_h, h>)``, the last the router
    weight's gradient, one number a row."""
    def epilogue(products, extras):
        d_h, w = extras
        a, pull = jax.vjp(ACTIVATIONS[act], products[0])
        h = a * products[1] if len(products) == 2 else a
        d_w = jnp.sum(d_h * h, axis=-1, keepdims=True)
        d_h = d_h * w
        (d_a,) = pull(d_h * products[1] if len(products) == 2 else d_h)
        return (h * w, d_a, *((d_h * a,) if len(products) == 2 else ()), d_w)
    return epilogue


def _summed(products, _):
    return (sum(products),)


def _expert_chunk_kernels(x, w_rows, sizes, act, ws):
    """:func:`_expert_chunk` where the grouped products are the kernels':
    two calls, the products into the experts' width with the activation and
    the gate, and the product back with the router weight."""
    *w_in, w_down = ws
    wide = ((x.dtype.name, False),)
    (h,) = grouped_products((x,), w_in, sizes, _into_experts(act), wide)
    return grouped_products((h,), (w_down,), sizes, _weighted, wide, extras=(w_rows[:, None],))[0]


def _expert_chunk_grads(x, w_rows, spans, act, ws, d_y, grads):
    """:func:`_expert_chunk_kernels`' backward over one pass, written out:
    d_y (C, d) is the pass's cotangent (whatever its dead rows hold); ``spans`` the
    pass's ``(sizes, continued, continues)``; ``grads`` a ``(stack gradient,
    carried float32 block)`` for each of ``ws`` → ``(d_x, d_w_rows, grads)``.
    Three calls beside the weight gradients': ``d_y·W_down^T``; the products
    into the experts' width AGAIN (no residual but the inputs; the product
    back is not needed: the router weights' gradient is ``<d_y·W_down^T, h>``
    a row) with every derivative of the activation, the gate and the weight;
    and the data gradients of those products, summed, each meeting its stack
    as it lies.  Each weight gradient is written into its stack by the
    kernel, the groups that end in this pass (``grouped_weight_grad``).  What
    the kernels left unwritten past the last live row stays in those rows,
    which the caller masks."""
    sizes = spans[0]
    *w_in, w_down = ws
    kind = x.dtype.name
    d_h = grouped_matmul(d_y, w_down, sizes, transposed=True)
    *wide, d_w = grouped_products(
        (x,), w_in, sizes, _into_experts_again(act), ((kind, False),) * (1 + len(w_in)) + (("float32", True),),
        extras=(d_h, w_rows[:, None]), name=REFWD)
    hw, *d_in = wide
    (d_x,) = grouped_products(d_in, w_in, sizes, _summed, ((kind, False),), lhs_of=range(len(w_in)), transposed=True,
                              name=DGRAD)
    pairs = [*((x, d) for d in d_in), (hw, d_y)]
    return d_x, d_w[:, 0], tuple(grouped_weight_grad(lhs, rhs, sizes, *grad, *spans[1:])
                                 for (lhs, rhs), grad in zip(pairs, grads))


def _passes(order, counts, weights, rows, dense):
    """The passes over the sorted held assignments, ``rows`` sorted rows each,
    on ONE grid: window w is rows ``w·rows ...`` whatever experts they belong
    to.  Grouped (``dense`` false): a pass is a window, multiplied as grouped
    products.  Dense: a pass is a window and ONE expert with rows in it,
    multiplied by that expert's matrices as plain products with the others'
    rows masked, so the passes number ``ceil(held / rows)`` plus the expert
    boundaries that fall inside a window (at most the experts less one): what
    the layer costs follows how MANY assignments it holds and not how they are
    split among its experts (PERF.md section 6, PR 34).

    Returns ``(n, at, w_sorted, n_held)``: the number of passes that hold any
    assignment, ``at(c)`` → the pass's first sorted row, its rows' tokens and
    router weights, which of its rows are live, its ``(sizes, continued,
    continues)`` — each held expert's rows in it, whether its first expert
    began in the pass before and whether its last goes on in the next
    (grouped; None dense) —, its ``product`` and the matrices of ``stacks``
    that takes, and how to add a pass's weight gradients to the stacks'."""
    k = weights.shape[1]
    order = jnp.pad(order, (0, (-order.shape[0]) % rows))      # whole windows to slice from
    ends = jnp.cumsum(counts)
    n_held = ends[-1]
    token_of, w_sorted, starts = order // k, weights.reshape(-1)[order], ends - counts
    if dense:
        per = jnp.where(counts > 0, (ends - 1) // rows - starts // rows + 1, 0)   # windows an expert has rows in
        through = jnp.cumsum(per)              # passes through each expert's last
        n = through[-1]
    else:
        n = (n_held + rows - 1) // rows

    def at(c, stacks):
        if dense:
            e = jnp.searchsorted(through, c, side="right").astype(jnp.int32)      # pass c is expert e's
            lo = (starts[e] // rows + c - through[e] + per[e]) * rows
        else:
            lo = c * rows
        idx = lax.dynamic_slice(token_of, (lo,), (rows,))
        w_rows = lax.dynamic_slice(w_sorted, (lo,), (rows,))
        if dense:
            at_row = lo + jnp.arange(rows)
            live = (at_row >= starts[e]) & (at_row < ends[e])
            ws = tuple(lax.dynamic_index_in_dim(w, e, keepdims=False) for w in stacks)
            return lo, idx, w_rows, live, None, jnp.dot, ws, lambda total, g: total.at[e].add(g.astype(total.dtype))
        live = lo + jnp.arange(rows) < n_held       # the last chunk's tail holds no assignment
        sizes = (jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)).astype(jnp.int32)
        # does the pass's first expert come from the pass before, does its last go on in the next
        spans = (sizes, *(jnp.any((starts < edge) & (ends > edge)) for edge in (lo, lo + rows)))
        return (lo, idx, w_rows, live, spans, functools.partial(grouped_matmul, sizes=sizes), stacks,
                lambda total, g: total + g.astype(total.dtype))

    return n, at, w_sorted, n_held


# The held experts' part of the layer, as a custom-VJP function whose two
# passes walk the sorted held assignments ``rows`` at a time under a loop with
# a DYNAMIC trip count: the work follows the assignments that are there (none
# is ever dropped: there is no bound to overflow, whatever the routing does),
# as the expert FLOPs do, and the memory is a pass's.  Each pass gathers its
# rows, runs the products (grouped: ``ops/grouped_matmul.grouped_matmul``,
# which leaves the rows past the last group unwritten; dense: one expert's,
# over a window that holds other experts' rows too; both masked here) and
# adds the rows to their tokens (a scatter-add, or where the kernels run
# ``ops/grouped_matmul.add_rows`` over the live rows).  Its residuals are its
# inputs and the routing, so a rematerialized block's second forward computes
# no expert product at all (the backward recomputes a pass's products where
# it needs them).  The backward differentiates ``_expert_chunk`` where the
# products are XLA's (``lax.ragged_dot``, a dense window's ``jnp.dot``) and
# is written out where they are the kernels' (``_expert_chunk_grads``).


def _kernels_planned(tokens, stacks, rows, dense) -> bool:
    """Whether the grouped products of these passes are the kernels'."""
    held, d_in, d_out = stacks[0].shape
    return not dense and grouped_plan(rows, d_in, d_out, held, tokens.dtype).kind == "pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def held_experts(tokens, weights, order, counts, stacks, rows, act="silu", dense=False):
    """tokens (T, d), weights (T, k) f32, the sorted assignments of
    :func:`group_held_assignments`, the expert weight stacks (three: gated,
    two: plain; ``act`` names the activation), ``rows`` sorted rows a pass,
    grouped or ``dense`` (:func:`_passes`) → (T, d):
    ``out[t] = Σ_{j: expert(t, j) held} weights[t, j] · expert(tokens[t])``."""
    n, at, _, n_held = _passes(order, counts, weights, rows, dense)
    kernels = _kernels_planned(tokens, stacks, rows, dense)

    def body(c, out):
        lo, idx, w_rows, live, spans, product, ws, _ = at(c, stacks)
        if kernels:       # (the combine walks the pass's live rows alone)
            return add_rows(out, idx, _expert_chunk_kernels(tokens[idx], w_rows, spans[0], act, ws), n_held - lo)
        y = _expert_chunk(tokens[idx], w_rows, product, act, *ws)
        return out.at[idx].add(jnp.where(live[:, None], y, 0).astype(out.dtype))

    return lax.fori_loop(0, n, body, jnp.zeros_like(tokens))


def _held_experts_fwd(tokens, weights, order, counts, stacks, rows, act, dense):
    out = held_experts(tokens, weights, order, counts, stacks, rows, act, dense)
    return out, (tokens, weights, order, counts, stacks)


def _held_experts_bwd(rows, act, dense, res, d_out):
    tokens, weights, order, counts, stacks = res
    n, at, w_sorted, n_held = _passes(order, counts, weights, rows, dense)

    def body(c, carry):
        d_tokens, d_w_sorted, d_stacks = carry
        lo, idx, w_rows, live, _, product, ws, add = at(c, stacks)
        _, pull = jax.vjp(
            lambda x, w, *ws: _expert_chunk(x, w, product, act, *ws),
            tokens[idx], w_rows, *ws)
        d_x, d_w, *d_ws = pull(jnp.where(live[:, None], d_out[idx], 0).astype(tokens.dtype))
        return (
            d_tokens.at[idx].add(jnp.where(live[:, None], d_x, 0)),
            # a dense window's other rows are other passes', before or after this one
            lax.dynamic_update_slice(d_w_sorted, jnp.where(
                live, d_w, lax.dynamic_slice(d_w_sorted, (lo,), (rows,)) if dense else 0.0), (lo,)),
            tuple(add(total, g) for total, g in zip(d_stacks, d_ws)),
        )

    def kernels_body(c, carry):
        d_tokens, d_w_sorted, grads = carry
        lo, idx, w_rows, live, spans, _, ws, _ = at(c, stacks)
        # (no kernel reads a row past its group's, so the dead rows' cotangents need no zeroing)
        d_x, d_w, grads = _expert_chunk_grads(
            tokens[idx], w_rows, spans, act, ws, d_out[idx].astype(tokens.dtype), grads)
        return (
            add_rows(d_tokens, idx, d_x, n_held - lo),
            lax.dynamic_update_slice(d_w_sorted, jnp.where(live, d_w, 0.0), (lo,)),
            grads,
        )

    # The stacks' gradients: float32 sums the passes add to, rounded at the
    # end — or, where the kernels run, the stacks' own dtype from the start,
    # every expert's block written by the pass its rows end in, beside ONE
    # float32 block a stack for the expert that straddles two passes.
    if _kernels_planned(tokens, stacks, rows, dense):
        d_tokens, d_w_sorted, grads = lax.fori_loop(0, n, kernels_body, (
            jnp.zeros_like(tokens), jnp.zeros_like(w_sorted),
            tuple((jnp.zeros_like(w), jnp.zeros(w.shape[1:], jnp.float32)) for w in stacks),
        ))
        d_stacks = tuple(stack for stack, _ in grads)
    else:
        d_tokens, d_w_sorted, d_stacks = lax.fori_loop(0, n, body, (
            jnp.zeros_like(tokens), jnp.zeros_like(w_sorted),
            tuple(jnp.zeros(w.shape, jnp.float32) for w in stacks),
        ))
    # back from sorted order: assignment a sits at place[a]
    place = jnp.argsort(order)
    d_weights = jnp.where(place < n_held, d_w_sorted[place], 0.0).reshape(weights.shape)
    d_stacks = tuple(g.astype(w.dtype) for g, w in zip(d_stacks, stacks))
    return d_tokens, d_weights.astype(weights.dtype), None, None, d_stacks


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class TopKMoe(nn.Module):
    """Experts under a dropless top-k router: (B, L, D) → (B, L, D),
    ``Σ_{e ∈ top-k ∩ held} w_e · expert_e(x)``.  ``gated`` (the default)
    experts are ``(act(x·Wgate_e) ⊙ (x·Wup_e))·Wdown_e`` with ``activation``
    ``"silu"`` (SDAR's, Instella's); ``gated=False`` ones are
    ``act(x·Wup_e)·Wdown_e`` and hold no ``w_gate`` (Nemotron-H's, with
    ``activation="relu2"``).

    ``experts_held = (first, count)`` is this chip's share of the
    ``num_experts`` the router scores (None: all of them).  Only the held
    experts have weights here.  Every held assignment is computed
    (:func:`held_experts`): ``ROWS_CHUNK`` sorted rows a pass of its loop as
    grouped products — the Pallas kernels of ``ops/grouped_matmul.py`` where
    its plan takes the widths (multiples of the lane tile: SDAR's,
    Instella's), ``lax.ragged_dot`` where not — or, ``expert_window`` rows
    given, windows of that many sorted rows, a pass for each expert with rows
    in the window, as plain products: what ``ragged_dot`` costs a live row
    does not fall with the rows an expert has, a plain one over a few hundred
    rows runs near the matrix unit's pace (PERF.md section 6, PR 34;
    Nemotron-H's form, whose 1856-wide experts the kernels' plan refuses).

    The router is SDAR's by default (softmax over all outputs).
    ``scoring="sigmoid"``, ``selection_bias`` (a ``router_bias`` (E,) leaf
    that starts at zero, is added to the scores for the SELECTION only and
    takes no gradient; nothing here updates it), ``routed_scaling_factor``
    and ``seq_aux`` (the sequence-wise balance term, sown into ``losses``
    WITHOUT its coefficient) make it DeepSeek-V3's (``models/instella_moe``).

    Sown into ``moe_counters`` (one scalar a layer, f32): the held
    assignments and the busiest held expert's rows.
    """

    num_experts: int
    num_experts_per_tok: int
    mlp_dim: int
    experts_held: tuple | None = None
    norm_topk_prob: bool = True
    scoring: str = "softmax"
    selection_bias: bool = False
    routed_scaling_factor: float = 1.0
    seq_aux: bool = False
    gated: bool = True
    activation: str = "silu"
    expert_window: int | None = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, l, d = x.shape
        t, k, e = b * l, self.num_experts_per_tok, self.num_experts
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"activation {self.activation!r}: one of {sorted(ACTIVATIONS)}")
        first, held = self.experts_held or (0, e)
        if first < 0 or held < 1 or first + held > e:
            raise ValueError(f"experts_held {self.experts_held} outside 0..{e}")
        if self.expert_window is not None and self.expert_window < 1:
            raise ValueError(f"expert_window {self.expert_window}: a number of rows, or None for grouped products")
        tokens = x.reshape(t, d).astype(self.dtype)

        init = nn.initializers.normal(stddev=0.02)
        router = self.param("router", init, (d, e), jnp.float32)
        shapes = {"w_gate": (held, d, self.mlp_dim), "w_up": (held, d, self.mlp_dim),
                  "w_down": (held, self.mlp_dim, d)}
        if not self.gated:
            del shapes["w_gate"]
        stacks = tuple(
            self.param(name, init, shape, jnp.float32).astype(self.dtype)
            for name, shape in shapes.items()
        )

        bias = (self.param("router_bias", nn.initializers.zeros, (e,), jnp.float32)
                if self.selection_bias else None)

        with scope("moe/route"):
            logits = jnp.dot(tokens, router.astype(self.dtype),
                             preferred_element_type=jnp.float32)
            weights, experts, scores = topk_route(
                logits, k, self.norm_topk_prob, scoring=self.scoring, bias=bias,
                scale=self.routed_scaling_factor,
            )
            order, counts = group_held_assignments(experts, first, held)
            if self.seq_aux:
                self.sow("losses", "moe_balance", sequence_balance(
                    scores.reshape(b, l, e), experts.reshape(b, l, k)))
        self.sow("moe_counters", "moe_held_assignments", jnp.sum(counts).astype(jnp.float32))
        self.sow("moe_counters", "moe_load_max", jnp.max(counts).astype(jnp.float32))

        with scope("moe/experts"):
            out = held_experts(tokens, weights, order, counts, stacks,
                               self.expert_window or min(ROWS_CHUNK, t * k), self.activation,
                               self.expert_window is not None)
        return out.reshape(b, l, d).astype(x.dtype)

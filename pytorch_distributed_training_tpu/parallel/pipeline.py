"""Pipeline parallelism: three schedules over the mesh's ``pipeline`` axis.

Absent from the reference (SURVEY.md §2c "PP" row) and beyond BASELINE's
required scope, but the mesh reserves a ``pipeline`` axis and this module
fills it with three schedules sharing one SPMD formulation:

  * ``pipeline_forward`` — GPipe: M microbatches through a scan of M+S-1
    ticks, ``ppermute`` handing activations onward each tick, autodiff
    backward; bubble (S-1)/(M+S-1).  Its tick loop is BRANCH-FREE, which
    makes it the only schedule that soundly hosts collectives inside the
    stage body (ring-attention SP, per-tick FSDP param gathers).
  * ``pipeline_train_1f1b`` — PipeDream-flush: manual fwd/bwd interleave
    with per-stage recompute; live activations bounded by S, not M.
  * ``pipeline_train_interleaved`` — Megatron interleaved 1F1B: V model
    chunks per device divide the bubble by ~V (table-driven from
    ``pipeline_schedule.make_interleaved_schedule``).

The manual schedules gate each tick's work behind ``lax.cond`` branches
whose predicates vary over the pipeline axis, so collectives inside the
STAGE BODY are unsound there (the SP ban below).  FSDP needs no stage-body
collective: its param all-gather does not depend on branch data, so both
manual engines hoist it before the tick scan and psum-scatter the
accumulated grads after it (``fsdp_gather_specs``) — PP x FSDP composes
with all three schedules.

XLA overlaps each tick's ppermute with the next tick's stage compute on
the ICI torus.

SPMD formulation (every device runs the same program):
  * stage params are a pytree whose leaves are stacked on axis 0 (one slice
    per stage) and sharded over ``pipeline`` — inside shard_map each device
    sees exactly its stage's slice;
  * the per-tick state is one activation block per device; stage 0 injects
    microbatch t at tick t, stage S-1 emits a finished microbatch at tick
    t ≥ S-1;
  * reverse-mode AD through the scan + ppermute gives a correct GPipe
    backward out of the box; it is activation-heavy — the scan carries the
    activations of all M+S-1 ticks (including stage-0's clamped recompute of
    the last microbatch on ticks t >= M), so backward memory grows with the
    microbatch count.  Use ``remat_ticks=True`` to ``jax.checkpoint`` each
    tick and bound the stored residuals to the carried activations alone.

The inner function is exact: pipeline_forward == sequentially applying the
S stages to each microbatch (verified in tests/test_pipeline.py).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..comm.compress import (
    PP_COMPRESS_MODES, boundary_has_residual, boundary_permute,
)
from ..comm.mesh import AXIS_PIPELINE, AXIS_SEQUENCE, BATCH_AXES
from ..compat import pcast, shard_map, typeof
from ..obs.trace import scope


def _vma_markers(reference: jax.Array, axis_name: str):
    """(mark_varying, mv_tree) for a shard_map body's carry typing.

    The scan carry varies over the pipeline axis (each stage computes
    different activations) and over whatever batch axes the caller sharded
    ``reference`` (the microbatch stack) over, even when the inits are
    constants — shard_map's varying-axes typing needs them pre-marked with
    a comm-free ``pcast``.  Shared by the GPipe and 1F1B locals: wrong
    marking inside per-stage ``lax.cond`` branches is the deadlock class
    the 1F1B docstring warns about, so there must be exactly one copy of
    this logic.

    NOT unioned: axes the STAGE PARAMS are sharded over.  Tensor-sharded
    params end in psum-completed (tensor-invariant) outputs, and
    fsdp-sharded params require fsdp-sharded microbatches
    (``_micro_spec_for`` enforces it), so ``reference`` already carries
    fsdp — a params union would mis-type PP x TP carries as
    tensor-varying and break their replicated out_specs.
    """
    ref_vma = tuple(typeof(reference).vma)
    want = (axis_name,) + tuple(a for a in ref_vma if a != axis_name)

    def mark_varying(v):
        have = typeof(v).vma
        missing = tuple(a for a in want if a not in have)
        return pcast(v, missing, to="varying") if missing else v

    def mv_tree(tree):
        return jax.tree_util.tree_map(mark_varying, tree)

    return mark_varying, mv_tree


def stack_stage_params(per_stage_params: list[Any]) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with leaves stacked on axis 0.

    All stages must share a pytree structure (same layer shapes) — the usual
    homogeneous-transformer-stack case.
    """
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params
    )


def _scoped_tick(tick: Callable) -> Callable:
    """Scan-body wrapper giving every schedule's tick the same xprof phase
    name (obs/trace.py "pipeline/tick") in traced-op metadata."""
    def body(carry, t):
        with scope("pipeline/tick"):
            return tick(carry, t)
    return body


def _pipeline_local(
    stage_params: Any,
    micro_in: jax.Array,
    rng: jax.Array | None,
    stage_fn: Callable[..., jax.Array],
    *,
    axis_name: str,
    num_stages: int,
    remat_ticks: bool = False,
    with_aux: bool = False,
    aux_mean_axes: tuple[str, ...] = (),
    boundary_compress: str = "none",
    boundary_stripe: int = 1,
):
    """Runs inside shard_map. micro_in: (M, mb, ...) full microbatch stack
    (replicated); stage_params: this stage's slice, leaves (1, ...).

    ``rng`` (optional): per-tick randomness — stage_fn is then called as
    ``stage_fn(params, x, key)`` with a key folded from (tick, stage), so
    every (stage, microbatch) pair draws independent noise (dropout) and
    the backward replays the identical mask (keys are deterministic).

    ``with_aux``: stage_fn returns ``(y, aux)`` with ``aux`` a pytree of
    scalars (the MoE load-balancing loss and drop stats); contributions
    from VALID ticks only (stage s processes real microbatch t-s iff
    0 <= t-s < M — outside that window stages chew zeros/clamped repeats
    whose aux must not pollute the sum) are accumulated in the scan carry,
    psum'd over the pipeline axis (each stage owns different layers) and
    pmean'd over ``aux_mean_axes`` (the batch axes the microbatches are
    sharded over — per-shard aux averages like any data-parallel loss
    term).  GPipe's branch-free tick loop is what makes these collectives
    sound here; the cond-gated schedules cannot host them (module
    docstring)."""
    my_stage = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda l: l[0], stage_params)
    num_micro = micro_in.shape[0]
    ticks = num_micro + num_stages - 1
    # Send each stage's output to the next; the wraparound edge (last → 0)
    # carries values stage 0 ignores (it re-injects fresh microbatches).
    perm = [(s, (s + 1) % num_stages) for s in range(num_stages)]

    def tick(carry, t):
        cur, outputs, aux_acc, bresid = carry
        # Stage 0 ingests microbatch t (clamped: beyond M-1 it reprocesses
        # the last microbatch and the result is never used).
        inject = micro_in[jnp.minimum(t, num_micro - 1)]
        x = jnp.where(my_stage == 0, inject, cur)
        with scope("pipeline/tick"):
            if rng is not None:
                key = jax.random.fold_in(jax.random.fold_in(rng, t), my_stage)
                y = stage_fn(params, x, key)
            else:
                y = stage_fn(params, x)
        if with_aux:
            y, aux = y
            valid = (t >= my_stage) & (t - my_stage < num_micro)
            # reshape(acc.shape): rank-0 aux broadcasts against the (1,)
            # accumulator (see aux0 below) without changing its shape.
            aux_acc = jax.tree_util.tree_map(
                lambda acc, a: acc + jnp.where(valid, a, 0.0).reshape(acc.shape),
                aux_acc, aux,
            )
        # Last stage finishes microbatch t-(S-1) at tick t.
        out_idx = t - (num_stages - 1)
        is_done = jnp.logical_and(my_stage == num_stages - 1, out_idx >= 0)
        updated = lax.dynamic_update_index_in_dim(
            outputs, y, jnp.maximum(out_idx, 0), axis=0
        )
        outputs = jnp.where(is_done, updated, outputs)
        # Stage-boundary hop, optionally compressed (--pp-compress): the
        # encoded payload is what crosses the link (and, on multi-slice
        # pipelines, DCN), with int8's error-feedback residual riding the
        # scan carry.  GPipe sends a real activation EVERY tick (the loop
        # is branch-free), so the residual updates unconditionally.
        nxt, bresid = boundary_permute(
            y, bresid, axis_name, perm, boundary_compress, boundary_stripe
        )
        return (nxt, outputs, aux_acc, bresid), None

    cur0 = jnp.zeros_like(micro_in[0])
    outputs0 = jnp.zeros_like(micro_in)
    mark_varying, mv_tree = _vma_markers(micro_in, axis_name)
    cur0, outputs0 = mark_varying(cur0), mark_varying(outputs0)
    if with_aux:
        aux_shape = jax.eval_shape(
            lambda: stage_fn(
                params, cur0,
                *(() if rng is None else (jax.random.PRNGKey(0),)),
            )[1]
        )
        # Rank-0 aux leaves are carried as (1,): a scalar scan carry at the
        # shard_map boundary becomes a rank-0 residual, which old JAX's
        # shard_map transpose mis-specs ("rank 0 outputs which are not
        # constant over the mesh") — the singleton axis sidesteps it on
        # every version; pipeline_forward squeezes it back outside.
        aux0 = mv_tree(jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape or (1,), jnp.float32), aux_shape
        ))
    else:
        aux0 = ()
    if boundary_has_residual(boundary_compress):
        bresid0 = mark_varying(
            jnp.zeros(cur0.shape, jnp.float32)
        )
    else:
        bresid0 = ()
    body = jax.checkpoint(tick) if remat_ticks else tick
    (_, outputs, aux_acc, _), _ = lax.scan(
        body, (cur0, outputs0, aux0, bresid0), jnp.arange(ticks)
    )
    # Only the last stage holds real outputs; broadcast them to every stage
    # so the shard_map out_spec can be replicated.
    src = num_stages - 1
    outputs = jnp.where(my_stage == src, outputs, jnp.zeros_like(outputs))
    outputs = lax.psum(outputs, axis_name)
    if not with_aux:
        return outputs
    aux_total = jax.tree_util.tree_map(
        lambda a: lax.psum(a, axis_name), aux_acc
    )
    if aux_mean_axes:
        aux_total = jax.tree_util.tree_map(
            lambda a: lax.pmean(a, aux_mean_axes), aux_total
        )
    # Undo the (1,) carry promotion: callers get stage_fn's own aux shapes.
    aux_total = jax.tree_util.tree_map(
        lambda a, s: a.reshape(s.shape), aux_total, aux_shape
    )
    return outputs, aux_total


def _act_zeros(first_fn, first_params, x0, key):
    """Zeros shaped like one stage activation (= first_fn's output)."""
    if key is None:
        ev = jax.eval_shape(first_fn, first_params, x0)
    else:
        ev = jax.eval_shape(first_fn, first_params, x0, key)
    return jnp.zeros(ev.shape, ev.dtype)


def fsdp_gather_leaves(tree: Any, specs: Any) -> Any:
    """All-gather each leaf's fsdp-sharded dim (named in its spec).

    Shared by the GPipe per-tick stage-body gather (gpt2_pipeline) and the
    manual schedules' hoisted pre-scan gather.  Leaves whose spec has no
    ``fsdp`` entry (biases, norm scales) pass through."""
    from ..comm.mesh import AXIS_FSDP

    def gather(leaf, spec):
        for i, entry in enumerate(tuple(spec)):
            if entry == AXIS_FSDP:
                return lax.all_gather(leaf, AXIS_FSDP, axis=i, tiled=True)
        return leaf

    return jax.tree_util.tree_map(gather, tree, specs)


def _finalize_fsdp_grads(
    gacc: Any, gather_specs: Any, fsdp_size: int, batch_used: tuple[str, ...]
) -> Any:
    """Cross-shard combine for stage grads accumulated in GATHERED (full)
    form by the manual-schedule engines.

    The engines differentiate w.r.t. the hoisted-gather params, so each
    device holds full-shape stage grads from its own microbatch shard.
    fsdp-sharded leaves take one ``psum_scatter`` over ``fsdp`` (the vjp
    of the pre-scan all_gather, done HERE — branch-free, after the scan —
    instead of inside the cond-gated backward ticks) divided by the axis
    size, so the result is the fsdp mean already in sharded layout;
    remaining batch axes are pmean'd as usual.  Unsharded leaves pmean
    over every batch axis."""
    from ..comm.mesh import AXIS_FSDP

    other = tuple(a for a in batch_used if a != AXIS_FSDP)

    def finalize(g, spec):
        entries = tuple(spec)
        if AXIS_FSDP in entries:
            d = entries.index(AXIS_FSDP)
            g = lax.psum_scatter(
                g, AXIS_FSDP, scatter_dimension=d, tiled=True
            ) / fsdp_size
            return lax.pmean(g, other) if other else g
        return lax.pmean(g, batch_used) if batch_used else g

    return jax.tree_util.tree_map(finalize, gacc, gather_specs)


def _combine_accumulators(
    gacc, facc, lacc, loss_acc, *, inputs, axis_name, gather_specs, fsdp_size,
):
    """Post-scan cross-batch-shard combine shared by both manual engines.

    Batch-sharded microbatches: each data row saw 1/D of every microbatch
    and its last_fn mean covered only that slice, so the cross-shard
    combine is a pmean — for the per-example-mean losses these engines
    serve (CE), mean-of-shard-means == the global mean, and grads scale
    identically.  With ``gather_specs`` the stage grads instead take the
    psum-scatter path (``_finalize_fsdp_grads``)."""
    # The microbatches' own varying-axes type says exactly which mesh
    # axes they were sharded over.
    batch_used = tuple(a for a in typeof(inputs).vma if a != axis_name)
    if gather_specs is not None:
        gacc = _finalize_fsdp_grads(gacc, gather_specs, fsdp_size, batch_used)
        if batch_used:
            facc, lacc, loss_acc = lax.pmean(
                (facc, lacc, loss_acc), batch_used
            )
    elif batch_used:
        gacc, facc, lacc, loss_acc = lax.pmean(
            (gacc, facc, lacc, loss_acc), batch_used
        )
    return gacc, facc, lacc, loss_acc


def _1f1b_local(
    first_params: Any,
    stage_params: Any,
    last_params: Any,
    inputs: jax.Array,
    targets: jax.Array,
    rng: jax.Array | None,
    *,
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    axis_name: str,
    num_stages: int,
    gather_specs: Any = None,
    fsdp_size: int = 1,
    boundary_compress: str = "none",
    boundary_stripe: int = 1,
):
    """Runs inside shard_map: the 1F1B tick loop for one stage.

    Schedule (unit-time fwd/bwd ticks, derived from the last stage's
    F0 B0 F1 B1... cadence and the 1-tick ppermute hops):
      warmup forwards  : stage s runs fwd f at tick  s + f        for f < w_s
      steady forwards  : fwd f at tick  2S - s + 2(f - w_s)       for f >= w_s
      backwards        : bwd b at tick  2S - 1 - s + 2b
    with w_s = min(M, S - s) in-flight microbatches — the 1F1B memory
    bound.  Total ticks 2(M + S - 1); fwd and bwd ticks never collide on a
    stage (opposite parities), so each tick takes exactly one lax.cond
    branch and idle ticks cost ~nothing.
    """
    s = lax.axis_index(axis_name)
    S = num_stages
    M = inputs.shape[0]
    T = 2 * (M + S - 1)
    perm_next = [(i, (i + 1) % S) for i in range(S)]
    perm_prev = [(i, (i - 1) % S) for i in range(S)]
    is_last = s == S - 1
    is_first = s == 0

    def key_first(f):
        # Stage-independent (salt S, outside 0..S-1): stage 0's fwd and its
        # bwd recompute must draw the identical embed-dropout mask.
        return jax.random.fold_in(jax.random.fold_in(rng, f), S)

    def key_stage(f):
        return jax.random.fold_in(jax.random.fold_in(rng, f), s)

    def apply_first(fp, f):
        x_raw = inputs[jnp.clip(f, 0, M - 1)]
        if rng is None:
            return first_fn(fp, x_raw)
        return first_fn(fp, x_raw, key_first(f))

    def apply_stage(p, x, f):
        if rng is None:
            return stage_fn(p, x)
        return stage_fn(p, x, key_stage(f))

    # Varying-axes marking (shared helper): every cond branch must agree on
    # which mesh axes its outputs vary over, so constants (zero
    # activations, zero grad trees) are pre-cast to the carry's varying set
    # — the pipeline axis plus whatever batch axes the microbatches use.
    mark_varying, mv_tree = _vma_markers(inputs, axis_name)

    # CRITICAL: differentiate only w.r.t. fully-varying values.  vjp w.r.t.
    # a replicated (unvarying) input inserts an implicit psum to reduce the
    # per-device cotangents — but here the vjps run inside lax.cond branches
    # whose predicates differ per stage, so that hidden collective would be
    # executed by a subset of devices and deadlock the mesh.  pcast is
    # comm-free; the explicit pmean/psum after the scan do the one combined
    # reduction instead.
    params = jax.tree_util.tree_map(lambda l: l[0], stage_params)
    if gather_specs is not None:
        # FSDP composition: all-gather the fsdp-sharded param dims HERE —
        # unconditionally, before the tick scan — so no collective ever
        # sits inside the cond-gated branches (the unsoundness the SP ban
        # cites).  Grads accumulate in gathered form; the matching
        # psum_scatter runs branch-free after the scan
        # (``_finalize_fsdp_grads``).
        params = fsdp_gather_leaves(params, gather_specs)
    params = mv_tree(params)
    first_params = mv_tree(first_params)
    last_params = mv_tree(last_params)

    act0 = mark_varying(_act_zeros(
        first_fn, first_params, inputs[0],
        None if rng is None else jax.random.PRNGKey(0),
    ))

    def fwd_sched(stage, t):
        """(did_fwd, microbatch index) for ``stage`` at tick ``t``."""
        ws = jnp.minimum(M, S - stage)
        f_warm = t - stage
        warm_ok = (f_warm >= 0) & (f_warm < ws)
        steady_off = t - (2 * S - stage)
        f_steady = ws + steady_off // 2
        steady_ok = (steady_off >= 0) & (steady_off % 2 == 0) & (f_steady < M)
        f = jnp.clip(jnp.where(warm_ok, f_warm, f_steady), 0, M - 1)
        return warm_ok | steady_ok, f

    bc_resid = boundary_has_residual(boundary_compress)

    def tick(carry, t):
        (y_send, cot_send, in_buf, x_buf, gacc, facc, lacc, loss_acc,
         rx, rc) = carry
        # Stage-boundary hops, optionally compressed (--pp-compress).
        # Both streams (activations forward, cotangents backward) go
        # through the codec; the int8 error-feedback residuals ride the
        # carry but only COMMIT on ticks where this stage actually sent a
        # fresh payload — idle ticks permute zeros the receiver never
        # banks, and letting them consume the residual would drain real
        # EF state into ignored junk.
        x_in, rx_new = boundary_permute(                     # from stage s-1
            y_send, rx, axis_name, perm_next, boundary_compress, boundary_stripe
        )
        cot_in, rc_new = boundary_permute(                   # from s+1
            cot_send, rc, axis_name, perm_prev, boundary_compress, boundary_stripe
        )
        if bc_resid:
            sent_fwd = fwd_sched(s, t - 1)[0]     # did fwd run last tick?
            boff_prev = (t - 1) - (2 * S - 1 - s)
            sent_bwd = (
                (boff_prev >= 0) & (boff_prev % 2 == 0)
                & (boff_prev // 2 < M)
            )
            rx = jnp.where(sent_fwd, rx_new, rx)
            rc = jnp.where(sent_bwd, rc_new, rc)

        # Stage s-1's warmup runs ahead of stage s's consumption (the gap
        # at the warmup->steady boundary exceeds one tick), so arrivals are
        # banked in a small circular buffer keyed by the SENDER's schedule
        # and read at this stage's own fwd ticks.  Max unconsumed arrivals
        # is bounded by the warmup-depth difference (< S), so S slots
        # suffice.
        sender_did, sender_f = fwd_sched(s - 1, t - 1)
        sender_did = sender_did & (s > 0)

        def bank(buf):
            return lax.dynamic_update_index_in_dim(buf, x_in, sender_f % S, 0)

        in_buf = lax.cond(sender_did, bank, lambda buf: buf, in_buf)

        do_f, f = fwd_sched(s, t)
        bwd_off = t - (2 * S - 1 - s)
        b = jnp.clip(bwd_off // 2, 0, M - 1)
        do_b = (bwd_off >= 0) & (bwd_off % 2 == 0) & (bwd_off // 2 < M)

        # --- forward tick ---
        def fwd_branch(xbuf):
            x = lax.cond(
                is_first,
                lambda: mark_varying(apply_first(first_params, f)),
                lambda: lax.dynamic_index_in_dim(in_buf, f % S, 0,
                                                 keepdims=False),
            )
            y = apply_stage(params, x, f)
            return lax.dynamic_update_index_in_dim(xbuf, x, f % S, 0), y

        x_buf, y_new = lax.cond(
            do_f, fwd_branch, lambda xbuf: (xbuf, jnp.zeros_like(act0)), x_buf
        )

        # --- backward tick (recompute-from-input remat + manual vjp) ---
        def bwd_branch(args):
            gacc, facc, lacc, loss_acc = args
            x_saved = lax.dynamic_index_in_dim(x_buf, b % S, 0, keepdims=False)
            y_b, vjp = jax.vjp(lambda p, xx: apply_stage(p, xx, b), params, x_saved)

            def seed_from_loss():
                def loss_of(lp, yy):
                    return last_fn(lp, yy, targets[b])

                loss_b, (lbar, ybar) = jax.value_and_grad(
                    loss_of, argnums=(0, 1)
                )(last_params, y_b)
                return mark_varying(loss_b), mv_tree(lbar), mark_varying(ybar)

            def seed_from_next():
                return (
                    mark_varying(jnp.zeros((), jnp.float32)),
                    mv_tree(jax.tree_util.tree_map(jnp.zeros_like, last_params)),
                    cot_in,
                )

            loss_b, lbar, ybar = lax.cond(is_last, seed_from_loss, seed_from_next)
            pbar, xbar = vjp(ybar)

            def first_grads():
                _, first_vjp = jax.vjp(
                    lambda fp: apply_first(fp, b), first_params
                )
                return first_vjp(xbar)[0]

            fbar = lax.cond(
                is_first, lambda: mv_tree(first_grads()),
                lambda: mv_tree(
                    jax.tree_util.tree_map(jnp.zeros_like, first_params)
                ),
            )
            gacc = jax.tree_util.tree_map(lambda a, g: a + g, gacc, pbar)
            facc = jax.tree_util.tree_map(lambda a, g: a + g, facc, fbar)
            lacc = jax.tree_util.tree_map(lambda a, g: a + g, lacc, lbar)
            return (gacc, facc, lacc, loss_acc + loss_b), xbar

        def bwd_skip(args):
            return args, jnp.zeros_like(act0)

        (gacc, facc, lacc, loss_acc), xbar_new = lax.cond(
            do_b, bwd_branch, bwd_skip, (gacc, facc, lacc, loss_acc)
        )
        return (
            y_new, xbar_new, in_buf, x_buf, gacc, facc, lacc, loss_acc,
            rx, rc,
        ), None

    x_buf0 = jnp.broadcast_to(act0, (S,) + act0.shape)
    resid0 = (
        jnp.zeros(act0.shape, jnp.float32) if bc_resid else ()
    )
    carry0 = jax.tree_util.tree_map(mark_varying, (
        act0, act0, x_buf0, x_buf0,
        jax.tree_util.tree_map(jnp.zeros_like, params),
        jax.tree_util.tree_map(jnp.zeros_like, first_params),
        jax.tree_util.tree_map(jnp.zeros_like, last_params),
        jnp.zeros((), jnp.float32),
        resid0, resid0,
    ))
    (_, _, _, _, gacc, facc, lacc, loss_acc, _, _), _ = lax.scan(
        _scoped_tick(tick), carry0, jnp.arange(T)
    )
    gacc, facc, lacc, loss_acc = _combine_accumulators(
        gacc, facc, lacc, loss_acc, inputs=inputs, axis_name=axis_name,
        gather_specs=gather_specs, fsdp_size=fsdp_size,
    )
    # Stage grads stay per-stage (leading axis restored); everything else
    # is nonzero on exactly one stage — psum replicates it.
    stacked = jax.tree_util.tree_map(lambda g: g[None], gacc)
    loss = lax.psum(loss_acc, axis_name)
    facc = lax.psum(facc, axis_name)
    lacc = lax.psum(lacc, axis_name)
    return loss, facc, stacked, lacc


def pipeline_train_1f1b(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    first_params: Any,
    stacked_params: Any,
    last_params: Any,
    inputs: jax.Array,
    targets: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_PIPELINE,
    rng: jax.Array | None = None,
    param_specs: Any = None,
    sequence_sharded: bool = False,
    fsdp_gather_specs: Any = None,
    boundary_compress: str = "none",
    boundary_stripe: int = 1,
):
    """Loss + grads for one training step under the 1F1B schedule.

    The GPipe path (``pipeline_forward`` under ``jax.grad``) leaves the
    backward to autodiff, which must retain residuals for all M + S - 1
    forward ticks — activation memory grows with the microbatch count M.
    1F1B (PipeDream-flush) interleaves stage backwards with later
    microbatch forwards so at most ``min(S - s, M)`` saved stage inputs are
    live per stage, and each backward recomputes its stage from that saved
    input (per-stage remat).  Memory is bounded by S, not M; the bubble
    fraction (S-1)/(M+S-1) is identical to GPipe's (the *interleaved*
    variant, ``pipeline_train_interleaved``, divides it by the chunk
    count).  Measured comparison: PIPELINE_SCHEDULES.json.

    Args:
      first_fn(first_params, inputs_mb[, key]): per-microbatch stage-0
        input producer (e.g. token embedding + positional).
      stage_fn(params, x[, key]): one stage (params = one stage's slice).
      last_fn(last_params, y_mb, targets_mb) -> scalar: per-microbatch
        loss INCLUDING any 1/M averaging (each microbatch's loss cotangent
        is seeded with 1).
      inputs/targets: (M, mb, ...) arrays, microbatch-major.
      rng: optional dropout key; the backward's recompute folds the same
        (microbatch, stage) keys so masks replay exactly.
      sequence_sharded: additionally shard dim 2 (sequence) over the
        ``sequence`` mesh axis.  WARNING: sound here only for stage/
        first/last fns WITHOUT collectives (purely local sequence math,
        plus cross-shard-correct loss normalization) — a collective such
        as a ring-attention ppermute inside this engine's cond-gated
        branches returns wrong numerics (the canary
        tests/test_pipeline.py::test_collective_stage_needs_gpipe pins
        the repro); collective-bearing SP composes with the branch-free
        GPipe schedule instead (``gpt2_pipeline.PipelinedGPT2``).

      fsdp_gather_specs: optional pytree of PartitionSpecs over the
        STAGE-SLICED param leaves (leading stage dim dropped) naming the
        fsdp-sharded dims.  When given, the engine all-gathers those dims
        once before the tick scan (branch-free — sound under the
        cond-gated schedule, unlike a gather inside the stage body) and
        psum-scatters the accumulated grads after it, returning
        fsdp-sharded stage grads matching ``param_specs``.

    Returns ``(loss, (first_grads, stacked_stage_grads, last_grads))`` with
    ``loss`` = sum of per-microbatch losses.
    """
    from ..comm.mesh import AXIS_FSDP

    if boundary_compress not in PP_COMPRESS_MODES:
        raise ValueError(
            f"boundary_compress {boundary_compress!r} not in "
            f"{PP_COMPRESS_MODES}"
        )
    num_stages = mesh.shape[axis_name]
    local = functools.partial(
        _1f1b_local,
        first_fn=first_fn,
        stage_fn=stage_fn,
        last_fn=last_fn,
        axis_name=axis_name,
        num_stages=num_stages,
        gather_specs=fsdp_gather_specs,
        fsdp_size=mesh.shape.get(AXIS_FSDP, 1),
        boundary_compress=boundary_compress,
        boundary_stripe=boundary_stripe,
    )
    loss, fbar, stacked, lbar = _launch_schedule_local(
        local, mesh, first_params, stacked_params, last_params,
        inputs, targets, rng, param_specs, axis_name,
        sequence_sharded=sequence_sharded,
    )
    return loss, (fbar, stacked, lbar)


def _interleaved_local(
    first_params: Any,
    stage_params: Any,
    last_params: Any,
    inputs: jax.Array,
    targets: jax.Array,
    rng: jax.Array | None,
    *,
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    axis_name: str,
    sched: Any,
    gather_specs: Any = None,
    fsdp_size: int = 1,
    boundary_compress: str = "none",
    boundary_stripe: int = 1,
):
    """Runs inside shard_map: the interleaved-1F1B tick loop for one device.

    All scheduling is table-driven (``pipeline_schedule``): the scan body
    looks up this device's row of the precomputed tick tables and takes a
    ``lax.cond`` per action — fwd on one of this device's V chunks, bwd
    with recompute-from-saved-input, banking of ring arrivals.  Virtual
    stage vs = chunk * S + device, so chunk crossings use the same
    next-device ppermute edge as ordinary stage hops and no special wiring
    is needed at chunk boundaries.

    ``stage_params``: this device's slice, leaves (1, V, ...) — axis 0 is
    the (sharded) device axis, axis 1 the chunk.  Differentiation follows
    the non-interleaved engine's rule: everything differentiated inside
    per-device cond branches must be fully varying (pcast), or vjp's
    implicit psum for replicated inputs would deadlock the mesh.
    """
    s = lax.axis_index(axis_name)
    S, V, M, T = sched.S, sched.V, sched.M, sched.T
    perm_next = [(i, (i + 1) % S) for i in range(S)]
    perm_prev = [(i, (i - 1) % S) for i in range(S)]

    # Device row of each tick table, gathered once (S is the mesh axis).
    tb = {
        name: jnp.asarray(getattr(sched, name))[s]
        for name in (
            "f_do", "f_chunk", "f_mb", "f_first", "f_in_slot", "f_save_slot",
            "r_do", "r_slot", "b_do", "b_chunk", "b_mb", "b_first",
            "b_seed_loss", "b_cot_slot", "b_x_slot", "c_do", "c_slot",
        )
    }

    mark_varying, mv_tree = _vma_markers(inputs, axis_name)
    params = jax.tree_util.tree_map(lambda l: l[0], stage_params)
    if gather_specs is not None:
        # Hoisted FSDP gather — branch-free, before the scan; see
        # ``_1f1b_local`` (identical rationale).  ``gather_specs`` entries
        # cover the sliced (V, ...) leaves, chunk dim included.
        params = fsdp_gather_leaves(params, gather_specs)
    params = mv_tree(params)
    first_params = mv_tree(first_params)
    last_params = mv_tree(last_params)

    def key_first(m):
        # Chunk-0 fwd and its bwd recompute share the embed-dropout mask;
        # salt S*V sits outside every virtual-stage salt.
        return jax.random.fold_in(jax.random.fold_in(rng, m), S * V)

    def apply_first(fp, m):
        x_raw = inputs[jnp.clip(m, 0, M - 1)]
        if rng is None:
            return first_fn(fp, x_raw)
        return first_fn(fp, x_raw, key_first(m))

    def apply_chunk(p_chunk, x, m, chunk):
        if rng is None:
            return stage_fn(p_chunk, x)
        vs = chunk * S + s
        key = jax.random.fold_in(jax.random.fold_in(rng, m), vs)
        return stage_fn(p_chunk, x, key)

    act0 = mark_varying(_act_zeros(
        first_fn, first_params, inputs[0],
        None if rng is None else jax.random.PRNGKey(0),
    ))

    bc_resid = boundary_has_residual(boundary_compress)

    def tick(carry, t):
        (y_send, cot_send, in_buf, x_buf, cot_buf,
         gacc, facc, lacc, loss_acc, rx, rc) = carry
        # Compressed stage-boundary hops (--pp-compress): same contract as
        # the non-interleaved engine — int8 EF residuals ride the carry
        # and commit only on ticks whose send was real (the tick tables
        # say whether THIS device ran a fwd/bwd last tick).
        x_in, rx_new = boundary_permute(                     # from s-1
            y_send, rx, axis_name, perm_next, boundary_compress, boundary_stripe
        )
        cot_in, rc_new = boundary_permute(                   # from s+1
            cot_send, rc, axis_name, perm_prev, boundary_compress, boundary_stripe
        )
        if bc_resid:
            prev = jnp.maximum(t - 1, 0)
            sent_fwd = (t > 0) & (tb["f_do"][prev] == 1)
            sent_bwd = (t > 0) & (tb["b_do"][prev] == 1)
            rx = jnp.where(sent_fwd, rx_new, rx)
            rc = jnp.where(sent_bwd, rc_new, rc)

        in_buf = lax.cond(
            tb["r_do"][t] == 1,
            lambda buf: lax.dynamic_update_index_in_dim(
                buf, x_in, tb["r_slot"][t], 0
            ),
            lambda buf: buf,
            in_buf,
        )
        cot_buf = lax.cond(
            tb["c_do"][t] == 1,
            lambda buf: lax.dynamic_update_index_in_dim(
                buf, cot_in, tb["c_slot"][t], 0
            ),
            lambda buf: buf,
            cot_buf,
        )

        # --- forward tick ---
        def fwd_branch(x_buf):
            m, chunk = tb["f_mb"][t], tb["f_chunk"][t]
            x = lax.cond(
                tb["f_first"][t] == 1,
                lambda: mark_varying(apply_first(first_params, m)),
                lambda: lax.dynamic_index_in_dim(
                    in_buf, tb["f_in_slot"][t], 0, keepdims=False
                ),
            )
            p_chunk = jax.tree_util.tree_map(
                lambda l: lax.dynamic_index_in_dim(l, chunk, 0,
                                                   keepdims=False),
                params,
            )
            y = apply_chunk(p_chunk, x, m, chunk)
            x_buf = lax.dynamic_update_index_in_dim(
                x_buf, x, tb["f_save_slot"][t], 0
            )
            return x_buf, y

        x_buf, y_new = lax.cond(
            tb["f_do"][t] == 1,
            fwd_branch,
            lambda x_buf: (x_buf, jnp.zeros_like(act0)),
            x_buf,
        )

        # --- backward tick (recompute-from-input remat + manual vjp) ---
        def bwd_branch(args):
            gacc, facc, lacc, loss_acc = args
            m, chunk = tb["b_mb"][t], tb["b_chunk"][t]
            x_saved = lax.dynamic_index_in_dim(
                x_buf, tb["b_x_slot"][t], 0, keepdims=False
            )
            p_chunk = jax.tree_util.tree_map(
                lambda l: lax.dynamic_index_in_dim(l, chunk, 0,
                                                   keepdims=False),
                params,
            )
            y_b, vjp = jax.vjp(
                lambda p, xx: apply_chunk(p, xx, m, chunk), p_chunk, x_saved
            )

            def seed_from_loss():
                def loss_of(lp, yy):
                    return last_fn(lp, yy, targets[jnp.clip(m, 0, M - 1)])

                loss_b, (lbar, ybar) = jax.value_and_grad(
                    loss_of, argnums=(0, 1)
                )(last_params, y_b)
                return mark_varying(loss_b), mv_tree(lbar), mark_varying(ybar)

            def seed_from_buffer():
                return (
                    mark_varying(jnp.zeros((), jnp.float32)),
                    mv_tree(jax.tree_util.tree_map(
                        jnp.zeros_like, last_params
                    )),
                    lax.dynamic_index_in_dim(
                        cot_buf, tb["b_cot_slot"][t], 0, keepdims=False
                    ),
                )

            loss_b, lbar, ybar = lax.cond(
                tb["b_seed_loss"][t] == 1, seed_from_loss, seed_from_buffer
            )
            pbar, xbar = vjp(ybar)

            def first_grads():
                _, first_vjp = jax.vjp(
                    lambda fp: apply_first(fp, m), first_params
                )
                return first_vjp(xbar)[0]

            fbar = lax.cond(
                tb["b_first"][t] == 1,
                lambda: mv_tree(first_grads()),
                lambda: mv_tree(
                    jax.tree_util.tree_map(jnp.zeros_like, first_params)
                ),
            )
            gacc = jax.tree_util.tree_map(
                lambda a, g: a.at[chunk].add(g), gacc, pbar
            )
            facc = jax.tree_util.tree_map(lambda a, g: a + g, facc, fbar)
            lacc = jax.tree_util.tree_map(lambda a, g: a + g, lacc, lbar)
            return (gacc, facc, lacc, loss_acc + loss_b), xbar

        def bwd_skip(args):
            return args, jnp.zeros_like(act0)

        (gacc, facc, lacc, loss_acc), xbar_new = lax.cond(
            tb["b_do"][t] == 1, bwd_branch, bwd_skip,
            (gacc, facc, lacc, loss_acc),
        )
        return (
            y_new, xbar_new, in_buf, x_buf, cot_buf,
            gacc, facc, lacc, loss_acc, rx, rc,
        ), None

    def buf(n):
        return jnp.broadcast_to(act0, (n,) + act0.shape)

    resid0 = (
        jnp.zeros(act0.shape, jnp.float32) if bc_resid else ()
    )
    carry0 = jax.tree_util.tree_map(mark_varying, (
        act0, act0,
        buf(sched.n_in_slots), buf(sched.n_x_slots), buf(sched.n_cot_slots),
        jax.tree_util.tree_map(jnp.zeros_like, params),
        jax.tree_util.tree_map(jnp.zeros_like, first_params),
        jax.tree_util.tree_map(jnp.zeros_like, last_params),
        jnp.zeros((), jnp.float32),
        resid0, resid0,
    ))
    (_, _, _, _, _, gacc, facc, lacc, loss_acc, _, _), _ = lax.scan(
        _scoped_tick(tick), carry0, jnp.arange(T)
    )
    gacc, facc, lacc, loss_acc = _combine_accumulators(
        gacc, facc, lacc, loss_acc, inputs=inputs, axis_name=axis_name,
        gather_specs=gather_specs, fsdp_size=fsdp_size,
    )
    stacked = jax.tree_util.tree_map(lambda g: g[None], gacc)
    loss = lax.psum(loss_acc, axis_name)
    facc = lax.psum(facc, axis_name)
    lacc = lax.psum(lacc, axis_name)
    return loss, facc, stacked, lacc


def stack_virtual_stage_params(per_stage_params: list[Any], S: int) -> Any:
    """[vs0_tree, vs1_tree, ...] (len S*V, virtual-stage order) → one tree
    with leaves shaped (S, V, ...): axis 0 the device (shard over
    ``pipeline``), axis 1 the chunk — device s holds virtual stages
    ``{v*S + s}``."""
    SV = len(per_stage_params)
    if SV % S:
        raise ValueError(f"{SV} virtual stages not divisible by {S} devices")
    V = SV // S
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0).reshape(
            (V, S) + leaves[0].shape
        ).swapaxes(0, 1),
        *per_stage_params,
    )


def _micro_spec_for(
    mesh: Mesh,
    inputs: jax.Array,
    sequence_sharded: bool,
    param_specs: Any = None,
) -> P:
    """PartitionSpec for (M, mb, L, ...) microbatch stacks: batch axes on
    dim 1 when divisible (tiny standalone uses fall back to replication),
    plus — opt-in, because the stage function must speak ring attention
    for it to be correct — the ``sequence`` axis on dim 2."""
    from ..comm.mesh import AXIS_FSDP

    batch_extent = 1
    for a in BATCH_AXES:
        batch_extent *= mesh.shape[a]
    divisible = inputs.shape[1] % batch_extent == 0
    if not divisible and param_specs is not None and any(
        AXIS_FSDP in tuple(s) for s in jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P)
        )
    ):
        # FSDP-sharded stage params make the per-tick gathered
        # activations fsdp-varying; with a replicated microbatch fallback
        # the outputs could not satisfy a replicated out_spec.  FSDP is
        # data parallelism with sharded params — the batch must shard
        # over its axis.
        raise ValueError(
            f"fsdp-sharded stage params need the per-microbatch size "
            f"({inputs.shape[1]}) divisible by the batch axes extent "
            f"({batch_extent})"
        )
    entries: list[Any] = [None, BATCH_AXES if divisible else None]
    if sequence_sharded:
        seq = mesh.shape[AXIS_SEQUENCE]
        if inputs.ndim < 3 or inputs.shape[2] % seq:
            raise ValueError(
                f"sequence_sharded needs dim 2 divisible by the sequence "
                f"axis ({seq}); got shape {inputs.shape}"
            )
        entries.append(AXIS_SEQUENCE)
    return P(*entries)


def _launch_schedule_local(
    local: Callable,
    mesh: Mesh,
    first_params: Any,
    stacked_params: Any,
    last_params: Any,
    inputs: jax.Array,
    targets: jax.Array,
    rng: jax.Array | None,
    param_specs: Any,
    axis_name: str,
    sequence_sharded: bool = False,
):
    """Shared shard_map launcher for the manual-schedule engines (1F1B and
    interleaved): stage params shard over ``pipeline`` (or the caller's
    per-leaf specs), microbatches shard over the batch axes on dim 1 when
    divisible (tiny standalone uses fall back to replication) and — when
    the caller's stage functions are sequence-parallel-aware — over the
    ``sequence`` axis on dim 2.  Returns the local fn's (loss,
    first_grads, stacked_stage_grads, last_grads)."""
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), stacked_params
        )
    micro_spec = _micro_spec_for(mesh, inputs, sequence_sharded, param_specs)
    replicated = P()
    if rng is None:
        fn = shard_map(
            lambda fp, sp, lp, i, t: local(fp, sp, lp, i, t, None),
            mesh=mesh,
            in_specs=(
                replicated, param_specs, replicated, micro_spec, micro_spec,
            ),
            out_specs=(replicated, replicated, param_specs, replicated),
        )
        return fn(first_params, stacked_params, last_params, inputs, targets)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            replicated, param_specs, replicated, micro_spec, micro_spec,
            replicated,
        ),
        out_specs=(replicated, replicated, param_specs, replicated),
    )
    return fn(first_params, stacked_params, last_params, inputs, targets, rng)


def pipeline_train_interleaved(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    first_params: Any,
    stacked_params: Any,
    last_params: Any,
    inputs: jax.Array,
    targets: jax.Array,
    mesh: Mesh,
    *,
    num_chunks: int,
    axis_name: str = AXIS_PIPELINE,
    rng: jax.Array | None = None,
    param_specs: Any = None,
    sequence_sharded: bool = False,
    fsdp_gather_specs: Any = None,
    boundary_compress: str = "none",
    boundary_stripe: int = 1,
):
    """Loss + grads for one training step under interleaved 1F1B.

    The interleaved (multi-chunk) schedule assigns each device V =
    ``num_chunks`` model chunks — virtual stage vs = chunk * S + device —
    so the pipeline ramp crosses each device V times with 1/V-sized stage
    work, dividing the bubble by ~V at the cost of ~V× the in-flight
    activations of non-interleaved 1F1B and V-1 extra ring hops per
    microbatch (Megatron-LM's schedule; generated and statically verified
    by ``pipeline_schedule.make_interleaved_schedule``, measured bubble
    rows in PIPELINE_SCHEDULES.json).

    Args match ``pipeline_train_1f1b`` except ``stacked_params``: leaves
    are (S, V, ...) — axis 0 sharded over ``pipeline``, axis 1 the chunk
    (``stack_virtual_stage_params``).  ``stage_fn(params, x[, key])`` runs
    ONE chunk (1/(S·V) of the model).  Returns ``(loss, (first_grads,
    stacked_stage_grads, last_grads))``.  ``fsdp_gather_specs``: as in
    ``pipeline_train_1f1b`` — specs over the sliced (V, ...) leaves.
    """
    from ..comm.mesh import AXIS_FSDP
    from .pipeline_schedule import make_interleaved_schedule

    if boundary_compress not in PP_COMPRESS_MODES:
        raise ValueError(
            f"boundary_compress {boundary_compress!r} not in "
            f"{PP_COMPRESS_MODES}"
        )
    num_stages = mesh.shape[axis_name]
    M = inputs.shape[0]
    sched = make_interleaved_schedule(num_stages, num_chunks, M)
    local = functools.partial(
        _interleaved_local,
        first_fn=first_fn,
        stage_fn=stage_fn,
        last_fn=last_fn,
        axis_name=axis_name,
        sched=sched,
        gather_specs=fsdp_gather_specs,
        fsdp_size=mesh.shape.get(AXIS_FSDP, 1),
        boundary_compress=boundary_compress,
        boundary_stripe=boundary_stripe,
    )
    loss, fbar, stacked, lbar = _launch_schedule_local(
        local, mesh, first_params, stacked_params, last_params,
        inputs, targets, rng, param_specs, axis_name,
        sequence_sharded=sequence_sharded,
    )
    return loss, (fbar, stacked, lbar)


def pipeline_forward(
    stage_fn: Callable[..., jax.Array],
    stacked_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_PIPELINE,
    remat_ticks: bool = False,
    rng: jax.Array | None = None,
    param_specs: Any = None,
    sequence_sharded: bool = False,
    with_aux: bool = False,
    boundary_compress: str = "none",
    boundary_stripe: int = 1,
) -> jax.Array:
    """Run (M, mb, ...) microbatches through S pipelined stages.

    ``stacked_params`` leaves have a leading stage axis of size S =
    ``mesh.shape[axis_name]`` (see ``stack_stage_params``); ``stage_fn(params,
    x)`` is one stage's computation with x shaped like one microbatch.
    Returns the (M, mb, ...) outputs — equal to folding each microbatch
    through all S stages in order.  ``remat_ticks`` checkpoints each pipeline
    tick: the backward recomputes the stage function instead of storing its
    internals, bounding residual memory to the carried activations.
    ``rng`` switches stage_fn to the 3-arg form ``(params, x, key)`` with a
    per-(tick, stage) key — dropout inside pipelined stages.
    ``param_specs`` overrides the per-leaf in_specs (default: every leaf
    sharded over the stage axis only) — the PP x TP path passes specs that
    additionally shard Megatron kernel dims over ``tensor``.
    ``with_aux``: stage_fn returns ``(y, aux_scalars_tree)``; the call then
    returns ``(outputs, aux_tree)`` with valid-tick contributions summed
    over stages/microbatches and averaged over the batch axes (the MoE x PP
    path's load-balancing loss — see ``_pipeline_local``).
    ``boundary_compress`` (``--pp-compress``): compress the per-tick
    stage-boundary ppermute payloads — bf16 halves them; int8 quarters
    them with a per-token scale and error-feedback residuals carried in
    the tick scan, and the autodiff backward's cotangent permutes travel
    through the same codec (``comm.compress.boundary_permute``).
    """
    if boundary_compress not in PP_COMPRESS_MODES:
        raise ValueError(
            f"boundary_compress {boundary_compress!r} not in "
            f"{PP_COMPRESS_MODES}"
        )
    num_stages = mesh.shape[axis_name]
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), stacked_params
        )
    # Microbatches stay sharded over the data axes on their batch dim
    # (axis 1 of (M, mb, ...)): each data-parallel row pipelines only its
    # own batch slice — replicating here would nullify data parallelism.
    # Indivisible microbatch sizes (tiny standalone uses) fall back to
    # replication.  ``sequence_sharded`` additionally shards dim 2 (the
    # caller's stage_fn must then be SP-aware — ring attention).
    micro_spec = _micro_spec_for(mesh, microbatches, sequence_sharded, param_specs)
    # Axes the microbatches are actually sharded over (batch + sequence):
    # the aux scalars pmean over exactly these so their out_spec can be
    # fully replicated.
    aux_axes = tuple(
        a
        for dim in tuple(micro_spec)
        if dim is not None
        for a in ((dim,) if isinstance(dim, str) else tuple(dim))
    )
    local = functools.partial(
        _pipeline_local,
        stage_fn=stage_fn,
        axis_name=axis_name,
        num_stages=num_stages,
        remat_ticks=remat_ticks,
        with_aux=with_aux,
        aux_mean_axes=aux_axes if with_aux else (),
        boundary_compress=boundary_compress,
        boundary_stripe=boundary_stripe,
    )
    out_specs = (micro_spec, P()) if with_aux else micro_spec
    if rng is None:
        fn = shard_map(
            lambda p, m: local(p, m, None),
            mesh=mesh,
            in_specs=(param_specs, micro_spec),
            out_specs=out_specs,
        )
        return fn(stacked_params, microbatches)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, micro_spec, P()),
        out_specs=out_specs,
    )
    return fn(stacked_params, microbatches, rng)

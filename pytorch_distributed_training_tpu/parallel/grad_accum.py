"""Gradient accumulation as a ``lax.scan`` over microbatches.

Absent from the reference (its loop at src/main.py:68-79 steps the optimizer
every batch) but required by BASELINE.json configs[3] (GPT-2 + gradient
accumulation).  The torch idiom — N forward/backwards before one
``optimizer.step()`` — becomes a single jitted scan: the microbatch loop is
*inside* the compiled step, the gradient sums are f32 arrays in HBM that
the loop carries from one microbatch to the next, and the optimizer update
follows the last microbatch in the same program.

Which rows make a microbatch: under a mesh the batch is sharded on its
leading axis over ``BATCH_AXES`` (``parallel/sharding.py::batch_sharding``),
each chip holding one contiguous block of rows.  Microbatch *i* is then the
*i*-th slice of EVERY chip's own block — DDP's ``no_sync`` accumulation,
every rank over microbatches of its own rows — so the split moves nothing
between chips and the scanned microbatch keeps the batch sharding.
(Contiguous microbatches ``i*m … (i+1)*m - 1`` would each live on a few of
the chips, and GSPMD re-gathers activations inside the loop to spread
them.)  With no mesh, one batch shard, or inside a ``shard_map`` body (the
batch is one device's rows already) microbatches are contiguous.  The
step's mean is over the same samples either way.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..comm.mesh import BATCH_AXES
from ..compat import ambient_mesh
from ..obs.trace import scope


def _batch_shards() -> int:
    """How many contiguous blocks of rows the batch's leading axis is cut
    into where this is traced: the ambient mesh's ``BATCH_AXES`` sizes,
    or 1 with no mesh and inside a ``shard_map`` body."""
    mesh, manual = ambient_mesh()
    if mesh is None or manual:
        return 1
    # .get: an ambient mesh need not be one of ours with all six axes.
    return math.prod(mesh.shape.get(a, 1) for a in BATCH_AXES)


def _split_microbatches(batch: Any, num_microbatches: int) -> Any:
    """(N*m, ...) leaves → (num_microbatches, m, ...) leaves.

    Microbatch *i* takes the *i*-th ``m / ways`` rows of each of the
    ``ways`` batch shards (module docstring); where the shards do not
    divide ``m``, or ``ways`` is 1, rows ``i*m … (i+1)*m - 1``."""
    ways = _batch_shards()

    def split(x):
        if x.shape[0] % num_microbatches != 0:
            raise ValueError(
                f"batch dim {x.shape[0]} not divisible by "
                f"num_microbatches={num_microbatches}"
            )
        m, rest = x.shape[0] // num_microbatches, x.shape[1:]
        if ways > 1 and m % ways == 0:
            # Every step keeps the leading axis' ways-fold sharding, so
            # each chip only re-indexes its own rows.
            x = x.reshape(ways, num_microbatches, m // ways, *rest)
            x = x.swapaxes(0, 1)
        return x.reshape(num_microbatches, m, *rest)
    return jax.tree_util.tree_map(split, batch)


def accumulate_gradients(
    loss_fn: Callable[[Any, Any], Any],
    params: Any,
    batch: Any,
    num_microbatches: int,
    *,
    has_aux: bool = False,
    pass_microbatch_index: bool = False,
    sync_fn: Callable | None = None,
    sync_carry: Any = (),
    sync_overlap: bool = True,
):
    """Mean loss/grads of ``loss_fn`` over ``num_microbatches`` splits of ``batch``.

    ``loss_fn(params, microbatch)`` → scalar loss (or ``(loss, aux)`` with
    ``has_aux``).  Returns ``(loss, grads)`` or ``((loss, aux), grads)``,
    exactly matching ``jax.value_and_grad``'s contract so callers can swap
    this in for the non-accumulated path.  Aux values are averaged.

    ``pass_microbatch_index`` calls ``loss_fn(params, microbatch, i)`` with
    the scan index so per-microbatch randomness (dropout keys) can decorrelate
    across the accumulation.

    ``sync_fn(grads_f32_tree, carry) -> (synced_tree, carry)`` plugs in an
    explicit cross-device gradient sync (comm/hierarchical.GradSync's
    two-tier reduce; only meaningful inside shard_map, where gradients are
    per-device partials).  The return gains a third element, the final
    carry (error-feedback residuals).  With ``sync_overlap`` the scan syncs
    microbatch *i−1*'s gradients while microbatch *i*'s fwd+bwd computes —
    the sync has no data dependency on the current microbatch, so XLA's
    latency-hiding scheduler interleaves the transfer with compute (DDP's
    bucket overlap, as dataflow).  Without it, one sync runs on the
    accumulated sum after the scan (DDP's ``no_sync`` contract: M× less
    traffic, no interleave).

    With ``num_microbatches == 1`` this reduces to plain value_and_grad with
    no scan overhead (plus the single sync when ``sync_fn`` is given).
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=has_aux)
    if pass_microbatch_index:
        base_call = grad_fn
    else:
        base_call = lambda p, m, i: grad_fn(p, m)

    def call(p, m, i):
        # Trace-time phase name for one microbatch's fwd+bwd — xprof/HLO
        # metadata (obs/trace.py scope), NOT a host span: the scan body
        # runs inside one compiled program, where a host clock would
        # record trace time (graftcheck: host-clock-in-trace).  The host
        # span for the whole step carries microbatch count as an attr.
        with scope("grad_accum/microbatch"):
            return base_call(p, m, i)

    def to_f32(tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), tree
        )

    def cast_like_params(grads):
        return jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), grads, params
        )

    tree_add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)

    if num_microbatches <= 1:
        value, grads = call(params, batch, jnp.zeros((), jnp.int32))
        if sync_fn is None:
            return value, grads
        synced, sync_carry = sync_fn(to_f32(grads), sync_carry)
        return value, cast_like_params(synced), sync_carry

    micro = _split_microbatches(batch, num_microbatches)
    idx = jnp.arange(num_microbatches, dtype=jnp.int32)
    # f32 accumulators regardless of compute dtype: N bf16 adds lose bits.
    zero_grads = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    inv = 1.0 / num_microbatches

    if sync_fn is not None and sync_overlap:
        # Pipelined: microbatch 0 computes before the scan; each scan step
        # computes microbatch i while syncing i−1's gradients (held in the
        # carry); the last microbatch syncs after the scan.  Every add goes
        # through the synced tree, so the accumulator IS the running global
        # mean numerator.
        first = jax.tree_util.tree_map(lambda x: x[0], micro)
        rest = jax.tree_util.tree_map(lambda x: x[1:], micro)
        value0, grads0 = call(params, first, idx[0])

        def body(carry, inputs):
            i, microbatch = inputs
            acc_value, acc_grads, pending, sc = carry
            value, grads = call(params, microbatch, i)
            synced, sc = sync_fn(pending, sc)
            acc_value = tree_add(acc_value, value)
            acc_grads = tree_add(acc_grads, synced)
            return (acc_value, acc_grads, to_f32(grads), sc), None

        (value, acc_grads, pending, sync_carry), _ = jax.lax.scan(
            body,
            (to_f32(value0), zero_grads, to_f32(grads0), sync_carry),
            (idx[1:], rest),
        )
        synced, sync_carry = sync_fn(pending, sync_carry)
        acc_grads = tree_add(acc_grads, synced)
        value = jax.tree_util.tree_map(lambda v: v * inv, value)
        grads = cast_like_params(
            jax.tree_util.tree_map(lambda g: g * inv, acc_grads)
        )
        return value, grads, sync_carry

    def body(carry, inputs):
        i, microbatch = inputs
        value, grads = call(params, microbatch, i)
        acc_value, acc_grads = carry
        acc_value = tree_add(acc_value, value)
        acc_grads = tree_add(acc_grads, grads)
        return (acc_value, acc_grads), None

    zero_value = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.float32),
        jax.eval_shape(
            lambda m: call(params, m, jnp.zeros((), jnp.int32))[0],
            jax.tree_util.tree_map(lambda x: x[0], micro),
        ),
    )
    (value, grads), _ = jax.lax.scan(
        body, (zero_value, zero_grads), (idx, micro)
    )

    value = jax.tree_util.tree_map(lambda v: v * inv, value)
    if sync_fn is not None:
        synced, sync_carry = sync_fn(grads, sync_carry)
        grads = cast_like_params(
            jax.tree_util.tree_map(lambda g: g * inv, synced)
        )
        return value, grads, sync_carry
    grads = jax.tree_util.tree_map(
        lambda g, p: (g * inv).astype(p.dtype), grads, params
    )
    return value, grads

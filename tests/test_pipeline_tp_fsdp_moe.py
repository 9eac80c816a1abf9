"""Tests for pipeline parallelism composed with the other axes: PP x TP
(Megatron blocks inside the stage), PP x FSDP (ZeRO-3-sharded stage
params) and MoE x PP, each against the plain model.  The pipeline alone,
1F1B, the interleaved schedule, SP x PP and the compressed stage payloads
are in tests/test_pipeline.py, whose plain GPT-2 reference this file
shares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compiled
from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
from test_pipeline import plain_gpt2, plain_logits


# ---------------------------------------------------------------------------
# PP x TP (Megatron blocks inside the pipeline stage function)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
def test_pp_x_tp_matches_plain(devices8, schedule):
    """PipelinedGPT2 over (data=2, pipeline=2, tensor=2): loss and every
    merged grad leaf equal the plain model under BOTH schedules.  The
    stage body is the manual Megatron block (_tp_block) — explicit fwd
    psums after row-parallel matmuls; backward reductions from shard_map's
    varying-axes AD."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params_pp_tp, split_gpt2_params_pp_tp,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=16, num_layers=4, num_heads=4,
        hidden_dim=32, dropout_rate=0.0,
    )
    mesh = make_mesh(MeshConfig(data=2, pipeline=2, tensor=2))
    tokens, variables, ref_loss, ref_grads = plain_gpt2(cfg, (4, 16))

    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2, schedule=schedule)
    chunks = pp.num_chunks if pp.num_chunks > 1 else 0
    pp_params = split_gpt2_params_pp_tp(
        variables["params"], 2, cfg.num_heads, num_chunks=chunks
    )
    with mesh:
        if schedule == "gpipe":
            def loss_fn(p):
                logits = pp.apply({"params": p}, tokens, train=False)
                return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(pp_params)
        else:
            loss, grads = jax.jit(
                lambda p, t: pp.value_and_grad(p, t)
            )(pp_params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    merged = merge_gpt2_params_pp_tp(
        jax.tree.map(np.asarray, grads), 2, cfg.num_heads, num_chunks=chunks
    )
    from jax.flatten_util import ravel_pytree

    np.testing.assert_allclose(
        np.asarray(ravel_pytree(merged)[0]),
        np.asarray(ravel_pytree(ref_grads)[0]),
        rtol=5e-4, atol=1e-5, err_msg=f"schedule={schedule}",
    )


def test_pp_x_tp_qkv_permutation_roundtrip():
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        _permute_qkv_cols,
    )

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((8, 24)))  # D=8, 3*H(4)*dh(2)=24
    rt = _permute_qkv_cols(
        _permute_qkv_cols(k, num_heads=4), num_heads=4, inverse=True
    )
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(k))


def test_pp_x_tp_cli_smoke():
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--cpu-devices", "8", "--model", "gpt2",
            "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=4,hidden_dim=32,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--pipeline-parallel", "2",
            "--tensor-parallel", "2", "--pipeline-schedule", "1f1b",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "'pipeline': 2" in result.output
    assert "'tensor': 2" in result.output
    assert "training finished" in result.output


def test_pp_x_tp_dropout_trains_and_replays(devices8):
    """PP x TP WITH dropout: finite decreasing loss, and identical rng =>
    identical loss+grads (the 1F1B backward recompute must replay the same
    masks, and masks must be tensor-rank-invariant)."""
    import optax

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, make_pipeline_grad_fn, pp_tp_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=16, num_layers=2, num_heads=4,
        hidden_dim=32, dropout_rate=0.2,
    )
    mesh = make_mesh(MeshConfig(data=2, pipeline=2, tensor=2))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2, schedule="1f1b")
    tokens = jnp.zeros((4, 16), jnp.int32)
    batch = {
        "tokens": np.random.default_rng(3).integers(0, 128, (4, 16), np.int32)
    }

    def run():
        state = create_train_state(
            pp, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
            mesh=mesh, rules=pp_tp_rules(), init_kwargs={"train": False},
        )
        step = make_train_step(
            kind="lm", base_rng=jax.random.PRNGKey(5),
            grad_fn=make_pipeline_grad_fn(pp),
        )
        losses = []
        with mesh:
            for _ in range(3):
                state, m = step(state, shard_batch(batch, mesh))
                losses.append(float(m["loss"]))
        return losses, state

    losses1, s1 = run()
    losses2, s2 = run()
    assert np.isfinite(losses1).all()
    assert losses1[-1] < losses1[0]
    # Determinism: same seeds => identical trajectory (mask replay holds).
    np.testing.assert_allclose(losses1, losses2, rtol=0, atol=0)
    from jax.flatten_util import ravel_pytree

    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(jax.tree.map(np.asarray, s1.params))[0]),
        np.asarray(ravel_pytree(jax.tree.map(np.asarray, s2.params))[0]),
    )


# ---------------------------------------------------------------------------
# PP x FSDP (ZeRO-3-sharded stage params: per-tick gathers under gpipe,
# hoisted pre-scan gather under the manual schedules)
# ---------------------------------------------------------------------------


def test_pp_x_fsdp_gpipe_matches_plain(devices8):
    """GPipe x FSDP (and the SP x FSDP x PP triple): fsdp-sharded stage
    params all-gathered per tick; loss and every merged grad leaf equal
    the plain model."""
    from jax.flatten_util import ravel_pytree

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params, merge_gpt2_params_pp_tp,
        pp_fsdp_specs, split_gpt2_params, split_gpt2_params_pp_tp,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=4, num_heads=4,
        hidden_dim=256, dropout_rate=0.0,
    )
    tokens, variables, ref_loss, ref_grads = plain_gpt2(cfg, (8, 32))
    ref_flat = np.asarray(ravel_pytree(ref_grads)[0])

    mesh = make_mesh(MeshConfig(data=-1, pipeline=2, fsdp=2))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2, schedule="gpipe")
    pp_params = split_gpt2_params(variables["params"], 2)
    # The big kernels actually fsdp-shard; tiny leaves stay pipeline-only.
    specs = pp_fsdp_specs(pp_params["stages"], mesh)
    assert "fsdp" in tuple(specs["layer_0"]["attn"]["qkv"]["kernel"])
    assert tuple(specs["layer_0"]["ln1"]["scale"]) == ("pipeline",)

    def loss_fn(p, t):
        logits = pp.apply({"params": p}, t, train=False)
        return cross_entropy_loss(logits[:, :-1], t[:, 1:])

    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(pp_params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    merged = merge_gpt2_params(jax.tree.map(np.asarray, grads), 2)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(merged)[0]), ref_flat, rtol=5e-4, atol=1e-5,
    )

    # Triple composition: sequence x fsdp x pipeline (all gpipe-legal).
    mesh3 = make_mesh(
        MeshConfig(data=1, pipeline=2, fsdp=2, sequence=2)
    )
    pp3 = PipelinedGPT2(cfg, mesh3, num_microbatches=2, schedule="gpipe")
    pp3_params = split_gpt2_params_pp_tp(variables["params"], 2, cfg.num_heads)

    def loss_fn3(p, t):
        logits = pp3.apply({"params": p}, t, train=False)
        return cross_entropy_loss(logits[:, :-1], t[:, 1:])

    with mesh3:
        loss3, grads3 = jax.jit(jax.value_and_grad(loss_fn3))(
            pp3_params, tokens
        )
    np.testing.assert_allclose(float(loss3), float(ref_loss), rtol=1e-5)
    merged3 = merge_gpt2_params_pp_tp(
        jax.tree.map(np.asarray, grads3), 2, cfg.num_heads
    )
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(merged3)[0]), ref_flat, rtol=5e-4, atol=1e-5,
    )


@pytest.mark.parametrize("schedule", ["1f1b", "interleaved"])
def test_pp_x_fsdp_manual_schedule_matches_plain(devices8, schedule):
    """1F1B / interleaved x FSDP: the engines hoist the fsdp param
    all-gather before the tick scan (branch-free — no collective inside
    the cond-gated branches) and psum-scatter the grads after it.  Loss
    and every merged grad leaf equal plain autodiff, and the returned
    stage grads stay fsdp-sharded."""
    from jax.flatten_util import ravel_pytree

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params, merge_gpt2_params_interleaved,
        pp_fsdp_specs, split_gpt2_params, split_gpt2_params_interleaved,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=4, num_heads=4,
        hidden_dim=256, dropout_rate=0.0,
    )
    tokens, variables, ref_loss, ref_grads = plain_gpt2(cfg, (8, 32))

    mesh = make_mesh(MeshConfig(data=-1, pipeline=2, fsdp=2))
    interleaved = schedule == "interleaved"
    pp = PipelinedGPT2(
        cfg, mesh, num_microbatches=2, schedule=schedule, num_chunks=2
    )
    if interleaved:
        pp_params = split_gpt2_params_interleaved(variables["params"], 2, 2)
    else:
        pp_params = split_gpt2_params(variables["params"], 2)
    # The big kernels actually fsdp-shard under both leaf layouts.
    specs = pp_fsdp_specs(pp_params["stages"], mesh)
    assert "fsdp" in tuple(specs["layer_0"]["attn"]["qkv"]["kernel"])

    ref_logits = plain_logits(cfg, tuple(tokens.shape))
    with mesh:
        loss, grads = jax.jit(
            lambda p, t: pp.value_and_grad(p, t)
        )(pp_params, tokens)
        # Forward/eval path too: for interleaved this exercises the
        # chunk0-derived gather specs feeding the per-chunk GPipe ramps.
        logits = jax.jit(
            lambda p, t: pp.apply({"params": p}, t, train=False)
        )(pp_params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    # Returned stage grads keep the fsdp-sharded layout of the params.
    gleaf = grads["stages"]["layer_0"]["attn"]["qkv"]["kernel"]
    gspec = gleaf.sharding.spec
    assert "fsdp" in tuple(gspec), gspec
    if interleaved:
        merged = merge_gpt2_params_interleaved(
            jax.tree.map(np.asarray, grads), 2, 2
        )
    else:
        merged = merge_gpt2_params(jax.tree.map(np.asarray, grads), 2)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(merged)[0]),
        np.asarray(ravel_pytree(ref_grads)[0]),
        rtol=5e-4, atol=1e-5,
    )


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
def test_pp_x_fsdp_cli_smoke(schedule):
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--cpu-devices", "8", "--model", "gpt2",
            "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=4,hidden_dim=256,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--pipeline-parallel", "2",
            "--fsdp", "2", "--pipeline-schedule", schedule,
            "--pipeline-microbatches", "2", "--pipeline-chunks", "2",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output


# ---------------------------------------------------------------------------
# MoE x PP (GPT-2 MoE blocks inside the pipeline stage function)
# ---------------------------------------------------------------------------


def _pp_moe_cfg(**over):
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config

    base = dict(
        vocab_size=128, max_seq_len=32, num_layers=4, num_heads=4,
        hidden_dim=32, num_experts=4,
    )
    return GPT2Config(**{**base, **over})


def test_moe_pipeline_matches_plain_per_microbatch(devices8):
    """MoE x PP (GPipe): logits equal the plain MoE model applied PER
    MICROBATCH (expert capacity is cf*T_micro/E — the same semantics the
    gradient-accumulation path has), and the engine-accumulated aux loss
    equals the mean of the per-microbatch sown aux losses."""
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, split_gpt2_params,
    )

    cfg = _pp_moe_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    plain = GPT2(cfg=cfg)
    m = 2
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (4, 16)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)

    # Reference: plain model per microbatch (matching capacity semantics).
    micro = tokens.reshape(m, 2, 16)
    refs, auxes = [], []
    plain_apply = jax.jit(lambda p, t: plain.apply(
        {"params": p}, t, train=False, mutable=["losses", "moe_stats"],
    ))
    for i in range(m):
        # params only: passing init-time variables would replay their sown
        # losses into the mutable output and double-count the aux.
        logits, sown = plain_apply(variables["params"], micro[i])
        refs.append(np.asarray(logits))
        auxes.append(sum(
            float(jnp.sum(l))
            for l in jax.tree_util.tree_leaves(sown["losses"])
        ))
    ref = np.concatenate(refs, axis=0)

    pp = PipelinedGPT2(cfg, mesh, num_microbatches=m)
    pp_params = split_gpt2_params(variables["params"], 2)
    with mesh:
        out, sown_pp = jax.jit(
            lambda p, t: pp.apply(
                {"params": p}, t, train=False,
                mutable=["losses", "moe_stats"],
            )
        )(pp_params, tokens)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        float(sown_pp["losses"]["moe_aux_loss"]),
        np.mean(auxes), rtol=1e-5,
    )
    drop = float(sown_pp["moe_stats"]["drop_rate"])
    assert 0.0 <= drop <= 1.0
    # flax mutable contract: only requested collections come back.
    with mesh:
        only_losses = pp.apply(
            {"params": pp_params}, tokens, train=False, mutable=["losses"]
        )[1]
    assert set(only_losses) == {"losses"}


def test_moe_pipeline_grads_match_plain_per_microbatch(devices8):
    """MoE x PP gradient exactness: d(mean per-microbatch loss)/d(params)
    under the pipeline equals the plain model's, aux loss included."""
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params, split_gpt2_params,
    )

    cfg = _pp_moe_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    plain = GPT2(cfg=cfg)
    m = 2
    aux_w = 0.01
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, (4, 16)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)

    def nll(logits, t):
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], axis=-1))

    def plain_loss(p):
        micro = tokens.reshape(m, 2, 16)
        total = 0.0
        for i in range(m):
            logits, sown = plain.apply(
                {"params": p}, micro[i], train=False,
                mutable=["losses", "moe_stats"],
            )
            aux = sum(
                jnp.sum(l) for l in jax.tree_util.tree_leaves(sown["losses"])
            )
            total = total + nll(logits, micro[i]) + aux_w * aux
        return total / m

    ref_grads = compiled.grad(plain_loss)(variables["params"])

    pp = PipelinedGPT2(cfg, mesh, num_microbatches=m)
    pp_params = split_gpt2_params(variables["params"], 2)

    def pp_loss(p):
        logits, sown = pp.apply(
            {"params": p}, tokens, train=False, mutable=["losses"]
        )
        return nll(logits, tokens) + aux_w * sown["losses"]["moe_aux_loss"]

    with mesh:
        pp_grads = jax.jit(jax.grad(pp_loss))(pp_params)
    merged = merge_gpt2_params(jax.tree.map(np.asarray, pp_grads), 2)
    for (path, g_ref), (_, g_pp) in zip(
        jax.tree_util.tree_leaves_with_path(ref_grads),
        jax.tree_util.tree_leaves_with_path(merged),
    ):
        np.testing.assert_allclose(
            np.asarray(g_pp), np.asarray(g_ref), rtol=2e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )


def test_moe_pipeline_trains_end_to_end(devices8):
    """Full train step over MoE x PP on a data x pipeline mesh: loss drops,
    aux joins the objective, drop-rate metric surfaces."""
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, pipelined_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = _pp_moe_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2)
    tokens = jnp.zeros((4, 16), jnp.int32)
    state = create_train_state(
        pp, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
        mesh=mesh, rules=pipelined_rules(), init_kwargs={"train": False},
    )
    step_fn = make_train_step(kind="lm")
    batch = {
        "tokens": np.random.default_rng(2).integers(0, 128, (4, 16)).astype(np.int32)
    }
    with mesh:
        losses = []
        for _ in range(3):
            state, m = step_fn(state, shard_batch(batch, mesh))
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert 0.0 <= float(m["moe_drop_rate"]) <= 1.0


def test_moe_pipeline_guards(devices8):
    """MoE x PP composition limits fail loudly: non-GPipe schedules, odd
    layers per stage, tensor/fsdp axes."""
    import pytest

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2,
    )

    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    with pytest.raises(ValueError, match="gpipe only"):
        PipelinedGPT2(_pp_moe_cfg(), mesh, schedule="1f1b")
    with pytest.raises(ValueError, match="even number of layers"):
        PipelinedGPT2(_pp_moe_cfg(num_layers=6), mesh)
    tp_mesh = make_mesh(MeshConfig(data=-1, pipeline=2, tensor=2))
    with pytest.raises(ValueError, match="plain GPipe only"):
        PipelinedGPT2(_pp_moe_cfg(), tp_mesh)


def test_moe_pipeline_more_microbatches_than_stages(devices8):
    """MoE x PP exactness holds at M > S (the bubble-amortizing regime):
    logits equal the plain model per microbatch for M=4 over S=2."""
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, split_gpt2_params,
    )

    cfg = _pp_moe_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    plain = GPT2(cfg=cfg)
    m = 4
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 128, (8, 16)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)
    micro = tokens.reshape(m, 2, 16)
    refs = []
    auxes = []
    plain_apply = jax.jit(lambda p, t: plain.apply(
        {"params": p}, t, train=False, mutable=["losses", "moe_stats"],
    ))
    for i in range(m):
        logits, sown = plain_apply(variables["params"], micro[i])
        refs.append(np.asarray(logits))
        auxes.append(sum(
            float(jnp.sum(l))
            for l in jax.tree_util.tree_leaves(sown["losses"])
        ))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=m)
    pp_params = split_gpt2_params(variables["params"], 2)
    with mesh:
        out, sown_pp = jax.jit(
            lambda p, t: pp.apply(
                {"params": p}, t, train=False, mutable=["losses"]
            )
        )(pp_params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.concatenate(refs, axis=0), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        float(sown_pp["losses"]["moe_aux_loss"]), np.mean(auxes), rtol=1e-5
    )


def test_moe_pipeline_dropout_trains_and_is_deterministic(devices8):
    """MoE x PP with dropout: the same seed gives the identical loss twice
    (tick-folded keys are deterministic), different seeds differ, and the
    aux accumulator still reaches the objective."""
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, pipelined_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = _pp_moe_cfg(dropout_rate=0.1)
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2)
    tokens = jnp.zeros((4, 16), jnp.int32)
    batch = {
        "tokens": np.random.default_rng(6).integers(0, 128, (4, 16)).astype(np.int32)
    }

    def first_loss(seed):
        state = create_train_state(
            pp, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
            mesh=mesh, rules=pipelined_rules(), init_kwargs={"train": False},
        )
        step_fn = make_train_step(kind="lm", base_rng=jax.random.PRNGKey(seed))
        with mesh:
            _, m = step_fn(state, shard_batch(dict(batch), mesh))
        return float(m["loss"]), float(m["moe_drop_rate"])

    l1, d1 = first_loss(7)
    l2, _ = first_loss(7)
    l3, _ = first_loss(8)
    assert l1 == l2  # same seed -> identical masks -> identical loss
    assert l1 != l3  # different seed -> different masks
    assert 0.0 <= d1 <= 1.0

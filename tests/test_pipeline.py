"""Tests for GPipe pipeline parallelism: exactness vs sequential stages.
PP x TP, PP x FSDP and MoE x PP are in tests/test_pipeline_tp_fsdp_moe.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compiled
from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
from pytorch_distributed_training_tpu.parallel.pipeline import (
    pipeline_forward,
    stack_stage_params,
)


def mlp_stage(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def make_stages(num_stages, d, seed=0):
    rng = np.random.default_rng(seed)
    stages = []
    for _ in range(num_stages):
        stages.append({
            "w1": jnp.asarray(rng.standard_normal((d, 2 * d)) * 0.3, jnp.float32),
            "b1": jnp.zeros((2 * d,)),
            "w2": jnp.asarray(rng.standard_normal((2 * d, d)) * 0.3, jnp.float32),
            "b2": jnp.zeros((d,)),
        })
    return stages


def sequential_ref(stages, micro):
    def one(x):
        for p in stages:
            x = mlp_stage(p, x)
        return x
    return jnp.stack([one(micro[i]) for i in range(micro.shape[0])])


@pytest.mark.parametrize("num_micro", [4, 7])
def test_pipeline_matches_sequential(devices8, num_micro):
    mesh = make_mesh(MeshConfig(data=2, pipeline=4))
    d = 8
    stages = make_stages(4, d)
    stacked = stack_stage_params(stages)
    rng = np.random.default_rng(1)
    micro = jnp.asarray(rng.standard_normal((num_micro, 2, d)), jnp.float32)

    ref = sequential_ref(stages, micro)
    with mesh:
        out = jax.jit(
            lambda p, m: pipeline_forward(mlp_stage, p, m, mesh)
        )(stacked, micro)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_grads_match_sequential(devices8):
    mesh = make_mesh(MeshConfig(data=2, pipeline=4))
    d = 4
    stages = make_stages(4, d, seed=2)
    stacked = stack_stage_params(stages)
    rng = np.random.default_rng(3)
    micro = jnp.asarray(rng.standard_normal((4, 2, d)), jnp.float32)

    def loss_pipe(p):
        return jnp.sum(pipeline_forward(mlp_stage, p, micro, mesh) ** 2)

    def loss_ref(stage_list):
        return jnp.sum(sequential_ref(stage_list, micro) ** 2)

    with mesh:
        g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
    g_ref_list = compiled.grad(loss_ref)(stages)
    g_ref = stack_stage_params(g_ref_list)
    for k in ("w1", "b1", "w2", "b2"):
        np.testing.assert_allclose(
            np.asarray(g_pipe[k]), np.asarray(g_ref[k]), atol=5e-4
        )


def test_pipeline_single_stage_degenerates(devices8):
    mesh = make_mesh(MeshConfig(data=8, pipeline=1))
    d = 4
    stages = make_stages(1, d, seed=4)
    stacked = stack_stage_params(stages)
    micro = jnp.asarray(np.random.default_rng(5).standard_normal((3, 2, d)), jnp.float32)
    ref = sequential_ref(stages, micro)
    with mesh:
        out = jax.jit(lambda p, m: pipeline_forward(mlp_stage, p, m, mesh))(stacked, micro)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# --- pipelined GPT-2 integration (VERDICT r1 item 6) ---

def _pp_gpt2_cfg():
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config

    return GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=4, num_heads=4, hidden_dim=32
    )


@functools.cache
def plain_gpt2(cfg, shape):
    """The plain GPT-2 a pipelined one is compared with, made once a file
    for each configuration and batch: the batch (``default_rng(1)`` of
    ``shape``), the parameters (``PRNGKey(0)``), and the next-token CE loss
    and its gradients, computed as one compiled program."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2
    from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss

    plain = GPT2(cfg=cfg)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, shape), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)

    def ref_loss_fn(p, tokens):
        logits = plain.apply({"params": p}, tokens, train=False)
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    ref_loss, ref_grads = compiled.value_and_grad(ref_loss_fn)(
        variables["params"], tokens
    )
    return tokens, variables, ref_loss, ref_grads


@functools.cache
def plain_logits(cfg, shape):
    """``plain_gpt2``'s model's logits on its batch, compiled."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2

    plain = GPT2(cfg=cfg)
    tokens, variables, _, _ = plain_gpt2(cfg, shape)
    return jax.jit(lambda p, t: plain.apply({"params": p}, t, train=False))(
        variables["params"], tokens
    )


def test_pipelined_gpt2_matches_plain_forward(devices8):
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params, split_gpt2_params,
    )

    cfg = _pp_gpt2_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    plain = GPT2(cfg=cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (4, 16)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)
    ref = jax.jit(lambda v, t: plain.apply(v, t, train=False))(variables, tokens)

    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2)
    pp_params = split_gpt2_params(variables["params"], 2)
    # split/merge round-trips the plain tree exactly.
    merged = merge_gpt2_params(pp_params, 2)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(variables["params"]),
        jax.tree_util.tree_leaves_with_path(merged),
    ):
        assert str(pa) == str(pb)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    with mesh:
        out = jax.jit(
            lambda p, t: pp.apply({"params": p}, t, train=False)
        )(pp_params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_pipelined_gpt2_grads_match_plain(devices8):
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params, split_gpt2_params,
    )

    cfg = _pp_gpt2_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    plain = GPT2(cfg=cfg)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, (4, 16)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)

    def nll(logits, t):
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], axis=-1))

    ref_grads = compiled.grad(
        lambda p: nll(plain.apply({"params": p}, tokens, train=False), tokens)
    )(variables["params"])

    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2)
    pp_params = split_gpt2_params(variables["params"], 2)
    with mesh:
        pp_grads = jax.jit(jax.grad(
            lambda p: nll(pp.apply({"params": p}, tokens, train=False), tokens)
        ))(pp_params)
    merged_grads = merge_gpt2_params(jax.tree.map(np.asarray, pp_grads), 2)
    for (path, g_ref), (_, g_pp) in zip(
        jax.tree_util.tree_leaves_with_path(ref_grads),
        jax.tree_util.tree_leaves_with_path(merged_grads),
    ):
        np.testing.assert_allclose(
            np.asarray(g_pp), np.asarray(g_ref), rtol=2e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )


def test_pipelined_gpt2_trains(devices8):
    """Full train step (create_train_state + make_train_step) over the
    pipelined model on a data x pipeline mesh."""
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, pipelined_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = _pp_gpt2_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2)
    tokens = jnp.zeros((4, 16), jnp.int32)
    state = create_train_state(
        pp, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
        mesh=mesh, rules=pipelined_rules(), init_kwargs={"train": False},
    )
    # Stage leaves actually sharded over the pipeline axis.
    leaf = jax.tree.leaves(state.params["stages"])[0]
    assert leaf.sharding.spec == jax.sharding.PartitionSpec("pipeline")
    step_fn = make_train_step(kind="lm")
    batch = {"tokens": np.random.default_rng(2).integers(0, 128, (4, 16)).astype(np.int32)}
    with mesh:
        losses = []
        for _ in range(3):
            state, m = step_fn(state, shard_batch(batch, mesh))
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # same batch: loss must drop


def test_pipelined_gpt2_dropout_trains_and_is_deterministic(devices8):
    """Dropout inside the pipeline (per-(tick, stage) keys): trains with
    finite decreasing loss, identical rng => identical loss (backward
    replays the same masks), different step => different masks."""
    import optax

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, pipelined_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=16, num_layers=2, num_heads=2,
        hidden_dim=32, dropout_rate=0.2,
    )
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2)
    tokens = jnp.zeros((4, 16), jnp.int32)

    def fresh():
        return create_train_state(
            pp, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
            mesh=mesh, rules=pipelined_rules(), init_kwargs={"train": False},
        )

    step_fn = make_train_step(kind="lm", base_rng=jax.random.PRNGKey(7))
    batch = {
        "tokens": np.random.default_rng(2).integers(0, 128, (4, 16)).astype(np.int32)
    }
    with mesh:
        placed = shard_batch(batch, mesh)
        s1, m1 = step_fn(fresh(), placed)
        s2, m2 = step_fn(fresh(), placed)
        # Same state, same base rng, same step counter: identical masks.
        assert float(m1["loss"]) == float(m2["loss"])
        # Next step folds a new key: different masks, different loss (also
        # true without dropout from the update, so check drop over steps).
        losses = [float(m1["loss"])]
        state = s1
        for _ in range(3):
            state, m = step_fn(state, placed)
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]

    # Eval path stays deterministic (no rng): apply without train.
    # (state, not s1/s2 — those were donated into later steps.)
    variables = {"params": jax.device_get(state.params)}
    a = pp.apply(variables, jnp.asarray(batch["tokens"]))
    b = pp.apply(variables, jnp.asarray(batch["tokens"]))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_cli_smoke(tmp_path):
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=4,hidden_dim=32,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--pipeline-parallel", "2",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "'pipeline': 2" in result.output
    assert "training finished" in result.output


# ---------------------------------------------------------------------------
# 1F1B schedule (parallel/pipeline.pipeline_train_1f1b)
# ---------------------------------------------------------------------------


def _1f1b_toy(mesh, S, M, mb=2, d=8, seed=0):
    from pytorch_distributed_training_tpu.parallel.pipeline import (
        pipeline_train_1f1b,
    )

    rng = np.random.default_rng(seed)
    first_params = {"emb": jnp.asarray(rng.standard_normal((5, d)), jnp.float32)}
    stages = make_stages(S, d, seed=seed + 1)
    last_params = {
        "head": jnp.asarray(rng.standard_normal((d, 3)) * 0.3, jnp.float32)
    }
    inputs = jnp.asarray(rng.integers(0, 5, (M, mb, 7)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 3, (M, mb)), jnp.int32)

    def first_fn(fp, x):
        return fp["emb"][x].mean(1)

    def last_fn(lp, y, t):
        logp = jax.nn.log_softmax(y @ lp["head"])
        return -jnp.take_along_axis(logp, t[:, None], 1).mean() / M

    def ref(fp, stage_list, lp):
        tot = 0.0
        for m in range(M):
            x = first_fn(fp, inputs[m])
            for p in stage_list:
                x = mlp_stage(p, x)
            tot = tot + last_fn(lp, x, targets[m])
        return tot

    ref_out = compiled.value_and_grad(ref, argnums=(0, 1, 2))(
        first_params, stages, last_params
    )
    with mesh:
        out = jax.jit(
            lambda fp, sp, lp, i, t: pipeline_train_1f1b(
                first_fn, mlp_stage, last_fn, fp, sp, lp, i, t, mesh
            )
        )(first_params, stack_stage_params(stages), last_params, inputs, targets)
    return ref_out, out


@pytest.mark.parametrize("num_micro", [1, 3, 4, 8])
def test_1f1b_exact_loss_and_grads(devices8, num_micro):
    """1F1B == sequential fwd+bwd: loss, first/stage/last grads, including
    M < S (all-warmup), M == S, and M > S (steady-state) schedules."""
    S = 4
    mesh = make_mesh(MeshConfig(data=2, pipeline=S))
    (ref_loss, (ref_f, ref_stages, ref_l)), (loss, (fbar, sbar, lbar)) = (
        _1f1b_toy(mesh, S, num_micro)
    )
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fbar["emb"]), np.asarray(ref_f["emb"]), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(lbar["head"]), np.asarray(ref_l["head"]), rtol=1e-4,
        atol=1e-6,
    )
    for s in range(S):
        for k in ("w1", "b1", "w2", "b2"):
            np.testing.assert_allclose(
                np.asarray(sbar[k][s]), np.asarray(ref_stages[s][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"stage {s} {k}",
            )


def test_pipelined_gpt2_1f1b_matches_plain_grads(devices8):
    """PipelinedGPT2(schedule='1f1b').value_and_grad == plain GPT-2
    autodiff: the CE loss and every merged grad leaf."""
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params, split_gpt2_params,
    )

    cfg = _pp_gpt2_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    tokens, variables, ref_loss, ref_grads = plain_gpt2(cfg, (4, 16))

    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2, schedule="1f1b")
    pp_params = split_gpt2_params(variables["params"], 2)
    with mesh:
        loss, grads = jax.jit(
            lambda p, t: pp.value_and_grad(p, t)
        )(pp_params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    merged = merge_gpt2_params(jax.tree.map(np.asarray, grads), 2)
    for (path, g_ref), (_, g_pp) in zip(
        jax.tree_util.tree_leaves_with_path(ref_grads),
        jax.tree_util.tree_leaves_with_path(merged),
    ):
        np.testing.assert_allclose(
            np.asarray(g_pp), np.asarray(g_ref), rtol=2e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )


def test_1f1b_cli_smoke(tmp_path):
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=4,hidden_dim=32,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--pipeline-parallel", "2",
            "--pipeline-schedule", "1f1b", "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output


# ---------------------------------------------------------------------------
# Interleaved (multi-chunk) 1F1B
# ---------------------------------------------------------------------------


def test_interleaved_schedule_properties():
    """The static scheduler self-validates (DAG replay with slot-identity
    checks); here: V=1 reproduces the closed-form 1F1B makespan
    2(M + S - 1), and interleaving shrinks the wall-clock bubble —
    (T - 2MV)/T with tick time proportional to 1/V."""
    from pytorch_distributed_training_tpu.parallel.pipeline_schedule import (
        make_interleaved_schedule,
    )

    s1 = make_interleaved_schedule(4, 1, 8)
    assert s1.T == 2 * (8 + 4 - 1)
    s2 = make_interleaved_schedule(4, 2, 8)
    assert s2.bubble_fraction() < s1.bubble_fraction()
    s4 = make_interleaved_schedule(4, 4, 16)
    assert s4.bubble_fraction() < make_interleaved_schedule(
        4, 2, 16
    ).bubble_fraction()


def _interleaved_toy(mesh, S, V, M, mb=2, d=8, seed=0):
    from pytorch_distributed_training_tpu.parallel.pipeline import (
        pipeline_train_interleaved, stack_virtual_stage_params,
    )

    SV = S * V
    rng = np.random.default_rng(seed)
    first_params = {"emb": jnp.asarray(rng.standard_normal((5, d)), jnp.float32)}
    stages = make_stages(SV, d, seed=seed + 1)
    last_params = {
        "head": jnp.asarray(rng.standard_normal((d, 3)) * 0.3, jnp.float32)
    }
    inputs = jnp.asarray(rng.integers(0, 5, (M, mb, 7)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 3, (M, mb)), jnp.int32)

    def first_fn(fp, x):
        return fp["emb"][x].mean(1)

    def last_fn(lp, y, t):
        logp = jax.nn.log_softmax(y @ lp["head"])
        return -jnp.take_along_axis(logp, t[:, None], 1).mean() / M

    def ref(fp, stage_list, lp):
        tot = 0.0
        for m in range(M):
            x = first_fn(fp, inputs[m])
            for p in stage_list:
                x = mlp_stage(p, x)
            tot = tot + last_fn(lp, x, targets[m])
        return tot

    ref_out = compiled.value_and_grad(ref, argnums=(0, 1, 2))(
        first_params, stages, last_params
    )
    with mesh:
        out = jax.jit(
            lambda fp, sp, lp, i, t: pipeline_train_interleaved(
                first_fn, mlp_stage, last_fn, fp, sp, lp, i, t, mesh,
                num_chunks=V,
            )
        )(
            first_params, stack_virtual_stage_params(stages, S), last_params,
            inputs, targets,
        )
    return ref_out, out


@pytest.mark.parametrize("V,num_micro", [(2, 2), (2, 4), (2, 7), (3, 4)])
def test_interleaved_exact_loss_and_grads(devices8, V, num_micro):
    """Interleaved 1F1B == sequential fwd+bwd over S*V virtual stages:
    loss, first/stage/last grads, covering M < S, M == S, M > S and an
    odd (non-divisible) microbatch count."""
    S = 2
    mesh = make_mesh(MeshConfig(data=-1, pipeline=S))
    (ref_loss, (ref_f, ref_stages, ref_l)), (loss, (fbar, sbar, lbar)) = (
        _interleaved_toy(mesh, S, V, num_micro)
    )
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fbar["emb"]), np.asarray(ref_f["emb"]), rtol=1e-4,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(lbar["head"]), np.asarray(ref_l["head"]), rtol=1e-4,
        atol=1e-6,
    )
    for vs in range(S * V):
        s_, v_ = vs % S, vs // S
        for k in ("w1", "b1", "w2", "b2"):
            np.testing.assert_allclose(
                np.asarray(sbar[k][s_, v_]), np.asarray(ref_stages[vs][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"virtual stage {vs} {k}",
            )


def test_pipelined_gpt2_interleaved_matches_plain(devices8):
    """PipelinedGPT2(schedule='interleaved', 2 chunks x 2 stages):
    value_and_grad AND the forward-only apply path (V successive GPipe
    ramps) match the plain model."""
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params_interleaved,
        split_gpt2_params_interleaved,
    )

    cfg = _pp_gpt2_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    tokens, variables, ref_loss, ref_grads = plain_gpt2(cfg, (4, 16))
    ref_logits = plain_logits(cfg, tuple(tokens.shape))

    pp = PipelinedGPT2(
        cfg, mesh, num_microbatches=2, schedule="interleaved", num_chunks=2
    )
    pp_params = split_gpt2_params_interleaved(variables["params"], 2, 2)
    with mesh:
        loss, grads = jax.jit(
            lambda p, t: pp.value_and_grad(p, t)
        )(pp_params, tokens)
        logits = jax.jit(
            lambda p, t: pp.apply({"params": p}, t, train=False)
        )(pp_params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    merged = merge_gpt2_params_interleaved(jax.tree.map(np.asarray, grads), 2, 2)
    from jax.flatten_util import ravel_pytree

    np.testing.assert_allclose(
        np.asarray(ravel_pytree(merged)[0]),
        np.asarray(ravel_pytree(ref_grads)[0]),
        rtol=2e-4, atol=1e-5,
    )


def test_interleaved_dropout_trains_and_replays(devices8):
    """Interleaved schedule WITH dropout: finite decreasing loss and
    identical seeds => identical trajectory (the backward recompute must
    replay the per-(microbatch, virtual stage) masks)."""
    import optax

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, make_pipeline_grad_fn, pipelined_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=16, num_layers=4, num_heads=4,
        hidden_dim=32, dropout_rate=0.2,
    )
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    pp = PipelinedGPT2(
        cfg, mesh, num_microbatches=2, schedule="interleaved", num_chunks=2
    )
    tokens = jnp.zeros((4, 16), jnp.int32)
    batch = {
        "tokens": np.random.default_rng(3).integers(0, 128, (4, 16), np.int32)
    }

    def run():
        state = create_train_state(
            pp, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
            mesh=mesh, rules=pipelined_rules(), init_kwargs={"train": False},
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
        return losses

    losses1 = run()
    losses2 = run()
    assert np.isfinite(losses1).all()
    assert losses1[-1] < losses1[0]
    np.testing.assert_allclose(losses1, losses2, rtol=0, atol=0)


def test_interleaved_cli_smoke(tmp_path):
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--cpu-devices", "8", "--model", "gpt2",
            "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=8,hidden_dim=32,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--pipeline-parallel", "2",
            "--pipeline-schedule", "interleaved", "--pipeline-chunks", "2",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output

# ---------------------------------------------------------------------------
# SP x PP (ring attention inside pipeline stages — gpipe schedule only)
# ---------------------------------------------------------------------------


def test_collective_stage_needs_gpipe(devices8):
    """Why SP is gpipe-only: (a) the constructor refuses the manual
    schedules; (b) CANARY — a ppermute-ring stage under the cond-gated
    1F1B engine diverges from the sequential reference (the measured
    unsoundness the ban cites).  If (b) ever fails because the delta
    became ~0, a jax upgrade fixed collective execution under
    pipeline-varying lax.cond gating — revisit the ban."""
    from jax import lax

    from pytorch_distributed_training_tpu.comm.mesh import AXIS_SEQUENCE
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2,
    )
    from pytorch_distributed_training_tpu.parallel.pipeline import (
        pipeline_train_1f1b, stack_stage_params,
    )

    cfg = GPT2Config(
        vocab_size=64, max_seq_len=16, num_layers=4, num_heads=2,
        hidden_dim=16, dropout_rate=0.0,
    )
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2, sequence=2))
    for schedule in ("1f1b", "interleaved"):
        with pytest.raises(ValueError, match="gpipe"):
            PipelinedGPT2(cfg, mesh, schedule=schedule)

    # (b) the minimal repro: ring-mix stage under the 1F1B engine.
    S, M, mb, L, d, n_seq = 2, 2, 2, 8, 4, 2
    rng = np.random.default_rng(0)
    first_params = {"emb": jnp.asarray(rng.standard_normal((5, d)), jnp.float32)}
    stages = [
        {"w": jnp.asarray(rng.standard_normal((d, d)) * 0.4, jnp.float32)}
        for _ in range(S)
    ]
    last_params = {
        "head": jnp.asarray(rng.standard_normal((d, 3)) * 0.3, jnp.float32)
    }
    inputs = jnp.asarray(rng.integers(0, 5, (M, mb, L)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 3, (M, mb, L)), jnp.int32)

    def first_fn(fp, x):
        return fp["emb"][x]

    def stage_ring(p, x):
        h = jnp.tanh(x @ p["w"])

        def step(carry, _):
            acc, cur = carry
            acc = acc + cur.sum(1, keepdims=True)
            cur = lax.ppermute(
                cur, AXIS_SEQUENCE,
                [(j, (j - 1) % n_seq) for j in range(n_seq)],
            )
            return (acc, cur), None

        (acc, _), _ = jax.lax.scan(
            jax.checkpoint(step),
            (jnp.zeros_like(h[:, :1]), h), jnp.arange(n_seq),
        )
        return h + 0.1 * acc

    def stage_ref(p, x):
        h = jnp.tanh(x @ p["w"])
        return h + 0.1 * h.sum(1, keepdims=True)

    def last_fn(lp, y, t):
        logp = jax.nn.log_softmax(y @ lp["head"])
        per = -jnp.take_along_axis(logp, t[..., None], -1)[..., 0]
        l_loc = t.shape[1]
        gpos = jax.lax.axis_index(AXIS_SEQUENCE) * l_loc + jnp.arange(l_loc)
        valid = (gpos < L - 1).astype(jnp.float32)[None]
        return jnp.sum(per * valid) * n_seq / ((L - 1) * t.shape[0]) / M

    def ref(fp, sl, lp):
        tot = 0.0
        for m in range(M):
            x = first_fn(fp, inputs[m])
            for p in sl:
                x = stage_ref(p, x)
            logp = jax.nn.log_softmax(x @ lp["head"])
            per = -jnp.take_along_axis(
                logp, targets[m][..., None], -1
            )[..., 0]
            tot = tot + per[:, : L - 1].sum() / ((L - 1) * mb) / M
        return tot

    ref_loss = float(ref(first_params, stages, last_params))
    with mesh:
        loss, _ = jax.jit(
            lambda fp, sp_, lp, i, t: pipeline_train_1f1b(
                first_fn, stage_ring, last_fn, fp, sp_, lp, i, t, mesh,
                sequence_sharded=True,
            )
        )(
            first_params, stack_stage_params(stages), last_params,
            inputs, targets,
        )
    assert abs(float(loss) - ref_loss) > 1e-3, (
        "cond-gated collective now EXACT — jax fixed varying-predicate "
        "collective execution; consider lifting the SP-needs-gpipe ban "
        f"(loss={float(loss)}, ref={ref_loss})"
    )


@pytest.mark.parametrize("tp", [1, 2])
def test_sp_x_pp_gpipe_matches_plain(devices8, tp):
    """GPipe x ring-SP (x TP): loss and every merged grad leaf equal the
    plain model — autodiff through the per-tick ring scan is exact
    because the gpipe tick loop is branch-free."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2Config
    from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, merge_gpt2_params_pp_tp, split_gpt2_params_pp_tp,
    )
    from jax.flatten_util import ravel_pytree

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=4, num_heads=4,
        hidden_dim=32, dropout_rate=0.0,
    )
    tokens, variables, ref_loss, ref_grads = plain_gpt2(cfg, (4, 32))

    mesh = make_mesh(
        MeshConfig(data=-1, pipeline=2, sequence=2, tensor=tp)
    )
    pp = PipelinedGPT2(cfg, mesh, num_microbatches=2, schedule="gpipe")
    pp_params = split_gpt2_params_pp_tp(variables["params"], 2, cfg.num_heads)

    def loss_fn(p, t):
        logits = pp.apply({"params": p}, t, train=False)
        return cross_entropy_loss(logits[:, :-1], t[:, 1:])

    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(pp_params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    merged = merge_gpt2_params_pp_tp(
        jax.tree.map(np.asarray, grads), 2, cfg.num_heads
    )
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(merged)[0]),
        np.asarray(ravel_pytree(ref_grads)[0]),
        rtol=5e-4, atol=1e-5,
    )


def test_sp_x_pp_cli_smoke():
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
            "--sequence-parallel", "2", "--pipeline-schedule", "gpipe",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output

def test_interleaved_schedule_property_sweep():
    """Grid-sweep the static scheduler: every (S, V, M) combination
    generates, self-validates (DAG replay + slot-identity checks run at
    construction), and improves or matches the V=1 wall-clock bubble."""
    from pytorch_distributed_training_tpu.parallel.pipeline_schedule import (
        make_interleaved_schedule,
    )

    for S in (1, 2, 3, 4, 6, 8):
        base = {M: make_interleaved_schedule(S, 1, M).bubble_fraction()
                for M in (1, 2, 5, 8, 16)}
        for V in (2, 3, 4):
            for M in (1, 2, 5, 8, 16):
                sched = make_interleaved_schedule(S, V, M)
                assert sched.T >= 2 * M * V
                if S > 1 and M >= S:
                    # Steady-state regime: interleaving must not lose.
                    assert sched.bubble_fraction() <= base[M] + 1e-9, (
                        S, V, M, sched.bubble_fraction(), base[M],
                    )


# --------------------------------------------------------------------- #
# compressed stage-boundary payloads (--pp-compress, ISSUE 6)
# --------------------------------------------------------------------- #


def _pp_compress_step(schedule, mode, devices8, pp_stripe=1):
    """One full train step of the tiny pipelined GPT-2 under
    ``--pp-compress mode``; returns (loss, params_after) — the same
    harness shape as the hier-sync parity tests."""
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2, make_pipeline_grad_fn, pipelined_rules,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = _pp_gpt2_cfg()
    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    net = PipelinedGPT2(
        cfg, mesh, num_microbatches=4, schedule=schedule, pp_compress=mode,
        pp_stripe=pp_stripe,
    )
    state = create_train_state(
        net, jax.random.PRNGKey(0), jnp.zeros((8, 16), jnp.int32),
        optax.adam(1e-3), mesh=mesh, rules=pipelined_rules(),
        init_kwargs={"train": False},
    )
    grad_fn = make_pipeline_grad_fn(net) if schedule != "gpipe" else None
    step = make_train_step(kind="lm", grad_fn=grad_fn)
    batch = {
        "tokens": np.random.default_rng(3).integers(0, 128, (8, 16), np.int32)
    }
    with mesh:
        state, metrics = step(state, shard_batch(batch, mesh))
    params = jax.tree_util.tree_map(np.asarray, state.params)
    return float(metrics["loss"]), params


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
def test_pp_compress_int8_matches_uncompressed(devices8, schedule):
    """int8-compressed stage boundaries (per-token scale + EF residuals in
    the tick scan, compressed cotangents on the way back) train within a
    tight band of the uncompressed schedule — loss parity pins the
    forward codec, the one-Adam-step param delta bounds the backward's
    compressed cotangent error.  GPipe's backward goes through the
    custom-vjp permute (autodiff), the manual schedules through the
    explicit cot stream — all three are exercised."""
    loss_ref, params_ref = _pp_compress_step(schedule, "none", devices8)
    loss_c, params_c = _pp_compress_step(schedule, "int8", devices8)
    assert abs(loss_ref - loss_c) < 5e-3, (schedule, loss_ref, loss_c)
    delta = max(
        np.abs(np.asarray(a) - np.asarray(b)).max()
        for a, b in zip(
            jax.tree_util.tree_leaves(params_ref),
            jax.tree_util.tree_leaves(params_c),
        )
    )
    assert delta < 5e-3, (schedule, delta)


def test_pp_compress_bf16_gpipe_close(devices8):
    loss_ref, _ = _pp_compress_step("gpipe", "none", devices8)
    loss_c, _ = _pp_compress_step("gpipe", "bf16", devices8)
    assert abs(loss_ref - loss_c) < 5e-3


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
@pytest.mark.parametrize("mode", ["none", "int8"])
def test_pp_stripe_bitwise_parity(devices8, schedule, mode):
    """Striped stage-boundary channels (--grad-sync-stripe under
    --pipeline-parallel): splitting each ppermute payload into k
    concurrent chunks on the same edge is a pure transport transform —
    loss and params after one step are BITWISE identical to the
    single-channel schedule, through the custom-vjp permute (gpipe) and
    the explicit cotangent stream (1f1b/interleaved), int8's per-token
    scales and EF residuals included."""
    loss_ref, params_ref = _pp_compress_step(schedule, mode, devices8)
    loss_s, params_s = _pp_compress_step(
        schedule, mode, devices8, pp_stripe=3
    )
    assert loss_ref == loss_s, (schedule, mode, loss_ref, loss_s)
    for a, b in zip(
        jax.tree_util.tree_leaves(params_ref),
        jax.tree_util.tree_leaves(params_s),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pp_compress_validation(devices8):
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.parallel.gpt2_pipeline import (
        PipelinedGPT2,
    )

    mesh = make_mesh(MeshConfig(data=-1, pipeline=2))
    with pytest.raises(ValueError, match="pp_compress"):
        PipelinedGPT2(_pp_gpt2_cfg(), mesh, pp_compress="int4")


def test_pp_compress_cli_requires_pipeline():
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    r = CliRunner().invoke(
        cli_main,
        ["--use-cpu", "--synthetic-data", "--pp-compress", "int8"],
    )
    assert r.exit_code != 0 and "--pipeline-parallel" in r.output

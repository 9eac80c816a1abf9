"""Tests for parallel/: sharding rules, grad accumulation, ring/Ulysses SP.

Strategy per SURVEY.md §4: everything on the simulated 8-device CPU mesh;
numerics tests assert the parallel path equals the single-device reference
computation (the DP test the reference never had)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
from pytorch_distributed_training_tpu.ops.attention import _xla_attention
from pytorch_distributed_training_tpu.parallel import (
    accumulate_gradients,
    batch_sharding,
    infer_params_sharding,
    ring_self_attention,
    shard_batch,
    shard_params,
    tp_rules_for,
    ulysses_attention,
)
from pytorch_distributed_training_tpu.parallel.sharding import DDP_RULES, FSDP_RULES


def test_batch_sharding_splits_dim0(devices8):
    mesh = make_mesh(MeshConfig(data=-1))
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    arr = shard_batch(x, mesh)
    assert arr.sharding.spec == P(("data", "fsdp"), None)
    # Each device holds one row shard.
    assert arr.addressable_shards[0].data.shape == (1, 8)
    np.testing.assert_array_equal(np.asarray(arr), x)


def test_fsdp_sharding_rules(devices8):
    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    params = {
        "dense": {"kernel": jnp.ones((256, 512)), "bias": jnp.ones((512,))},
        "norm": {"scale": jnp.ones((64,))},
    }
    shardings = infer_params_sharding(params, mesh, FSDP_RULES)
    # Largest divisible axis of the kernel sharded over fsdp.
    assert shardings["dense"]["kernel"].spec == P(None, "fsdp")
    # Tiny params replicated.
    assert shardings["dense"]["bias"].spec == P()
    assert shardings["norm"]["scale"].spec == P()
    placed = shard_params(params, mesh, FSDP_RULES)
    assert placed["dense"]["kernel"].addressable_shards[0].data.shape == (256, 128)


def test_tp_rules_gpt2(devices8):
    mesh = make_mesh(MeshConfig(data=2, tensor=4))
    rules = tp_rules_for("gpt2")
    params = {
        "block_0": {
            "attn": {"qkv": {"kernel": jnp.ones((64, 192))},
                     "proj": {"kernel": jnp.ones((64, 64))}},
            "mlp_up": {"kernel": jnp.ones((64, 256))},
            "mlp_down": {"kernel": jnp.ones((256, 64))},
        }
    }
    s = infer_params_sharding(params, mesh, rules)
    assert s["block_0"]["attn"]["qkv"]["kernel"].spec == P(None, "tensor")
    assert s["block_0"]["attn"]["proj"]["kernel"].spec == P("tensor", None)
    assert s["block_0"]["mlp_up"]["kernel"].spec == P(None, "tensor")
    assert s["block_0"]["mlp_down"]["kernel"].spec == P("tensor", None)

    # Every family member gets the transformer rules, not just the
    # flagship names — a silent FSDP fallback here would waste the tensor
    # axis on replicated work.
    for name in ("gpt2_medium", "gpt2_xl", "vit_s16", "vit_l16"):
        s2 = infer_params_sharding(params, mesh, tp_rules_for(name))
        assert s2["block_0"]["attn"]["qkv"]["kernel"].spec == P(None, "tensor"), name


def test_tp_rules_degrade_to_fsdp_on_fsdp_only_mesh(devices8):
    """On a mesh with tensor=1 (an --fsdp-only run), matched TP rules must
    fall through to the fsdp heuristic instead of silently replicating the
    big kernels — for gpt2_xl that's the difference between training and
    OOM (1.5B params + Adam moments whole on every chip)."""
    import dataclasses as _dc

    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    rules = _dc.replace(tp_rules_for("gpt2_xl"), min_fsdp_size=1)
    params = {
        "block_0": {
            "attn": {"qkv": {"kernel": jnp.ones((64, 192))},
                     "proj": {"kernel": jnp.ones((64, 64))}},
            "mlp_up": {"kernel": jnp.ones((64, 256))},
            "mlp_down": {"kernel": jnp.ones((256, 64))},
        }
    }
    s = infer_params_sharding(params, mesh, rules)
    for path in (("attn", "qkv"), ("attn", "proj"), ("mlp_up",), ("mlp_down",)):
        node = s["block_0"]
        for k in path:
            node = node[k]
        assert "fsdp" in str(node["kernel"].spec), (path, node["kernel"].spec)


def test_grad_accum_matches_full_batch():
    params = {"w": jnp.array([1.5, -0.5, 2.0])}
    batch = {"x": jnp.arange(24, dtype=jnp.float32).reshape(8, 3),
             "y": jnp.arange(8, dtype=jnp.float32)}

    def loss_fn(p, b):
        pred = b["x"] @ p["w"]
        return jnp.mean((pred - b["y"]) ** 2)

    loss_full, grads_full = jax.value_and_grad(loss_fn)(params, batch)
    loss_acc, grads_acc = accumulate_gradients(loss_fn, params, batch, 4)
    np.testing.assert_allclose(loss_acc, loss_full, rtol=1e-6)
    np.testing.assert_allclose(grads_acc["w"], grads_full["w"], rtol=1e-6)


def test_grad_accum_with_aux():
    params = {"w": jnp.ones((4,))}
    batch = {"x": jnp.ones((6, 4))}

    def loss_fn(p, b):
        pred = b["x"] @ p["w"]
        return jnp.mean(pred**2), {"pred_mean": jnp.mean(pred)}

    (loss, aux), grads = accumulate_gradients(
        loss_fn, params, batch, 3, has_aux=True
    )
    (loss_ref, aux_ref), grads_ref = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch
    )
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-6)
    np.testing.assert_allclose(aux["pred_mean"], aux_ref["pred_mean"], rtol=1e-6)
    np.testing.assert_allclose(grads["w"], grads_ref["w"], rtol=1e-6)


def _microbatch_rows(batch, num_microbatches):
    """``rows[i]`` = the row ids microbatch *i* held: the aux puts them in
    slot *i*, scaled so that ``accumulate_gradients``' mean over
    microbatches returns them as they are."""
    def loss_fn(p, b, i):
        slot = jax.nn.one_hot(i, num_microbatches)[:, None]
        return p["w"] * jnp.mean(b["id"]), slot * b["id"][None, :] * num_microbatches

    (_, rows), _ = accumulate_gradients(
        loss_fn, {"w": jnp.ones(())}, batch, num_microbatches,
        has_aux=True, pass_microbatch_index=True,
    )
    return rows


def test_grad_accum_microbatch_is_a_slice_of_every_shards_rows(devices8):
    """Under a mesh microbatch i takes the i-th 8 rows of each chip's 16
    (DDP's no_sync accumulation: every rank over its own rows), so the
    split moves nothing between chips."""
    mesh = make_mesh(MeshConfig(data=4), devices=devices8[:4])
    ids = np.arange(64, dtype=np.float32)
    with mesh:
        rows = jax.jit(_microbatch_rows, static_argnums=1)(
            shard_batch({"id": ids}, mesh), 2
        )
    expect = np.stack([
        np.concatenate([16 * d + 8 * i + np.arange(8) for d in range(4)])
        for i in range(2)
    ])
    np.testing.assert_array_equal(np.asarray(rows), expect)


def test_grad_accum_microbatch_is_contiguous_without_batch_shards(devices8):
    """No mesh: rows i*m … (i+1)*m - 1.  Inside a shard_map the batch is
    one device's rows already, and the same holds of those."""
    ids = np.arange(64, dtype=np.float32)
    rows = _microbatch_rows({"id": jnp.asarray(ids)}, 2)
    np.testing.assert_array_equal(np.asarray(rows), ids.reshape(2, 32))

    mesh = make_mesh(MeshConfig(data=4), devices=devices8[:4])
    batch_spec = P(("data", "fsdp"))
    with mesh:
        local = jax.jit(jax.shard_map(
            lambda b: _microbatch_rows(b, 2), mesh=mesh,
            in_specs=({"id": batch_spec},), out_specs=batch_spec,
            check_vma=False,
        ))(shard_batch({"id": ids}, mesh))
    # device d returns (2, 8): its rows 16d + 8i … 16d + 8i + 7
    np.testing.assert_array_equal(np.asarray(local), ids.reshape(8, 8))


@pytest.mark.parametrize(
    "has_aux,pass_index", [(False, False), (True, False), (True, True)]
)
def test_grad_accum_under_mesh_matches_full_batch(devices8, has_aux, pass_index):
    """Which rows share a microbatch changes under a mesh; the step's mean
    over the batch does not."""
    mesh = make_mesh(MeshConfig(data=4), devices=devices8[:4])
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    batch = {"x": rng.normal(size=(64, 5)).astype(np.float32),
             "y": rng.normal(size=(64,)).astype(np.float32)}

    def loss_fn(p, b, *index):
        pred = b["x"] @ p["w"]
        loss = jnp.mean((pred - b["y"]) ** 2)
        return (loss, {"pred_mean": jnp.mean(pred)}) if has_aux else loss

    ref, grads_ref = jax.value_and_grad(loss_fn, has_aux=has_aux)(params, batch)
    with mesh:
        out, grads = jax.jit(
            lambda p, b: accumulate_gradients(
                loss_fn, p, b, 4, has_aux=has_aux,
                pass_microbatch_index=pass_index,
            )
        )(params, shard_batch(batch, mesh))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
        (out, grads), (ref, grads_ref),
    )


def _tiny_gpt2_step(mesh, rows):
    """The flat ``make_train_step`` for a small GPT-2 with 2 microbatches,
    compiled under ``mesh`` for a ``(rows, 128)`` batch: ``(compiled,
    state, batch)``."""
    import optax

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = GPT2Config(vocab_size=256, max_seq_len=128, num_layers=2,
                     num_heads=2, hidden_dim=64)
    state = create_train_state(
        GPT2(cfg=cfg), jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32),
        optax.adam(1e-3), mesh=mesh, rules=DDP_RULES,
        init_kwargs={"train": False},
    )
    tokens = np.random.default_rng(0).integers(0, 256, (rows, 128), np.int32)
    batch = shard_batch({"tokens": tokens}, mesh)
    step = make_train_step(kind="lm", num_microbatches=2)
    with mesh:
        return step.lower(state, batch).compile(), state, batch


@pytest.mark.parametrize("mesh_cfg,rows,shard_local", [
    pytest.param(dict(data=4), 16, True, id="data4"),
    pytest.param(dict(data=2, fsdp=2), 16, True, id="data2-fsdp2"),
    # 6 rows a microbatch over 4 shards: the contiguous split, as ever
    pytest.param(dict(data=4), 12, False, id="microbatch-not-divided"),
])
def test_accumulating_step_computes_on_each_chips_own_rows(
    devices8, mesh_cfg, rows, shard_local
):
    """Reads the compiled step, not only its result: a split that cuts
    across the batch sharding still trains, on twice the FLOPs and with
    the microbatch gathered inside the loop (params replicated here, so
    any all-gather would be of the batch or of activations)."""
    from pytorch_distributed_training_tpu.obs.cost import (
        collective_census, compiled_cost,
    )

    mesh = make_mesh(MeshConfig(**mesh_cfg), devices=devices8[:4])
    compiled, state, batch = _tiny_gpt2_step(mesh, rows)
    if not shard_local:
        with mesh:
            _, metrics = compiled(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        return
    census = collective_census(compiled.as_text())
    assert not {"all-gather", "collective-permute", "all-to-all"} & set(census), census
    one = make_mesh(MeshConfig(data=1), devices=devices8[:1])
    quarter, _, _ = _tiny_gpt2_step(one, rows // 4)
    assert compiled_cost(compiled)["flops"] == pytest.approx(
        compiled_cost(quarter)["flops"], rel=0.05
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(devices8, causal):
    mesh = make_mesh(MeshConfig(data=1, sequence=8))
    b, l, h, d = 2, 64, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)

    ref = _xla_attention(q, k, v, causal=causal)
    with mesh:
        out = jax.jit(
            lambda q, k, v: ring_self_attention(q, k, v, mesh, causal=causal)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grads_flow(devices8):
    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    b, l, h, d = 2, 32, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    k, v = q + 0.1, q - 0.1

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    with mesh:
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(devices8, causal):
    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    b, l, h, d = 2, 32, 8, 16  # 8 heads over 4-way axis: 2 heads/member
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)

    ref = _xla_attention(q, k, v, causal=causal)
    with mesh:
        out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=causal))(
            q, k, v
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_rejects_indivisible_heads(devices8):
    mesh = make_mesh(MeshConfig(data=1, sequence=8))
    x = jnp.zeros((1, 16, 4, 8))  # 4 heads, 8-way axis
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(x, x, x, mesh)


# --- TP numerics parity (VERDICT r1 item 4) ---

def _tiny_gpt2():
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=4,
        hidden_dim=64,
    )
    return GPT2(cfg=cfg)


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_numerics_match_unsharded(devices8, tp):
    """GPT-2 logits and grads under tensor={2,4} must equal the unsharded
    model (the test that catches a wrong einsum/rule — placement-only checks
    cannot)."""
    from pytorch_distributed_training_tpu.parallel.sharding import (
        shard_batch, shard_params, tp_rules_for,
    )

    model = _tiny_gpt2()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (4, 16)), jnp.int32
    )
    variables = model.init(jax.random.PRNGKey(0), tokens, train=False)
    params = variables["params"]

    def loss_fn(p, t):
        logits = model.apply({"params": p}, t, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        tgt = t[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return jnp.mean(nll)

    ref_logits = model.apply({"params": params}, tokens, train=False)
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, tokens)

    mesh = make_mesh(MeshConfig(data=-1, tensor=tp))
    assert mesh.shape["tensor"] == tp
    rules = tp_rules_for("gpt2")
    with mesh:
        p_sh = shard_params(params, mesh, rules)
        t_sh = shard_batch({"t": np.asarray(tokens)}, mesh)["t"]
        tp_logits = jax.jit(
            lambda p, t: model.apply({"params": p}, t, train=False)
        )(p_sh, t_sh)
        tp_loss, tp_grads = jax.jit(jax.value_and_grad(loss_fn))(p_sh, t_sh)

    np.testing.assert_allclose(
        np.asarray(tp_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(tp_loss), float(ref_loss), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(ref_grads)
    flat_tp = {tuple(str(k) for k in path): g
               for path, g in jax.tree_util.tree_leaves_with_path(tp_grads)}
    for path, g_ref in flat_ref:
        g_tp = flat_tp[tuple(str(k) for k in path)]
        np.testing.assert_allclose(
            np.asarray(g_tp), np.asarray(g_ref), rtol=2e-3, atol=2e-5,
            err_msg=f"grad mismatch at {path}",
        )


def test_tp_cli_smoke(tmp_path):
    """One CLI run with --tensor-parallel 2 (VERDICT r1 item 4)."""
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=2,hidden_dim=64,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--tensor-parallel", "2",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "'tensor': 2" in result.output
    assert "training finished" in result.output


# --- sequence-parallel GPT-2 integration ---

def test_gpt2_ring_attention_matches_plain(devices8):
    """GPT-2 with sp_mesh (sequence-parallel ring attention) must equal the
    plain model — the SP analogue of the TP/PP exactness tests."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=4, hidden_dim=64
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4))
    plain = GPT2(cfg=cfg)
    ring = GPT2(cfg=cfg, sp_mesh=mesh)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (4, 32)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)
    ref = plain.apply(variables, tokens, train=False)

    with mesh:
        t_sh = shard_batch(
            {"t": np.asarray(tokens)}, mesh, sequence_sharded=True
        )["t"]
        out = jax.jit(
            lambda p, t: ring.apply({"params": p}, t, train=False)
        )(variables["params"], t_sh)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_gpt2_ring_attention_grads_match_plain(devices8):
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=4, hidden_dim=64
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4))
    plain = GPT2(cfg=cfg)
    ring = GPT2(cfg=cfg, sp_mesh=mesh)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, (4, 32)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)

    def nll(model, p):
        logits = model.apply({"params": p}, tokens, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    g_ref = jax.grad(lambda p: nll(plain, p))(variables["params"])
    with mesh:
        g_ring = jax.jit(jax.grad(lambda p: nll(ring, p)))(variables["params"])
    from jax.flatten_util import ravel_pytree

    # Host-gather before ravel: ravel_pytree's eager concatenate over
    # mesh-sharded leaves miscomputes (scales by an axis size) on jax 0.4.x.
    a = np.asarray(ravel_pytree(jax.tree.map(np.asarray, g_ring))[0])
    b = np.asarray(ravel_pytree(g_ref)[0])
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


def test_gpt2_ulysses_matches_plain(devices8):
    """GPT-2 with sp_mode="ulysses" (all-to-all head resharding through the
    full model) must equal the plain model — the VERDICT r2 item-6
    integration: Ulysses as a first-class, model-reachable SP strategy."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=4, hidden_dim=64
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4))
    plain = GPT2(cfg=cfg)
    uly = GPT2(cfg=cfg, sp_mesh=mesh, sp_mode="ulysses")
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 128, (4, 32)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)
    ref = plain.apply(variables, tokens, train=False)

    with mesh:
        t_sh = shard_batch(
            {"t": np.asarray(tokens)}, mesh, sequence_sharded=True
        )["t"]
        out = jax.jit(
            lambda p, t: uly.apply({"params": p}, t, train=False)
        )(variables["params"], t_sh)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_gpt2_ulysses_grads_match_plain(devices8):
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=4, hidden_dim=64
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4))
    plain = GPT2(cfg=cfg)
    uly = GPT2(cfg=cfg, sp_mesh=mesh, sp_mode="ulysses")
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 128, (4, 32)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)

    def nll(model, p):
        logits = model.apply({"params": p}, tokens, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    g_ref = jax.grad(lambda p: nll(plain, p))(variables["params"])
    with mesh:
        g_uly = jax.jit(jax.grad(lambda p: nll(uly, p)))(variables["params"])
    from jax.flatten_util import ravel_pytree

    a = np.asarray(ravel_pytree(jax.tree.map(np.asarray, g_uly))[0])
    b = np.asarray(ravel_pytree(g_ref)[0])
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


def test_ulysses_cli_smoke():
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=2,hidden_dim=64,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--sequence-parallel", "2",
            "--sequence-parallel-mode", "ulysses",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "'sequence': 2" in result.output
    assert "training finished" in result.output


def test_ulysses_cli_rejects_indivisible_heads():
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=2,hidden_dim=66,num_heads=3,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "1", "--sequence-parallel", "2",
            "--sequence-parallel-mode", "ulysses",
        ],
    )
    assert result.exit_code != 0
    assert "divisible" in result.output


def test_sequence_parallel_cli_smoke(tmp_path):
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=2,hidden_dim=64,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--sequence-parallel", "2",
            "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "'sequence': 2" in result.output
    assert "training finished" in result.output


def test_zero1_weight_update_sharding_matches_ddp(devices8):
    """ZeRO-1 (replicated params, data-sharded optimizer slots) must train
    identically to plain DDP: same params after several steps, with the
    slots genuinely sharded over `data` (the optimizer-memory win the
    layout exists for — arXiv:2004.13336)."""
    import optax

    from pytorch_distributed_training_tpu.parallel.sharding import (
        DDP_RULES, ZERO1_OPT_RULES,
    )
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )
    import dataclasses as _dc

    model = _tiny_gpt2()
    mesh = make_mesh(MeshConfig(data=-1))
    rng = np.random.default_rng(7)
    batches = [
        {"tokens": rng.integers(0, 128, (8, 16)).astype(np.int32)}
        for _ in range(3)
    ]
    step = make_train_step(kind="lm")

    def run(opt_rules):
        state = create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((8, 16), jnp.int32),
            optax.adam(1e-2), mesh=mesh, rules=DDP_RULES,
            opt_rules=opt_rules, init_kwargs={"train": False},
        )
        with mesh:
            for b in batches:
                state, m = step(state, shard_batch(dict(b), mesh))
        return state

    z1_rules = _dc.replace(ZERO1_OPT_RULES, min_fsdp_size=1)
    s_ddp = run(None)
    s_z1 = run(z1_rules)
    # Optimizer slots actually sharded over `data` under zero1.
    specs = {str(l.sharding.spec) for l in jax.tree.leaves(s_z1.opt_state)}
    assert any("data" in s for s in specs), specs
    from jax.flatten_util import ravel_pytree

    a = np.asarray(ravel_pytree(jax.tree.map(np.asarray, s_z1.params))[0])
    b = np.asarray(ravel_pytree(jax.tree.map(np.asarray, s_ddp.params))[0])
    # Adam's rsqrt(nu) amplifies f32 reduction-order noise ratio-wise where
    # early-training nu ~ 0, so elementwise rtol is meaningless on those
    # entries; relative L2 over all params pins equivalence.
    rel = np.linalg.norm(a - b) / np.linalg.norm(b)
    assert rel < 1e-4, rel


def test_fsdp_numerics_match_unsharded(devices8):
    """FSDP-sharded GPT-2 (params sharded over `fsdp`) must produce the
    same logits/loss/grads as the unsharded model — the FSDP analogue of
    the TP parity test (SURVEY.md §2c)."""
    model = _tiny_gpt2()
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 128, (8, 16)), jnp.int32
    )
    variables = model.init(jax.random.PRNGKey(0), tokens, train=False)
    params = variables["params"]

    def loss_fn(p, t):
        logits = model.apply({"params": p}, t, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, t[:, 1:, None], axis=-1)
        return jnp.mean(nll)

    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, tokens)

    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    # Use a tiny min-size so the small test params actually shard.
    import dataclasses as _dc

    rules = _dc.replace(FSDP_RULES, min_fsdp_size=1)
    with mesh:
        p_sh = shard_params(params, mesh, rules)
        # At least one leaf must actually be sharded over fsdp.
        specs = {str(l.sharding.spec) for l in jax.tree.leaves(p_sh)}
        assert any("fsdp" in s for s in specs), specs
        t_sh = shard_batch({"t": np.asarray(tokens)}, mesh)["t"]
        fs_loss, fs_grads = jax.jit(jax.value_and_grad(loss_fn))(p_sh, t_sh)
    np.testing.assert_allclose(float(fs_loss), float(ref_loss), rtol=1e-5)
    from jax.flatten_util import ravel_pytree

    np.testing.assert_allclose(
        np.asarray(ravel_pytree(jax.tree.map(np.asarray, fs_grads))[0]),
        np.asarray(ravel_pytree(ref_grads)[0]),
        rtol=2e-4, atol=1e-5,
    )


# ---------------------------------------------------------------------------
# SP x TP composition (Megatron-style: sequence-sharded activations with
# tensor-sharded QKV/MLP; heads shard over `tensor` inside the SP wrappers)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_gpt2_sp_x_tp_matches_plain(devices8, sp_mode):
    """GPT-2 over a (data=2, sequence=2, tensor=2) mesh — ring or Ulysses
    attention with Megatron TP rules — must equal the unsharded model in
    logits AND grads."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_training_tpu.parallel.sharding import (
        shard_batch, shard_params, tp_rules_for,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=4,
        hidden_dim=64,
    )
    mesh = make_mesh(MeshConfig(data=2, sequence=2, tensor=2))
    plain = GPT2(cfg=cfg)
    sp = GPT2(cfg=cfg, sp_mesh=mesh, sp_mode=sp_mode)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (4, 32)), jnp.int32
    )
    variables = plain.init(jax.random.PRNGKey(0), tokens, train=False)
    params = variables["params"]

    def loss_fn(model, p, t):
        logits = model.apply({"params": p}, t, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.mean(
            jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        )

    ref_logits = plain.apply({"params": params}, tokens, train=False)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: loss_fn(plain, p, tokens)
    )(params)

    with mesh:
        p_sh = shard_params(params, mesh, tp_rules_for("gpt2"))
        t_sh = shard_batch(
            {"t": np.asarray(tokens)}, mesh, sequence_sharded=True
        )["t"]
        out = jax.jit(
            lambda p, t: sp.apply({"params": p}, t, train=False)
        )(p_sh, t_sh)
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p, t: loss_fn(sp, p, t), argnums=0)
        )(p_sh, t_sh)

    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    from jax.flatten_util import ravel_pytree

    np.testing.assert_allclose(
        np.asarray(ravel_pytree(jax.tree.map(np.asarray, grads))[0]),
        np.asarray(ravel_pytree(ref_grads)[0]),
        rtol=5e-4, atol=1e-5,
    )


def test_sp_x_tp_cli_smoke():
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--cpu-devices", "8", "--model", "gpt2",
            "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=2,hidden_dim=64,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--sequence-parallel", "2",
            "--tensor-parallel", "2", "--learning-rate", "0.001",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "'sequence': 2" in result.output
    assert "'tensor': 2" in result.output
    assert "training finished" in result.output

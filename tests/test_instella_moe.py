"""Instella-MoE on the normal path at a toy size (float32, CPU): YaRN's
frequencies and the softmax scale against hand-computed values, the sigmoid
router with its selection-only bias, the far-skip residual against the
standard block, the MTP module's targets and shared leaves, the step that
finds the objective on the model, and the CLI."""

import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tpu import models, train
from pytorch_distributed_training_tpu.models import instella_moe as im, moe
from pytorch_distributed_training_tpu.ops import pallas_attention as pa
from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss

TOY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
           qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, kv_lora_rank=16,
           intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2)
YARN = dict(im._YARN)


def toy(**overrides):
    net = models.create_model("instella_moe_16b_a3b", cfg_overrides={**TOY, **overrides})
    params = net.init(jax.random.PRNGKey(1), jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    return net, params


def test_yarn_frequencies_against_hand_computed_values():
    """32 rotary dimensions at theta 8e6, factor 40 over 4096: the correction
    dimensions are floor(3.03) = 3 and ceil(6.52) = 7, so pairs 0..3 keep
    theta^(-i/16), pairs 7..15 turn 40 times slower, 4..6 blend by quarters."""
    d = lambda r: 32 * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(8e6))
    assert (math.floor(d(32)), math.ceil(d(1))) == (3, 7)
    got = np.asarray(im.yarn_inv_freq(32, 8e6, YARN))
    plain = np.array([8e6 ** (-i / 16) for i in range(16)])
    blend = np.clip((np.arange(16) - 3) / 4, 0, 1)
    np.testing.assert_allclose(got, plain * (1 - blend) + plain / 40 * blend, rtol=1e-6)
    np.testing.assert_allclose(got[:4], plain[:4], rtol=1e-6)
    np.testing.assert_allclose(got[7:], plain[7:] / 40, rtol=1e-6)
    assert got[5] == pytest.approx(plain[5] * (0.5 + 0.5 / 40), rel=1e-6)


def test_softmax_scale_is_yarns_mscale_squared():
    cfg = im.InstellaMoeConfig()
    assert im.softmax_scale(cfg) == pytest.approx(128 ** -0.5 * (0.1 * math.log(40) + 1) ** 2, rel=1e-12)
    assert im.softmax_scale(cfg) == pytest.approx(0.165627, rel=1e-5)     # 0.0883883 x 1.368888^2
    plain = im.InstellaMoeConfig(rope_scaling={**YARN, "mscale_all_dim": 0})
    assert im.softmax_scale(plain) == pytest.approx(128 ** -0.5)
    assert im.InstellaMoeConfig(rope_scaling=dict(YARN)) == cfg            # a dict (JSON) is the same config


def test_published_sizes_are_the_defaults():
    cfg = im.InstellaMoeConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank) == (2048, 16, 96, 32, 128, 512)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.n_shared_experts, cfg.num_hidden_layers, cfg.first_k_dense_replace) == (10944, 1408, 64, 6, 2, 27, 1)
    assert (cfg.vocab_size, cfg.routed_scaling_factor, cfg.rope_theta, cfg.rms_norm_eps) == (128896, 2.5, 8e6, 1e-6)


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_the_selection_bias_changes_which_experts_and_never_their_weights(norm_topk_prob):
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    plain_w, plain_e, scores = moe.topk_route(logits, 2, norm_topk_prob, scoring="sigmoid", scale=2.5)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(logits), rtol=1e-6)
    zero_w, zero_e, _ = moe.topk_route(logits, 2, norm_topk_prob, scoring="sigmoid", scale=2.5,
                                       bias=jnp.zeros((8,)))
    np.testing.assert_array_equal(zero_e, plain_e)
    np.testing.assert_allclose(zero_w, plain_w, rtol=1e-6)
    bias = jnp.zeros((8,)).at[5].set(10.0)                    # expert 5 wins every selection
    w, e, _ = moe.topk_route(logits, 2, norm_topk_prob, scoring="sigmoid", scale=2.5, bias=bias)
    assert bool(jnp.all(jnp.any(e == 5, axis=-1))) and not bool(jnp.all(jnp.any(plain_e == 5, axis=-1)))
    chosen = jnp.take_along_axis(jax.nn.sigmoid(logits), e, axis=-1)       # the UNBIASED scores
    want = 2.5 * (chosen / chosen.sum(-1, keepdims=True) if norm_topk_prob else chosen)
    np.testing.assert_allclose(w, want, rtol=1e-6)
    # and it takes no gradient
    grad = jax.grad(lambda b: moe.topk_route(logits, 2, norm_topk_prob, scoring="sigmoid", bias=b)[0].sum())(bias)
    np.testing.assert_array_equal(grad, 0.0)


def test_softmax_scoring_is_still_sdars_router():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w, e, scores = moe.topk_route(logits, 2)
    top_w, top_e = jax.lax.top_k(jax.nn.softmax(logits), 2)
    np.testing.assert_array_equal(e, top_e)
    np.testing.assert_allclose(w, top_w / top_w.sum(-1, keepdims=True), rtol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        moe.topk_route(logits, 2, scoring="tanh")


def test_sequence_balance_is_one_under_even_routing_and_counts_by_sequence():
    scores = jnp.full((2, 16, 8), 0.5)
    experts = (jnp.arange(2 * 16 * 2).reshape(2, 16, 2) % 8).astype(jnp.int32)      # every expert 4 times a sequence
    assert float(moe.sequence_balance(scores, experts)) == pytest.approx(1.0)
    skew = jnp.zeros((2, 16, 2), jnp.int32).at[..., 1].set(1)                        # experts 0 and 1 only
    hot = scores.at[..., :2].set(0.9)
    want = 2 * (8 / 2) * (0.9 / (2 * 0.9 + 6 * 0.5))            # f = E/k for the two, P = their score share
    assert float(moe.sequence_balance(hot, skew)) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("farskip", [True, False])
def test_farskip_off_is_the_standard_prenorm_block_and_on_is_the_ladder(farskip):
    """The block written out by hand from its sublayers, both ways."""
    net, params = toy(farskip=farskip)
    cfg = net.cfg
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    pos = jnp.arange(32)
    p = params["block_1"]
    norm = lambda name, t: im.RMSNorm(cfg.rms_norm_eps).apply({"params": p[name]}, t)
    attn = lambda t: im.MlaAttention(cfg).apply({"params": p["attn"]}, t, pos)
    routed = lambda t: moe.TopKMoe(8, 2, 32, scoring="sigmoid", selection_bias=True, routed_scaling_factor=2.5,
                                   seq_aux=True).apply({"params": p["moe"]}, t, mutable=["losses", "moe_counters"])[0]
    ffn = lambda t: routed(t) + im.GatedMlp(64).apply({"params": p["shared"]}, t)
    before = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    (mid, out), _ = im.InstellaBlock(cfg).apply({"params": p}, before, x, pos, mutable=["losses", "moe_counters"])
    if farskip:
        want_mid = x + attn(norm("ln1", before))
        want_out = want_mid + ffn(norm("ln2", x))
    else:
        want_mid = x + attn(norm("ln1", x))
        want_out = want_mid + ffn(norm("ln2", want_mid))
    np.testing.assert_allclose(mid, want_mid, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-6)


def test_farskip_changes_the_model():
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    on, params = toy(farskip=True)
    off, _ = toy(farskip=False)
    a, b = on.apply({"params": params}, tokens), off.apply({"params": params}, tokens)
    assert float(jnp.abs(a - b).max()) > 1e-3


def test_mtp_predicts_two_ahead_with_the_trunks_head_and_embedding():
    net, params = toy()
    assert {"embed", "lm_head", "mtp_proj", "mtp_hnorm", "mtp_enorm", "mtp_block", "mtp_final"} <= set(params)
    assert not any(k.startswith("mtp") and ("embed" in params[k] or "lm_head" in params[k]) for k in params)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    apply = lambda prm, t: net.apply({"params": prm}, t, mtp=True, mutable=["losses", "moe_counters"])[0]
    logits, mtp_logits = apply(params, tokens)
    assert logits.shape == mtp_logits.shape == (2, 32, 512)
    np.testing.assert_array_equal(logits, net.apply({"params": params}, tokens))
    # the module's row t reads tokens up to t + 1 and nothing later
    later = tokens.at[:, 10:].set((tokens[:, 10:] + 1) % 512)
    _, moved = apply(params, later)
    np.testing.assert_allclose(moved[:, :9], mtp_logits[:, :9], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(moved[:, 9] - mtp_logits[:, 9]).max()) > 1e-4
    # both heads are the trunk's leaves: the MTP loss reaches them, and the embedding twice over
    grads = jax.grad(lambda prm: cross_entropy_loss(apply(prm, tokens)[1][:, :-2], tokens[:, 2:]))(params)
    assert float(jnp.abs(grads["lm_head"]["kernel"]).max()) > 0 and float(jnp.abs(grads["embed"]).max()) > 0
    # the step's loss: next-token CE + 0.3 x CE two ahead + alpha x balance
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens, optax.sgd(0.0), init_kwargs={"train": False})
    assert train.step.lm_objective(state) == ("next_token_mtp", net.cfg)
    state, metrics = train.make_train_step(kind="lm")(state, {"tokens": tokens})    # a rate of 0: the same weights
    logits, mtp_logits = apply(state.params, tokens)
    ce = cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
    mtp = cross_entropy_loss(mtp_logits[:, :-2], tokens[:, 2:])
    assert float(metrics["mtp_loss"]) == pytest.approx(float(mtp), rel=1e-5)
    assert float(metrics["loss"]) == pytest.approx(float(ce + 0.3 * mtp) + float(metrics["moe_balance_loss"]), rel=1e-5)
    # a fresh sigmoid router over 8 experts: about alpha x 1 a layer, three expert places
    assert 2.5e-4 < float(metrics["moe_balance_loss"]) < 4e-4
    assert float(metrics["moe_held_assignments"]) == 2 * 3 * 32 * 2         # all held: sequences x places x T x k


@pytest.mark.parametrize("microbatches", [1, 2])
def test_the_step_returns_the_loss_parts_and_counters(microbatches):
    net, _ = toy(experts_held=(2, 4), remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 512)
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens[:1], optax.adamw(1e-3),
                                     init_kwargs={"train": False})
    bias = np.asarray(state.params["block_1"]["moe"]["router_bias"])      # the step donates its state
    losses = []
    for _ in range(3):
        state, metrics = train.make_train_step(kind="lm", num_microbatches=microbatches)(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        assert set(train.step.STEP_LOSS_PARTS) <= set(metrics) and "moe_load_max" in metrics
        assert 0 < float(metrics["moe_held_assignments"]) < 4 * 3 * 32 * 2
    assert losses[2] < losses[0]
    # nothing updates the selection bias: zero gradient, zero decay of zero
    np.testing.assert_array_equal(state.params["block_1"]["moe"]["router_bias"], bias)


def test_remat_wraps_the_pair_and_changes_nothing():
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    plain, params = toy()
    remat, _ = toy(remat=True)

    def grads(net):
        def loss(prm):
            (a, b), _ = net.apply({"params": prm}, tokens, mtp=True, mutable=["losses", "moe_counters"])
            return cross_entropy_loss(a[:, :-1], tokens[:, 1:]) + cross_entropy_loss(b[:, :-2], tokens[:, 2:])
        return jax.grad(loss)(params)

    for a, b in zip(jax.tree_util.tree_leaves(grads(plain)), jax.tree_util.tree_leaves(grads(remat))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_eval_and_hidden_states_take_the_trunk_alone():
    net, params = toy()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens, optax.sgd(0.0), init_kwargs={"train": False})
    out = train.make_eval_step(kind="lm")(state, {"tokens": tokens})
    logits = net.apply({"params": state.params}, tokens, train=False)
    assert float(out["loss"]) == pytest.approx(float(cross_entropy_loss(logits[:, :-1], tokens[:, 1:])), rel=1e-5)
    assert net.apply({"params": params}, tokens, return_hidden=True).shape == (2, 32, 64)


def test_the_causal_multi_tile_launch_notes_its_visited_share(monkeypatch):
    """At 8192 positions in 1024-tiles 36 of 64 tiles a head are live (0.5625,
    0.5001 of the pairs): 28 whole and 8 on the diagonal, which the tabled
    pair takes at their sub-ranges — 36 of 64 sub-squares of 128 in the
    forward, 10 of 16 of 256 in the backward."""
    for name in ("_flash_tabled_fwd", "_flash_tabled_bwd"):    # jitted: traced once a process
        monkeypatch.setattr(pa, name, getattr(pa, name).__wrapped__)
    monkeypatch.setattr(pa, "_visited_pair_share", {})
    q = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: pa.flash_attention(q, k, v, causal=True, interpret=True)
                            .astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, q, q)
    assert pa.flash_visited_pair_share() == {"flash_fwd": (28 + 8 * 36 / 64) / 64, "flash_bwd": (28 + 8 * 10 / 16) / 64}
    monkeypatch.setattr(pa, "_visited_pair_share", {})
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: pa.flash_attention(q, k, v, causal=False, interpret=True), q, q, q)
    assert pa.flash_visited_pair_share() == {"flash_fwd": 1.0}


def test_compiled_step_names_the_new_phases():
    from pytorch_distributed_training_tpu.obs.schema import METRICS
    from pytorch_distributed_training_tpu.obs.trace import PHASES

    assert set(train.step.STEP_LOSS_PARTS) <= set(METRICS)
    net, _ = toy(remat=True)
    tokens = jnp.zeros((2, 32), jnp.int32)
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens, optax.sgd(0.1), init_kwargs={"train": False})
    text = train.make_train_step(kind="lm").lower(state, {"tokens": tokens}).compile().as_text()
    for phase in ("attn/mla", "moe/shared", "train/mtp", "moe/route", "moe/experts", "train/loss"):
        assert phase in PHASES and phase in text, phase


def test_cli_trains_the_toy_size(tmp_path):
    overrides = ",".join(f"{k}={v}" for k, v in TOY.items()) + ",experts_held=2:4"
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main", "--use-cpu", "--model",
         "instella_moe_16b_a3b", "--dataset", "synthetic-tokens", "--seq-len", "32", "--model-overrides", overrides,
         "--batch-size", "4", "--accum-steps", "2", "--num-workers", "0", "--steps-per-epoch", "3",
         "--learning-rate", "1e-3", "--remat", "--metrics-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600,
        # one CPU device: the suite's 8-device XLA_FLAGS would want a batch of 8
        env={k: v for k, v in {**os.environ, "JAX_PLATFORMS": "cpu"}.items() if k != "XLA_FLAGS"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-2000:]
    assert "training started" in out.stdout
    recorded = "".join(p.read_text() for p in tmp_path.rglob("*") if p.is_file())
    for name in ("mtp_loss", "moe_balance_loss", "moe_held_assignments"):
        assert name in recorded, name

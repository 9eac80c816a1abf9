"""Qwen3-Next on the normal path at a toy size (float32, CPU): the model against
the plain reference (loss, logits and every leaf's gradient), the 3 : 1 layer
pattern, the zero-centred norms, the gated shared expert, the expert shares
against the uncut layer, the step that trains it, and the CLI."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compiled
import toy_cli
from benchmark.reference import qwen3_next as ref
from pytorch_distributed_training_tpu import models, train
from pytorch_distributed_training_tpu.models import moe, qwen3_next as qn
from pytorch_distributed_training_tpu.models.layers import GatedMlp
from pytorch_distributed_training_tpu.models.sdar import RMSNorm
from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss

TOY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
           head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
           linear_value_head_dim=16, num_experts=16, num_experts_per_tok=4,
           moe_intermediate_size=32, shared_expert_intermediate_size=48)
KEYS = ("full_attention_interval", "rms_norm_eps", "num_attention_heads", "num_key_value_heads", "head_dim",
        "partial_rotary_factor", "rope_theta", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim", "num_experts_per_tok", "norm_topk_prob")


@functools.cache
def toy(**overrides):
    """The toy model and its parameters, made once a file for each set of
    overrides (a test that changes a leaf changes a copy)."""
    net = models.create_model("qwen3_next_80b_a3b", cfg_overrides={**TOY, **overrides})
    params = net.init(jax.random.PRNGKey(1), jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    return net, params


def reference_config(net):
    """The reference reads a configuration FILE's keys: the toy's, from the model's own config."""
    return {**{k: getattr(net.cfg, k) for k in KEYS}, "layers": net.cfg.num_hidden_layers, "system": {
        "overrides": {"num_experts": net.cfg.num_experts, "experts_held": net.cfg.experts_held}}}


def moved(params):
    """Norm weights start at zero (or one), dt_bias at one: move every vector,
    or a wrong centre or an ignored weight would not show."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)])


def system_loss(net, tokens):
    def fn(prm):
        logits, sown = net.apply({"params": prm}, tokens, mutable=["moe_counters"])
        held = sum(jnp.sum(x) for path, x in jax.tree_util.tree_leaves_with_path(sown["moe_counters"])
                   if "moe_held_assignments" in jax.tree_util.keystr(path))
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:]), (logits, held)
    return fn


@pytest.mark.parametrize("held, length", [(None, 128), ((4, 8), 136)])
def test_the_model_is_the_reference(held, length):
    """Logits and loss to 1e-4 relative; every leaf's gradient to 2e-3
    relative plus 1e-4 of the leaf's largest entry: float32 on both sides,
    the chunked rule, the sorted experts and the flash-free attention of the
    program against the recurrence, the dense experts and the dense mask of
    the reference differ by summation order, which a gradient through four
    layers and a softmax over the vocabulary widens to ~1e-4 of its scale.
    Both lengths are several of the rule's chunks of 64; one no chunk
    divides pads the rule."""
    net, params = toy(experts_held=held)
    params, cfg = moved(params), reference_config(net)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, length), 0, 512)
    (want_loss, (want_logits, want_held)), want_grads = compiled.value_and_grad(
        system_loss(net, tokens), has_aux=True)(params)
    logits_of = compiled.reference(ref.logits_of, cfg)                  # at the "highest" matmul precision
    for n in range(2):
        np.testing.assert_allclose(logits_of(params, tokens[n])[0], want_logits[n], rtol=1e-4, atol=2e-5)
    got_loss, parts, got_grads, got_held = compiled.reference(ref.loss_and_grads, cfg)(params, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-4) and parts.shape == (1,)
    assert float(got_held) == float(want_held) and (held is not None or float(got_held) == 2 * 4 * length * 4)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert len(flat_want) == 3 * 17 + 16 + 3
    for path, got in jax.tree_util.tree_leaves_with_path(got_grads):
        want, name = flat_want[path], jax.tree_util.keystr(path)
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6 + 1e-4 * float(jnp.abs(want).max()), err_msg=name)


def test_three_linear_layers_to_one_full_and_the_published_leaves():
    net, params = toy(num_hidden_layers=8)
    mixers = [sorted(set(params[f"block_{i}"]) - {"input_layernorm", "post_attention_layernorm"}) for i in range(8)]
    lin = ["linear_attn", "moe", "shared_expert", "shared_expert_gate"]
    full = ["moe", "self_attn", "shared_expert", "shared_expert_gate"]
    assert mixers == [lin, lin, lin, full] * 2
    gdn, attn = params["block_0"]["linear_attn"], params["block_3"]["self_attn"]
    assert gdn["in_proj_qkvz"]["kernel"].shape == (64, 2 * 32 + 2 * 64)            # q | k | v | z
    assert gdn["in_proj_ba"]["kernel"].shape == (64, 8) and gdn["conv_w"].shape == (4, 2 * 32 + 64)
    assert set(gdn) == {"in_proj_qkvz", "in_proj_ba", "conv_w", "A_log", "dt_bias", "norm", "out_proj"}  # no conv bias
    a = np.exp(np.asarray(gdn["A_log"]))
    assert a.shape == (4,) and (0 < a).all() and (a < 16).all()
    np.testing.assert_array_equal(gdn["dt_bias"], 1.0)
    np.testing.assert_array_equal(gdn["norm"], 1.0)                                 # the gated norm: not zero-centred
    assert attn["q_proj"]["kernel"].shape == (64, 4 * 2 * 32)                       # a head's query and its gate
    for leaf in (attn["q_norm"]["scale"], attn["k_norm"]["scale"], params["norm"]["scale"],
                 params["block_0"]["input_layernorm"]["scale"]):
        np.testing.assert_array_equal(leaf, 0.0)                                    # zero-centred: (1 + w) from w = 0
    assert params["block_0"]["shared_expert_gate"]["kernel"].shape == (64, 1)
    assert set(params["block_0"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}  # softmax router: no selection bias
    assert [qn.Qwen3NextConfig().full_attention(i) for i in range(8)] == [False, False, False, True] * 2


def test_published_sizes_are_the_defaults():
    cfg = qn.Qwen3NextConfig()
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers, cfg.full_attention_interval) == (2048, 151936, 48, 4)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.partial_rotary_factor,
            cfg.rope_theta) == (16, 2, 256, 0.25, 1e7)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.norm_topk_prob, cfg.rms_norm_eps) == (512, 10, 512, 512, True, 1e-6)
    assert sum(cfg.full_attention(i) for i in range(48)) == 12


def test_rms_norm_zero_centred_is_a_field_and_the_plain_norm_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    plain, centred = RMSNorm(1e-6), RMSNorm(1e-6, zero_centred=True)
    p_plain, p_centred = plain.init(jax.random.PRNGKey(1), x), centred.init(jax.random.PRNGKey(1), x)
    assert jax.tree_util.tree_structure(p_plain) == jax.tree_util.tree_structure(p_centred)   # one leaf, "scale"
    np.testing.assert_array_equal(p_plain["params"]["scale"], 1.0)
    np.testing.assert_array_equal(p_centred["params"]["scale"], 0.0)
    np.testing.assert_allclose(plain.apply(p_plain, x), centred.apply(p_centred, x), rtol=1e-6)
    w = jnp.linspace(-0.5, 0.5, 8)
    np.testing.assert_allclose(centred.apply({"params": {"scale": w}}, x),
                               plain.apply({"params": {"scale": 1.0 + w}}, x), rtol=1e-6)


def test_the_partial_rotary_turns_the_first_quarter_of_a_head():
    """A position's rotation moves the first head_dim / 4 lanes of q and k and
    no other lane, and the scores depend on the positions' difference alone."""
    net, params = toy(num_hidden_layers=4)
    attn, cfg = params["block_3"]["self_attn"], reference_config(net)
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 2, 32))
    turned = ref.rotary(x, cfg)
    np.testing.assert_array_equal(turned[:, :, 8:], x[:, :, 8:])
    np.testing.assert_array_equal(turned[0], x[0])                                  # position 0: no turn
    assert float(jnp.abs(turned[1:, :, :8] - x[1:, :, :8]).max()) > 1e-3
    layer = qn.GatedAttention(net.cfg)
    same = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(4), (1, 1, 64)), (1, 16, 64))
    out = layer.apply({"params": attn}, same)
    np.testing.assert_allclose(out[0, 1:], out[0, :1].repeat(15, 0), rtol=1e-4, atol=1e-5)   # equal tokens, equal rows


def test_sixteen_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer():
    """The deployment's cut: 512 routed experts 32 a chip over 16 chips (here
    64 experts 4 a chip), the shared expert and its gate whole on each.  The
    chips' routed parts, each computed by the program with its own share's
    weights, plus the gated shared expert counted ONCE equal what the uncut
    reference gives for the whole expert sublayer."""
    cfg = {"num_experts_per_tok": 10, "norm_topk_prob": True}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 32))
    layer = lambda held: moe.TopKMoe(64, 10, 16, experts_held=held)
    routed = layer(None).init(jax.random.PRNGKey(1), x)["params"]
    routed["router"] = 25.0 * routed["router"]                      # scores that differ, so the top-10 is decided
    shared_mlp = GatedMlp(24)
    shared = {"shared_expert": shared_mlp.init(jax.random.PRNGKey(2), x)["params"],
              "shared_expert_gate": {"kernel": jax.random.normal(jax.random.PRNGKey(3), (32, 1))}}
    with jax.default_matmul_precision("highest"):
        want_routed, all_held = ref.experts(x[0], routed, cfg, (0, 64))
        want = want_routed + ref.shared(x[0], shared)
    assert float(all_held) == 64 * 10
    gate = jax.nn.sigmoid(x @ shared["shared_expert_gate"]["kernel"])
    total, counted = gate * shared_mlp.apply({"params": shared["shared_expert"]}, x), 0.0   # every chip alike: once
    for first in range(0, 64, 4):
        share = {"router": routed["router"], **{k: routed[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        part, sown = layer((first, 4)).apply({"params": share}, x, mutable=["moe_counters"])
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(part[0], ref.experts(x[0], share, cfg, (first, 4))[0], rtol=2e-4, atol=1e-6)
        total, counted = total + part, counted + float(sown["moe_counters"]["moe_held_assignments"][0])
    assert counted == 64 * 10                                       # every assignment lands on exactly one chip
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=2e-6)


def test_the_reference_rule_is_the_written_recurrence():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q, kk = unit(np.asarray(jax.random.normal(k[0], (5, 2, 3)))), unit(np.asarray(jax.random.normal(k[1], (5, 2, 3))))
    v = np.asarray(jax.random.normal(k[2], (5, 2, 4)))
    g, beta = -np.exp(np.asarray(jax.random.normal(k[3], (5, 2)))), np.asarray(jax.nn.sigmoid(jax.random.normal(k[4], (5, 2))))
    got = np.asarray(ref.delta_rule(q, kk, v, g, beta))
    state = np.zeros((2, 4, 3))
    for t in range(5):
        for h in range(2):
            state[h] = np.exp(g[t, h]) * state[h] @ (np.eye(3) - beta[t, h] * np.outer(kk[t, h], kk[t, h])) \
                + beta[t, h] * np.outer(v[t, h], kk[t, h])
            np.testing.assert_allclose(got[t, h], state[h] @ q[t, h], rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def trained():
    """The toy's train step, built and compiled once for the file: AdamW
    with its rate as state, remat, two microbatches of (2, 32)."""
    net, _ = toy(experts_held=(4, 8), remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 512)
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens[:1], compiled.adamw(),
                                     init_kwargs={"train": False})
    return net, tokens, state, train.make_train_step(kind="lm", num_microbatches=2)


def test_the_step_trains_it_by_next_token_cross_entropy_and_returns_the_counters(trained):
    net, tokens, state, step = trained
    state = compiled.at_rate(state, 0.0)
    assert train.step.lm_objective(state) == ("next_token", None) and state.float32_params == ("A_log", "dt_bias")
    state, metrics = step(state, {"tokens": tokens})                             # rate 0: same weights
    logits = net.apply({"params": state.params}, tokens, mutable=["moe_counters"])[0]
    assert float(metrics["loss"]) == pytest.approx(float(cross_entropy_loss(logits[:, :-1], tokens[:, 1:])), rel=1e-5)
    assert set(train.step.STEP_LOSS_PARTS).isdisjoint(metrics) and "moe_load_max" in metrics
    assert 0 < float(metrics["moe_held_assignments"]) < 4 * 4 * 32 * 4           # sequences x layers x T x k
    state, losses = compiled.at_rate(state, 1e-3), []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
    assert losses[2] < losses[0]


def test_the_bf16_policy_leaves_the_decays_leaves_in_float32():
    net, params = toy()
    cast = train.make_policy("bf16").cast_to_compute(params, keep=net.float32_params)
    gdn = cast["block_0"]["linear_attn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert gdn["conv_w"].dtype == gdn["in_proj_qkvz"]["kernel"].dtype == cast["embed"].dtype == jnp.bfloat16
    logits = net.clone(dtype=jnp.bfloat16).apply({"params": cast}, jnp.zeros((1, 32), jnp.int32), mutable=["moe_counters"])[0]
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_remat_changes_nothing():
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    plain, params = toy()
    remat, _ = toy(remat=True)
    grads = lambda net: compiled.grad(system_loss(net, tokens), has_aux=True)(params)[0]
    for a, b in zip(jax.tree_util.tree_leaves(grads(plain)), jax.tree_util.tree_leaves(grads(remat))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_compiled_step_names_the_new_phases(trained):
    from pytorch_distributed_training_tpu.obs.trace import PHASES

    _, tokens, state, step = trained
    text = step.lower(state, {"tokens": tokens}).compile().as_text()             # the program the file compiled
    for phase in ("gdn/proj", "gdn/conv", "gdn/delta", "gdn/gate", "attn/proj", "attn/core", "moe/route",
                  "moe/experts", "moe/shared", "block/norm", "train/head"):
        assert phase in PHASES and phase in text, phase


def test_cli_trains_the_toy_size(tmp_path):
    overrides = ",".join(f"{k}={v}" for k, v in TOY.items()) + ",experts_held=4:8"
    out = toy_cli.train("qwen3_next_80b_a3b", overrides, "--remat", "--metrics-dir", str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-2000:]
    assert "training started" in out.stdout
    recorded = "".join(p.read_text() for p in tmp_path.rglob("*") if p.is_file())
    # which form each op took: the toy's shapes miss the lane tiles
    for name in ("moe_held_assignments", "moe_load_max", "conv_plan[kind=xla]", "grouped_plan[kind=xla]",
                 "gated_delta_plan[kind=xla]"):
        assert name in recorded, name

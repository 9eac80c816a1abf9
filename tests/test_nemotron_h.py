"""Nemotron-H on the normal path at a toy size (float32, CPU): the model
against the plain reference, the chunked scan against the position-by-position
recurrence, the convolution's causality, ``TopKMoe``'s plain experts against a
dense loop and the 16-share sum, grouped K/V under the causal mask, the step
that trains it by plain next-token cross entropy, and the CLI."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import compiled
import toy_cli
from benchmark.reference import nemotron_h as ref
from pytorch_distributed_training_tpu import models, train
from pytorch_distributed_training_tpu.models import moe, nemotron_h as nh
from pytorch_distributed_training_tpu.ops import attention, pallas_attention as pa
from pytorch_distributed_training_tpu.ops.causal_conv import causal_conv
from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss
from pytorch_distributed_training_tpu.ops.ssd import ssd_chunked

TOY = dict(vocab_size=512, hidden_size=64, hybrid_override_pattern="ME*ME", num_hidden_layers=5,
           mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16, n_routed_experts=8,
           num_experts_per_tok=2, moe_intermediate_size=32, moe_shared_expert_intermediate_size=64)


@functools.cache
def toy(**overrides):
    """The toy model and its parameters, made once a file for each set of
    overrides (a test that changes a leaf changes a copy)."""
    net = models.create_model("nemotron_h_30b_a3b", cfg_overrides={**TOY, **overrides})
    params = net.init(jax.random.PRNGKey(1), jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    return net, params


def reference_config(net):
    """The reference reads a configuration FILE's keys: the toy's, from the model's own config."""
    cfg = {k: getattr(net.cfg, k) for k in (
        "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
        "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "norm_eps", "layer_norm_epsilon")}
    return {**cfg, "layers": net.cfg.num_hidden_layers, "system": {"overrides": {
        "n_routed_experts": net.cfg.n_routed_experts, "experts_held": net.cfg.experts_held}}}


def moved(params):
    """Norm scales start at one, D at one and the selection bias at zero:
    move every vector, or a wrong scale or an ignored bias would not show."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)])


def recurrence(x, dt, a, b, c):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t, one position a step."""
    per_group = x.shape[2] // b.shape[2]
    b, c = jnp.repeat(b, per_group, axis=2), jnp.repeat(c, per_group, axis=2)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    return jnp.moveaxis(jax.lax.scan(step, zero, tuple(jnp.moveaxis(m, 1, 0) for m in (x, dt, b, c)))[1], 0, 1)


def scan_inputs(t=64, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(k[0], (2, t, h, p)), jax.nn.softplus(jax.random.normal(k[1], (2, t, h)) - 3.0),
            -jnp.arange(1.0, h + 1.0), jax.random.normal(k[2], (2, t, g, n)), jax.random.normal(k[3], (2, t, g, n)))


def scan_cost(*inputs, chunk=None):
    """The chunked scan's output (the recurrence's, without a chunk) under a sine, summed."""
    return jnp.sum(jnp.sin(recurrence(*inputs) if chunk is None else ssd_chunked(*inputs, chunk=chunk)))


@pytest.mark.parametrize("chunk", [16, 32])           # four chunks and two
def test_chunked_scan_is_the_recurrence_forward_and_backward(chunk):
    args = scan_inputs()
    want = compiled.jit(recurrence)(*args)
    scan = compiled.jit(ssd_chunked, chunk=chunk)
    np.testing.assert_allclose(scan(*args), want, rtol=2e-5, atol=2e-5)
    # the state carried between chunks is not small: the last chunk alone, from a zero state, reads otherwise
    alone = scan(*(m[:, -chunk:] if m.ndim > 1 else m for m in args))
    assert float(jnp.abs(alone - want[:, -chunk:]).max()) > 0.05 * float(jnp.abs(want).max())
    got = compiled.grad(scan_cost, argnums=(0, 1, 2, 3, 4), chunk=chunk)(*args)
    for name, g, w in zip("x dt a b c".split(), got, compiled.grad(scan_cost, argnums=(0, 1, 2, 3, 4))(*args)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4 * float(jnp.abs(w).max()), err_msg=name)


def test_chunked_scan_says_why_a_ragged_length_is_refused():
    x, dt, a, b, c = scan_inputs(t=48)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd_chunked(x, dt, a, b, c, chunk=32)
    assert ssd_chunked(x, dt, a, b, c, chunk=16).shape == x.shape


def test_the_convolution_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 6))
    w, bias = jax.random.normal(jax.random.PRNGKey(1), (4, 6)), jax.random.normal(jax.random.PRNGKey(2), (6,))
    y = causal_conv(x, w, bias)
    for t in (0, 2, 9):            # by the definition, zeros before the sequence
        want = bias + sum(w[j] * (x[0, t - 3 + j] if t - 3 + j >= 0 else 0.0) for j in range(4))
        np.testing.assert_allclose(y[0, t], want, rtol=1e-5, atol=1e-6)
    later = causal_conv(x.at[0, 9:, :].add(1.0), w, bias)       # a later token moves
    np.testing.assert_array_equal(later[0, :9], y[0, :9])           # ... and no earlier output does
    assert float(jnp.abs(later[0, 9] - y[0, 9]).min()) > 0
    other = causal_conv(x.at[0, :, 3].add(1.0), w, bias)         # a channel reads itself only
    np.testing.assert_array_equal(jnp.delete(other, 3, axis=-1), jnp.delete(y, 3, axis=-1))
    np.testing.assert_allclose(ref.convolution(x[0], w, bias), y[0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_the_model_is_the_reference(held):
    """Logits, the loss, the held assignments and every leaf's gradient, on a
    pattern with all three letters."""
    net, params = toy(experts_held=held)
    params, cfg = moved(params), reference_config(net)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)

    def system_loss(prm):
        logits, sown = net.apply({"params": prm}, tokens, mutable=["moe_counters"])
        held_n = sum(jnp.sum(x) for path, x in jax.tree_util.tree_leaves_with_path(sown["moe_counters"])
                     if "moe_held_assignments" in jax.tree_util.keystr(path))
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:]), (logits, held_n)

    (want_loss, (want_logits, want_held)), want_grads = compiled.value_and_grad(system_loss, has_aux=True)(params)
    logits_of = compiled.reference(ref.logits_of, cfg)                  # at the "highest" matmul precision
    for n in range(2):
        np.testing.assert_allclose(logits_of(params, tokens[n])[0], want_logits[n], rtol=2e-4, atol=2e-5)
    got_loss, parts, got_grads, got_held = compiled.reference(ref.loss_and_grads, cfg)(params, tokens)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5) and parts.shape == (1,)
    assert float(got_held) == float(want_held) and (held is not None or float(got_held) == 2 * 2 * 32 * 2)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert len(flat_want) == 40
    for path, got in jax.tree_util.tree_leaves_with_path(got_grads):
        want, name = flat_want[path], jax.tree_util.keystr(path)
        assert (float(jnp.abs(want).max()) > 0) != ("router_bias" in name), name    # only the bias takes none
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6 + 1e-4 * float(jnp.abs(want).max()), err_msg=name)


def test_published_sizes_are_the_defaults():
    cfg = nh.NemotronHConfig()
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers, len(cfg.hybrid_override_pattern)) == (2688, 131072, 52, 52)
    assert [cfg.layers.count(k) for k in "ME*"] == [23, 23, 6] and cfg.layers[:9] == "MEMEM*EME"
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel,
            cfg.chunk_size) == (64, 64, 8, 128, 4, 128)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.moe_shared_expert_intermediate_size, cfg.routed_scaling_factor) == (128, 6, 1856, 3712, 2.5)
    assert nh.NemotronHConfig(num_hidden_layers=9).layers == "MEMEM*EME"
    with pytest.raises(ValueError, match="letters M, E and"):
        nh.NemotronHConfig(hybrid_override_pattern="MXE")


def test_a_layer_is_one_sublayer_and_the_mixers_leaves_are_the_published_shapes():
    net, params = toy()
    assert [sorted(set(params[f"block_{i}"]) - {"norm"}) for i in range(5)] == [
        ["mixer"], ["moe", "shared"], ["attn"], ["mixer"], ["moe", "shared"]]
    mixer = params["block_0"]["mixer"]
    assert mixer["in_proj"]["kernel"].shape == (64, 2 * 64 + 2 * 2 * 16 + 8)           # z | x B C | dt
    assert mixer["conv_w"].shape == (4, 64 + 2 * 2 * 16) and mixer["conv_b"].shape == (128,)
    np.testing.assert_allclose(mixer["A_log"], np.log(np.arange(1, 9)), rtol=1e-6)
    np.testing.assert_array_equal(mixer["D"], 1.0)
    steps = jax.nn.softplus(mixer["dt_bias"])                                          # the steps the bias stands for
    assert float(steps.min()) >= 1e-3 * 0.999 and float(steps.max()) <= 0.1 * 1.001
    assert float(jnp.abs(mixer["conv_w"]).max()) <= 0.5 and set(params["block_1"]["moe"]) == {
        "router", "router_bias", "w_up", "w_down"}                                     # no gate


@pytest.mark.parametrize("gated, activation", [(True, "silu"), (False, "relu2"), (False, "silu")])
def test_topk_moe_is_a_dense_loop_over_its_experts(gated, activation, monkeypatch):
    """Either expert form against every expert on every token, weighted by
    the routing; several passes of the sorted-rows loop; the gradients of the
    hand-written backward against ``jax.grad`` of the dense loop."""
    monkeypatch.setattr(moe, "ROWS_CHUNK", 48)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 32))
    layer = moe.TopKMoe(8, 2, 16, scoring="sigmoid", selection_bias=True, routed_scaling_factor=2.5,
                        gated=gated, activation=activation)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert ("w_gate" in params) == gated
    act = moe.ACTIVATIONS[activation]

    def dense(prm, x):
        w, e, _ = moe.topk_route(x[0] @ prm["router"], 2, scoring="sigmoid", bias=prm["router_bias"], scale=2.5)
        out = 0.0
        for i in range(8):
            hidden = act(x[0] @ prm["w_gate"][i]) * (x[0] @ prm["w_up"][i]) if gated else act(x[0] @ prm["w_up"][i])
            out = out + jnp.sum(jnp.where(e == i, w, 0.0), axis=-1)[:, None] * (hidden @ prm["w_down"][i])
        return out[None]

    apply = lambda prm, x: layer.apply({"params": prm}, x, mutable=["moe_counters"])[0]
    np.testing.assert_allclose(apply(params, x), dense(params, x), rtol=2e-4, atol=1e-6)
    cost = lambda fn: lambda prm, x: jnp.sum(jnp.sin(fn(prm, x)))
    got, want = jax.grad(cost(apply), argnums=(0, 1))(params, x), jax.grad(cost(dense), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5)
    with pytest.raises(ValueError, match="activation"):
        moe.TopKMoe(8, 2, 16, activation="gelu").init(jax.random.PRNGKey(1), x)


@pytest.mark.parametrize("gated, window", [(False, 1), (False, 5), (False, 16), (False, 64), (False, 100), (True, 5), (True, 7), (True, 300)])
def test_dense_windows_are_the_grouped_passes(gated, window):
    """``expert_window``: the sorted held rows in windows of that many on one
    grid, a plain product for each expert with rows in a window, against the
    grouped passes — the output, the counters and every gradient of the
    hand-written backward.  The share holds an expert the router never picks
    (no pass), one with fewer rows than a window and one with several windows;
    the larger windows hold the rows of two, three and all of the experts."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96, 32))
    make = lambda w: moe.TopKMoe(8, 3, 16, experts_held=(2, 4), scoring="sigmoid", selection_bias=True,
                                 gated=gated, activation="silu" if gated else "relu2", expert_window=w)
    params = make(None).init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = params["router_bias"].at[jnp.array([3, 4])].set(jnp.array([-10.0, 10.0]))
    apply = lambda w: lambda prm, x: make(w).apply({"params": prm}, x, mutable=["moe_counters"])
    (want, counters), (got, counters_w) = apply(None)(params, x), apply(window)(params, x)
    assert float(counters["moe_counters"]["moe_load_max"][0]) == 96 and counters == counters_w
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    cost = lambda w: lambda prm, x: jnp.sum(jnp.sin(apply(w)(prm, x)[0]))
    got, want = (jax.grad(cost(w), argnums=(0, 1))(params, x) for w in (window, None))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5, err_msg=jax.tree_util.keystr(path))
    assert float(jnp.abs(got[0]["w_up"][1]).max()) == 0.0 < float(jnp.abs(got[0]["w_up"][2]).max())


@pytest.mark.parametrize("counts, passes", [
    ((10, 0, 3, 7), 5 + 2),                 # 20 rows in windows of 4: five windows, boundaries inside two of them (10 | 13)
    ((8, 4, 0, 8), 5 + 0),                  # every boundary on the grid
    ((1, 1, 1, 1), 1 + 3),                  # one window, four experts
    ((0, 0, 0, 9), 3 + 0),
    ((0, 0, 0, 0), 0),
])
def test_dense_passes_follow_the_held_rows_and_not_their_split(counts, passes):
    """``expert_window``'s passes: the windows of ONE grid over the sorted held
    rows, and one more for each expert boundary that falls inside a window."""
    counts = jnp.array(counts, jnp.int32)
    n, *_ = moe._passes(jnp.arange(24), counts, jnp.ones((24, 1)), 4, True)
    assert int(n) == passes


def test_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The deployment's cut: 128 routed experts 8 a chip over 16 chips, the
    shared expert whole on each.  The chips' routed parts, each computed by
    the program with its own share's weights, plus the shared expert counted
    ONCE equal what the uncut reference gives for the whole ``E`` sublayer."""
    cfg = {"num_experts_per_tok": 6, "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 32))
    layer = lambda held: moe.TopKMoe(128, 6, 16, experts_held=held, scoring="sigmoid", selection_bias=True,
                                     routed_scaling_factor=2.5, gated=False, activation="relu2")
    routed = layer(None).init(jax.random.PRNGKey(1), x)["params"]
    routed["router"] = 25.0 * routed["router"]                      # scores that differ, so the top-6 is decided
    routed["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (128,))
    shared = nh.Relu2Mlp(48).init(jax.random.PRNGKey(2), x)["params"]
    with jax.default_matmul_precision("highest"):
        want_routed, all_held = ref.experts(x[0], routed, cfg, (0, 128))
        want = want_routed + ref.relu2(x[0] @ shared["w_up"]["kernel"]) @ shared["w_down"]["kernel"]
    assert float(all_held) == 64 * 6
    total, counted = nh.Relu2Mlp(48).apply({"params": shared}, x), 0.0      # every chip computes it alike: once
    for first in range(0, 128, 8):
        share = {"router": routed["router"], "router_bias": routed["router_bias"],
                 **{k: routed[k][first:first + 8] for k in ("w_up", "w_down")}}
        part, sown = layer((first, 8)).apply({"params": share}, x, mutable=["moe_counters"])
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(part[0], ref.experts(x[0], share, cfg, (first, 8))[0], rtol=2e-4, atol=1e-6)
        total, counted = total + part, counted + float(sown["moe_counters"]["moe_held_assignments"][0])
    assert counted == 64 * 6                                        # every assignment lands on exactly one chip
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_grouped_kv_under_the_causal_mask_reads_kv_at_their_own_head_count(causal):
    """The XLA path, and the tabled kernels (interpreted) with two tiles a key
    row, against K/V repeated to the query heads; gradients too."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 256, 4, 64))
    kv = [jax.random.normal(key, (1, 256, 2, 64)) for key in k[1:]]
    full = lambda q, k_, v_: attention._xla_attention(
        q, jnp.repeat(k_, 2, axis=2), jnp.repeat(v_, 2, axis=2), causal=causal)
    want = full(q, *kv)
    np.testing.assert_allclose(attention.dot_product_attention(q, *kv, causal=causal, use_flash=False), want,
                               rtol=1e-5, atol=1e-5)
    tabled = lambda q, k_, v_: pa.flash_attention(q, k_, v_, causal=causal, block_q=128, block_k=128, interpret=True)
    assert pa.flash_plan(256, 256, 4, 2, 64, 4, causal=causal, block_diffusion=None, block_q=128, block_k=128).kind == "tabled"
    np.testing.assert_allclose(tabled(q, *kv), want, rtol=2e-3, atol=2e-3)
    cost = lambda fn: lambda *args: jnp.sum(jnp.sin(fn(*args)))
    for g, w in zip(jax.grad(cost(tabled), argnums=(0, 1, 2))(q, *kv), jax.grad(cost(full), argnums=(0, 1, 2))(q, *kv)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=5e-3, atol=5e-3)
    # a key row of one tile takes kernels that know one head count: K/V are repeated for them
    np.testing.assert_allclose(pa.flash_attention(q, *kv, causal=causal, interpret=True), want, rtol=2e-3, atol=2e-3)
    with pytest.raises(ValueError):
        attention.dot_product_attention(q, *(jnp.zeros((1, 256, 3, 64)),) * 2, causal=causal)    # 4 is no multiple of 3


def test_the_cells_attention_call_notes_its_visited_share(monkeypatch):
    """32 query heads over 2 K/V heads at 8192: Instella's causal launch (36 of
    64 tiles a head live, the 8 diagonal ones at their sub-ranges) with K/V at
    their own head count."""
    for name in ("_flash_tabled_fwd", "_flash_tabled_bwd"):    # jitted: traced once a process
        monkeypatch.setattr(pa, name, getattr(pa, name).__wrapped__)
    monkeypatch.setattr(pa, "_visited_pair_share", {})
    assert pa.flash_plan(8192, 8192, 32, 2, 128, 2, causal=True, block_diffusion=None).kind == "tabled"
    q, kv = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16), jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)
    grads = jax.eval_shape(jax.grad(lambda q, k, v: pa.flash_attention(q, k, v, causal=True, interpret=True)
                                    .astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert [g.shape for g in grads] == [q.shape, kv.shape, kv.shape]
    assert pa.flash_visited_pair_share() == {"flash_fwd": (28 + 8 * 36 / 64) / 64, "flash_bwd": (28 + 8 * 10 / 16) / 64}


@pytest.fixture(scope="module")
def trained():
    """The toy's train step, built and compiled once for the file: AdamW
    with its rate as state, remat, two microbatches of (2, 32)."""
    net, _ = toy(experts_held=(2, 4), remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 512)
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens[:1], compiled.adamw(),
                                     init_kwargs={"train": False})
    return net, tokens, state, train.make_train_step(kind="lm", num_microbatches=2)


def test_the_step_trains_it_by_next_token_cross_entropy_and_returns_the_counters(trained):
    net, tokens, state, step = trained
    state = compiled.at_rate(state, 0.0)
    assert train.step.lm_objective(state) == ("next_token", None)
    state, metrics = step(state, {"tokens": tokens})                             # rate 0: same weights
    logits = net.apply({"params": state.params}, tokens, mutable=["moe_counters"])[0]
    assert float(metrics["loss"]) == pytest.approx(float(cross_entropy_loss(logits[:, :-1], tokens[:, 1:])), rel=1e-5)
    assert set(train.step.STEP_LOSS_PARTS).isdisjoint(metrics) and "moe_load_max" in metrics
    assert 0 < float(metrics["moe_held_assignments"]) < 4 * 2 * 32 * 2           # sequences x E layers x T x k
    state = compiled.at_rate(state, 1e-3)
    bias = np.asarray(state.params["block_1"]["moe"]["router_bias"])             # the step donates its state
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
    assert losses[2] < losses[0]
    np.testing.assert_array_equal(state.params["block_1"]["moe"]["router_bias"], bias)   # nothing updates it


def test_the_bf16_policy_leaves_the_decays_leaves_in_float32(monkeypatch):
    net, params = toy()
    assert net.float32_params == ("A_log", "dt_bias", "D")
    cast = train.make_policy("bf16").cast_to_compute(params, keep=net.float32_params)
    mixer = cast["block_0"]["mixer"]
    assert {k: mixer[k].dtype for k in ("A_log", "dt_bias", "D")} == {k: jnp.float32 for k in ("A_log", "dt_bias", "D")}
    assert mixer["conv_w"].dtype == mixer["in_proj"]["kernel"].dtype == cast["embed"].dtype == jnp.bfloat16
    assert train.make_policy("bf16").cast_to_compute(jnp.ones((2,))).dtype == jnp.bfloat16    # a bare leaf has no key
    # and the step hands them over so
    asked = []
    real = train.policy.Policy.cast_to_compute
    monkeypatch.setattr(train.policy.Policy, "cast_to_compute",
                        lambda self, tree, keep=(): asked.append(keep) or real(self, tree, keep))
    state = train.create_train_state(net, jax.random.PRNGKey(1), jnp.zeros((1, 32), jnp.int32), optax.sgd(0.1),
                                     init_kwargs={"train": False})
    assert state.float32_params == net.float32_params
    # the names ride on the state: a wrapped ``apply_fn`` (no ``__self__`` to ask) changes nothing
    state = state.replace(apply_fn=lambda *args, **kwargs: net.apply(*args, **kwargs))
    train.make_train_step(kind="lm", policy=train.make_policy("bf16"))(state, {"tokens": jnp.zeros((2, 32), jnp.int32)})
    assert net.float32_params in asked


def test_remat_changes_nothing():
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    plain, params = toy()
    remat, _ = toy(remat=True)

    def grads(net):
        def loss(prm):
            logits, _ = net.apply({"params": prm}, tokens, mutable=["moe_counters"])
            return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
        return compiled.grad(loss)(params)

    for a, b in zip(jax.tree_util.tree_leaves(grads(plain)), jax.tree_util.tree_leaves(grads(remat))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_eval_and_hidden_states():
    net, params = toy()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)
    state = train.create_train_state(net, jax.random.PRNGKey(1), tokens, optax.sgd(0.0), init_kwargs={"train": False})
    out = train.make_eval_step(kind="lm")(state, {"tokens": tokens})
    logits = net.apply({"params": state.params}, tokens, train=False)
    assert float(out["loss"]) == pytest.approx(float(cross_entropy_loss(logits[:, :-1], tokens[:, 1:])), rel=1e-5)
    assert net.apply({"params": params}, tokens, return_hidden=True).shape == (2, 32, 64)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        net.apply({"params": params}, tokens[:, :24])


def test_compiled_step_names_the_new_phases(trained):
    from pytorch_distributed_training_tpu.obs.schema import METRICS
    from pytorch_distributed_training_tpu.obs.trace import PHASES

    assert {"moe_held_assignments", "moe_load_max"} <= set(METRICS)
    _, tokens, state, step = trained
    text = step.lower(state, {"tokens": tokens}).compile().as_text()             # the program the file compiled
    for phase in ("ssm/conv", "ssm/scan", "ssm/gate", "moe/route", "moe/experts", "moe/shared", "train/loss"):
        assert phase in PHASES and phase in text, phase


def test_cli_trains_the_toy_size(tmp_path):
    overrides = ",".join(f"{k}={v}" for k, v in TOY.items()) + ",experts_held=2:4"
    out = toy_cli.train("nemotron_h_30b_a3b", overrides, "--remat", "--metrics-dir", str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-2000:]
    assert "training started" in out.stdout
    recorded = "".join(p.read_text() for p in tmp_path.rglob("*") if p.is_file())
    # ... and which form of the scan the mixers took: the toy's shapes miss the lane tile
    # (and of the convolution before it: ``conv_plan``)
    for name in ("moe_held_assignments", "moe_load_max", "ssd_plan[kind=xla]", "conv_plan[kind=xla]"):
        assert name in recorded, name
    assert "ssd_plan[kind=pallas]" not in recorded and "conv_plan[kind=pallas]" not in recorded

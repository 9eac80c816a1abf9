"""The Gated DeltaNet chunk's unit-lower solve as a Pallas pair
(``ops/gated_delta.py``) under the CPU's interpreter: the forward kernel
against ``lax.linalg.triangular_solve`` at the Qwen3-Next cell's (64, 64) /
(64, 256) blocks, over several grid steps of chunk-heads, with decays near 0
and near -inf, β near 1 and entries of ``L`` near 1; the pair's gradients
against ``jax.grad`` of the triangular-solve form; the whole rule in the
``pallas`` form against the position-by-position recurrence at heads of 128;
the plan's answers; the kernels' names; the pair under a mesh of several
devices; and a toy Qwen3-Next layer whose traced gradient holds both pairs'
calls under ``gdn/delta``.  (The chunk recurrence's pair alone is in
``tests/test_gated_delta_chunk_pallas.py``; the whole rule here runs it
too.)  (The pair compiled for a described v5e at the cell's
call, and its calls under ``gdn/delta`` in ``obs/cost.scope_table`` of a
compiled step, are in ``tests/test_flash_grouped_causal.py``, with the other
compiles for a described chip.)"""

import ast
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compiled
from pytorch_distributed_training_tpu import models
from pytorch_distributed_training_tpu.obs.cost import mosaic_kernels
from pytorch_distributed_training_tpu.ops import gated_delta as gd
from test_gated_delta import cost as rule_cost, recurrence
from test_qk_norm_rope_pallas import kernels_in

C, W = 64, 256                        # the cell's chunk, and d_v + d_k of its heads of 128
# log-decays (mean of log(-g), its spread), β (mean of its logit), keys (how alike a chunk's are)
REGIMES = {
    "near-0": ((-6.0, 0.5), 0.0, 0.0),
    "near-inf": ((3.0, 0.5), 0.0, 0.0),
    "beta-one": ((-4.0, 1.0), 8.0, 0.0),
    "entries-near-1": ((-8.0, 0.5), 8.0, 0.95),
}


def blocks(regime, lead=(2, 3, 48), seed=0):
    """``lower`` and ``rhs`` as the rule makes them, lead + (C, C) / (C, W):
    unit keys, ``L_ij = β_i (k_i·k_j) e^{γ_i - γ_j}`` below the diagonal."""
    (mu, sd), beta_at, alike = REGIMES[regime]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    keys = jax.random.normal(k[0], lead + (C, 128))
    keys = alike * jax.random.normal(k[1], lead + (1, 128)) + (1 - alike) * keys
    keys = keys / jnp.linalg.norm(keys, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(beta_at + jax.random.normal(k[2], lead + (C,)))
    gamma = jnp.cumsum(-jnp.exp(mu + sd * jax.random.normal(k[3], lead + (C,))), axis=-1)
    below = jnp.tril(jnp.ones((C, C), bool), -1)
    decay = jnp.exp(jnp.where(below, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    lower = beta[..., :, None] * jnp.einsum("...id,...jd->...ij", keys, keys) * decay
    return lower, jax.random.normal(k[4], lead + (C, W))


def plain(lower, rhs):
    return gd._solve_xla(lower, rhs)


def pair(lower, rhs):
    return gd._solve_pallas(lower, rhs, interpret=True)


def cost(lower, rhs, d, solve):
    return jnp.sum(jnp.sin(solve(lower, rhs)) * d)


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_forward_kernel_is_the_triangular_solve(regime):
    """288 chunk-heads: three grid steps of 128, the last one padded; ``T``
    is the inverse of ``I + L``."""
    lower, rhs = blocks(regime)
    if regime == "entries-near-1":
        assert float(jnp.abs(lower).max()) > 0.9
    want = compiled.jit(plain)(lower, rhs)
    np.testing.assert_allclose(compiled.jit(pair)(lower, rhs), want, rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()))
    flat = lambda m: m.reshape(-1, *m.shape[-2:])
    n = flat(lower).shape[0]
    padded = lambda m: jnp.pad(flat(m), ((0, -n % 128), (0, 0), (0, 0)))
    solved, inverse = compiled.jit(gd._solve_fwd, interpret=True)(padded(lower), padded(rhs))
    eye = jnp.eye(C)
    np.testing.assert_allclose(inverse[:n], compiled.jit(plain)(flat(lower), jnp.broadcast_to(eye, (n, C, C))),
                               atol=2e-5)
    np.testing.assert_array_equal(inverse[n:], jnp.broadcast_to(eye, inverse[n:].shape))    # L 0: T is I
    assert float(jnp.abs(jnp.triu(inverse, 1)).max()) == 0.0


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_pairs_gradients_are_the_triangular_solves(regime):
    lower, rhs = blocks(regime, lead=(1, 2, 80), seed=1)
    d = jax.random.normal(jax.random.PRNGKey(9), rhs.shape)
    got = compiled.grad(cost, argnums=(0, 1), solve=pair)(lower, rhs, d)
    want = compiled.grad(cost, argnums=(0, 1), solve=plain)(lower, rhs, d)
    for name, g, w in zip(("lower", "rhs"), got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)
    assert float(jnp.abs(jnp.triu(got[0])).max()) == 0.0            # only the strict lower part is read


def rule_inputs(t, groups=1, heads=2, decay=(-3.0, 1.0)):
    """As ``tests/test_gated_delta.py::inputs`` makes them, at heads of 128."""
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(key[0], (1, t, groups, 128))) * 128 ** -0.5
    k = unit(jax.random.normal(key[1], (1, t, groups, 128)))
    v = jax.random.normal(key[2], (1, t, heads, 128))
    g = -jnp.exp(decay[0] + decay[1] * jax.random.normal(key[3], (1, t, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (1, t, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("t, decay", [(192, (-3.0, 1.0)), (192, (-6.0, 0.5)), (200, (2.0, 0.5))],
                         ids=["three-chunks", "near-0", "padded-near-inf"])
def test_the_rule_in_the_pallas_form_is_the_recurrence_forward_and_backward(t, decay):
    args = rule_inputs(t, decay=decay)
    assert gd.gated_delta_plan(t, 1, 2, 128, 128) == ("pallas", 64, True)
    want = compiled.jit(recurrence)(*args)
    got = compiled.jit(gd.gated_delta_rule)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grads = compiled.grad(rule_cost, argnums=(0, 1, 2, 3, 4), chunk=gd.CHUNK)(*args)       # the plan's chunk
    wants = compiled.grad(rule_cost, argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * max(1.0, float(jnp.abs(w).max())), err_msg=name)


@pytest.mark.parametrize("call, kind", [
    # seq_len, key heads, value heads, d_k, d_v
    ((8192, 16, 32, 128, 128), "pallas"),          # the Qwen3-Next cell's layer
    ((256, 2, 4, 256, 128), "pallas"),
    ((32, 2, 4, 16, 16), "xla"),                   # the toy widths of the tests and the rehearsal
    ((8192, 16, 32, 64, 128), "xla"),
    ((8192, 16, 32, 128, 96), "xla"),
])
def test_the_plan_is_a_function_of_the_shapes(call, kind):
    for backend in (None, "tpu", "cpu"):
        plan = gd.gated_delta_plan(*call, backend=backend)
        assert (plan.kind, plan.chunk) == (kind, gd.CHUNK)
        assert plan.interpret == (kind == "pallas" and backend != "tpu")
    assert gd.gated_delta_plan(*call, backend="gpu") == ("xla", gd.CHUNK, False)
    assert gd.gated_delta_plan(*call, chunk=32, backend="tpu") == ("xla", 32, False)   # the kernels' chunk is 64
    source = inspect.getsource(gd.gated_delta_plan) + inspect.getsource(gd.gated_delta_rule)
    assert "environ" not in source and "getenv" not in source and "qwen" not in source.lower()


def test_a_call_at_toy_widths_runs_the_xla_solve_and_both_are_counted():
    from pytorch_distributed_training_tpu.obs.schema import METRICS
    from test_gated_delta import inputs

    before = gd.gated_delta_plans_traced()
    gd.gated_delta_rule(*inputs(t=64))
    gd.gated_delta_rule(*rule_inputs(64))
    after = gd.gated_delta_plans_traced()
    assert after.get("xla", 0) == before.get("xla", 0) + 1
    assert after.get("pallas", 0) == before.get("pallas", 0) + 1
    assert METRICS["gated_delta_plan"]["labeled"]      # the gauges ``gated_delta_plan[kind=..]`` the CLI emits them as


def test_every_kernel_is_launched_under_a_listed_name_that_mosaic_kernels_counts():
    tree = ast.parse(inspect.getsource(gd))
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call) and ast.unparse(n.func) == "pl.pallas_call"]
    names = [next(k.value.value for k in call.keywords if k.arg == "name") for call in calls]
    assert sorted(names) == sorted(gd.KERNEL_NAMES)
    assert gd.KERNEL_NAMES == ("gated_delta_solve_fwd", "gated_delta_solve_bwd", "gated_delta_chunk_fwd",
                               "gated_delta_chunk_bwd")
    text = "\n".join(f'  %{name}.{i} = f32[512,64,256] custom-call(), custom_call_target="tpu_custom_call"'
                     for i, name in enumerate(["gated_delta_solve_fwd", "gated_delta_chunk_fwd"] * 3
                                              + ["gated_delta_solve_bwd", "gated_delta_chunk_bwd"]))
    assert mosaic_kernels(text) == {"gated_delta_solve_fwd": 3, "gated_delta_solve_bwd": 1,
                                    "gated_delta_chunk_fwd": 3, "gated_delta_chunk_bwd": 1}


@pytest.mark.parametrize("data, tensor", [(2, 2), (4, 1), (1, 4)])
def test_under_a_mesh_of_several_devices_the_pair_runs_a_shard(data, tensor):
    """A Mosaic call cannot be partitioned: under GSPMD the pair goes into a
    ``shard_map``, the batch over ``data`` and the heads over ``tensor``."""
    from pytorch_distributed_training_tpu import comm

    lower, rhs = blocks("near-0", lead=(4, 4, 8), seed=2)
    d = jax.random.normal(jax.random.PRNGKey(3), rhs.shape)
    results = lambda lower, rhs: jax.value_and_grad(lambda l, r: jnp.sum(pair(l, r) * d), argnums=(0, 1))(lower, rhs)
    want = results(lower, rhs)
    mesh = comm.make_mesh(comm.MeshConfig(data=data, tensor=tensor), devices=jax.devices()[:data * tensor])
    with mesh:
        got = jax.jit(results)(lower, rhs)
        assert "shard_map" in str(jax.make_jaxpr(pair)(lower, rhs))
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()))


# one Gated DeltaNet layer whose heads are 128 lanes, so that the plan takes the pair
LAYER = dict(vocab_size=256, hidden_size=128, num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
             head_dim=64, linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=128,
             linear_value_head_dim=128, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
             shared_expert_intermediate_size=32)


def test_a_layers_gradient_runs_the_pair_under_gdn_delta():
    """Remat on: each pair's forward kernel runs for the first forward and
    for the span's recompute, its backward kernel once, all under
    ``gdn/delta``; the layer's loss and gradients are the ``xla`` form's."""
    net = models.create_model("qwen3_next_80b_a3b", cfg_overrides={**LAYER, "remat": True})
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0, 255)
    params = net.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    loss = lambda p: jnp.mean(net.apply({"params": p}, tokens, train=False).astype(jnp.float32) ** 2)
    grad = jax.value_and_grad(loss)
    found = {name: scopes for name, scopes in kernels_in(grad, params).items() if name.startswith("gated_delta")}
    assert sorted(found) == sorted(gd.KERNEL_NAMES), found            # (the convolution's pair is there too)
    assert all("gdn/delta" in scope for name in gd.KERNEL_NAMES for scope in found[name]), found
    for pair in ("solve", "chunk"):
        assert len(found[f"gated_delta_{pair}_bwd"]) == 1 and len(found[f"gated_delta_{pair}_fwd"]) >= 2, found
    value, grads = jax.jit(grad)(params)
    xla = lambda *a, **k: gd.GatedDeltaPlan("xla", gd.CHUNK, False)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gd, "gated_delta_plan", xla)
        value_x, grads_x = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(value, value_x, rtol=1e-6)
    for (path, g), g_x in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_x)):
        np.testing.assert_allclose(g, g_x, rtol=1e-4, atol=1e-4 * float(jnp.abs(g_x).max()) + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))

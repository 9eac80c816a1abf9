"""The chunked gated delta rule (``ops/gated_delta.py``) against the rule
position by position, forward and ``jax.grad``, in float32 on the CPU: over
chunk sizes, lengths of one chunk and of several (and one no chunk divides),
grouped keys, and decays near 0 and near -inf."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compiled
from pytorch_distributed_training_tpu.ops.gated_delta import gated_delta_plan, gated_delta_rule


def recurrence(q, k, v, g, beta):
    """S_t = e^{g_t} (I - β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ, o_t = q_t S_t; value head h reads key head h // (H / G)."""
    per = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, per, axis=2), jnp.repeat(k, per, axis=2)

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        delta = b_t[..., None] * (v_t - jnp.einsum("bhd,bhde->bhe", k_t, state))
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state)

    zero = jnp.zeros((v.shape[0], v.shape[2], q.shape[-1], v.shape[-1]))
    return jnp.moveaxis(jax.lax.scan(step, zero, tuple(jnp.moveaxis(m, 1, 0) for m in (q, k, v, g, beta)))[1], 0, 1)


def inputs(t=64, groups=2, heads=4, dk=8, dv=16, decay=(-3.0, 1.0)):
    """Unit keys and queries (as the layer makes them), β in (0, 1), g = -softplus-like log-decays."""
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(key[0], (2, t, groups, dk))) * dk ** -0.5
    k = unit(jax.random.normal(key[1], (2, t, groups, dk)))
    v = jax.random.normal(key[2], (2, t, heads, dv))
    g = -jnp.exp(decay[0] + decay[1] * jax.random.normal(key[3], (2, t, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (2, t, heads)))
    return q, k, v, g, beta


def cost(*args, chunk=None):
    """The chunked rule's output (the recurrence's, without a chunk) under a sine and a weight a value lane."""
    out = recurrence(*args) if chunk is None else gated_delta_rule(*args, chunk=chunk)
    return jnp.sum(jnp.sin(out) * jnp.arange(args[2].shape[-1]))


# the decay regime is data: the three of one (t, chunk) share these programs' compiles
@pytest.mark.parametrize("t, chunk", [(64, 16), (64, 64), (48, 16), (40, 16), (16, 64), (96, 32)])
@pytest.mark.parametrize("decay", [(-6.0, 0.5), (-1.0, 1.0), (3.0, 0.5)], ids=["near-0", "mixed", "near-inf"])
def test_chunked_rule_is_the_recurrence_forward_and_backward(t, chunk, decay):
    args = inputs(t=t, decay=decay)
    want = compiled.jit(recurrence)(*args)
    got = compiled.jit(gated_delta_rule, chunk=chunk)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grads = compiled.grad(cost, argnums=(0, 1, 2, 3, 4), chunk=chunk)(*args)
    wants = compiled.grad(cost, argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * max(1.0, float(jnp.abs(w).max())), err_msg=name)


def test_the_state_carried_between_chunks_is_not_small():
    args = inputs(t=64, decay=(-4.0, 0.5))
    want = compiled.jit(recurrence)(*args)
    alone = gated_delta_rule(*(m[:, -16:] for m in args), chunk=16)      # the last chunk from a zero state
    assert float(jnp.abs(alone - want[:, -16:]).max()) > 0.05 * float(jnp.abs(want).max())


def test_a_decay_near_minus_infinity_forgets_and_beta_one_overwrites():
    """g = -inf at a position wipes the state there; with β 1 and a unit key the
    write replaces what the key read: o_t = (q_t·k_t) v_t."""
    q, k, v, g, beta = inputs(t=32, groups=4, heads=4)
    g = g.at[:, 20].set(-1e4)
    beta = beta.at[:, 20].set(1.0)
    o = gated_delta_rule(q, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(o[:, 20], jnp.einsum("bhd,bhd->bh", q[:, 20], k[:, 20])[..., None] * v[:, 20],
                               rtol=1e-5, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(o)))


def test_the_plan_has_one_answer_today_and_heads_must_group():
    """The plan's two answers: the Pallas solve at the cell's shapes on the
    TPU or the CPU's interpreter, XLA's at toy widths (and on any other
    backend); the chunk is 64 unless a call gives its own."""
    assert gated_delta_plan(8192, 16, 32, 128, 128, backend="tpu") == ("pallas", 64, False)
    assert gated_delta_plan(8192, 16, 32, 128, 128, backend="cpu") == ("pallas", 64, True)
    assert gated_delta_plan(8192, 16, 32, 128, 128, backend="gpu") == ("xla", 64, False)
    assert gated_delta_plan(96, 2, 4, 8, 16) == ("xla", 64, False)
    args = inputs(t=96)
    np.testing.assert_array_equal(gated_delta_rule(*args), gated_delta_rule(*args, chunk=64))
    q, k, v, g, beta = inputs(heads=3)
    with pytest.raises(ValueError, match="no multiple"):
        gated_delta_rule(q, k, v, g, beta, chunk=16)


def test_bf16_inputs_keep_a_float32_state():
    """bf16 operands to the products, float32 decays, solve and state: the
    result stays near the float32 rule over several chunks."""
    args = inputs(t=128, decay=(-3.0, 0.5))
    want = compiled.jit(recurrence)(*args)
    low = [m.astype(jnp.bfloat16) if i < 3 else m for i, m in enumerate(args)]
    got = gated_delta_rule(*low, chunk=32)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.03 * float(jnp.abs(want).max())


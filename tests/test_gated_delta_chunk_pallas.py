"""The Gated DeltaNet chunk recurrence and output as a Pallas pair
(``ops/gated_delta.py``: ``gated_delta_chunk_fwd`` / ``_bwd``) under the
CPU's interpreter, at heads of 128 with fewer key heads than value heads:
the forward's output and the state after the span against the recurrence
over chunks written plainly, from a state that is not zero, with decays near
0 and strongly negative; the pair's cotangents of q, k, the solution, γ and
the entering state against ``jax.grad`` of that recurrence; how many value
heads a grid step takes; and the pair under a mesh of several devices.  (The
pair compiled for a described v5e at the cell's call is in
``tests/test_flash_grouped_causal.py``; the whole rule through it, in
``tests/test_gated_delta_pallas.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops import gated_delta as gd

C, D = gd.CHUNK, 128
# log-decays: mean of log(-g) and its spread
DECAYS = {"near-0": (-6.0, 0.5), "strongly-negative": (1.5, 0.5)}


def operands(decay, t=2 * C, groups=1, heads=2, seed=0):
    """q, k (B, T, G, d_k) unit rows, as the layer makes them; the solution
    ``[U | W]`` (B, H, nc, C, d_v + d_k) of moderate size; ``γ`` the chunks'
    running sums of log-decays; a state that is not zero."""
    mu, sd = DECAYS[decay]
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(key[0], (1, t, groups, D))) * D ** -0.5
    k = unit(jax.random.normal(key[1], (1, t, groups, D)))
    solved = 0.5 * jax.random.normal(key[2], (1, heads, t // C, C, 2 * D))
    g = -jnp.exp(mu + sd * jax.random.normal(key[3], (1, heads, t // C, C)))
    state = 0.2 * jax.random.normal(key[4], (1, heads, D, D))
    return q, k, solved, jnp.cumsum(g, axis=-1), state


def recurrence(q, k, solved, gamma, state):
    """Chunk by chunk and head by head: ``V' = U - W S``, ``O = (e^{γ} ⊙ Q)
    S + ((Q Kᵀ) ⊙ E) V'``, ``S ← e^{γ_C} S + (e^{γ_C - γ} ⊙ K)ᵀ V'``."""
    per = solved.shape[1] // q.shape[2]
    seen = jnp.tril(jnp.ones((C, C), bool))
    outs, states = [], []
    for h in range(solved.shape[1]):
        s, o = state[0, h], []
        for c in range(solved.shape[2]):
            qc, kc = q[0, c * C:(c + 1) * C, h // per], k[0, c * C:(c + 1) * C, h // per]
            u, w, gam = solved[0, h, c, :, :D], solved[0, h, c, :, D:], gamma[0, h, c]
            decay = jnp.exp(jnp.where(seen, gam[:, None] - gam[None, :], -jnp.inf))
            new = u - w @ s
            o.append((jnp.exp(gam)[:, None] * qc) @ s + ((qc @ kc.T) * decay) @ new)
            s = jnp.exp(gam[-1]) * s + (jnp.exp(gam[-1] - gam)[:, None] * kc).T @ new
        outs.append(jnp.concatenate(o))
        states.append(s)
    return jnp.stack(states)[None], jnp.stack(outs, axis=1)[None]


def pair(*args):
    return gd._chunks_pallas(*args, interpret=True)


def tolerance(want):
    return dict(rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_the_forward_is_the_recurrence_over_chunks(decay):
    args = operands(decay)
    state, o = pair(*args)
    want_state, want_o = recurrence(*args)
    assert o.shape == (1, 2 * C, 2, D) and state.shape == (1, 2, D, D)
    np.testing.assert_allclose(o, want_o, **tolerance(want_o))
    np.testing.assert_allclose(state, want_state, **tolerance(want_state))
    if decay == "strongly-negative":          # e^{γ_C} underflows: no chunk keeps the state it entered with
        assert float(jnp.exp(args[3][..., -1]).max()) == 0.0


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_the_backward_is_the_gradient_of_the_recurrence(decay):
    """Cotangents of both results, of every operand: q, k at their key head
    (two value heads summed), the solution, γ, the entering state."""
    args = operands(decay, seed=1)
    key = jax.random.split(jax.random.PRNGKey(2), 2)
    dstate, do = jax.random.normal(key[0], args[4].shape), jax.random.normal(key[1], (1, 2 * C, 2, D))
    cost = lambda fn: lambda *a: jnp.sum(fn(*a)[0] * dstate) + jnp.sum(fn(*a)[1] * do)
    got = jax.grad(cost(pair), argnums=range(5))(*args)
    want = jax.grad(cost(recurrence), argnums=range(5))(*args)
    for name, g, w in zip(("q", "k", "solved", "gamma", "state"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


@pytest.mark.parametrize("groups, heads, step", [(16, 32, 4), (1, 2, 2), (2, 4, 4), (1, 8, 8), (3, 3, 3), (4, 4, 4)])
def test_a_grid_step_takes_whole_key_heads(groups, heads, step):
    """The cell's 32 value heads over 16 key heads: two key heads a step."""
    assert gd._heads_a_step(groups, heads) == step
    assert heads % step == 0 and step % (heads // groups) == 0


@pytest.mark.parametrize("data, tensor", [(2, 2), (1, 4)])
def test_under_a_mesh_of_several_devices_the_pair_runs_a_shard(data, tensor):
    """A Mosaic call cannot be partitioned: under GSPMD the pair goes into a
    ``shard_map``, the batch over ``data`` and the key heads (with their
    value heads) over ``tensor``."""
    from pytorch_distributed_training_tpu import comm

    q, k, solved, gamma, state = operands("near-0", t=C, groups=4, heads=4, seed=3)
    widen = lambda m: jnp.concatenate([m, 0.5 * m], axis=0)                 # batch 2
    args = tuple(widen(m) for m in (q, k, solved, gamma, state))
    do = jax.random.normal(jax.random.PRNGKey(4), (2, C, 4, D))
    results = lambda *a: jax.value_and_grad(lambda *a: jnp.sum(pair(*a)[1] * do) + jnp.sum(pair(*a)[0]),
                                            argnums=range(5))(*a)
    want = results(*args)
    mesh = comm.make_mesh(comm.MeshConfig(data=data, tensor=tensor), devices=jax.devices()[:data * tensor])
    with mesh:
        got = jax.jit(results)(*args)
        assert "shard_map" in str(jax.make_jaxpr(pair)(*args))
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()))

"""Plain references: each architecture's forward pass and loss in
straightforward ``jax.numpy`` and float32, with no kernels, cache or
batching, reading the same params tree as the system.  On a TPU a float32
matmul runs in lower precision unless told otherwise, so every entry point
runs under ``jax.default_matmul_precision("highest")``."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def attention(x, p, heads: int, causal: bool):
    """Fused-qkv multi-head attention; q, k, v are the first, second and
    third ``d`` columns of the packed projection."""
    b, l, d = x.shape
    q, k, v = jnp.split(dense(x, p["qkv"]), 3, axis=-1)
    q, k, v = (t.reshape(b, l, heads, d // heads) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d // heads)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return dense(o.reshape(b, l, d), p["proj"])


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))

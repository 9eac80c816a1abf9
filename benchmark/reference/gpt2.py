"""GPT-2 (Radford et al. 2019) as published: learned token and position
embeddings, pre-LN blocks, tanh GELU (``gelu_new``), causal attention, the
output head tied to the token embedding; loss = mean next-token cross
entropy.  Departure, listed in the configuration's ``reduced``: the layer-norm
epsilon is the one the configuration file states (the system's, 1e-6)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention, cross_entropy, dense, f32, gelu_tanh, global_norm, layer_norm


def logits(params, tokens, cfg):
    p = f32(params)
    eps = cfg["layer_norm_epsilon"]
    x = p["wte"][tokens] + p["wpe"][: tokens.shape[1]][None]
    for i in range(cfg["n_layer"]):
        blk = p[f"block_{i}"]
        x = x + attention(layer_norm(x, blk["ln1"], eps), blk["attn"], cfg["n_head"], True)
        y = gelu_tanh(dense(layer_norm(x, blk["ln2"], eps), blk["mlp_up"]))
        x = x + dense(y, blk["mlp_down"])
    return layer_norm(x, p["ln_final"], eps) @ p["wte"].T


def loss(params, batch, cfg):
    tokens = batch["tokens"]
    return cross_entropy(logits(params, tokens, cfg)[:, :-1], tokens[:, 1:])


def loss_and_grad_norm(params, batch, cfg):
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(f32(params), batch, cfg)
        return value, global_norm(grads)


def greedy_deficit(params, tokens, cfg):
    """For one sequence ``tokens`` (1, L): at every position, how far the
    reference's logit of the NEXT token of the sequence lies under the
    reference's top logit there (0 where the next token is the reference's own
    greedy choice).  (L-1,) float32."""
    with jax.default_matmul_precision("highest"):
        lg = logits(params, tokens, cfg)[0, :-1]
        chosen = jnp.take_along_axis(lg, tokens[0, 1:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - chosen

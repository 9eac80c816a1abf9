"""ViT (Dosovitskiy et al. 2020) as published: 16x16 patch embedding (a
strided convolution, here the same matmul over flattened patches), a CLS
token, learned position embeddings, pre-LN encoder blocks, a linear head on
the CLS token; loss = mean cross entropy.  Departures, listed in the
configuration's ``reduced``: tanh GELU and the layer-norm epsilon the
configuration file states (the system's)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention, cross_entropy, dense, f32, gelu_tanh, global_norm, layer_norm


def prepare(image, normalize):
    """uint8 records -> normalized floats, as the system's step does on the
    device; float images pass."""
    if image.dtype != jnp.uint8:
        return jnp.asarray(image, jnp.float32)
    x = image.astype(jnp.float32) / 255.0
    if normalize is not None:
        x = (x - jnp.asarray(normalize[0], jnp.float32)) / jnp.asarray(normalize[1], jnp.float32)
    return x


def logits(params, image, cfg):
    p = f32(params)
    eps, ps = cfg["layer_norm_eps"], cfg["patch_size"]
    b, h, w, c = image.shape
    x = image.reshape(b, h // ps, ps, w // ps, ps, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, (h // ps) * (w // ps), ps * ps * c)
    x = x @ p["patch_embed"]["kernel"].reshape(ps * ps * c, -1) + p["patch_embed"]["bias"]
    cls = jnp.broadcast_to(p["cls_token"], (b, 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1) + p["pos_embed"]
    for i in range(cfg["num_hidden_layers"]):
        blk = p[f"block_{i}"]
        x = x + attention(layer_norm(x, blk["ln1"], eps), blk["attn"], cfg["num_attention_heads"], False)
        y = gelu_tanh(dense(layer_norm(x, blk["ln2"], eps), blk["mlp"]["fc1"]))
        x = x + dense(y, blk["mlp"]["fc2"])
    return dense(layer_norm(x, p["ln_final"], eps)[:, 0], p["head"])


def loss(params, batch, cfg, normalize=None):
    return cross_entropy(logits(params, prepare(batch["image"], normalize), cfg), batch["label"])


def loss_and_grad_norm(params, batch, cfg, normalize=None):
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(f32(params), batch, cfg, normalize)
        return value, global_norm(grads)

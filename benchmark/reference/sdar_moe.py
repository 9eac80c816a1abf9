"""SDAR-MoE (JetLM/SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``) and its
block-diffusion training loss, in plain ``jax.numpy`` and float32.

One layer, P positions in, no bias anywhere:

    a = RMSNorm(x);  q = a Wq (P,H,dh), k = a Wk (P,Hkv,dh), v = a Wv (P,Hkv,dh)
    q, k = RMSNorm_dh(q), RMSNorm_dh(k)  (per head, learned scale);  q, k = RoPE(q, k; pos, theta, rotate-half)
    s[h] = q[h] k[h // (H/Hkv)]^T / sqrt(dh) + M;  o = softmax(s) v[h // (H/Hkv)];  x = x + concat(o) Wo
    b = RMSNorm(x);  g = softmax(b Wr) over ALL router outputs;  S = top-k(g);  w_e = g_e / sum_{e' in S} g_e'
    x = x + sum_{e in S and held} w_e (silu(b Wgate_e) * (b Wup_e)) Wdown_e
    logits = RMSNorm(x_noisy) Whead

Training (BD3-LM): L clean tokens x0 enter as P = 2L positions, a noised
copy then the clean copy, both at pos 0 .. L-1.  With block(j) = j // B the
mask M lets noisy i see noisy j iff block(i) = block(j), noisy i see clean
j iff block(j) < block(i), clean i see clean j iff block(j) <= block(i);
clean never sees noisy.  loss = 1/(N L) sum_{masked i} (1/p) CE(logits[i], x0[i]).

It shares no code with the program's ``models/`` or ``ops/``: the mask is
built densely from the definition above, the experts are a loop over the
held range that runs every expert on every token, and nothing has a bound
on rows, so a dropped assignment or a wrong mask row in the program shows.
Attention runs in query blocks and each layer under ``jax.checkpoint`` so
that 8192 positions fit beside the resident state.

Departures from the published model, each under ``assumed`` or ``reduced``
in the configuration's file: the experts summed are the held range only
(the chip's share; what absent experts would add is left out, here as in
the program); the vocabulary may be a slice (ids, logits and loss over the
slice); ``layers`` may be one stage's; block length, noise schedule and
mask id as the file assumes; per-head q/k RMSNorm as in the Qwen3-MoE block;
no router auxiliary loss; no label shift.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import f32, global_norm

Q_BLOCK = 512          # query rows a block of the attention loop


def settings(cfg: dict) -> dict:
    """What the file assumes beyond the published keys (``system.overrides``)."""
    o = cfg["system"]["overrides"]
    return {
        "block_length": int(o["block_length"]),
        "mask_token_id": int(o["mask_token_id"]),
        "experts_held": tuple(o.get("experts_held") or (0, int(o["num_experts"]))),
    }


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (P, heads, dh); rotate-half: the first half of a head pairs with the second."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def training_mask(seq_len: int, block: int):
    """(2L, 2L) bool, True where the query (row) may see the key (column)."""
    i = jnp.arange(2 * seq_len)
    noisy = i < seq_len
    blk = jnp.where(noisy, i, i - seq_len) // block
    qn, kn, qb, kb = noisy[:, None], noisy[None, :], blk[:, None], blk[None, :]
    return (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) | (~qn & ~kn & (kb <= qb))


def attention(a, p, cfg, pos, mask):
    """a: (P, d) → (P, d)."""
    heads, kv_heads, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    n = a.shape[0]
    q = (a @ p["wq"]["kernel"]).reshape(n, heads, dh)
    k = (a @ p["wk"]["kernel"]).reshape(n, kv_heads, dh)
    v = (a @ p["wv"]["kernel"]).reshape(n, kv_heads, dh)
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), pos, theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), pos, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)      # head h reads K/V head h // group
    v = jnp.repeat(v, heads // kv_heads, axis=1)

    @jax.checkpoint
    def rows(q_blk, m_blk):
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) / jnp.sqrt(float(dh))
        s = jnp.where(m_blk[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    step = min(Q_BLOCK, n)
    o = jnp.concatenate([rows(q[i:i + step], mask[i:i + step]) for i in range(0, n, step)])
    return o.reshape(n, heads * dh) @ p["wo"]["kernel"]


def experts(b, p, cfg, held):
    """b: (P, d) → the held experts' part of the layer's result."""
    first, count = held
    g = jax.nn.softmax(b @ p["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(g, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    out = jnp.zeros_like(b)
    for i in range(count):                            # every held expert on every token
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        h = jax.nn.silu(b @ p["w_gate"][i]) * (b @ p["w_up"][i])
        out = out + w[:, None] * (h @ p["w_down"][i])
    return out


def hidden(params, ids, pos, mask, cfg):
    """ids: (P,) → (P, d) after the last block."""
    eps, held = cfg["rms_norm_eps"], settings(cfg)["experts_held"]

    @jax.checkpoint
    def layer(x, blk):
        x = x + attention(rms_norm(x, blk["ln1"]["scale"], eps), blk["attn"], cfg, pos, mask)
        return x + experts(rms_norm(x, blk["ln2"]["scale"], eps), blk["moe"], cfg, held)

    x = params["embed"][ids]
    for i in range(int(cfg["layers"])):
        x = layer(x, params[f"block_{i}"])
    return x


def noisy_logits(params, tokens, masked, cfg):
    """tokens, masked: (L,) → (L, V) logits at the noisy positions."""
    s = settings(cfg)
    length = tokens.shape[0]
    ids = jnp.concatenate([jnp.where(masked, s["mask_token_id"], tokens), tokens])
    pos = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
    x = hidden(params, ids, pos, training_mask(length, s["block_length"]), cfg)[:length]
    return rms_norm(x, params["ln_final"]["scale"], cfg["rms_norm_eps"]) @ params["lm_head"]["kernel"]


def loss(params, tokens, masked, p, cfg):
    """tokens, masked: (N, L); p: (N,) → the weighted masked cross entropy."""
    total = 0.0
    for n in range(tokens.shape[0]):
        logp = jax.nn.log_softmax(noisy_logits(params, tokens[n], masked[n], cfg), axis=-1)
        ce = -jnp.take_along_axis(logp, tokens[n][:, None], axis=-1)[:, 0]
        total = total + jnp.sum(jnp.where(masked[n], ce, 0.0)) / p[n]
    return total / tokens.size


def loss_and_grad_norm(params, tokens, masked, p, cfg):
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(f32(params), tokens, masked, p, cfg)
        return value, global_norm(grads)

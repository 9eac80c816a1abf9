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
that 8192 positions fit beside the resident state; both loops are
``lax.map`` / ``lax.scan`` and not unrolled, which compiles in a third of
the time (71 s against 236 for a described v5e, PR 28).

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

from . import f32

Q_BLOCK = 512          # query rows a block of the attention loop
NOISE_EPS = 1e-3       # the floor of the masking probability the file assumes


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
    blocks = lambda x: x.reshape(n // step, step, *x.shape[1:])
    o = jax.lax.map(lambda qm: rows(*qm), (blocks(q), blocks(mask)))
    return o.reshape(n, heads * dh) @ p["wo"]["kernel"]


def route(b, router, cfg):
    """b: (P, d) → each position's k experts and their weights, (P, k) each."""
    g = jax.nn.softmax(b @ router, axis=-1)
    top_w, top_e = jax.lax.top_k(g, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_e


def held_assignments(b, p, cfg, held):
    """How many (position, expert) assignments fall on the held range: what
    the program's ``moe_held_assignments`` counts, by this file's routing."""
    _, top_e = route(b, p["router"], cfg)
    return jnp.sum((top_e >= held[0]) & (top_e < held[0] + held[1])).astype(jnp.float32)


def experts(b, p, cfg, held):
    """b: (P, d) → the held experts' part of the layer's result."""
    first, count = held
    top_w, top_e = route(b, p["router"], cfg)

    def one(out, expert):                             # every held expert on every token
        i, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        h = jax.nn.silu(b @ w_gate) * (b @ w_up)
        return out + w[:, None] * (h @ w_down), None

    return jax.lax.scan(one, jnp.zeros_like(b), (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))[0]


def hidden(params, ids, pos, mask, cfg):
    """ids: (P,) → (P, d) after the last block, and the held assignments
    summed over the layers."""
    eps, held = cfg["rms_norm_eps"], settings(cfg)["experts_held"]

    @jax.checkpoint
    def layer(x, blk):
        x = x + attention(rms_norm(x, blk["ln1"]["scale"], eps), blk["attn"], cfg, pos, mask)
        b = rms_norm(x, blk["ln2"]["scale"], eps)
        return x + experts(b, blk["moe"], cfg, held), held_assignments(b, blk["moe"], cfg, held)

    x, count = params["embed"][ids], 0.0
    for i in range(int(cfg["layers"])):
        x, n = layer(x, params[f"block_{i}"])
        count = count + n
    return x, count


def _noisy_logits(params, tokens, masked, cfg):
    s = settings(cfg)
    length = tokens.shape[0]
    ids = jnp.concatenate([jnp.where(masked, s["mask_token_id"], tokens), tokens])
    pos = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
    x, count = hidden(params, ids, pos, training_mask(length, s["block_length"]), cfg)
    x = rms_norm(x[:length], params["ln_final"]["scale"], cfg["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"], count


def noisy_logits(params, tokens, masked, cfg):
    """tokens, masked: (L,) → (L, V) logits at the noisy positions."""
    return _noisy_logits(params, tokens, masked, cfg)[0]


def _loss(params, tokens, masked, p, cfg):
    total = count = 0.0
    for n in range(tokens.shape[0]):
        logits, held = _noisy_logits(params, tokens[n], masked[n], cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, tokens[n][:, None], axis=-1)[:, 0]
        total = total + jnp.sum(jnp.where(masked[n], ce, 0.0)) / p[n]
        count = count + held
    return total / tokens.size, count


def loss(params, tokens, masked, p, cfg):
    """tokens, masked: (N, L); p: (N,) → the weighted masked cross entropy."""
    return _loss(params, tokens, masked, p, cfg)[0]


def loss_and_grad_norms(params, tokens, masked, p, cfg):
    """→ the loss, the norm of every parameter's gradient (a tree like
    ``params``: one wrong leaf does not hide in a sum) and the held
    assignments summed over layers and sequences."""
    with jax.default_matmul_precision("highest"):
        (value, count), grads = jax.value_and_grad(_loss, has_aux=True)(f32(params), tokens, masked, p, cfg)
        norms = jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
        return value, norms, count


def noise_is_the_assumed(masked, p, sigmas: float = 6.0) -> bool:
    """Whether masks and probabilities that were handed in (host arrays:
    masked (N, L) bool, p (N,)) can be what the file assumes: every p in
    [NOISE_EPS, 1], and each sequence's masked count within ``sigmas``
    standard deviations of a binomial (L, p).  What a run cannot see of
    ``t ~ U(0, 1)`` in one sequence, the window's ``masked_tokens`` shows
    over its hundred (``kinds/train_block_diffusion.py``)."""
    length = masked.shape[1]
    count = masked.sum(axis=1)
    room = sigmas * (length * p * abs(1.0 - p)) ** 0.5 + 1.0
    return bool(((p >= NOISE_EPS * (1 - 1e-6)) & (p <= 1.0)).all() and (abs(count - length * p) <= room).all())

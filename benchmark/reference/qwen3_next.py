"""Qwen3-Next-80B-A3B's decoder and its training loss, in plain ``jax.numpy``
and float32.  ``N(x; w) = x / rms(x) · (1 + w)`` is a zero-centred RMSNorm
(eps ``rms_norm_eps``); no bias anywhere; T positions in.

    h_0 = Emb[x];   h' = h + mixer_i(N(h; w_i));   h_next = h' + moe(N(h'; w'_i))
    logits = N(h_L; w_f) W_head;   loss = CE(logits[:-1], x[1:])

Layer i's mixer is gated attention where ``(i + 1) % full_attention_interval
== 0``, Gated DeltaNet otherwise.

Gated DeltaNet (G key heads, H value heads, d_k, d_v, kernel K), input ``a``:

    [q | k | v | z] = a W_qkvz   (G d_k | G d_k | H d_v | H d_v);   [b | c] = a W_ba   (H | H)
    [q | k | v]_t <- silu(sum_{j<K} w[j] [q | k | v]_{t-K+1+j})      (zeros before the sequence, no bias)
    q <- q / sqrt(|q|^2 + 1e-6) * d_k^-1/2;   k <- k / sqrt(|k|^2 + 1e-6)   (a head; value head h reads key head h // (H / G))
    beta = sigmoid(b);   g = -exp(A_log) softplus(c + dt_bias)
    S_t = e^{g_t} S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,  S_0 = 0;   o_t = S_t q_t
    o <- o / rms(o over a head's d_v) * w_o * silu(z);   out = o W_out

Gated attention (``num_attention_heads`` over ``num_key_value_heads`` heads of
``head_dim``): ``[q | gate] = a W_q`` a head, ``k = a W_k``, ``v = a W_v``;
``q, k <- N(q), N(k)`` a head; rotate-half rotary on the first
``partial_rotary_factor`` of each head at ``rope_theta``; query head h reads
K/V head h // (heads / kv heads); ``softmax(q k^T head_dim^-1/2, causal) v``;
``o <- o * sigmoid(gate)``; ``W_o``.

Experts: ``p = softmax(a W_r)`` over all ``num_experts``; ``S`` = its top
``num_experts_per_tok``; ``g_e = p_e / sum_{S} p`` (``norm_topk_prob``);
``out = sum_{e in S and held} g_e (silu(a W_g,e) * a W_u,e) W_d,e +
sigmoid(a w_sg) (silu(a W_g,s) * a W_u,s) W_d,s``.

It shares no code with the program's ``models/`` or ``ops/``: the delta rule
runs POSITION BY POSITION as written (a ``lax.scan`` over positions, in
blocks under ``jax.checkpoint`` so that its backward fits at 8192; no WY form,
no linear solve), the convolution is K shifted adds, the causal
mask is dense, every held expert runs on every token and is weighted,
nothing is sorted or handed to a kernel.  Attention runs in query blocks
under ``jax.checkpoint``.

Departures from the published model, each under ``assumed``, ``reduced`` or
``not_included`` in the configuration's file: the experts summed are the held
range only, the vocabulary may be a slice, ``layers`` may be one stage's; the
contiguous column orders of ``W_qkvz`` and ``W_ba``; no MTP module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import f32

Q_BLOCK = 256          # query rows a block of the attention loop
T_BLOCK = 128          # positions a checkpointed block of the recurrence


def experts_held(cfg: dict) -> tuple:
    o = cfg["system"]["overrides"]
    return tuple(o.get("experts_held") or (0, int(o["num_experts"])))


def full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % int(cfg["full_attention_interval"]) == 0


def norm(x, w, eps):
    """The zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def convolution(x, w):
    """x (T, C), w (K, C): tap j reads K - 1 - j positions back."""
    out = w[-1] * x
    for back in range(1, w.shape[0]):
        out = out + w[-1 - back] * jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
    return out


def delta_rule(q, k, v, g, beta):
    """q, k (T, H, d_k) at the value heads, v (T, H, d_v), g and beta (T, H) ->
    (T, H, d_v): the state S (H, d_v, d_k) from zero, one position a step."""
    t, h, dv = v.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        state = state + b_t[:, None, None] * (v_t - jnp.einsum("hvk,hk->hv", state, k_t))[:, :, None] * k_t[:, None, :]
        return state, jnp.einsum("hvk,hk->hv", state, q_t)

    block = min(T_BLOCK, t)
    if t % block:
        block = t
    blocks = jax.checkpoint(lambda state, inputs: jax.lax.scan(step, state, inputs))
    _, o = jax.lax.scan(blocks, jnp.zeros((h, dv, q.shape[-1]), jnp.float32),
                        tuple(m.reshape(t // block, block, *m.shape[1:]) for m in (q, k, v, g, beta)))
    return o.reshape(t, h, dv)


def linear_attention(a, p, cfg):
    """a: (T, d) -> (T, d)."""
    g_heads, heads, dk, dv = (int(cfg[key]) for key in (
        "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    t, keys, values = a.shape[0], g_heads * dk, heads * dv
    q, k, v, z = jnp.split(a @ p["in_proj_qkvz"]["kernel"], [keys, 2 * keys, 2 * keys + values], axis=-1)
    b, c = jnp.split(a @ p["in_proj_ba"]["kernel"], [heads], axis=-1)
    q, k, v = jnp.split(jax.nn.silu(convolution(jnp.concatenate([q, k, v], axis=-1), p["conv_w"])),
                        [keys, 2 * keys], axis=-1)
    per = heads // g_heads
    q = jnp.repeat(unit(q.reshape(t, g_heads, dk)), per, axis=1) * dk ** -0.5
    k = jnp.repeat(unit(k.reshape(t, g_heads, dk)), per, axis=1)
    o = delta_rule(q, k, v.reshape(t, heads, dv), -jnp.exp(p["A_log"]) * jax.nn.softplus(c + p["dt_bias"]),
                   jax.nn.sigmoid(b))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"]) * p["norm"]
    return (o * jax.nn.silu(z.reshape(t, heads, dv))).reshape(t, values) @ p["out_proj"]["kernel"]


def rotary(x, cfg):
    """Rotate-half over each head's first ``partial_rotary_factor`` of its lanes; x (T, heads, d)."""
    t, dh = x.shape[0], x.shape[-1]
    rot = int(dh * float(cfg["partial_rotary_factor"]))
    half = rot // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * float(cfg["rope_theta"]) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attention(a, p, cfg):
    """a: (T, d) -> (T, d)."""
    heads, kv_heads, dh = (int(cfg[key]) for key in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    n, eps, per = a.shape[0], cfg["rms_norm_eps"], heads // kv_heads
    q_gate = (a @ p["q_proj"]["kernel"]).reshape(n, heads, 2 * dh)
    q = rotary(norm(q_gate[..., :dh], p["q_norm"]["scale"], eps), cfg).reshape(n, kv_heads, per, dh)
    k = rotary(norm((a @ p["k_proj"]["kernel"]).reshape(n, kv_heads, dh), p["k_norm"]["scale"], eps), cfg)
    v = (a @ p["v_proj"]["kernel"]).reshape(n, kv_heads, dh)

    @jax.checkpoint
    def rows(q_blk, first):
        s = jnp.einsum("qngd,knd->ngqk", q_blk, k) * dh ** -0.5
        seen = (first + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(n)[None, :]
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    step = min(Q_BLOCK, n)
    if n % step:
        step = n
    o = jax.lax.map(lambda qf: rows(*qf), (q.reshape(n // step, step, *q.shape[1:]), jnp.arange(0, n, step)))
    o = o.reshape(n, heads, dh) * jax.nn.sigmoid(q_gate[..., dh:])
    return o.reshape(n, heads * dh) @ p["o_proj"]["kernel"]


def swiglu(a, w_gate, w_up, w_down):
    return (jax.nn.silu(a @ w_gate) * (a @ w_up)) @ w_down


def route(b, p, cfg):
    """b: (T, d) -> the chosen experts (T, k) and their weights (T, k)."""
    s = jax.nn.softmax(b @ p["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(s, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w


def experts(b, p, cfg, held):
    """b: (T, d) -> (the held experts' part of the routed result, the
    assignments on the held range)."""
    first, count = held
    top_e, top_w = route(b, p, cfg)

    @jax.checkpoint                                   # a backward keeps no expert's activations
    def one(out, expert):                             # every held expert on every token
        i, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        return out + w[:, None] * swiglu(b, w_gate, w_up, w_down), None

    out = jax.lax.scan(one, jnp.zeros_like(b), (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))[0]
    return out, jnp.sum((top_e >= first) & (top_e < first + count)).astype(jnp.float32)


def shared(b, p):
    """The shared expert under its per-token gate."""
    s = p["shared_expert"]
    return jax.nn.sigmoid(b @ p["shared_expert_gate"]["kernel"]) * swiglu(
        b, s["w_gate"]["kernel"], s["w_up"]["kernel"], s["w_down"]["kernel"])


def layer(h, p, i, cfg):
    """Layer i -> (the stream one layer on, held assignments)."""
    eps = cfg["rms_norm_eps"]
    y = norm(h, p["input_layernorm"]["scale"], eps)
    h = h + (attention(y, p["self_attn"], cfg) if full_attention(cfg, i) else linear_attention(y, p["linear_attn"], cfg))
    y = norm(h, p["post_attention_layernorm"]["scale"], eps)
    routed, held = experts(y, p["moe"], cfg, experts_held(cfg))
    return h + routed + shared(y, p), held


def logits_of(params, ids, cfg):
    """ids: (T,) -> (logits (T, V), the held assignments over the layers)."""
    h, held = params["embed"][ids], 0.0
    for i in range(int(cfg["layers"])):
        h, n = jax.checkpoint(lambda h, p, i=i: layer(h, p, i, cfg))(h, params[f"block_{i}"])
        held = held + n
    return norm(h, params["norm"]["scale"], cfg["rms_norm_eps"]) @ params["lm_head"]["kernel"], held


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _loss(params, tokens, cfg):
    value, held = 0.0, 0.0
    for n in range(tokens.shape[0]):
        logits, h = logits_of(params, tokens[n], cfg)
        value, held = value + cross_entropy(logits[:-1], tokens[n][1:]), held + h
    return value / tokens.shape[0], held


def loss(params, tokens, cfg):
    """tokens: (N, T) -> the training loss."""
    return _loss(params, tokens, cfg)[0]


def loss_and_grads(params, tokens, cfg):
    """-> the loss, its parts in the form the kinds read (the next-token CE
    first, then the parts the cell names: none here), every parameter's
    gradient (a tree like ``params``) and the held assignments summed over
    the expert layers and sequences."""
    with jax.default_matmul_precision("highest"):
        (value, held), grads = jax.value_and_grad(_loss, has_aux=True)(f32(params), tokens, cfg)
        return value, jnp.stack([value]), grads, held

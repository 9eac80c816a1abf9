"""The ``nemotron_h`` tower of Nemotron-Labs-TwoTower-30B-A3B-Base and its
training loss, in plain ``jax.numpy`` and float32.  ``N(x; g)`` is an RMSNorm
(eps ``norm_eps``) with the learned scale ``g``; no bias but the
convolution's; T positions in.

    h_0 = Emb[x];   h_i = h_{i-1} + f_i(N(h_{i-1}; g_i));   logits = N(h_L; g_f) W_head
    loss = CE(logits[:-1], x[1:])

``f_i`` by the i-th letter of the first ``layers`` letters of
``hybrid_override_pattern``:

``M`` (Mamba-2; H heads of P, G groups, state N, kernel K), input ``u``:

    [z | xBC | dt] = u W_in                                  (H P | H P + 2 G N | H)
    xBC_t <- silu(b_c + sum_{j<K} w_c[j] xBC_{t-K+1+j})      (zeros before the sequence)
    xBC -> x (T, H, P), B (T, G, N), C (T, G, N);  head h reads group h // (H / G)
    D_t = softplus(dt_t + dt_bias)  (no clamp),   A = -exp(A_log)   (a scalar a head)
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t,  S_0 = 0;    y_t = S_t C_t + D x_t
    y <- y * silu(z);   y <- y / rms(y over each group of H P / G channels; layer_norm_epsilon) * g_n
    out = y W_out

``*``: ``q = a W_q`` (T, 32, 128), ``k = a W_k``, ``v = a W_v`` (T, 2, 128);
query head h reads K/V head h // 16; ``softmax(q k^T 128^-0.5, causal) v``;
``W_o``.  No positional encoding.

``E``: ``s = sigmoid(b W_r)`` (128 wide); ``S`` = top-6 of ``s + bias``
(bias: no gradient, zero here); ``g_e = 2.5 s_e / sum_{e' in S} s_e'``;
``out = sum_{e in S and held} g_e relu(b W_up,e)^2 W_down,e + relu(b W_up,s)^2 W_down,s``.

It shares no code with the program's ``models/`` or ``ops/``: the recurrence
runs POSITION BY POSITION as written (a ``lax.scan`` over positions, in
blocks under ``jax.checkpoint`` so that its backward fits at 8192; no chunked
algorithm), the convolution is K shifted adds, the causal mask is dense,
every held expert runs on every token and is weighted, nothing is sorted or
handed to a kernel.  Attention runs in query blocks under ``jax.checkpoint``.

Departures from the published model, each under ``assumed``, ``reduced`` or
``not_included`` in the configuration's file: the experts summed are the held
range only, the vocabulary may be a slice, ``layers`` may be one stage's; no
positions in attention; the gate before the group norm; the bias held at
zero; the second (denoiser) tower, adaLN, cross-tower conditioning and block
diffusion are in no key of ``config.json`` and are not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import f32

Q_BLOCK = 256          # query rows a block of the attention loop
T_BLOCK = 128          # positions a checkpointed block of the recurrence


def layers(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][: int(cfg["layers"])]


def experts_held(cfg: dict) -> tuple:
    o = cfg["system"]["overrides"]
    return tuple(o.get("experts_held") or (0, int(o["n_routed_experts"])))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def convolution(x, w, bias):
    """x (T, C), w (K, C): tap j reads K - 1 - j positions back."""
    out = bias + w[-1] * x
    for back in range(1, w.shape[0]):
        out = out + w[-1 - back] * jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
    return out


def recurrence(x, dt, a, b, c):
    """x (T, H, P), dt (T, H), a (H,), b and c (T, G, N) -> (T, H, P): the
    state S (H, P, N) from zero, one position a step."""
    t, h, p = x.shape
    per_group = h // b.shape[1]

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = jnp.repeat(b_t, per_group, axis=0), jnp.repeat(c_t, per_group, axis=0)      # (H, N)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    block = min(T_BLOCK, t)
    if t % block:
        block = t
    blocks = jax.checkpoint(lambda state, inputs: jax.lax.scan(step, state, inputs))
    _, y = jax.lax.scan(blocks, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                        tuple(m.reshape(t // block, block, *m.shape[1:]) for m in (x, dt, b, c)))
    return y.reshape(t, h, p)


def mixer(u, p, cfg):
    """u: (T, d) -> (T, d)."""
    h, dim, g, n = (int(cfg[k]) for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    t, inner = u.shape[0], h * dim
    z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"], [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(convolution(xbc, p["conv_w"], p["conv_b"]))
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(t, h, dim)
    y = recurrence(x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   b.reshape(t, g, n), c.reshape(t, g, n)) + p["D"][:, None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)        # the gate first, then the norm
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
    return (y.reshape(t, inner) * p["norm"]) @ p["out_proj"]["kernel"]


def attention(a, p, cfg):
    """a: (T, d) -> (T, d)."""
    heads, kv_heads, dh = (int(cfg[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    n = a.shape[0]
    q = (a @ p["wq"]["kernel"]).reshape(n, kv_heads, heads // kv_heads, dh)
    k = (a @ p["wk"]["kernel"]).reshape(n, kv_heads, dh)
    v = (a @ p["wv"]["kernel"]).reshape(n, kv_heads, dh)

    @jax.checkpoint
    def rows(q_blk, first):
        s = jnp.einsum("qngd,knd->ngqk", q_blk, k) * dh ** -0.5
        seen = (first + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(n)[None, :]
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    step = min(Q_BLOCK, n)
    if n % step:
        step = n
    o = jax.lax.map(lambda qf: rows(*qf), (q.reshape(n // step, step, *q.shape[1:]), jnp.arange(0, n, step)))
    return o.reshape(n, heads * dh) @ p["wo"]["kernel"]


def route(b, p, cfg):
    """b: (T, d) -> the chosen experts (T, k) and their weights (T, k)."""
    s = jax.nn.sigmoid(b @ p["router"])
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]), int(cfg["num_experts_per_tok"]))
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w * float(cfg["routed_scaling_factor"])


def experts(b, p, cfg, held):
    """b: (T, d) -> (the held experts' part of the routed result, the
    assignments on the held range)."""
    first, count = held
    top_e, top_w = route(b, p, cfg)

    def one(out, expert):                             # every held expert on every token
        i, w_up, w_down = expert
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        return out + w[:, None] * (relu2(b @ w_up) @ w_down), None

    out = jax.lax.scan(one, jnp.zeros_like(b), (jnp.arange(count), p["w_up"], p["w_down"]))[0]
    return out, jnp.sum((top_e >= first) & (top_e < first + count)).astype(jnp.float32)


def layer(h, p, kind, cfg):
    """One layer of ``kind`` -> (the stream one layer on, held assignments)."""
    y = rms_norm(h, p["norm"]["scale"], cfg["norm_eps"])
    if kind == "M":
        return h + mixer(y, p["mixer"], cfg), jnp.zeros(())
    if kind == "*":
        return h + attention(y, p["attn"], cfg), jnp.zeros(())
    routed, held = experts(y, p["moe"], cfg, experts_held(cfg))
    return h + routed + relu2(y @ p["shared"]["w_up"]["kernel"]) @ p["shared"]["w_down"]["kernel"], held


def logits_of(params, ids, cfg):
    """ids: (T,) -> (logits (T, V), the held assignments over the expert layers)."""
    h, held = params["embed"][ids], 0.0
    for i, kind in enumerate(layers(cfg)):
        h, n = jax.checkpoint(lambda h, p, kind=kind: layer(h, p, kind, cfg))(h, params[f"block_{i}"])
        held = held + n
    return rms_norm(h, params["ln_final"]["scale"], cfg["norm_eps"]) @ params["lm_head"]["kernel"], held


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

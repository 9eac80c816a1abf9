"""Instella-MoE-16B-A3B-Base (``model_type`` ``deepseek_v3``) and its training
loss, in plain ``jax.numpy`` and float32.  ``N(x; g)`` is an RMSNorm with the
learned scale ``g``; no bias anywhere; T positions in.

Attention (MLA without a query latent), sublayer input ``a``:

    q = a Wq  -> (T, H, 96 + 32)
    [c ; kr] = a Wkva  (c: 512, kr: 32, one rotary key for all heads)
    [kn ; v] = N(c; g_kv) Wkvb  -> (T, H, 96 + 128);  k[h] = [kn[h] ; kr]
    q[h], k[h] = N(q[h]; g_q), N(k[h]; g_k)       (qk_layernorm: over each head's 128, ASSUMED form)
    q[h], k[h] = their first 96 kept, their last 32 turned by RoPE at YaRN's frequencies (below)
    s[h] = q[h] k[h]^T * 128^-0.5 * (0.1 ln 40 + 1)^2, causal;  o[h] = softmax(s[h]) v[h]
    out = (concat(o) * sigmoid(a Wg)) Wo          (gated_attention: elementwise output gate, ASSUMED form)

YaRN (DeepSeek-V3's): pair i of the 16 turns at ``theta^(-i/16)`` where i <=
low, at that / 40 where i >= high, and at the linear blend between, with low
= floor(d(beta_fast)), high = ceil(d(beta_slow)), ``d(r) = 32 ln(4096 / (2 pi
r)) / (2 ln theta)``; the factor on cos / sin is mscale / mscale_all_dim = 1.
The pairing is half-split (dimension i with i + 16): the published
``rope_interleave`` pairing under a fixed permutation of Wq / Wkva columns.

Experts, sublayer input ``b``:

    s = sigmoid(b Wr)  (64 wide);  S = top-6 of s + bias  (bias: no gradient, zero here)
    g_e = 2.5 s_e / sum_{e' in S} s_e'
    out = sum_{e in S and held} g_e (silu(b Wgate_e) * (b Wup_e)) Wdown_e  +  Shared(b)
    balance = sum_i f_i P_i,  f_i = 64 / (6 T) #{t : i in S_t},  P_i = mean_t s_it / sum_j s_jt

``Shared`` is ONE SiLU-gated MLP of width 2816; layer 0 has a SiLU-gated MLP
of width 10,944 and no experts.

Far-skip (ASSUMED form), sublayers f_1 .. f_2L, r_0 the embedding:

    r_1 = r_0 + f_1(N_1(r_0));   r_k = r_{k-1} + f_k(N_k(r_{k-2}))  for k >= 2
    logits = N(r_2L) Whead

MTP (DeepSeek-V3 2.2, depth 1): ``h' = [N(r_2L; g_h) ; N(Emb(x_{t+1}); g_e)]
Weh`` (x past the end: id 0), one more expert block on (h', h') by the same
rule, a norm, the SAME head; its row t predicts x_{t+2}.

    loss = CE(logits[:-1], x[1:]) + lambda CE(mtp[:-2], x[2:]) + alpha sum_layers balance

It shares no code with the program's ``models/`` or ``ops/``: the causal mask
is dense, every held expert runs on every token and is weighted, nothing is
sorted, rematerialized by name or handed to a kernel.  Attention runs in
query blocks and each block under ``jax.checkpoint`` so that 8192 positions
fit beside the resident state.

Departures from the published model, each under ``assumed`` or ``reduced`` in
the configuration's file: the experts summed are the held range only, the
vocabulary may be a slice, ``layers`` may be one stage's; the forms of
``gated_attention``, ``qk_layernorm`` and ``farskip``; alpha, lambda, the
bias held at zero, the MTP module's input.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import f32

Q_BLOCK = 512          # query rows a block of the attention loop


def settings(cfg: dict) -> dict:
    """What the file states beyond the published keys (``system.overrides``)."""
    o = cfg["system"]["overrides"]
    return {
        "experts_held": tuple(o.get("experts_held") or (0, int(o["n_routed_experts"]))),
        "mtp_loss_weight": float(o["mtp_loss_weight"]),
        "seq_aux_alpha": float(o["seq_aux_alpha"]),
        "farskip": bool(cfg["farskip"]),
    }


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def yarn_frequencies(cfg: dict):
    """(rope_dim / 2,) inverse frequencies."""
    dim, theta, s = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"]), cfg["rope_scaling"]
    turns = lambda r: dim * math.log(s["original_max_position_embeddings"] / (r * 2 * math.pi)) / (2 * math.log(theta))
    low, high = max(math.floor(turns(s["beta_fast"])), 0), min(math.ceil(turns(s["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        blend = min(max((i - low) / (high - low if high > low else 1e-3), 0.0), 1.0)
        plain = theta ** (-2.0 * i / dim)
        out.append(plain * (1.0 - blend) + plain / s["factor"] * blend)
    return jnp.asarray(out, jnp.float32)


def score_scale(cfg: dict) -> float:
    s = cfg["rope_scaling"]
    m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
    return (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])) ** -0.5 * m * m


def rotate_tail(x, pos, inv, nope):
    """x: (T, H, nope + rope): the last ``rope`` dimensions turned, half-split."""
    head, tail = x[..., :nope], x[..., nope:]
    half = tail.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = tail[..., :half], tail[..., half:]
    return jnp.concatenate([head, x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(a, p, cfg):
    """a: (T, d) -> (T, d)."""
    heads, nope, rot, dv = (int(cfg[k]) for k in ("num_attention_heads", "qk_nope_head_dim",
                                                  "qk_rope_head_dim", "v_head_dim"))
    rank, eps, n = int(cfg["kv_lora_rank"]), cfg["rms_norm_eps"], a.shape[0]
    pos, inv = jnp.arange(n), yarn_frequencies(cfg)
    q = (a @ p["wq"]["kernel"]).reshape(n, heads, nope + rot)
    latent = a @ p["wkv_a"]["kernel"]
    kv = (rms_norm(latent[:, :rank], p["kv_norm"]["scale"], eps) @ p["wkv_b"]["kernel"]).reshape(n, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(latent[:, None, rank:], heads, axis=1)], axis=-1)
    v = kv[..., nope:]
    if cfg["qk_layernorm"]:
        q, k = rms_norm(q, p["q_norm"]["scale"], eps), rms_norm(k, p["k_norm"]["scale"], eps)
    q, k = rotate_tail(q, pos, inv, nope), rotate_tail(k, pos, inv, nope)
    scale = score_scale(cfg)

    @jax.checkpoint
    def rows(q_blk, first):
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        seen = (first + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(n)[None, :]
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), v)

    step = min(Q_BLOCK, n)
    if n % step:
        step = n
    o = jax.lax.map(lambda qf: rows(*qf), (q.reshape(n // step, step, heads, nope + rot),
                                           jnp.arange(0, n, step)))
    o = o.reshape(n, heads * dv)
    if cfg["gated_attention"]:
        o = o * jax.nn.sigmoid(a @ p["wg"]["kernel"])
    return o @ p["wo"]["kernel"]


def gated_mlp(b, p):
    return (jax.nn.silu(b @ p["w_gate"]["kernel"]) * (b @ p["w_up"]["kernel"])) @ p["w_down"]["kernel"]


def route(b, p, cfg):
    """b: (T, d) -> the chosen experts (T, k), their weights (T, k), and all
    the scores (T, E)."""
    s = jax.nn.sigmoid(b @ p["router"])
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]), int(cfg["num_experts_per_tok"]))
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w * float(cfg["routed_scaling_factor"]), s


def experts(b, p, cfg, held):
    """b: (T, d) -> (the held experts' part of the routed result, the
    balance term before alpha, the assignments on the held range)."""
    first, count = held
    top_e, top_w, s = route(b, p, cfg)

    def one(out, expert):                             # every held expert on every token
        i, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        return out + w[:, None] * ((jax.nn.silu(b @ w_gate) * (b @ w_up)) @ w_down), None

    out = jax.lax.scan(one, jnp.zeros_like(b), (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))[0]
    width, k = s.shape[-1], top_e.shape[-1]
    f = jnp.sum(jax.nn.one_hot(top_e, width), axis=(0, 1)) * (width / (k * b.shape[0]))
    balance = jnp.sum(jax.lax.stop_gradient(f) * jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0))
    n_held = jnp.sum((top_e >= first) & (top_e < first + count)).astype(jnp.float32)
    return out, balance, n_held


def block(before, stream, p, cfg, s):
    """One block over the two streams -> (the pair one block on, balance, held)."""
    eps = cfg["rms_norm_eps"]
    mid = stream + attention(rms_norm(before if s["farskip"] else stream, p["ln1"]["scale"], eps), p["attn"], cfg)
    b = rms_norm(stream if s["farskip"] else mid, p["ln2"]["scale"], eps)
    if "mlp" in p:
        return (mid, mid + gated_mlp(b, p["mlp"])), jnp.zeros(()), jnp.zeros(())
    routed, balance, held = experts(b, p["moe"], cfg, s["experts_held"])
    return (mid, mid + routed + gated_mlp(b, p["shared"])), balance, held


def logits_of(params, ids, cfg):
    """ids: (T,) -> (logits (T, V), the MTP module's logits (T, V), the
    balance terms summed over the expert layers, the held assignments)."""
    s, eps = settings(cfg), cfg["rms_norm_eps"]
    step = jax.checkpoint(lambda pair, p: block(*pair, p, cfg, s))
    x = params["embed"][ids]
    pair, balance, held = (x, x), 0.0, 0.0
    for i in range(int(cfg["layers"])):
        pair, b, h = step(pair, params[f"block_{i}"])
        balance, held = balance + b, held + h
    last = pair[1]
    head = params["lm_head"]["kernel"]
    logits = rms_norm(last, params["ln_final"]["scale"], eps) @ head
    ahead = params["embed"][jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])]
    y = jnp.concatenate([rms_norm(last, params["mtp_hnorm"]["scale"], eps),
                         rms_norm(ahead, params["mtp_enorm"]["scale"], eps)], axis=-1) @ params["mtp_proj"]["kernel"]
    pair, b, h = step((y, y), params["mtp_block"])
    mtp = rms_norm(pair[1], params["mtp_final"]["scale"], eps) @ head
    return logits, mtp, balance + b, held + h


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _loss(params, tokens, cfg):
    s = settings(cfg)
    parts = jnp.zeros((3,))
    held = 0.0
    for n in range(tokens.shape[0]):
        logits, mtp, balance, h = logits_of(params, tokens[n], cfg)
        parts = parts + jnp.stack([cross_entropy(logits[:-1], tokens[n][1:]),
                                   cross_entropy(mtp[:-2], tokens[n][2:]),
                                   s["seq_aux_alpha"] * balance])
        held = held + h
    parts = parts / tokens.shape[0]
    return parts[0] + s["mtp_loss_weight"] * parts[1] + parts[2], (parts, held)


def loss(params, tokens, cfg):
    """tokens: (N, T) -> the training loss."""
    return _loss(params, tokens, cfg)[0]


def loss_and_grads(params, tokens, cfg):
    """-> the loss, its three parts (next-token CE, the MTP module's CE
    before lambda, alpha x balance), every parameter's gradient (a tree like
    ``params``) and the held assignments summed over the expert layers and
    sequences."""
    with jax.default_matmul_precision("highest"):
        (value, (parts, held)), grads = jax.value_and_grad(_loss, has_aux=True)(f32(params), tokens, cfg)
        return value, parts, grads, held


def loss_and_grad_norms(params, tokens, cfg):
    """As ``loss_and_grads``, with the norm of every parameter's gradient in
    the gradients' place."""
    value, parts, grads, held = loss_and_grads(params, tokens, cfg)
    return value, parts, jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads), held

"""Nemotron-H — a hybrid decoder whose layers are ONE sublayer each, of three
kinds read from a pattern string (``model_type`` ``nemotron_h``; the tower
``config.json`` of nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 defines):
``M`` a Mamba-2 state-space mixer, ``*`` grouped-query attention, ``E``
routed experts beside a shared one.

With ``N`` an RMSNorm (eps ``norm_eps``, a learned scale): ``h_0 = Emb[x]``,
``h_i = h_{i-1} + f_i(N_i(h_{i-1}))`` with ``f_i`` by the pattern's i-th
letter, logits ``= N_f(h_L) W_head`` (untied).  No bias but the
convolution's.

- **M** (H heads of P, d_inner = H·P, G groups, state N, kernel K):
  ``[z | xBC | dt] = u W_in`` (d_inner | d_inner + 2·G·N | H).  ``xBC ←
  silu(b_c + Σ_{j<K} w_{c,j} · xBC_{t-K+1+j, c})``: causal, depthwise, zeros
  before the sequence (``ops/causal_conv.causal_conv_silu``: at lane-aligned
  sizes the ``causal_conv_fwd`` / ``causal_conv_bwd`` Pallas pair, which
  reads ``xBC`` in place in the projection's result and hands back ``x``,
  ``B`` and ``C``; at toy sizes ``causal_conv``'s ``jnp`` form:
  ``ops/causal_conv.conv_plan`` decides from the shapes).  ``xBC → x (T, H,
  P), B, C (T, G, N)``; head h reads group ``h // (H / G)``.  ``Δ = softplus(dt +
  dt_bias)`` (``time_step_limit`` (0, ∞): no clamp), ``A = -exp(A_log)``,
  ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t``
  (``ops/ssd.ssd_chunked`` in chunks of ``chunk_size``; the state and the
  decays float32; at lane-aligned sizes the ``ssd_fwd`` / ``ssd_bwd`` Pallas
  pair, which keeps a chunk's blocks and the carried state in VMEM, at toy
  sizes the ``jnp`` form: ``ops/ssd.ssd_plan`` decides from the shapes).  ``y ← GroupRMSNorm(y ⊙ silu(z))``: the gate FIRST, then
  an RMS norm over each of the G groups of d_inner / G channels (eps
  ``layer_norm_epsilon``, one learned scale of d_inner), as ``nemotron_h``
  orders them.  ``out = y W_out``.
- **\\*** : ``num_attention_heads`` query heads over ``num_key_value_heads``
  K/V heads of ``head_dim``, causal, scale ``head_dim^-0.5``, through
  ``ops.attention.dot_product_attention``.  NO positional encoding: the
  Mamba-2 layers carry position (``rope_theta`` is in the published config
  and ``nemotron_h``'s attention does not read it).
- **E**: ``models/moe.TopKMoe`` with sigmoid scores, the selection-only
  bias, ``routed_scaling_factor``, and PLAIN experts ``relu(x W_up)²
  W_down`` (``mlp_hidden_act`` ``relu2``, no gate), plus one shared expert
  of the same form, ``moe_shared_expert_intermediate_size`` wide, that every
  token takes.

The published model's second tower (an adaLN denoiser over the same pattern,
cross-tower conditioning, block diffusion) is in no key of ``config.json``
and is NOT here (ROADMAP R5).  The config's fields are the published keys;
``num_hidden_layers`` runs the pattern's first that many letters (a pipeline
stage's), ``experts_held`` and a sliced ``vocab_size`` are a chip's share, as
in ``models/sdar.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import scope
from ..ops.attention import dot_product_attention
from ..ops.causal_conv import causal_conv_silu
from ..ops.ssd import ssd_chunked
from .instella_moe import _dense
from .moe import TopKMoe, relu2
from .sdar import REMAT_SAVE, RMSNorm

# Sorted rows a window of ``models/moe.held_experts`` holds (``TopKMoe(
# expert_window=...)``: a plain product for each expert with rows in it).  At
# the published widths (2688 x 1856, a few hundred rows an expert a microbatch)
# a pass of 512 costs a v5e what 512 assignments' model FLOPs cost the whole
# step, 1.03 us each, so the step's MFU does not follow how many assignments a
# fresh model's routing sends this chip; at 384 an assignment costs 1.28 us, as
# grouped products 2.33 (PERF.md section 6, PR 34).
EXPERT_WINDOW = 512
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = PATTERN
    num_hidden_layers: int = 52        # the pattern's first this many letters run
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    layer_norm_epsilon: float = 1e-5
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 128        # the router's width, never cut
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    # This chip's share of each layer's routed experts, as SdarConfig's.
    experts_held: tuple | None = None
    remat: bool = False

    def __post_init__(self):
        if self.experts_held is not None:      # JSON hands a list
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if set(self.layers) - set("ME*") or not self.layers:
            raise ValueError(f"layers {self.layers!r}: letters M, E and * only, at least one")

    @property
    def layers(self) -> str:
        return self.hybrid_override_pattern[: self.num_hidden_layers]


def _dt_bias_init(cfg: NemotronHConfig):
    """Steps log-uniform in [time_step_min, time_step_max], floored, through
    the inverse of the softplus (Mamba-2's initialisation)."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo), cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Mamba2Mixer(nn.Module):
    """``u`` (B, T, d) → (B, T, d): the ``M`` sublayer of the module docstring,
    everything between the two projections in float32 but the recurrence's
    matrix products.  The recurrence is ``ops/ssd.ssd_chunked``: two Mosaic
    custom calls under ``ssm/scan`` (``ssd_fwd``, ``ssd_bwd``; no (Q, Q)
    block, running sum or chunk state of the forward in HBM) where
    ``ssd_plan`` finds lane-aligned shapes, plain XLA operations otherwise.
    The convolution and its SiLU are ``ops/causal_conv.causal_conv_silu``:
    two more under ``ssm/conv`` (``causal_conv_fwd``, ``causal_conv_bwd``; no
    float32 copy of ``xBC`` in HBM) where ``conv_plan`` finds them, the
    ``jnp`` form otherwise.  The D skip, the gate and the grouped norm are
    XLA's either way."""

    cfg: NemotronHConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        h, g, k = cfg.mamba_num_heads, cfg.n_groups, cfg.conv_kernel
        inner = h * cfg.mamba_head_dim
        wide = inner + 2 * g * cfg.ssm_state_size
        uniform = lambda key, shape, dtype=jnp.float32: jax.random.uniform(
            key, shape, dtype, -k ** -0.5, k ** -0.5)
        a_log = self.param("A_log", lambda key, shape: jnp.log(jnp.arange(1.0, shape[0] + 1.0)), (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (h,))
        conv_w = self.param("conv_w", uniform, (k, wide))
        conv_b = self.param("conv_b", uniform, (wide,))
        scale = self.param("norm", nn.initializers.ones, (inner,), jnp.float32)

        p, n = cfg.mamba_head_dim, cfg.ssm_state_size
        with scope("ssm/proj"):
            projected = _dense(inner + wide + h, "in_proj", self.dtype)(u)        # [z | xBC | dt]
            z, dt = projected[..., :inner], projected[..., inner + wide:]
        bsz, t, _ = z.shape
        with scope("ssm/conv"):
            x, b, c = causal_conv_silu(projected, conv_w, conv_b, offset=inner, splits=(inner, g * n, g * n))
        with scope("ssm/scan"):
            x = x.reshape(bsz, t, h, p)
            b, c = b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n)
            y = ssd_chunked(
                x, jax.nn.softplus(dt.astype(jnp.float32) + dt_bias), -jnp.exp(a_log), b, c,
                chunk=cfg.chunk_size,
            ).astype(jnp.float32) + d_skip[:, None] * x.astype(jnp.float32)
        with scope("ssm/gate"):
            y = (y.reshape(bsz, t, inner) * nn.silu(z.astype(jnp.float32))).reshape(bsz, t, g, inner // g)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.layer_norm_epsilon)
            y = (y.reshape(bsz, t, inner) * scale).astype(z.dtype)
        with scope("ssm/proj"):
            return _dense(cfg.hidden_size, "out_proj", self.dtype)(y)


class GqaAttention(nn.Module):
    cfg: NemotronHConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        bsz, t, _ = x.shape
        h, hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        with scope("attn/proj"):
            q = _dense(h * dh, "wq", self.dtype)(x).reshape(bsz, t, h, dh)
            k = _dense(hkv * dh, "wk", self.dtype)(x).reshape(bsz, t, hkv, dh)
            v = _dense(hkv * dh, "wv", self.dtype)(x).reshape(bsz, t, hkv, dh)
            q, k, v = (checkpoint_name(m, "attn_qkv") for m in (q, k, v))
        o = dot_product_attention(q, k, v, causal=True)
        with scope("attn/proj"):
            return _dense(cfg.hidden_size, "wo", self.dtype)(o.reshape(bsz, t, h * dh))


class Relu2Mlp(nn.Module):
    """``relu(x W_up)² W_down``, no bias, no gate."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        return _dense(x.shape[-1], "w_down", self.dtype)(relu2(_dense(self.width, "w_up", self.dtype)(x)))


class NemotronHLayer(nn.Module):
    """``h + f(N(h))`` with ``f`` the sublayer of ``kind`` (one of ``M``,
    ``*``, ``E``)."""

    cfg: NemotronHConfig
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        with scope("block/norm"):
            y = RMSNorm(cfg.norm_eps, self.dtype, name="norm")(h)
        if self.kind in "M*":
            sub = Mamba2Mixer(cfg, self.dtype, name="mixer") if self.kind == "M" \
                else GqaAttention(cfg, self.dtype, name="attn")
            y = sub(y)
            with scope("block/norm"):
                return h + y
        routed = TopKMoe(
            cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            scoring="sigmoid", selection_bias=True,
            routed_scaling_factor=cfg.routed_scaling_factor,
            gated=False, activation="relu2", expert_window=EXPERT_WINDOW, dtype=self.dtype, name="moe",
        )(y)
        with scope("moe/shared"):
            shared = Relu2Mlp(cfg.moe_shared_expert_intermediate_size, self.dtype, name="shared")(y)
        with scope("block/norm"):
            return h + routed + shared


class NemotronH(nn.Module):
    """(B, L) tokens → (B, L, vocab) logits; L a multiple of ``chunk_size``
    wherever the pattern has an ``M``.  Trains by plain next-token cross
    entropy (``train/step.py``'s default objective)."""

    cfg: NemotronHConfig
    dtype: Any = jnp.float32

    # Leaves ``train/step.py`` hands over as stored, not in the compute
    # dtype: 64 numbers each behind a T-step product of decays.
    float32_params = ("A_log", "dt_bias", "D")

    @nn.compact
    def __call__(self, tokens, train: bool = True, return_hidden: bool = False):
        cfg = self.cfg
        embed = self.param(
            "embed", nn.initializers.normal(stddev=0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        layer_cls = NemotronHLayer
        if cfg.remat:
            layer_cls = nn.remat(
                NemotronHLayer,
                policy=jax.checkpoint_policies.save_only_these_names(*REMAT_SAVE),
            )
        h = embed.astype(self.dtype)[tokens]
        for i, kind in enumerate(cfg.layers):
            h = layer_cls(cfg, kind, self.dtype, name=f"block_{i}")(h)
        with scope("block/norm"):
            h = RMSNorm(cfg.norm_eps, self.dtype, name="ln_final")(h)
        if return_hidden:
            return h
        with scope("train/head"):
            return _dense(cfg.vocab_size, "lm_head", self.dtype)(h).astype(jnp.float32)


def nemotron_h_30b_a3b(cfg_overrides: dict | None = None, **kw) -> NemotronH:
    """The ``nemotron_h`` tower of Nemotron-Labs-TwoTower-30B-A3B-Base as
    published: 52 layers (23 M, 23 E, 6 *), hidden 2688, Mamba-2 with 64
    heads of 64 over 8 groups and a state of 128, attention with 32 / 2
    heads of 128, 128 relu² experts of width 1856, 6 a token, beside a
    shared one of 3712, vocabulary 131,072.  ``cfg_overrides`` patches
    NemotronHConfig fields (a chip's share, toy sizes)."""
    return NemotronH(cfg=NemotronHConfig(**(cfg_overrides or {})), **kw)

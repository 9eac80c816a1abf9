"""Memory-bandwidth-saving BatchNorm for TPU (output-saving backward).

The reference reaches BatchNorm through torchvision's ResNet (implicit in
``resnet18(...)``, /root/reference/src/main.py:49); the stock backward saves
the pre-normalization conv output ``x`` for the gradient, while the ReLU that
follows saves its own input ``z = bn(x)`` — two full activation tensors per
norm layer.  On TPU the ResNet-50 train step is HBM-bandwidth-bound
(profiled: ~46 GB/step at >95% of v5e peak), so every elided tensor is
throughput.

``batch_norm`` here is a ``jax.custom_vjp`` whose residual is the *output*
``z`` instead of the input: the backward reconstructs ``xhat = (z - beta) /
gamma`` — exact, everywhere, because BN is affine and invertible (unlike
ReLU; In-Place ABN, Rota Bulò et al. 2018, needs leaky activations for the
same reason — saving pre-activation ``z`` sidesteps that entirely).  The
following ReLU's backward needs only ``sign(z)``, so ``z`` is the *single*
saved tensor for the whole conv→BN→ReLU group and the conv output is never
re-read in the backward.

Restriction: the reconstruction divides by ``gamma``; do not use where
``gamma`` is initialized to exactly zero (the zero-init-residual final block
BN) — there ``xhat`` is unrecoverable and ``dgamma`` would stay zero
forever.  Transiently tiny ``gamma`` is safe (clamped denominator; ``z -
beta`` shrinks with ``gamma``, so the ratio stays accurate).

Statistics are float32 (matching flax BatchNorm), computed as E[x] and
E[x^2] - E[x]^2 so both reductions fuse into the producing conv's epilogue.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

F32 = jnp.float32


def _stat_dtype(x):
    # f32 stats for bf16/f32 compute; f64 stats under jax_enable_x64.
    return jnp.promote_types(x.dtype, F32)


def _bn_core(x, gamma, beta, eps):
    """Forward math shared by the primal and the vjp-fwd: returns (z, mean, var)."""
    xf = x.astype(_stat_dtype(x))
    reduce_axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(xf, reduce_axes)
    var = jnp.mean(jnp.square(xf), reduce_axes) - jnp.square(mean)
    rstd = lax.rsqrt(var + eps)
    scale = (gamma * rstd).astype(x.dtype)
    bias = (beta - mean * gamma * rstd).astype(x.dtype)
    return x * scale + bias, mean, var


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm(x, gamma, beta, eps=1e-5):
    """Train-mode BatchNorm ``(x, gamma, beta) -> (z, mean, var)``.

    ``mean``/``var`` are the batch statistics (for the running-average
    update); their cotangents are ignored by the custom backward — treat
    them as stop-gradient values.
    """
    return _bn_core(x, gamma, beta, eps)


def _bn_fwd(x, gamma, beta, eps):
    z, mean, var = _bn_core(x, gamma, beta, eps)
    # Residuals deliberately exclude x: z carries the full information.
    return (z, mean, var), (z, gamma, beta, var)


def _bn_bwd_core(z, gamma, beta, var, dz, eps):
    """Shared backward math: BN gradient with xhat reconstructed from the
    *output* ``z``.  Returns ``(dx, dgamma, dbeta)``.

    The gamma clamp lets a transiently tiny gamma still reconstruct
    ``xhat = (z - beta) / gamma`` without overflow — preserving sign
    (copysign), since replacing a tiny negative gamma with +tiny would flip
    xhat's sign; see module docstring for the exactly-zero caveat.
    """
    stat = _stat_dtype(z)
    rstd = lax.rsqrt(var + eps)
    g = gamma.astype(stat)
    tiny = jnp.asarray(1e-12, g.dtype)
    safe_g = jnp.where(jnp.abs(g) < tiny, jnp.copysign(tiny, g), g)
    xhat = z.astype(stat) / safe_g - beta.astype(stat) / safe_g
    reduce_axes = tuple(range(z.ndim - 1))
    n = z.size // z.shape[-1]
    dzf = dz.astype(stat)
    sum_dz = jnp.sum(dzf, reduce_axes)
    sum_dz_xhat = jnp.sum(dzf * xhat, reduce_axes)
    dx = (g * rstd) * (dzf - sum_dz / n - xhat * (sum_dz_xhat / n))
    return dx.astype(z.dtype), sum_dz_xhat, sum_dz


def _bn_bwd(eps, residuals, cotangents):
    dz = cotangents[0]  # d(mean), d(var) are zero by construction (see batch_norm)
    z, gamma, beta, var = residuals
    return _bn_bwd_core(z, gamma, beta, var, dz, eps)


batch_norm.defvjp(_bn_fwd, _bn_bwd)


def bn_relu(x, gamma, beta, eps=1e-5):
    """Fused-for-memory BatchNorm + ReLU: returns (y, mean, var).

    The ReLU is a plain op: its backward and ``batch_norm``'s backward both
    read the same saved ``z``, so the group saves one tensor total.
    """
    z, mean, var = batch_norm(x, gamma, beta, eps)
    return jnp.maximum(z, 0), mean, var


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def bn_add_relu(x, r, gamma, beta, eps=1e-5):
    """Residual-block tail ``relu(bn(x) + r)`` saving only ``z = bn(x)``.

    The textbook composition persists two full activation tensors for the
    backward — the conv output ``x`` (BN residual) and the pre-ReLU sum
    ``z + r`` (ReLU residual).  Here the residuals are ``(z, r)``: the ReLU
    mask is recomputed as ``(z + r) > 0`` and ``xhat`` is reconstructed from
    ``z`` as in :func:`batch_norm`.  ``r`` is the block's residual input,
    which the autodiff graph *already* saves (it is conv1's backward
    residual, or the downsample ``batch_norm`` output when that path also
    uses the output-saving BN), so XLA CSEs it to the same buffer and the
    group's only new saved tensor is ``z`` — one instead of two.

    Same gamma-zero restriction as :func:`batch_norm` (don't combine with
    zero-init residual gamma).  Returns ``(out, mean, var)``.
    """
    z, mean, var = _bn_core(x, gamma, beta, eps)
    return jnp.maximum(z + r.astype(z.dtype), 0), mean, var


def _bnar_fwd(x, r, gamma, beta, eps):
    z, mean, var = _bn_core(x, gamma, beta, eps)
    out = jnp.maximum(z + r.astype(z.dtype), 0)
    return (out, mean, var), (z, r, gamma, beta, var)


def _bnar_bwd(eps, residuals, cotangents):
    dout = cotangents[0]
    z, r, gamma, beta, var = residuals
    # ReLU mask recomputed from the two saved tensors (no pre-ReLU sum kept).
    ds = jnp.where(z + r.astype(z.dtype) > 0, dout, jnp.zeros((), dout.dtype))
    dx, dgamma, dbeta = _bn_bwd_core(z, gamma, beta, var, ds, eps)
    return dx, ds.astype(r.dtype), dgamma, dbeta


bn_add_relu.defvjp(_bnar_fwd, _bnar_bwd)


class _FusedBNBase(nn.Module):
    """Shared param/batch-stat machinery for the fused BN variants.

    Parameter/collection layout matches ``flax.linen.BatchNorm`` (params
    ``scale``/``bias``; batch_stats ``mean``/``var``), so swapping a variant
    in keeps checkpoint trees identical when given the same module name.
    ``dtype`` is accepted for constructor parity with ``flax.linen.BatchNorm``
    but unused: computation follows the input's dtype (stats in f32).
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    def _params_and_stats(self, features):
        gamma = self.param("scale", nn.initializers.ones, (features,), F32)
        beta = self.param("bias", nn.initializers.zeros, (features,), F32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((features,), F32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((features,), F32)
        )
        return gamma, beta, ra_mean, ra_var

    def _eval_scale_bias(self, gamma, beta, ra_mean, ra_var, dtype):
        """Running stats folded into a per-channel affine (eval mode)."""
        rstd = lax.rsqrt(ra_var.value + self.epsilon)
        scale = (gamma * rstd).astype(dtype)
        bias = (beta - ra_mean.value * gamma * rstd).astype(dtype)
        return scale, bias

    def _update_stats(self, ra_mean, ra_var, mean, var):
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * lax.stop_gradient(mean)
            ra_var.value = m * ra_var.value + (1 - m) * lax.stop_gradient(var)


class FusedBNRelu(_FusedBNBase):
    """Drop-in for ``BatchNorm -> relu`` pairs with the memory-saving
    backward (see :func:`bn_relu` and the base class for layout)."""

    @nn.compact
    def __call__(self, x):
        gamma, beta, ra_mean, ra_var = self._params_and_stats(x.shape[-1])
        if self.use_running_average:
            scale, bias = self._eval_scale_bias(gamma, beta, ra_mean, ra_var, x.dtype)
            return jnp.maximum(x * scale + bias, 0)
        y, mean, var = bn_relu(x, gamma, beta, self.epsilon)
        self._update_stats(ra_mean, ra_var, mean, var)
        return y


class FusedBN(_FusedBNBase):
    """Drop-in for a bare ``flax.linen.BatchNorm`` with the output-saving
    backward (no activation).  Saving ``z`` instead of ``x`` is byte-neutral
    for the BN itself but lets a consumer that also needs ``z`` (e.g.
    :class:`FusedBNAddRelu` on the residual join) share the same buffer.

    Same layout/caveats as :class:`FusedBNRelu`; gamma must not be
    initialized to exactly zero.
    """

    @nn.compact
    def __call__(self, x):
        gamma, beta, ra_mean, ra_var = self._params_and_stats(x.shape[-1])
        if self.use_running_average:
            scale, bias = self._eval_scale_bias(gamma, beta, ra_mean, ra_var, x.dtype)
            return x * scale + bias
        z, mean, var = batch_norm(x, gamma, beta, self.epsilon)
        self._update_stats(ra_mean, ra_var, mean, var)
        return z


class FusedBNAddRelu(_FusedBNBase):
    """Drop-in for ``BatchNorm -> (+residual) -> relu`` block tails.

    Persists one activation tensor (the BN output) for the whole group —
    see :func:`bn_add_relu`.  Not usable with zero-init residual gamma
    (reconstruction divides by gamma); the model falls back to the plain
    composition in that configuration.
    """

    @nn.compact
    def __call__(self, x, residual):
        gamma, beta, ra_mean, ra_var = self._params_and_stats(x.shape[-1])
        if self.use_running_average:
            scale, bias = self._eval_scale_bias(gamma, beta, ra_mean, ra_var, x.dtype)
            return jnp.maximum(x * scale + bias + residual.astype(x.dtype), 0)
        y, mean, var = bn_add_relu(x, residual, gamma, beta, self.epsilon)
        self._update_stats(ra_mean, ra_var, mean, var)
        return y


# ---------------------------------------------------------------------------
# Low-memory LayerNorm for the transformer families.
#
# flax's nn.LayerNorm under reverse-mode AD leaves XLA to choose residuals;
# on the bf16 GPT-2/ViT steps the compiled graphs materialize a (B, L, D)
# f32 normalized intermediate per LN (12-25 MB each, observed as relayout
# copies in GPT2_ROOFLINE/VIT_ROOFLINE (deleted: not measured on the current
# machine) analyses).  This custom-vjp LN saves
# only the low-precision INPUT plus the (B, L, 1) stat columns and
# recomputes xhat in the backward — the standard LN gradient:
#   dxhat = dy * scale
#   dx    = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat * xhat))
# computed in the promoted stats dtype (f32 for f32/bf16 inputs, f64
# under jax_enable_x64 — same _stat_dtype rule the BN ops use), with
# dscale/dbias reduced in that dtype.
#
# Measured: swapping it into GPT-2 124M (147.3k vs 147.7k tok/s) and
# ViT-B/16 (1033 vs 1024-1039 img/s) is throughput-NEUTRAL on v5e — XLA
# already overlaps the f32 residual traffic at these sizes.  It is kept as
# the deterministic low-activation-memory option (guaranteed no (B, L, D)
# f32 residual) for configs that are activation-memory-bound rather than
# bandwidth-bound; the stock models stay on nn.LayerNorm.
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, scale, bias, eps=1e-6):
    """LayerNorm over the last axis with a low-memory backward.

    Numerically equal to ``nn.LayerNorm(epsilon=eps)`` (statistics in the
    promoted dtype — f32 for f32/bf16 inputs, f64 under x64 — output in
    ``x.dtype``); the backward stores x (already live as the producing
    layer's activation), mean and rstd — no higher-precision (B, L, D)
    residual.
    """
    y, _, _ = _ln_core(x, scale, bias, eps)
    return y


def _ln_core(x, scale, bias, eps):
    sd = _stat_dtype(x)
    xf = x.astype(sd)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = (xf - mean) * rstd
    y = xhat * scale.astype(sd) + bias.astype(sd)
    return y.astype(x.dtype), mean, rstd


def _ln_fwd(x, scale, bias, eps):
    y, mean, rstd = _ln_core(x, scale, bias, eps)
    # bias rides along only to type its own cotangent ((D,) — negligible).
    return y, (x, scale, bias, mean, rstd)


def _ln_bwd(eps, residuals, dy):
    x, scale, bias, mean, rstd = residuals
    sd = _stat_dtype(x)
    xf = x.astype(sd)
    xhat = (xf - mean) * rstd
    dyf = dy.astype(sd)
    dxhat = dyf * scale.astype(sd)
    m1 = dxhat.mean(-1, keepdims=True)
    m2 = (dxhat * xhat).mean(-1, keepdims=True)
    dx = (rstd * (dxhat - m1 - xhat * m2)).astype(x.dtype)
    red_axes = tuple(range(dy.ndim - 1))
    dscale = jnp.sum(dyf * xhat, axis=red_axes).astype(scale.dtype)
    dbias = jnp.sum(dyf, axis=red_axes).astype(bias.dtype)
    return dx, dscale, dbias


layer_norm.defvjp(_ln_fwd, _ln_bwd)


class FusedLayerNorm(nn.Module):
    """Drop-in for ``nn.LayerNorm`` (same param names/shapes/init, same
    promoted-dtype statistics) with the low-memory backward of
    :func:`layer_norm`.

    Statistics are computed from the ORIGINAL-precision input (matching
    flax, which normalizes before casting to ``dtype``); only the output
    is cast.  Note the saved residual is therefore the input at its own
    precision — the memory win applies when the surrounding network runs
    low-precision activations, the usual bf16-policy case."""

    epsilon: float = 1e-6
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), F32)
        bias = self.param("bias", nn.initializers.zeros, (d,), F32)
        y = layer_norm(x, scale, bias, self.epsilon)
        return y.astype(self.dtype) if self.dtype is not None else y

"""The block-diffusion LM objective (BD3-LM, Arriola et al. 2025; the
masking schedule in LLaDA's form, Nie et al. 2025).

A sequence of L clean tokens ``x0`` is noised per sequence: ``t ~ U(0, 1)``,
``p = (1 - eps) t + eps`` with ``eps = NOISE_EPS``, and every position
becomes the mask id with probability ``p``.  The model runs the noised copy followed by the clean
copy (2L positions) under the block-diffusion mask and is scored where the
noise fell, each masked position predicting its own token (no shift):

    loss = 1 / (N L) * sum_{masked i} (1 / p) * CE(logits_noisy[i], x0[i])

``noise`` is one public function of ``(tokens, key, config)`` so that a
reference can be handed the very masks a step drew: the step's key is
``fold_in(fold_in(base_rng, step), microbatch)``, as for dropout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


NOISE_EPS = 1e-3     # the floor of the masking probability (LLaDA's)


def noise(tokens: jax.Array, key: jax.Array, cfg):
    """tokens (N, L) int → ``(noisy (N, L), masked (N, L) bool, p (N,))``.
    ``cfg`` carries ``mask_token_id`` (None: the vocabulary's last row) and
    ``vocab_size``."""
    n, length = tokens.shape
    t_key, m_key = jax.random.split(key)
    p = (1.0 - NOISE_EPS) * jax.random.uniform(t_key, (n,), jnp.float32) + NOISE_EPS
    masked = jax.random.uniform(m_key, (n, length), jnp.float32) < p[:, None]
    mask_id = cfg.vocab_size - 1 if cfg.mask_token_id is None else cfg.mask_token_id
    return jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens), masked, p


def weighted_masked_ce(logits: jax.Array, targets: jax.Array, masked: jax.Array,
                       p: jax.Array) -> jax.Array:
    """logits (N, L, V) f32 of the noisy positions, targets (N, L) the clean
    tokens → the loss above."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jax.nn.logsumexp(logits, axis=-1) - picked
    weight = masked.astype(jnp.float32) / p[:, None]
    return jnp.sum(weight * ce) / masked.size

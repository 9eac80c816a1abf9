"""Autoregressive text generation for the GPT-2 family.

The reference is a training-only driver (image classification,
/root/reference/src/main.py:47-49) with no inference path at all; a
framework carrying a GPT-2 family owes one.  TPU-native shape: the whole
decode loop is a single jitted ``lax.scan`` over token positions — the KV
cache (flax ``cache`` collection, see ``models/layers.py`` decode mode)
rides in the scan carry, so steady-state generation is one device program
with no per-token dispatch, static shapes throughout, and O(L) attention
per token.

Prompt handling: prompts are consumed through the same scan (one token per
tick, teacher-forced), keeping a single executable for prefill + decode.
Batched prompts of different lengths are supported via ``prompt_lengths``:
shorter prompts start sampling earlier; positions past a prompt's length
take the sampled token, positions inside it take the prompt token.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def uses_approx_top_k(exact_top_k: bool = False) -> bool:
    """True when :func:`sample_logits` will take the approx_max_k
    threshold — the single source of the dispatch rule."""
    return not exact_top_k and jax.default_backend() == "tpu"


def filter_logits(logits, *, temperature, top_k=None, exact_top_k=False):
    """Temperature scaling + top-k filtering over the last axis (any
    leading shape).  The ONE place the sampling distribution is shaped —
    shared by :func:`sample_logits` and the serving engine's speculative
    verify program (serve/engine.py), whose rejection-style acceptance
    probabilities must be computed under exactly the distribution the
    non-speculative sampler draws from.  Greedy callers
    (``temperature == 0`` / ``top_k == 1``) must argmax the RAW logits
    instead of calling this."""
    if temperature <= 0.0:
        raise ValueError("filter_logits needs temperature > 0 (greedy is argmax)")
    logits = logits / jnp.asarray(temperature, logits.dtype)
    if top_k is not None:
        if uses_approx_top_k(exact_top_k):
            kth = lax.approx_max_k(logits, top_k)[0][..., -1:]
        else:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, jnp.finfo(logits.dtype).min, logits)
    return logits


def sample_logits(logits, rng, *, temperature=1.0, top_k=None, exact_top_k=False):
    """Sample token ids from (B, V) logits.

    ``temperature=0`` is greedy argmax; ``top_k`` restricts sampling to the
    k most likely tokens (the standard GPT-2 sampling recipe).

    The k-th-largest threshold uses ``lax.approx_max_k`` on TPU — the
    hardware-accelerated partial sort (recall >= 0.95 per element, i.e. the
    cut may land a few ranks off among near-tied logits, a sub-temperature
    perturbation of the sampling distribution).  A full-vocab
    ``lax.top_k`` sort measured 45% of the whole decode step at GPT-2's
    50k vocab (rounds 1-5, another machine); pass ``exact_top_k=True`` for
    the exact semantics where that matters more than throughput.
    """
    if temperature == 0.0 or top_k == 1:
        # top_k=1 IS greedy whatever the temperature; keeping it on the
        # argmax path also preserves that invariant under the approximate
        # threshold below (whose cut may land below the true max).
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(
        logits, temperature=temperature, top_k=top_k, exact_top_k=exact_top_k
    )
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def eos_cut_length(tokens, eos_token_id) -> int:
    """How many tokens of a proposed emission to keep: everything up to
    and INCLUDING the first EOS, the whole list when EOS is absent or
    None.  The single EOS-in-draft rule shared by the static decoder's
    early-exit accounting (``generate`` halts a row AFTER writing its
    EOS, so ``gen_lengths`` equals this cut applied to the row) and the
    serving engine's multi-token speculative emission (an EOS inside an
    accepted draft retires the slot AT the EOS position, never after the
    full k) — one rule, pinned by tests, so the two paths cannot drift."""
    tokens = np.asarray(tokens)
    if eos_token_id is None:
        return int(tokens.size)
    hits = np.nonzero(tokens == eos_token_id)[0]
    return int(hits[0]) + 1 if hits.size else int(tokens.size)


@partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k",
                     "exact_top_k", "eos_token_id"),
)
def generate(
    model,
    params,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    rng: jax.Array,
    prompt_lengths: jax.Array | None = None,
    temperature: float = 1.0,
    top_k: int | None = None,
    exact_top_k: bool = False,
    eos_token_id: int | None = None,
):
    """Generate up to position ``P + max_new_tokens`` for every row.

    Every output row has length ``P + max_new_tokens``.  A row whose
    ``prompt_lengths`` entry is shorter than ``P`` starts sampling right
    after its own prompt, so it receives ``P - length + max_new_tokens``
    generated tokens — the budget bounds the *sequence length*, not the
    per-row generated-token count; slice per row if you need the latter.

    ``eos_token_id``: a row that samples EOS (at or past its own prompt
    end) writes the EOS token, then stops — later positions are simply
    never overwritten, so they keep the buffer's prior contents: zeros
    past the prompt width, the caller's own padding bytes inside it (a
    ragged row that hits EOS before column P).  Use ``gen_lengths``, not
    a fill-value scan, to find each row's end.  The scan itself still
    runs its full static trip count; per-request compute reclamation is
    the serving engine's job (serve/engine.py).  With EOS set the return
    becomes ``(tokens, gen_lengths)`` where ``gen_lengths`` (B,) int32
    counts each row's generated tokens INCLUDING its EOS (rows that never
    hit EOS count their full ``P - length + max_new_tokens`` fill).

    Args:
      model: a ``GPT2`` module (its ``decode`` field is overridden here).
      params: trained parameter tree (``variables["params"]``).
      prompt: (B, P) int32 prompt tokens (right-padded if ragged).
      prompt_lengths: (B,) actual lengths; default = full P for every row.
      rng: sampling key (ignored for ``temperature=0`` greedy decoding).

    Returns:
      (B, P + max_new_tokens) int32: prompts followed by generated tokens;
      with ``eos_token_id`` set, the ``(tokens, gen_lengths)`` pair.
    """
    b, p = prompt.shape
    total = p + max_new_tokens
    if total > model.cfg.max_seq_len:
        # Without this, the decode-mode wpe gather would silently clamp
        # positions past max_seq_len (jit gather semantics) and emit
        # degenerate text instead of failing.
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model's max_seq_len ({model.cfg.max_seq_len})"
        )
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), p, jnp.int32)

    decoder = model.clone(decode=True)
    # Shape-level init: the cache skeleton is all zeros, so tracing the
    # full parameter init + a max-length forward just to throw the values
    # away would bloat compile time (noticeable at gpt2_xl scale).
    cache_shapes = jax.eval_shape(
        lambda: decoder.init(
            jax.random.PRNGKey(0), jnp.zeros((b, total), jnp.int32),
            train=False,
        )["cache"]
    )
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
    )

    # Tokens buffer: prompt then zeros; the scan fills positions 1..total-1
    # with either the teacher-forced prompt token or the sampled one.
    tokens = jnp.zeros((b, total), jnp.int32).at[:, :p].set(prompt)

    def tick(carry, i):
        cache, tokens, rng, done, gen_len = carry
        logits, updates = decoder.apply(
            {"params": params, "cache": cache},
            lax.dynamic_slice_in_dim(tokens, i, 1, axis=1),
            train=False,
            mutable=["cache"],
        )
        rng, key = jax.random.split(rng)
        sampled = sample_logits(
            logits[:, 0], key, temperature=temperature, top_k=top_k,
            exact_top_k=exact_top_k,
        )
        # A row writes its sample only while generating and not finished;
        # prompt positions stay teacher-forced, post-EOS positions keep the
        # buffer's zero fill ("stop overwriting").
        generating = (i + 1 >= prompt_lengths) & ~done
        nxt = jnp.where(generating, sampled, tokens[:, i + 1])
        tokens = lax.dynamic_update_slice(tokens, nxt[:, None], (0, i + 1))
        gen_len = gen_len + generating.astype(jnp.int32)
        if eos_token_id is not None:
            # The EOS write itself lands (and counts); the row halts after.
            done = done | (generating & (sampled == eos_token_id))
        return (updates["cache"], tokens, rng, done, gen_len), None

    done = jnp.zeros((b,), bool)
    gen_len = jnp.zeros((b,), jnp.int32)
    (cache, tokens, rng, done, gen_len), _ = lax.scan(
        tick, (cache, tokens, rng, done, gen_len), jnp.arange(total - 1)
    )
    if eos_token_id is None:
        return tokens
    return tokens, gen_len

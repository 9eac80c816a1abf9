"""The tabled multi-tile flash pair under the causal and the empty mask: a
transposed call whose key row is several tiles takes ``_flash_tabled_fwd`` and
ONE fused backward wherever a head's dk / dv fit VMEM, whole tiles build no
mask, and the kernels keep the causal names.  Interpret mode, two heads of 64,
small blocks so that every kind of tile occurs."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops import pallas_attention as pa
from pytorch_distributed_training_tpu.ops.attention import _xla_attention

CASES = {
    # name: (q_len, k_len, causal, block)
    "causal_square": (512, 512, True, 128),
    "causal_q_shorter": (256, 512, True, 128),          # causal_offset 256
    "causal_fully_masked_rows": (512, 256, True, 128),  # causal_offset -256
    "causal_padded_kv_len": (500, 500, True, 128),      # padded to 512, kv_len 500
    "causal_q_shorter_padded": (200, 450, True, 128),
    "causal_diagonal_classes": (768, 768, True, 256),   # tiles wide enough for sub-blocks
    "causal_diagonal_padded": (700, 700, True, 256),    # the last diagonal tile holds padding
    "no_mask": (512, 512, False, 128),
    "no_mask_padded": (300, 500, False, 128),
}


def kernel_names(fn, *args):
    """Names of the ``pallas_call``s in ``fn`` lowered for ``args``."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return set(re.findall(r'(flash_\w+)\)*/pallas_call\b', text))


def pair(q, k, v, w, causal, block):
    """Forward and the three gradients of a weighted sum, so that every
    output element has a gradient of its own."""
    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, causal=causal, block_q=block,
                                 block_k=block, interpret=True)
        return jnp.sum(out * w), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


def operands(case):
    q_len, k_len, causal, block = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    shape = lambda n: (1, n, 2, 64)
    q, w = (jax.random.normal(key, shape(q_len)) for key in keys[:2])
    k, v = (jax.random.normal(key, shape(k_len)) for key in keys[2:])
    return (q, k, v, w), causal, block


@pytest.mark.parametrize("case", CASES)
def test_tabled_pair_matches_xla(case):
    (q, k, v, w), causal, block = operands(case)
    plan = pa.flash_plan(q.shape[1], k.shape[1], 2, 2, 64, 4, causal=causal,
                         block_diffusion=None, block_q=block, block_k=block)
    assert plan.kind == "tabled"

    def ref(q, k, v):
        out = _xla_attention(q, k, v, causal=causal)
        return jnp.sum(out * w), out

    (_, want), want_grads = jax.value_and_grad(ref, (0, 1, 2), has_aux=True)(q, k, v)
    for got, want in zip(pair(q, k, v, w, causal, block), (want,) + want_grads):
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("case", CASES)
def test_fused_backward_agrees_with_the_split_pair(case, monkeypatch):
    """The same call with the fit forced false runs the plain forward and
    ``flash_bwd_dq`` + ``flash_bwd_dkv``: one recomputation against two."""
    (q, k, v, w), causal, block = operands(case)
    fused = pair(q, k, v, w, causal, block)
    monkeypatch.setattr(pa, "_fused_bwd_fits", lambda *a: False)
    names = kernel_names(lambda *a: pair(*a, causal, block), q, k, v, w)
    assert names == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for got, want in zip(fused, pair(q, k, v, w, causal, block)):
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_tabled_pair_keeps_the_causal_names(causal):
    """``kernel.flash_fwd_roofline.train``, ``kernel.flash_bwd_roofline.train``
    and ``kernel.flash_share.train`` read these names; a ``flash_bd_*`` name
    here would silence them in the Instella cell."""
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    names = kernel_names(lambda q, k, v, w: pair(q, k, v, w, causal, 1024), q, q, q, q)
    assert names == {"flash_fwd", "flash_bwd"}


def test_key_row_past_the_fit_keeps_the_split_backward():
    """32,768 keys of 128: two float32 accumulators and the dk / dv blocks
    alone are 64 MB, so the plan is ``transposed`` and the backward is the
    split pair; 16,384 keys still fit."""
    assert pa._fused_bwd_fits(8192, 128, 2, 1024, 1024)
    assert pa._fused_bwd_fits(16384, 128, 2, 1024, 1024)
    assert not pa._fused_bwd_fits(32768, 128, 2, 1024, 1024)
    plan = pa.flash_plan(32768, 32768, 16, 16, 128, 2, causal=True, block_diffusion=None)
    assert plan.kind == "transposed"
    q = jax.ShapeDtypeStruct((1, 32768, 1, 128), jnp.bfloat16)
    names = kernel_names(lambda q, k, v, w: pair(q, k, v, w, True, 1024), q, q, q, q)
    assert names == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}


@pytest.mark.parametrize("causal,causal_offset,kv_len", [
    (True, 0, None), (True, 128, None), (True, -128, None), (True, 60, None),
    (True, 0, 300), (False, 0, 300),
])
def test_causal_full_block_is_the_tiles_without_a_dead_pair(causal, causal_offset, kv_len):
    """Held against the dense mask, tile by tile, at 128-tiles; the live
    predicate beside it."""
    n, block = 3, 128
    rows, cols = np.arange(n * block)[:, None], np.arange(n * block)[None, :]
    dense = (rows + causal_offset >= cols) if causal else np.ones((n * block,) * 2, bool)
    if kv_len is not None:
        dense = dense & (cols < kv_len)
    mask = dict(causal=causal, causal_offset=causal_offset, kv_len=kv_len,
                block_q=block, block_k=block)
    for qi in range(n):
        for ki in range(n):
            tile = dense[qi * block:(qi + 1) * block, ki * block:(ki + 1) * block]
            assert bool(pa._causal_full_block(qi, ki, **mask)) == bool(tile.all()), (qi, ki)
            assert bool(pa._live_block(qi, ki, **mask)) == bool(tile.any()), (qi, ki)

"""Attention: XLA reference implementation + TPU flash-attention dispatch.

No attention exists in the reference (image classification only,
src/main.py:47-49; SURVEY.md §5 "long-context" row), but BASELINE.json
configs[2]/[3] (ViT-B/16, GPT-2) require it, and the framework treats
long-context as first-class.  Layout is (batch, length, heads, head_dim)
throughout — the TPU-friendly layout that keeps the head_dim*heads axis
contiguous for the MXU.

``dot_product_attention`` is the public entry: it dispatches to the Pallas
flash kernel on TPU when shapes allow (``ops.pallas_attention``), else to a
fused-softmax XLA implementation that the compiler maps onto MXU matmuls.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..obs.trace import scope


@jax.custom_vjp
def _softmax_lowp(logits: jax.Array) -> jax.Array:
    """Softmax over the last axis that computes in f32 but *saves* only the
    low-precision output for the backward.

    Plain ``jax.nn.softmax`` on upcast logits saves its f32 output as the
    VJP residual — at ViT-B/16 batch 128 that is a 238 MB
    (B, H, L, L) tensor per layer written forward and read back in the
    backward.  Storing the bf16 probabilities instead halves that traffic;
    the softmax-gradient identity dl = p * (dp - sum(dp*p)) is evaluated in
    f32 from the saved bf16 p, so the only precision loss is the bf16
    rounding of p itself — the same rounding the following
    probabilities @ V matmul applies anyway.
    """
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        logits.dtype
    )


def _softmax_lowp_fwd(logits):
    w = _softmax_lowp(logits)
    return w, w


def _softmax_lowp_bwd(w, dw):
    w32 = w.astype(jnp.float32)
    dw32 = dw.astype(jnp.float32)
    dl = w32 * (dw32 - jnp.sum(dw32 * w32, axis=-1, keepdims=True))
    return (dl.astype(w.dtype),)


_softmax_lowp.defvjp(_softmax_lowp_fwd, _softmax_lowp_bwd)


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Reference attention in pure XLA. q/k/v: (B, L, H, D).

    bf16 inputs take the AMP-faithful low-memory path: the score matmul
    writes bf16 (torch autocast's own behavior for the reference's
    AMP-equivalent config), the softmax arithmetic runs in f32 inside one
    fused kernel, and only bf16 probabilities are stored for the backward
    (``_softmax_lowp``).  f32 inputs keep the fully-f32 chain.
    """
    _, q_len, _, head_dim = q.shape
    k_len = k.shape[1]
    scale = scale if scale is not None else head_dim**-0.5
    # bf16 only: it shares f32's exponent range, so bf16 logits cannot
    # overflow where f32 would not.  f16 (narrow exponent) keeps the f32
    # accumulation path — q.k at head_dim 64 readily exceeds f16's 65504.
    lowp = q.dtype == jnp.bfloat16
    if lowp and not causal:
        # (B, L, H, L) probs layout: XLA's batched dot still emits (b,h,q,k)
        # internally, but asking for the h-interior layout here lets the
        # transpose fuse with the softmax chain instead of standing as a
        # materialized copy next to the (B,H,L,D) q/k/v transposes.
        # Measured on ViT-B/16 (the L=197 consumer of this path):
        # compiled bytes 100.3 -> 93.6 GB/step and 831 -> 909 img/s at
        # batch 128; +1.8% at the batch-44 headline (VIT_ROOFLINE (deleted: not
        # measured on the current machine)).
        # Causal keeps the (b,h,q,k) form — its mask broadcasts over
        # (None, None, q, k) and GPT-2's flash threshold routes L>=1024
        # away from this path anyway.
        logits = jnp.einsum("bqhd,bkhd->bqhk", q, k) * jnp.asarray(
            scale, q.dtype
        )
        weights = _softmax_lowp(logits)
        return jnp.einsum("bqhk,bkhd->bqhd", weights.astype(v.dtype), v)
    if lowp:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(
            scale, q.dtype
        )
    else:
        # Softmax accumulation in f32 regardless of input dtype.
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
        logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k=k_len - q_len)
        logits = jnp.where(
            mask[None, None, :, :], logits, jnp.finfo(logits.dtype).min
        )
    weights = _softmax_lowp(logits) if lowp else jax.nn.softmax(logits, axis=-1)
    if causal and k_len < q_len:
        # Fully-masked query rows (possible only when q_len > k_len) are
        # zero, matching the Pallas kernel — softmax alone would emit a
        # uniform distribution over masked keys and leak gradient into v.
        any_visible = jnp.any(mask, axis=-1)  # (q_len,)
        weights = jnp.where(any_visible[None, None, :, None], weights, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out


def block_diffusion_mask(seq_len: int, block_len: int) -> jax.Array:
    """The dense (2L, 2L) boolean block-diffusion mask, from its definition:
    positions 0 .. L-1 are the noised copy, L .. 2L-1 the clean copy, and
    position j of either half lies in block j // B.  A noisy query sees the
    noisy keys of its own block and the clean keys of earlier blocks; a
    clean query sees the clean keys of its own and earlier blocks."""
    pos = jnp.arange(2 * seq_len)
    noisy = pos < seq_len
    blk = (pos % seq_len) // block_len
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(
        qn, jnp.where(kn, qb == kb, kb < qb), ~kn & (kb <= qb)
    )


def _xla_masked_attention(q, k, v, mask, *, scale=None):
    """Pure-XLA attention under a dense (Lq, Lk) boolean ``mask`` with
    grouped K/V heads: q (B, Lq, H, D), k/v (B, Lk, Hkv, D), H = G * Hkv.
    Scores accumulate and the softmax runs in f32; K and V are indexed per
    group, never repeated.  The CPU and short-length path of the mask kinds
    the causal path above does not know."""
    b, q_len, h, d = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else d**-0.5
    qg = q.reshape(b, q_len, hkv, h // hkv, d)
    logits = jnp.einsum(
        "bqngd,bknd->bngqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    logits = jnp.where(mask[None, None, None], logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", weights.astype(v.dtype), v)
    return out.reshape(b, q_len, h, d)


def _xla_path(q, k, v, *, causal, scale, block_diffusion):
    """The XLA implementation for the call's mask."""
    if block_diffusion is not None:
        return _xla_masked_attention(
            q, k, v, block_diffusion_mask(*block_diffusion), scale=scale
        )
    if k.shape[2] != q.shape[2]:  # grouped K/V under the causal or no mask
        q_len, k_len = q.shape[1], k.shape[1]
        mask = jnp.ones((q_len, k_len), bool)
        return _xla_masked_attention(
            q, k, v, jnp.tril(mask, k=k_len - q_len) if causal else mask, scale=scale
        )
    return _xla_attention(q, k, v, causal=causal, scale=scale)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    interpret: bool | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array:
    """Blockwise (flash) attention via the Pallas TPU kernel.

    Any sequence length works: the kernel wrapper pads to the 128-lane tile
    and masks padded keys internally (``ops.pallas_attention``).  Falls back
    to the XLA implementation only when running on a backend the kernel does
    not target (neither TPU nor the CPU interpreter).
    """
    from . import pallas_attention

    backend = jax.default_backend()
    # CPU only counts when the interpreter is allowed: interpret=False on CPU
    # would try to lower the Mosaic TPU kernel there.
    backend_ok = (
        backend == "tpu"
        or (backend == "cpu" and interpret is not False)
        or bool(interpret)
    )
    if not backend_ok:
        return _xla_path(
            q, k, v, causal=causal, scale=scale,
            block_diffusion=block_diffusion,
        )
    kernel = functools.partial(
        pallas_attention.flash_attention, causal=causal, scale=scale,
        interpret=interpret, block_diffusion=block_diffusion,
    )
    # heads split over ``tensor`` only where the K/V heads divide too
    partition = _kernel_partition(q.shape[0], math.gcd(q.shape[2], k.shape[2]))
    if partition is None:
        return kernel(q, k, v)
    mesh, spec = partition
    from ..compat import shard_map

    return shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _kernel_partition(batch: int, heads: int):
    """How a ``pallas_call`` over (B, L, H, D) operands must be split when
    it is traced under a multi-device mesh: ``(mesh, spec)``, or None when
    it can be called as it is (no mesh, one device, or already inside a
    ``shard_map`` body, where every axis is manual).

    A compiled Mosaic kernel is opaque to the partitioner ("Mosaic kernels
    cannot be automatically partitioned"), so under GSPMD the kernel runs
    per shard inside a ``shard_map``: attention is independent per batch
    row and per head, so the batch splits over the batch axes and the
    heads over ``tensor``, each only where it divides — what does not
    divide stays whole on every device (gathered: correct, never wrong).
    """
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import AXIS_TENSOR, BATCH_AXES
    from ..compat import ambient_mesh

    mesh, manual = ambient_mesh()
    if mesh is None or mesh.size == 1 or manual:
        return None
    # .get: an ambient mesh need not be one of ours with all six axes.
    batch_axes = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if batch % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    tensor_ways = mesh.shape.get(AXIS_TENSOR, 1)
    tensor = AXIS_TENSOR if (
        tensor_ways > 1 and heads % tensor_ways == 0
    ) else None
    return mesh, P(batch_axes or None, None, tensor, None)


def flash_preferred(
    q_len: int,
    k_len: int,
    head_dim: int,
    num_heads: int | None = None,
    *,
    itemsize: int = 2,
) -> bool:
    """Whether ``dot_product_attention``'s auto-dispatch will pick the
    Pallas flash path for these shapes (the full-model-measured rule
    below).  Exposed so upstream layers can co-optimize layout: the
    native-layout kernels consume (B, L, H*D) column groups directly, so
    producers feeding flash should slice q/k/v as LAST-AXIS column spans
    (GPT-2 full model: 142.5k -> 147.7k tok/s), while the XLA path fuses
    better with the (B, L, 3, H, Dh) axis-2 split (ViT batch 44: 943 vs
    872 img/s) — both forms select the identical elements.

    ``num_heads`` (when the caller knows it) additionally asks
    ``pallas_attention.flash_plan`` — the plan the kernel dispatch itself
    runs — so wide models whose native-layout configs do not fit VMEM
    (execution falls to the transposed kernels) get the XLA-favored split
    instead of paying the relayout twice.  Without ``num_heads`` the size
    rule alone answers (the dispatcher's own call)."""
    # The size rule: set by *full-model* measurement, not the isolated
    # micro-bench (rounds 1-5, another machine).  GPT-2 124M tokens/sec,
    # flash vs the low-memory XLA path (bf16 probs, _softmax_lowp), after
    # the r4 heads-fused native-layout kernels (the single-tile fwd/bwd
    # consume (B, L, H*D) directly — a free reshape — so the
    # (B,L,H,D) <-> (B,H,L,D) boundary transposes that used to hand
    # XLA the sub-1024 win are gone, ops/pallas_attention.py):
    #   L=197 (ViT-B/16): 946.9 vs 1038.7 img/s -> XLA (pad-to-256
    #                     waste: 30% dead keys + sub-tile q blocks)
    #   L=256: 146.8k vs 143.8k                 -> flash (+2%)
    #   L=512: 154.7k vs 134.0k                 -> flash (+15%)
    #   L=768: 143.3k vs 122.0k                 -> flash (+17%)
    #   L=1024: 142.5k vs 89.4k                 -> flash (+59%,
    #           grouped-heads native-layout variant)
    # The crossover sits at the 256 tile boundary: below it the kernel
    # pays pad-to-tile waste XLA does not.  Above ~2k the XLA path's
    # (B, H, L, L) materialization also stops fitting, so flash is the
    # only option on memory.  Only full-model A/Bs are trusted for this
    # threshold.
    size_ok = (
        jax.default_backend() == "tpu"
        and q_len >= 256
        and k_len >= 64
        and head_dim >= 64
    )
    if size_ok and num_heads is not None:
        from .pallas_attention import flash_plan

        # ``itemsize`` must be the activations' real byte width: the VMEM
        # fits use it, and an fp32 run checked at bf16 sizes would pick the
        # flash-favored split for configs the dispatch then rejects.  The
        # kind is the same under ``causal``: its cap on the q block never
        # takes the smallest block away, and the smallest decides the fit.
        plan = flash_plan(
            q_len, k_len, num_heads, num_heads, head_dim, itemsize,
            causal=False, block_diffusion=None,
        )
        # Beyond the native kernels' k-band the multi-tile transposed
        # kernel runs regardless (XLA's (B,H,L,L) materialization stops
        # fitting at long L), and the last-axis split keeps its measured
        # long-context behavior.
        if plan.k_len <= 1024:
            return plan.kind in ("single", "grouped")
    return size_ok


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    use_flash: bool | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array:
    """Public attention entry point. q/k/v: (B, L, H, D) → (B, L, H, D).

    ``use_flash=None`` auto-selects by :func:`flash_preferred`'s size rule:
    the Pallas flash kernels on a TPU from q_len 256 up, XLA (the dense-mask
    path under ``block_diffusion``) elsewhere and for short lengths.

    ``block_diffusion=(L, B)`` applies the block-diffusion training mask
    (:func:`block_diffusion_mask`): q and k hold 2L positions, a noised copy
    then the clean copy, in blocks of B.

    Under any mask k and v may carry fewer heads than q (grouped-query
    attention, read from ``k``'s shape: query head h reads K/V head
    h // (H / Hkv)).  The XLA path and the tabled multi-tile kernels read K/V
    at their own head count; ``pallas_attention.flash_attention`` says what
    the kernels of a short key row do.  With equal head counts the call is
    the one it always was, kernel for kernel.
    """
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"k/v carry {k.shape[2]}/{v.shape[2]} heads, q {q.shape[2]}"
        )
    if block_diffusion is not None:
        if causal or q.shape[1] != 2 * block_diffusion[0] or k.shape[1] != q.shape[1]:
            raise ValueError(
                f"block diffusion over L={block_diffusion[0]} takes 2L positions "
                f"and no causal flag; got q {q.shape}, k {k.shape}"
            )
    if use_flash is None:
        use_flash = flash_preferred(q.shape[1], k.shape[1], q.shape[3])
    attend = flash_attention if use_flash else _xla_path
    # The one place every model's attention product is named (an enclosing
    # ``attn/block_diffusion`` stays in the path; this, the inner name, wins).
    with scope("attn/core"):
        return attend(
            q, k, v, causal=causal, scale=scale, block_diffusion=block_diffusion
        )

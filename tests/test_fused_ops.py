"""Numerics parity for the TPU byte-saving fused ops against the textbook
composition (SURVEY.md §4 test strategy: sharded/fused paths must match the
plain reference implementation).

The fused ops change *how* bytes move, never the math:
- FusedBNRelu vs BatchNorm->relu (fwd, grads, running stats)
- SpaceToDepthStem vs 7x7/s2 conv (exact)
- max_pool_3x3_s2 vs nn.max_pool (fwd exact; grads on tie-free inputs)
- ResNet(tpu_fused=True) vs ResNet(tpu_fused=False): same param tree, same
  loss, matching grads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

import compiled
from pytorch_distributed_training_tpu.models import resnet50
from pytorch_distributed_training_tpu.ops import (
    FusedBNRelu,
    SpaceToDepthStem,
    bn_relu,
    max_pool_3x3_s2,
)


class _PlainBNRelu(nn.Module):
    use_running_average: bool = False

    @nn.compact
    def __call__(self, x):
        y = nn.BatchNorm(
            use_running_average=self.use_running_average,
            momentum=0.9, epsilon=1e-5,
        )(x)
        return nn.relu(y)


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def test_bn_relu_forward_matches_plain():
    key = jax.random.PRNGKey(0)
    x = _rand(key, (8, 6, 6, 16))
    fused = FusedBNRelu(dtype=jnp.float32)
    plain = _PlainBNRelu()
    vf = fused.init(key, x)
    vp = plain.init(key, x)
    yf, sf = fused.apply(vf, x, mutable=["batch_stats"])
    yp, sp = plain.apply(vp, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yp), atol=1e-5)
    for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(sp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_bn_relu_grads_match_plain():
    key = jax.random.PRNGKey(1)
    x = _rand(key, (8, 6, 6, 16))
    # Non-trivial gamma/beta so the recompute-from-output path is exercised.
    gamma = 0.5 + jax.random.uniform(jax.random.PRNGKey(2), (16,))
    beta = _rand(jax.random.PRNGKey(3), (16,))

    def loss_fused(x, g, b):
        y, _, _ = bn_relu(x, g, b, 1e-5)
        return jnp.sum(jnp.sin(y))

    def loss_plain(x, g, b):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.var(x, (0, 1, 2))
        y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
        return jnp.sum(jnp.sin(nn.relu(y)))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    gp = jax.grad(loss_plain, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(gf, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_bn_relu_negative_gamma_grads():
    """The output-recompute must be sign-correct for negative gamma."""
    x = _rand(jax.random.PRNGKey(4), (4, 5, 5, 8))
    gamma = -(0.5 + jax.random.uniform(jax.random.PRNGKey(5), (8,)))
    beta = _rand(jax.random.PRNGKey(6), (8,))

    def loss_fused(x):
        y, _, _ = bn_relu(x, gamma, beta, 1e-5)
        return jnp.sum(y * y)

    def loss_plain(x):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.var(x, (0, 1, 2))
        y = nn.relu((x - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta)
        return jnp.sum(y * y)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_fused)(x)),
        np.asarray(jax.grad(loss_plain)(x)),
        rtol=1e-4, atol=1e-5,
    )


def test_bn_add_relu_forward_and_grads_match_plain():
    from pytorch_distributed_training_tpu.ops import bn_add_relu

    x = _rand(jax.random.PRNGKey(20), (8, 6, 6, 16))
    r = _rand(jax.random.PRNGKey(21), (8, 6, 6, 16))
    gamma = 0.5 + jax.random.uniform(jax.random.PRNGKey(22), (16,))
    beta = _rand(jax.random.PRNGKey(23), (16,))

    def loss_fused(x, r, g, b):
        y, _, _ = bn_add_relu(x, r, g, b, 1e-5)
        return jnp.sum(jnp.sin(y))

    def loss_plain(x, r, g, b):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.var(x, (0, 1, 2))
        y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
        return jnp.sum(jnp.sin(nn.relu(y + r)))

    np.testing.assert_allclose(
        np.asarray(bn_add_relu(x, r, gamma, beta, 1e-5)[0]),
        np.asarray(nn.relu(
            (x - jnp.mean(x, (0, 1, 2))) * jax.lax.rsqrt(jnp.var(x, (0, 1, 2)) + 1e-5)
            * gamma + beta + r)),
        rtol=1e-4, atol=1e-5,
    )
    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    gp = jax.grad(loss_plain, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    for a, b in zip(gf, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_s2d_stem_exact_vs_7x7_conv():
    key = jax.random.PRNGKey(0)
    x = _rand(key, (2, 32, 32, 3))
    stem = SpaceToDepthStem(features=8, dtype=jnp.float32)
    v = stem.init(key, x)
    y_s2d = stem.apply(v, x)
    y_ref = jax.lax.conv_general_dilated(
        x, v["params"]["kernel"], (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    assert y_s2d.shape == y_ref.shape == (2, 16, 16, 8)
    np.testing.assert_allclose(np.asarray(y_s2d), np.asarray(y_ref), atol=1e-5)


def test_s2d_stem_grads_match_7x7_conv():
    key = jax.random.PRNGKey(7)
    x = _rand(key, (2, 16, 16, 3))
    stem = SpaceToDepthStem(features=4, dtype=jnp.float32)
    v = stem.init(key, x)
    k = v["params"]["kernel"]

    def loss_s2d(k, x):
        return jnp.sum(jnp.cos(stem.apply({"params": {"kernel": k}}, x)))

    def loss_ref(k, x):
        y = jax.lax.conv_general_dilated(
            x, k, (2, 2), ((3, 3), (3, 3)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(jnp.cos(y))

    gs = jax.grad(loss_s2d, argnums=(0, 1))(k, x)
    gr = jax.grad(loss_ref, argnums=(0, 1))(k, x)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_max_pool_forward_and_grads():
    # Continuous random input: tie-free with probability 1, so the routed
    # gradient must equal select-and-scatter's exactly.
    x = _rand(jax.random.PRNGKey(8), (2, 12, 12, 4))
    y_fast = max_pool_3x3_s2(x)
    y_ref = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref), atol=0)

    def loss_fast(x):
        return jnp.sum(jnp.sin(max_pool_3x3_s2(x)))

    def loss_ref(x):
        return jnp.sum(jnp.sin(
            nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))))

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_fast)(x)),
        np.asarray(jax.grad(loss_ref)(x)),
        rtol=1e-5, atol=1e-6,
    )


def test_max_pool_odd_extent_fallback():
    x = _rand(jax.random.PRNGKey(9), (1, 9, 9, 2))
    y_fast = max_pool_3x3_s2(x)
    y_ref = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref), atol=0)
    g = jax.grad(lambda x: jnp.sum(max_pool_3x3_s2(x) ** 2))(x)
    assert g.shape == x.shape and bool(jnp.any(g != 0))


def test_resnet50_fused_matches_plain_eval():
    """Full-depth eval parity.  Eval BN is a pure affine map from running
    stats (the fused modules fold it as x*(gamma*rstd)+bias vs flax's
    (x-mean)*rstd*gamma+beta — same math, different rounding), so the
    50-layer fused model must match the plain one to tight tolerance —
    train-mode full-depth parity is meaningless in f32 (a 1e-7 input
    perturbation alone moves the plain model's logits by ~3: batch-stat
    renormalization is chaotic at this depth), and is pinned instead by the
    shallow f32 test below plus the float64 exactness test."""
    fused = resnet50(num_classes=13, tpu_fused=True)
    plain = resnet50(num_classes=13, tpu_fused=False)
    x = _rand(jax.random.PRNGKey(10), (2, 32, 32, 3))
    vf = fused.init(jax.random.PRNGKey(0), x, train=False)
    vp = plain.init(jax.random.PRNGKey(0), x, train=False)
    # Identical parameter trees (checkpoint compatibility).
    assert jax.tree_util.tree_structure(vf) == jax.tree_util.tree_structure(vp)
    for a, b in zip(jax.tree.leaves(vf), jax.tree.leaves(vp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)

    yf = fused.apply(vf, x, train=False)
    yp = plain.apply(vp, x, train=False)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yp), rtol=1e-5, atol=1e-5)


def test_shallow_resnet_fused_matches_plain_train():
    """Train-mode forward parity on a depth where f32 roundoff can't
    amplify chaotically (see eval test docstring)."""
    from pytorch_distributed_training_tpu.models.resnet import ResNet, Bottleneck

    kw = dict(stage_sizes=(2, 2), block=Bottleneck, num_classes=13)
    fused = ResNet(tpu_fused=True, **kw)
    plain = ResNet(tpu_fused=False, **kw)
    x = _rand(jax.random.PRNGKey(10), (2, 32, 32, 3))
    v = fused.init(jax.random.PRNGKey(0), x, train=False)
    yf, sf = fused.apply(v, x, train=True, mutable=["batch_stats"])
    yp, sp = plain.apply(v, x, train=True, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yp), rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(sp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_shallow_resnet_zero_init_residual_parity():
    """zero_init_residual=True must route the tail through the *plain*
    composition (the fused tail's backward divides by gamma, which starts at
    exactly 0 here): tail gamma inits to zeros and fused==plain in both
    forward and grads."""
    from flax.traverse_util import flatten_dict
    from jax.flatten_util import ravel_pytree

    from pytorch_distributed_training_tpu.models.resnet import ResNet, Bottleneck

    kw = dict(stage_sizes=(1, 1), block=Bottleneck, num_classes=5,
              zero_init_residual=True)
    fused = ResNet(tpu_fused=True, **kw)
    plain = ResNet(tpu_fused=False, **kw)
    x = _rand(jax.random.PRNGKey(12), (2, 16, 16, 3))
    v = fused.init(jax.random.PRNGKey(0), x, train=False)
    tail_gammas = [
        p for k, p in flatten_dict(v["params"]).items()
        if k[-2].startswith("BatchNorm_2") and k[-1] == "scale"
    ]
    assert tail_gammas and all(float(jnp.abs(g).max()) == 0 for g in tail_gammas)

    def loss(model, params):
        y, _ = model.apply(
            {"params": params, "batch_stats": v["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return jnp.sum(y * y)

    lf, gf = compiled.value_and_grad(lambda p: loss(fused, p))(v["params"])
    lp, gp = compiled.value_and_grad(lambda p: loss(plain, p))(v["params"])
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(gf)[0]), np.asarray(ravel_pytree(gp)[0]),
        rtol=1e-4, atol=1e-5,
    )
    # dgamma on the zero-init tails must be nonzero (the plain path keeps
    # the gradient alive where the fused reconstruction could not).
    tail_dg = [
        g for k, g in flatten_dict(gf).items()
        if k[-2].startswith("BatchNorm_2") and k[-1] == "scale"
    ]
    assert any(float(jnp.abs(g).max()) > 0 for g in tail_dg)


def test_resnet_fused_grads_match_plain():
    from pytorch_distributed_training_tpu.models.resnet import ResNet, Bottleneck

    kw = dict(stage_sizes=(2, 2), block=Bottleneck, num_classes=7)
    fused = ResNet(tpu_fused=True, **kw)
    plain = ResNet(tpu_fused=False, **kw)
    x = _rand(jax.random.PRNGKey(11), (2, 32, 32, 3))
    labels = jnp.array([1, 4])
    v = fused.init(jax.random.PRNGKey(0), x, train=False)

    def loss(model, params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": v["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    gf = compiled.grad(lambda p: loss(fused, p))(v["params"])
    gp = compiled.grad(lambda p: loss(plain, p))(v["params"])
    from jax.flatten_util import ravel_pytree

    flat_f = np.asarray(ravel_pytree(gf)[0])
    flat_p = np.asarray(ravel_pytree(gp)[0])
    # Stacked BNs amplify f32 reduction-order roundoff chaotically, so
    # elementwise tolerances are meaningless even at this depth; the x64
    # test below pins exactness.  Here: relative L2 over the whole gradient.
    rel = np.linalg.norm(flat_f - flat_p) / np.linalg.norm(flat_p)
    assert rel < 2e-3, rel


def test_mini_resnet_fused_grads_exact_x64():
    """float64 parity on a 2-stage bottleneck net: the fused backward is
    *mathematically* identical, not just statistically close."""
    from pytorch_distributed_training_tpu.models.resnet import ResNet, Bottleneck

    jax.config.update("jax_enable_x64", True)
    try:
        kw = dict(stage_sizes=(1, 1), block=Bottleneck, num_classes=7,
                  dtype=jnp.float64)
        fused = ResNet(tpu_fused=True, **kw)
        plain = ResNet(tpu_fused=False, **kw)
        x = jax.random.normal(jax.random.PRNGKey(11), (2, 32, 32, 3), jnp.float64)
        labels = jnp.array([1, 4])
        v = fused.init(jax.random.PRNGKey(0), x, train=False)
        v = jax.tree.map(
            lambda t: t.astype(jnp.float64) if t.dtype == jnp.float32 else t, v
        )

        def loss(model, params):
            logits, _ = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

        from jax.flatten_util import ravel_pytree

        gf = np.asarray(ravel_pytree(compiled.grad(lambda p: loss(fused, p))(v["params"]))[0])
        gp = np.asarray(ravel_pytree(compiled.grad(lambda p: loss(plain, p))(v["params"]))[0])
        np.testing.assert_allclose(gf, gp, rtol=1e-6, atol=1e-9)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_fused_layer_norm_matches_flax():
    """FusedLayerNorm == nn.LayerNorm: identical param tree, exact f32
    forward+grads, and a bf16 backward at least as close to the f32 truth
    as flax's (the custom vjp stays f32 end-to-end)."""
    from pytorch_distributed_training_tpu.ops.fused_norm import FusedLayerNorm

    rng = np.random.default_rng(0)
    x64 = rng.standard_normal((4, 17, 64)) * 3 + 1
    p = {"params": {
        "scale": jnp.asarray(rng.standard_normal(64), jnp.float32) * 0.5 + 1.0,
        "bias": jnp.asarray(rng.standard_normal(64), jnp.float32) * 0.1,
    }}
    x = jnp.asarray(x64, jnp.float32)
    ref_mod, new_mod = nn.LayerNorm(dtype=jnp.float32), FusedLayerNorm(dtype=jnp.float32)
    assert jax.tree_util.tree_structure(
        ref_mod.init(jax.random.PRNGKey(0), x)
    ) == jax.tree_util.tree_structure(new_mod.init(jax.random.PRNGKey(0), x))

    def loss(mod):
        return lambda p, x: (mod.apply(p, x).astype(jnp.float32) ** 2).sum()

    lr, gr = jax.value_and_grad(loss(ref_mod), argnums=(0, 1))(p, x)
    ln, gn = jax.value_and_grad(loss(new_mod), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(float(lr), float(ln), rtol=1e-6)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(gr),
        jax.tree_util.tree_leaves_with_path(gn),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=str(path),
        )

    xb = jnp.asarray(x64, jnp.bfloat16)
    refb, newb = nn.LayerNorm(dtype=jnp.bfloat16), FusedLayerNorm(dtype=jnp.bfloat16)
    _, grb = jax.value_and_grad(loss(refb), argnums=(0, 1))(p, xb)
    _, gnb = jax.value_and_grad(loss(newb), argnums=(0, 1))(p, xb)
    for (path, t), (_, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(gr),
        jax.tree_util.tree_leaves_with_path(grb),
        jax.tree_util.tree_leaves_with_path(gnb),
    ):
        t = np.asarray(t, np.float32)
        da = np.abs(np.asarray(a, np.float32) - t).max()
        db = np.abs(np.asarray(b, np.float32) - t).max()
        assert db <= max(2.5 * da, 0.05), (str(path), da, db)


def test_fused_layer_norm_mixed_precision_and_param_dtypes():
    """The flax-matching corners: stats come from the ORIGINAL-precision
    input when dtype downcasts the output (f32 in / bf16 out), and the
    functional op's cotangents match each param's own dtype."""
    from pytorch_distributed_training_tpu.ops.fused_norm import (
        FusedLayerNorm, layer_norm,
    )

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 9, 32)) * 2 + 0.5, jnp.float32)
    p = {"params": {
        "scale": jnp.asarray(rng.standard_normal(32), jnp.float32) * 0.5 + 1.0,
        "bias": jnp.asarray(rng.standard_normal(32), jnp.float32) * 0.1,
    }}
    ref = nn.LayerNorm(dtype=jnp.bfloat16).apply(p, x)
    got = FusedLayerNorm(dtype=jnp.bfloat16).apply(p, x)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=1e-2, atol=1e-3,
    )

    # Functional surface with per-param dtypes: cotangent dtypes must
    # match the primals (a mismatched dbias dtype fails at trace time).
    scale = p["params"]["scale"]
    bias = p["params"]["bias"].astype(jnp.bfloat16)
    grads = jax.grad(
        lambda s, b: (layer_norm(x, s, b).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1),
    )(scale, bias)
    assert grads[0].dtype == jnp.float32
    assert grads[1].dtype == jnp.bfloat16

"""Model smoke + shape tests for the BASELINE families (SURVEY.md §2, §7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import (
    GPT2Config,
    create_model,
    gpt2_124m,
    resnet18,
    resnet50,
    vit_b16,
)
from pytorch_distributed_training_tpu.models.gpt2 import GPT2


def _param_count(params):
    return sum(np.prod(p.shape) for p in jax.tree.leaves(params))


def test_resnet18_forward_shape_cifar():
    model = resnet18(num_classes=10)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    # torchvision resnet18(num_classes=10) ≈ 11.18M params.
    n = _param_count(variables["params"])
    assert 10.5e6 < n < 12e6, n


def test_resnet50_param_count():
    model = resnet50(num_classes=1000)
    x = jnp.zeros((1, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    # torchvision resnet50 = 25.56M params.
    n = _param_count(variables["params"])
    assert 25e6 < n < 26e6, n


def test_resnet_batchnorm_updates():
    model = resnet18(num_classes=10, small_stem=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    out, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    assert out.shape == (4, 10)
    # Running stats must actually move.
    before = jax.tree.leaves(variables["batch_stats"])
    after = jax.tree.leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_vit_b16_forward_and_params():
    model = vit_b16(num_classes=1000)
    x = jnp.zeros((2, 224, 224, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 1000)
    # ViT-B/16 ≈ 86.6M params.
    n = _param_count(variables["params"])
    assert 85e6 < n < 88e6, n


def test_gpt2_forward_and_params():
    cfg = GPT2Config(vocab_size=50257, max_seq_len=1024)
    model = GPT2(cfg=cfg)
    tokens = jnp.zeros((2, 64), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens, train=False)
    out = model.apply(variables, tokens, train=False)
    assert out.shape == (2, 64, 50257)
    # GPT-2 small = 124M params (with tied embeddings).
    n = _param_count(variables["params"])
    assert 123e6 < n < 125e6, n


def test_gpt2_causality():
    """Changing a future token must not affect past logits."""
    cfg = GPT2Config(vocab_size=128, max_seq_len=32, num_layers=2, num_heads=2, hidden_dim=32)
    model = GPT2(cfg=cfg)
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(5)
    variables = model.init(jax.random.PRNGKey(0), t1, train=False)
    o1 = model.apply(variables, t1, train=False)
    o2 = model.apply(variables, t2, train=False)
    np.testing.assert_allclose(o1[0, :10], o2[0, :10], atol=1e-5)
    assert not np.allclose(o1[0, 10:], o2[0, 10:])


def test_registry():
    m = create_model("resnet18", num_classes=10)
    assert m.num_classes == 10
    with pytest.raises(ValueError):
        create_model("nope")


def _remat_parity(build, sample):
    """loss+grads of build(remat=True) must equal build(remat=False)."""
    results = {}
    for remat in (False, True):
        m = build(remat)
        v = m.init(jax.random.PRNGKey(1), sample, train=False)

        def loss(p):
            return jnp.mean(m.apply({"params": p}, sample, train=True) ** 2)

        results[remat] = jax.value_and_grad(loss)(v["params"])
    (l0, g0), (l1, g1) = results[False], results[True]
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        # atol absorbs sub-1e-6 reassociation noise: the recompute's fused
        # ops need not match the saved-residual path bit-for-bit on every
        # backend/compiler version.
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_remat_identical_loss_and_grads():
    """Block rematerialization (jax.checkpoint) must change memory, never
    math: loss and grads identical to the plain model for GPT-2 and ViT."""
    from pytorch_distributed_training_tpu.models import gpt2_124m, vit_b16

    shrink = dict(num_layers=2, hidden_dim=32, num_heads=2, vocab_size=64,
                  max_seq_len=16)
    tok = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    _remat_parity(
        lambda r: gpt2_124m(cfg_overrides={**shrink, "remat": r}), tok
    )

    vit_shrink = dict(depth=2, hidden_dim=32, num_heads=2, mlp_dim=64,
                      patch_size=16)
    img = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
    _remat_parity(
        lambda r: vit_b16(num_classes=5, cfg_overrides={**vit_shrink, "remat": r}),
        img,
    )


def test_stem_remat_identical_update():
    """Rematerializing the ResNet stem (conv+BN+ReLU+maxpool recomputed in
    the backward) must be a pure memory trade: identical loss, identical
    parameter update, identical param tree (checkpoint-compatible)."""
    import optax

    from pytorch_distributed_training_tpu.models import resnet18
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    imgs = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 64, 64, 3)), jnp.float32
    )
    batch = {"image": imgs, "label": jnp.asarray([1, 2], jnp.int32)}
    outs = {}
    for remat in (False, True):
        m = resnet18(num_classes=10, cfg_overrides={"stem_remat": remat})
        st = create_train_state(
            m, jax.random.PRNGKey(0), imgs, optax.sgd(1e-2),
            init_kwargs={"train": False},
        )
        st, met = make_train_step(kind="image_classifier")(st, batch)
        outs[remat] = (float(met["loss"]), st.params, st.batch_stats)
    assert outs[False][0] == outs[True][0]
    for a, b in zip(
        jax.tree.leaves(outs[False][1]), jax.tree.leaves(outs[True][1])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # Running BN stats advance identically under the remat too.
    for a, b in zip(
        jax.tree.leaves(outs[False][2]), jax.tree.leaves(outs[True][2])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# Published parameter counts the architectures must land on exactly:
# torchvision (ResNet-*, ViT-B/L at 1000 classes), timm (ViT-S/16), and
# the HF GPT-2 checkpoints (tied embeddings).  ``jax.eval_shape`` makes
# this shape-level — no FLOPs, so even gpt2_xl (1.56B) is cheap to check.
_PUBLISHED_PARAM_COUNTS = {
    "resnet18": 11_689_512,
    "resnet34": 21_797_672,
    "resnet50": 25_557_032,
    "resnet101": 44_549_160,
    "resnet152": 60_192_808,
    "vit_s16": 22_050_664,
    "vit_b16": 86_567_656,
    "vit_l16": 304_326_632,
    "gpt2": 124_439_808,
    "gpt2_medium": 354_823_168,
    "gpt2_large": 774_030_080,
    "gpt2_xl": 1_557_611_200,
}


@pytest.mark.parametrize("name", sorted(_PUBLISHED_PARAM_COUNTS))
def test_param_counts_match_published(name):
    from pytorch_distributed_training_tpu.models.registry import MODEL_REGISTRY

    model = create_model(name)
    sample = (
        jnp.zeros((1, 8), jnp.int32)
        if MODEL_REGISTRY[name].kind == "lm"
        else jnp.zeros((1, 224, 224, 3), jnp.float32)
    )
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample, train=False)
    )
    n = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes["params"])
    )
    assert n == _PUBLISHED_PARAM_COUNTS[name]


def test_bf16_compute_f32_logits():
    model = resnet18(num_classes=10, dtype=jnp.bfloat16, small_stem=True)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.dtype == jnp.float32  # head math promoted for stable loss


def test_vit_attn_layout_variants_parity():
    """The two attention layout contracts (auto / bhld2 —
    models/layers.SelfAttention.attn_layout) must share one param tree and
    produce matching outputs and gradients; bhld2 is the measured TPU
    default (r5 experiments, another machine).  Any other value raises:
    "bhld", the recorded negative, is gone."""
    from pytorch_distributed_training_tpu.models.vit import vit_b16

    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    common = dict(patch_size=16, hidden_dim=64, depth=2, num_heads=4,
                  mlp_dim=128)
    models = {
        layout: vit_b16(
            num_classes=10, cfg_overrides={**common, "attn_layout": layout}
        )
        for layout in ("auto", "bhld2")
    }
    inits = {
        layout: m.init(jax.random.PRNGKey(0), x, train=False)
        for layout, m in models.items()
    }
    ref = inits["auto"]["params"]
    outs = {}
    for layout, m in models.items():
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            ref, inits[layout]["params"],
        )
        outs[layout] = m.apply({"params": ref}, x, train=False)
    np.testing.assert_allclose(
        np.asarray(outs["auto"]), np.asarray(outs["bhld2"]), atol=2e-5
    )
    with pytest.raises(ValueError, match="unknown attn_layout 'bhld'"):
        vit_b16(
            num_classes=10, cfg_overrides={**common, "attn_layout": "bhld"}
        ).init(jax.random.PRNGKey(0), x, train=False)

    def loss(m, p):
        return jnp.sum(m.apply({"params": p}, x, train=False) ** 2)

    g_auto = jax.grad(lambda p: loss(models["auto"], p))(ref)
    g_bhld2 = jax.grad(lambda p: loss(models["bhld2"], p))(ref)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3
        ),
        g_auto, g_bhld2,
    )

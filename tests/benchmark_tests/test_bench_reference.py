"""The two plain references against the system at a toy size, float32 on
the CPU: the same params tree must give the same logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import load_json, model_overrides
from benchmark.reference import gpt2 as ref_gpt2
from benchmark.reference import vit as ref_vit
from pytorch_distributed_training_tpu import models


def test_gpt2_reference_matches_the_system():
    cfg = load_json("rehearsal", "tiny-gpt2.json")
    net = models.create_model("gpt2", dtype=jnp.float32, cfg_overrides=model_overrides(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, cfg["vocab_size"])
    params = net.init(jax.random.PRNGKey(1), tokens, train=False)["params"]
    want = net.apply({"params": params}, tokens, train=False)
    with jax.default_matmul_precision("highest"):
        got = ref_gpt2.logits(params, tokens, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    loss, norm = ref_gpt2.loss_and_grad_norm(params, {"tokens": tokens}, cfg)
    assert float(loss) == pytest.approx(np.log(cfg["vocab_size"]), rel=0.05) and float(norm) > 0
    deficit = ref_gpt2.greedy_deficit(params, tokens[:1], cfg)
    assert deficit.shape == (31,) and float(deficit.min()) >= 0.0
    greedy = jnp.argmax(want[0, :-1], axis=-1)
    assert float(ref_gpt2.greedy_deficit(
        params, jnp.concatenate([tokens[0, :1], greedy])[None], cfg)[0]) == pytest.approx(0.0, abs=1e-5)


def test_vit_reference_matches_the_system():
    cfg = load_json("rehearsal", "tiny-vit.json")
    net = models.create_model("vit_b16", dtype=jnp.float32, num_classes=cfg["num_labels"],
                              cfg_overrides=model_overrides(cfg))
    image = jax.random.uniform(jax.random.PRNGKey(0), (2, 32, 32, 3))
    params = net.init(jax.random.PRNGKey(1), image, train=False)["params"]
    want = net.apply({"params": params}, image, train=False)
    with jax.default_matmul_precision("highest"):
        got = ref_vit.logits(params, image, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    raw = (np.asarray(image) * 255).astype(np.uint8)
    norm = (np.asarray([0.5, 0.5, 0.5]), np.asarray([0.25, 0.25, 0.25]))
    prepared = ref_vit.prepare(jnp.asarray(raw), norm)
    np.testing.assert_allclose(prepared, (raw / 255.0 - 0.5) / 0.25, rtol=1e-5, atol=1e-6)

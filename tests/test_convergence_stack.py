"""Convergence-evidence stack: the ShapeImages learnable dataset, the
token-cache epoch iterator, and the CLI paths the CONVERGENCE (deleted: not
measured on the current machine) runs use
(token-file + sibling val.bin, --device-cache for LM).

The reference's entire purpose is the training epoch
(/root/reference/src/main.py:68-84); these pieces exist so the framework can
demonstrate *training to quality* — not just fast steps — in a zero-egress
sandbox where the reference's CIFAR-10 download (src/main.py:47) is
impossible.
"""

import json
import os

import jax
import numpy as np

from pytorch_distributed_training_tpu.data import (
    DeviceCachedTokens, ShapeImages,
)


def test_shapes_deterministic_and_disjoint():
    a, b = ShapeImages(n=32, seed=0), ShapeImages(n=32, seed=0)
    s0, s1 = a[7], b[7]
    np.testing.assert_array_equal(s0["image"], s1["image"])
    assert s0["label"] == s1["label"]
    # Val split is a different RNG stream, not a reindexing of train.
    val = ShapeImages(n=32, train=False, seed=0)
    assert not np.allclose(val[7]["image"], s0["image"])
    # Different seed -> different data (the CLI salts eval by split, not
    # seed, but seeds must still produce fresh draws).
    other = ShapeImages(n=32, seed=1)
    assert not np.allclose(other[7]["image"], s0["image"])


def test_shapes_record_properties():
    ds = ShapeImages(n=16, seed=3)
    imgs, labels = ds.images, ds.labels
    assert imgs.shape == (16, 32, 32, 3) and imgs.dtype == np.uint8
    assert labels.shape == (16,) and labels.dtype == np.int32
    # uint8 records quantize __getitem__'s floats.
    f = ds[5]["image"]
    np.testing.assert_allclose(imgs[5] / 255.0, f, atol=1 / 255.0 + 1e-7)
    assert set(np.unique(labels)).issubset(set(range(10)))


def test_shapes_classes_are_visually_distinct():
    """Mean intra-class pixel correlation must beat inter-class — the
    minimal 'labels carry signal' check that would catch a label/render
    mismatch without training a model."""
    per_class = 12
    ds = ShapeImages(n=4000, seed=0)
    buckets: dict[int, list[np.ndarray]] = {c: [] for c in range(10)}
    i = 0
    while any(len(v) < per_class for v in buckets.values()):
        s = ds[i]
        c = int(s["label"])
        if len(buckets[c]) < per_class:
            # Gray + normalized: kills the random-color nuisance.
            g = s["image"].mean(-1)
            g = (g - g.mean()) / (g.std() + 1e-6)
            buckets[c].append(g.ravel())
        i += 1
    means = {c: np.mean(v, axis=0) for c, v in buckets.items()}
    intra, inter = [], []
    for c, vecs in buckets.items():
        for v in vecs:
            intra.append(np.dot(v, means[c]) / len(v))
        for c2, m2 in means.items():
            if c2 != c:
                inter.append(np.dot(means[c], m2) / len(m2))
    assert np.mean(intra) > np.mean(inter) + 0.05, (
        np.mean(intra), np.mean(inter)
    )


def test_token_cache_batches_iterator():
    rng = np.random.default_rng(0)
    stream = rng.integers(0, 97, 4096, dtype=np.uint16)
    cache = DeviceCachedTokens(stream, seed=1, default_seq_len=16)
    bs = list(cache.batches(epoch=0, batch_size=4))
    assert len(bs) == 4096 // (4 * 16)
    for b in bs:
        assert b["tokens"].shape == (4, 16)
        assert b["tokens"].dtype == jax.numpy.int32
        assert int(b["tokens"].max()) < 97
    # Same epoch -> identical draws; next epoch -> fresh draws.
    again = next(iter(cache.batches(epoch=0, batch_size=4)))
    np.testing.assert_array_equal(
        np.asarray(bs[0]["tokens"]), np.asarray(again["tokens"])
    )
    nxt = next(iter(cache.batches(epoch=1, batch_size=4)))
    assert not np.array_equal(
        np.asarray(bs[0]["tokens"]), np.asarray(nxt["tokens"])
    )
    # steps override wins.
    assert len(list(cache.batches(0, 4, steps=3))) == 3


def _write_bin(path, tokens):
    np.asarray(tokens, np.uint16).tofile(path)


def test_cli_token_file_sibling_valbin_and_lm_device_cache(tmp_path):
    """token-file: with a sibling val.bin evals on it; --device-cache runs
    the HBM token cache through the Trainer; metrics JSONL records both."""
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    rng = np.random.default_rng(0)
    _write_bin(tmp_path / "train.bin", rng.integers(0, 251, 40_000))
    _write_bin(tmp_path / "val.bin", rng.integers(0, 251, 4_000))
    metrics = tmp_path / "m.jsonl"
    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2",
            "--dataset", f"token-file:{tmp_path / 'train.bin'}",
            "--model-overrides",
            "num_layers=2,hidden_dim=64,num_heads=4,vocab_size=256,max_seq_len=32",
            "--seq-len", "32", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "3", "--epochs", "2", "--eval",
            "--device-cache", "--learning-rate", "1e-3",
            "--metrics-jsonl", str(metrics),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output
    rows = [json.loads(l) for l in metrics.read_text().splitlines()]
    train_rows = [r for r in rows if "loss" in r and "eval_loss" not in r]
    eval_rows = [r for r in rows if "eval_loss" in r]
    assert len(train_rows) == 2 and len(eval_rows) == 2
    # 3 steps x batch 8 per epoch, and a finite val loss from val.bin.
    assert train_rows[0]["examples"] == 24
    assert np.isfinite(eval_rows[0]["eval_loss"])


def _shapes_train(mode, n_steps=18, seed=0, optimizer="adam"):
    """Train a tiny ResNet on ShapeImages under gradient-sync ``mode`` on
    the simulated 2-slice mesh; returns the loss trajectory.  Delegates to
    the canonical harness in tools/grad_sync_diag.py — the same body the
    published GRAD_SYNC_BENCH.json convergence entry runs."""
    from pytorch_distributed_training_tpu.comm import (
        MeshConfig, make_hybrid_mesh,
    )
    from tools.grad_sync_diag import shapes_convergence

    mesh = make_hybrid_mesh(
        MeshConfig(data=-1), devices=jax.devices()[:8], n_slices=2
    )
    return shapes_convergence(
        mesh, mode, n_steps, seed=seed, optimizer=optimizer
    )


def _assert_band(flat, compressed):
    drop = flat[0] - flat[-1]
    assert drop > 0.1, f"fp32 baseline failed to learn: {flat}"
    # Same band: the compressed trajectory's final loss within 15% of the
    # fp32 loss DROP (plus an absolute floor for the near-converged
    # regime) — the GRAD_SYNC_BENCH.json band definition.
    assert abs(compressed[-1] - flat[-1]) <= 0.15 * drop + 0.02, (
        flat, compressed,
    )


def test_int8_error_feedback_converges_in_fp32_band():
    """int8 + error feedback (--grad-sync hier-int8) must train the tiny
    ResNet into the same loss band as the flat fp32 sync: the EF residuals
    re-feed the quantization error, so the compressed trajectory tracks the
    exact one instead of biasing away (GRAD_SYNC_BENCH.json records the
    same check's measured values)."""
    _assert_band(_shapes_train("flat"), _shapes_train("hier-int8"))


def test_int4_error_feedback_converges_in_fp32_band():
    """Same contract one rung down the ladder: 4-bit payloads leave 16x
    coarser quantization error, and the EF residuals still dither it out
    inside the fp32 band (8x fewer DCN bytes than flat)."""
    _assert_band(_shapes_train("flat"), _shapes_train("hier-int4"))


def test_topk_error_feedback_converges_in_fp32_band():
    """Top-k(10%) + EF under sgd+momentum — the EF-matched optimizer
    class (see tools/grad_sync_diag.shapes_convergence: under Adam the
    sparse EF stream fights the per-coordinate normalization; under
    sgd-m the trajectory re-joins the band once the EF ramp warms up).
    Longer horizon than the dense modes for exactly that ramp."""
    flat = _shapes_train("flat", n_steps=60, optimizer="sgd-m")
    topk = _shapes_train("hier-topk", n_steps=60, optimizer="sgd-m")
    _assert_band(flat, topk)


def test_cli_shapes_dataset_trains(tmp_path):
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    metrics = tmp_path / "m.jsonl"
    result = CliRunner().invoke(
        cli_main,
        [
            "--use-cpu", "--model", "resnet18", "--dataset", "shapes",
            "--model-overrides", "small_stem=true",
            "--batch-size", "16", "--num-workers", "0",
            "--steps-per-epoch", "2", "--eval", "--eval-steps", "1",
            "--learning-rate", "1e-3", "--optimizer", "adamw",
            "--metrics-jsonl", str(metrics),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    rows = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert any("eval_accuracy" in r for r in rows)

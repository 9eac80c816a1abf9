"""Tests for cli/, checkpoint/, utils/: the reference's config-1 smoke run
(ResNet-18 / CIFAR-10-shaped data, world_size 1, CPU — BASELINE configs[0],
per SURVEY.md §4) plus save/resume round-trips."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from click.testing import CliRunner

from pytorch_distributed_training_tpu.cli.main import main as cli_main
from pytorch_distributed_training_tpu.models import resnet18
from pytorch_distributed_training_tpu.train import create_train_state, make_train_step
from pytorch_distributed_training_tpu.utils import MetricsLogger, seed_everything


def test_cli_smoke_config0(tmp_path):
    """BASELINE configs[0]: ResNet-18, world 1, CPU, one epoch — loss + prints."""
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--synthetic-data", "--batch-size", "8",
            "--num-workers", "0", "--learning-rate", "0.001",
            "--steps-per-epoch", "3", "--image-size", "32",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    out = result.output
    assert "training started" in out
    assert "training finished" in out
    assert "elapsed time" in out
    assert "loss=" in out
    assert "mesh:" in out


def test_cli_device_cache(tmp_path):
    """--device-cache trains from the HBM-resident dataset (on-device
    shuffle/crop/flip) and rejects LM datasets."""
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--synthetic-data", "--device-cache",
            "--batch-size", "8", "--num-workers", "0",
            "--learning-rate", "0.001", "--steps-per-epoch", "2",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output

    bad = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--device-cache", "--batch-size", "8", "--seq-len", "32",
            "--model-overrides", "num_layers=1,hidden_dim=32,num_heads=2,vocab_size=64",
        ],
    )
    # LM runs now get the HBM token cache — but only for datasets exposing
    # a token stream (token-file); synthetic-tokens has none.
    assert bad.exit_code != 0
    assert "token-stream dataset" in bad.output


def test_cli_gpt2_accum(tmp_path):
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--batch-size", "8", "--num-workers", "0", "--seq-len", "32",
            "--accum-steps", "2", "--learning-rate", "0.0003",
            "--steps-per-epoch", "1",
            "--model-overrides", "num_layers=2,hidden_dim=64,num_heads=2,vocab_size=512",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output


def test_checkpoint_roundtrip(tmp_path):
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    model = resnet18(num_classes=10, small_stem=True)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)),
        optax.adam(1e-3), init_kwargs={"train": False},
    )
    step = make_train_step(kind="image_classifier")
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.standard_normal((4, 8, 8, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, (4,)), jnp.int32),
    }
    state, _ = step(state, batch)
    state, _ = step(state, batch)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(state)
    assert mgr.all_steps() == [2]

    template = create_train_state(
        model, jax.random.PRNGKey(42), jnp.zeros((1, 8, 8, 3)),
        optax.adam(1e-3), init_kwargs={"train": False},
    )
    restored = mgr.restore_latest(template)
    assert int(restored.step) == 2
    np.testing.assert_array_equal(
        np.asarray(restored.params["head"]["kernel"]),
        np.asarray(state.params["head"]["kernel"]),
    )
    # Optimizer slots restored too (resume continues Adam moments).
    np.testing.assert_array_equal(
        np.asarray(restored.opt_state[0].mu["head"]["kernel"]),
        np.asarray(state.opt_state[0].mu["head"]["kernel"]),
    )


def test_checkpoint_async_save_overlaps_and_commits(tmp_path):
    """Async save returns before commit; wait_until_finished commits it."""
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    model = resnet18(num_classes=10, small_stem=True)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)),
        optax.adam(1e-3), init_kwargs={"train": False},
    )
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(state, step=1)
    # Training continues here while serialization runs in the background...
    mgr.wait_until_finished()
    assert mgr.all_steps() == [1]
    restored = mgr.restore_latest(state)
    assert int(restored.step) == int(state.step)


def test_checkpoint_crash_mid_save_restores_previous(tmp_path):
    """An uncommitted (crashed) save must not shadow the last good step.

    Orbax writes each step into a tmp dir and renames on commit; a process
    dying mid-save leaves exactly that tmp state.  Simulate it and assert
    restore_latest still returns the committed step.
    """
    import pathlib

    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    model = resnet18(num_classes=10, small_stem=True)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)),
        optax.adam(1e-3), init_kwargs={"train": False},
    )
    ckdir = tmp_path / "ckpt"
    mgr = CheckpointManager(str(ckdir))
    mgr.save(state, step=1, wait=True)

    # A crash mid-save of step 2: the step dir exists but was never
    # committed (orbax marks in-progress dirs with a tmp suffix / missing
    # commit marker).  Fabricate the wreckage a kill -9 leaves behind.
    committed = {p.name for p in pathlib.Path(ckdir).iterdir()}
    assert "1" in committed
    wreck = pathlib.Path(ckdir) / "2.orbax-checkpoint-tmp-1234"
    wreck.mkdir()
    (wreck / "partial_array").write_bytes(b"\x00" * 64)

    fresh = CheckpointManager(str(ckdir))
    assert fresh.all_steps() == [1]
    restored = fresh.restore_latest(state)
    assert restored is not None and int(restored.step) == int(state.step)


def test_metrics_logger_jsonl(tmp_path, capsys):
    path = tmp_path / "log" / "metrics.jsonl"
    logger = MetricsLogger(str(path), only_rank0=False)
    logger.log({"epoch": 0, "loss": 1.23456})
    out = capsys.readouterr().out
    assert "loss=1.235" in out
    import json

    rec = json.loads(path.read_text().strip())
    assert rec["epoch"] == 0


def test_seed_everything_returns_key():
    key = seed_everything(123)
    assert key.shape == (2,) or key.dtype == jax.dtypes.prng_key(123).dtype


def test_cli_eval_and_schedule(tmp_path):
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--synthetic-data", "--batch-size", "8",
            "--num-workers", "0", "--learning-rate", "0.001",
            "--steps-per-epoch", "2", "--eval", "--eval-steps", "2",
            "--lr-schedule", "warmup-cosine", "--warmup-steps", "2",
            "--metrics-jsonl", str(tmp_path / "m.jsonl"),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "eval_loss=" in result.output
    assert "eval_accuracy=" in result.output
    lines = (tmp_path / "m.jsonl").read_text().strip().splitlines()
    assert len(lines) >= 2  # train summary + eval record


def test_cli_eval_small_holdout(tmp_path):
    """Eval split smaller than the batch must still evaluate (review fix)."""
    import numpy as np

    tokens = np.random.default_rng(0).integers(0, 64, 5000).astype(np.uint16)
    path = tmp_path / "c.bin"
    tokens.tofile(path)
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", f"token-file:{path}",
            "--seq-len", "32", "--batch-size", "64", "--num-workers", "0",
            "--steps-per-epoch", "1", "--eval",
            "--model-overrides",
            "num_layers=1,hidden_dim=32,num_heads=2,vocab_size=64",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    # 5000//32 = 156 windows, holdout = 7 < batch 64 → shrink or warn, never
    # silently skip.
    assert ("eval_loss=" in result.output) or ("skipping eval" in result.output)


def test_coupled_adam_matches_torch():
    """The CLI's default optimizer must reproduce torch.optim.Adam's coupled
    L2 weight-decay semantics exactly (the reference's optimizer,
    src/main.py:63) — stepwise trajectory parity against real torch."""
    torch = __import__("pytest").importorskip("torch")
    import optax

    lr, wd = 0.1, 1e-3
    rng = np.random.default_rng(0)
    w0 = rng.standard_normal((5, 3)).astype(np.float32)

    # torch side
    tw = torch.nn.Parameter(torch.tensor(w0.copy()))
    topt = torch.optim.Adam([tw], lr=lr, weight_decay=wd)

    # our side (cli/main.py "adam" branch)
    tx = optax.chain(
        optax.add_decayed_weights(wd),
        optax.scale_by_adam(),
        optax.scale_by_learning_rate(lr),
    )
    params = {"w": jnp.asarray(w0)}
    opt_state = tx.init(params)

    for step in range(5):
        g = rng.standard_normal((5, 3)).astype(np.float32)
        topt.zero_grad()
        tw.grad = torch.tensor(g.copy())
        topt.step()
        updates, opt_state = tx.update({"w": jnp.asarray(g)}, opt_state, params)
        params = optax.apply_updates(params, updates)
        np.testing.assert_allclose(
            # f32 roundoff only: optax and torch order the bias-correction
            # arithmetic differently.
            np.asarray(params["w"]), tw.detach().numpy(), rtol=1e-4, atol=5e-6,
            err_msg=f"divergence at step {step}",
        )


def test_overlap_analyzer_counts_pairs():
    """The HLO overlap analyzer (tools/check_overlap.py) must detect compute
    scheduled between all-reduce-start/done pairs (VERDICT r1 item 7)."""
    import sys as _sys
    import os as _os

    _sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tools"))
    from check_overlap import analyze_hlo

    hlo = """
HloModule jit_train_step

%main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ar0 = f32[8] all-reduce-start(%p0), replica_groups={}
  %c1 = f32[8] fusion(%p0), kind=kLoop
  %conv = f32[8] convolution(%p0, %p0)
  %ar0d = f32[8] all-reduce-done(%ar0)
  %ar1 = f32[8] all-reduce-start(%c1), replica_groups={}
  %ar1d = f32[8] all-reduce-done(%ar1)
  %sync = f32[8] all-reduce(%conv)
  ROOT %out = f32[8] fusion(%ar1d), kind=kLoop
}
"""
    stats = analyze_hlo(hlo)
    assert stats["pairs"] == 2
    assert stats["overlapped"] == 1  # compute between ar0 start/done only
    assert stats["sync_allreduces"] == 1

    # FIFO completion order: each done must match ITS start by operand.
    fifo = """
%main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ar0 = f32[8] all-reduce-start(%p0)
  %c1 = f32[8] fusion(%p0), kind=kLoop
  %ar1 = f32[8] all-reduce-start(%c1)
  %ar0d = f32[8] all-reduce-done(%ar0)
  %c2 = f32[8] convolution(%p0, %p0)
  %ar1d = f32[8] all-reduce-done(%ar1)
  ROOT %out = f32[8] fusion(%ar1d), kind=kLoop
}
"""
    stats = analyze_hlo(fifo)
    assert stats["pairs"] == 2
    assert stats["overlapped"] == 2  # both pairs bracket compute

    # XLA:TPU scheduled-HLO form: synchronous tuple all-reduces (combiner
    # buckets).  Gradient buckets (rank>=2 operands) must be classified and
    # their interleaving with compute measured; BN-stat (1-D) all-reduces
    # must not count as gradient buckets.
    tpu_sync = """
HloModule jit_train_step

ENTRY %main_spmd (p0: bf16[3,3,64,64]) -> bf16[3,3,64,64] {
  %p0 = bf16[3,3,64,64] parameter(0)
  %f0 = bf16[3,3,64,64] fusion(%p0), kind=kOutput
  %stats = (f32[64]{0}, f32[64]{0}) all-reduce(%f0, %f0), channel_id=1
  %f1 = bf16[3,3,64,64] fusion(%f0), kind=kOutput
  %g0 = (bf16[3,3,64,64]{3,2,1,0}, bf16[1,1,64,256]{3,2,1,0}) all-reduce(%f1, %f1), channel_id=2
  %f2 = bf16[3,3,64,64] custom-call(%f1), custom_call_target="conv"
  %f3 = bf16[3,3,64,64] fusion(%f2), kind=kLoop
  %g1 = (bf16[3,3,64,64]{3,2,1,0}) all-reduce(%f3), channel_id=3
  ROOT %out = bf16[3,3,64,64] fusion(%f3), kind=kLoop
}
"""
    stats = analyze_hlo(tpu_sync)
    assert stats["sync_allreduces"] == 3
    assert stats["grad_buckets"] == 2  # the 1-D stats all-reduce excluded
    # g0 has compute between it and the last bucket; the last bucket's own
    # trailing (optimizer/ROOT) compute must not count as interleaving.
    assert stats["grad_buckets_interleaved"] == 1
    assert stats["total_compute_ops"] == 5
    # g0 issued after 2 of 5 compute ops -> 60% of compute remains; the
    # last bucket's tail (ROOT fusion) is 20%.
    assert stats["compute_fraction_after_first_bucket"] == 0.6
    assert stats["compute_fraction_after_last_bucket"] == 0.2
    # Sync lowering: async-pair fields are OMITTED, never published as
    # null (VERDICT r4 weak #6), and the lowering form is labeled.
    assert stats["collective_lowering"] == "sync"
    assert "pairs" not in stats and "overlap_ratio" not in stats


def test_scaling_collective_bytes_parser():
    """tools/scaling_analysis.py traffic accounting: sync and async
    all-reduce forms both counted; zero collectives is an error, not 100%
    efficiency."""
    import sys as _sys
    import os as _os

    _sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tools"))
    from scaling_analysis import collective_bytes

    hlo = """
ENTRY %main_spmd (p0: bf16[3,3,64,64]) -> bf16[3,3,64,64] {
  %p0 = bf16[3,3,64,64] parameter(0)
  %stats = (f32[64]{0}, f32[64]{0}) all-reduce(%p0, %p0), channel_id=1
  %g0 = (bf16[3,3,64,64]{3,2,1,0}) all-reduce(%p0), channel_id=2
  %g1 = (bf16[1,1,64,256]{3,2,1,0}, bf16[1,1,64,256]{3,2,1,0}) all-reduce-start(%p0), channel_id=3
  %g1d = bf16[1,1,64,256]{3,2,1,0} all-reduce-done(%g1)
}
"""
    t = collective_bytes(hlo)
    assert t["allreduce_count"] == 3  # done doesn't double-count its start
    assert t["stat_bytes"] == 2 * 64 * 4
    # The start op's (input, output) tuple counts once, not twice.
    assert t["grad_bytes"] == (3 * 3 * 64 * 64 + 1 * 1 * 64 * 256) * 2

    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="no all-reduce"):
        collective_bytes("ENTRY %m (p: f32[2]) -> f32[2] {\n}\n")


def test_scaling_hierarchical_op_census():
    """The multi-slice row's op census counts each collective form once
    (including -start variants) in the entry computation only."""
    import sys as _sys
    import os as _os

    _sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tools"))
    from scaling_analysis import hierarchical_op_census

    hlo = """
%helper (x: f32[4]) -> f32[4] {
  %x = f32[4] parameter(0)
  %r = f32[4] all-reduce(%x), channel_id=9
}
ENTRY %main_spmd (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8] parameter(0)
  %a = bf16[8,8] all-reduce(%p0), channel_id=1
  %b = (bf16[8,8]) all-reduce-start(%p0), channel_id=2
  %rs = bf16[4,8] reduce-scatter(%p0), channel_id=3
  %ag = bf16[16,8] all-gather(%p0), channel_id=4
  %s = bf16[8,8] send(%p0), channel_id=5
  %r = bf16[8,8] recv(%p0), channel_id=6
  %cp = bf16[8,8] collective-permute(%p0), channel_id=7
}
"""
    c = hierarchical_op_census(hlo)
    assert c["all_reduce_count"] == 2  # plain + -start; helper excluded
    assert c["reduce_scatter_count"] == 1
    assert c["all_gather_count"] == 1
    assert c["send_count"] == 1 and c["recv_count"] == 1
    assert c["collective_permute_count"] == 1


def test_scaling_multislice_row_math():
    """The DCN row's hierarchical cost model: ICI term over the 8-chip
    ring, DCN term over the per-host NIC, efficiency from both."""
    import sys as _sys
    import os as _os
    from unittest import mock

    _sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tools"))
    import scaling_analysis as sa

    s = 51_423_192
    with mock.patch.object(sa, "compile_for", return_value="ENTRY %m (p: f32[1]) -> f32[1] {\n  %p = f32[1] parameter(0)\n  %a = f32[1] all-reduce(%p)\n}"):
        row = sa.multislice_row(49.0, s, num_slices=2, slice_topology="v5e:2x4")
    t_ici = 2 * s * (7 / 8) / (sa.ICI_RING_BW_GBPS * 1e9) * 1e3
    t_dcn = 2 * s * (1 / 2) / (sa.DCN_HOST_BW_GBPS * 1e9) * 1e3
    assert row["chips"] == 16
    assert abs(row["modeled"]["t_comm_ms_ici_intra_slice"] - round(t_ici, 3)) < 1e-9
    assert abs(row["modeled"]["t_comm_ms_dcn_inter_slice"] - round(t_dcn, 3)) < 1e-9
    want_eff = 49.0 / (49.0 + t_ici + t_dcn)
    assert abs(row["modeled"]["scaling_efficiency"] - round(want_eff, 4)) < 1e-9
    # chips_per_slice derives from the topology string.
    with mock.patch.object(sa, "compile_for", return_value="ENTRY %m (p: f32[1]) -> f32[1] {\n  %p = f32[1] parameter(0)\n  %a = f32[1] all-reduce(%p)\n}"):
        row2 = sa.multislice_row(49.0, s, num_slices=2, slice_topology="v5e:4x4")
    assert row2["chips"] == 32


def test_sgd_matches_torch_semantics():
    """The CLI's sgd chain (coupled L2 + momentum) == torch.optim.SGD over
    several steps on the same gradients."""
    import optax
    import torch

    lr, wd, mom = 0.1, 0.01, 0.9
    tx = optax.chain(
        optax.add_decayed_weights(wd), optax.sgd(lr, momentum=mom)
    )
    p = jnp.asarray([1.0, -2.0, 3.0])
    opt_state = tx.init(p)
    tp = torch.tensor([1.0, -2.0, 3.0], requires_grad=True)
    topt = torch.optim.SGD([tp], lr=lr, momentum=mom, weight_decay=wd)
    rng = np.random.default_rng(0)
    for _ in range(4):
        g = rng.standard_normal(3).astype(np.float32)
        updates, opt_state = tx.update(jnp.asarray(g), opt_state, p)
        p = optax.apply_updates(p, updates)
        tp.grad = torch.tensor(g)
        topt.step()
    np.testing.assert_allclose(
        np.asarray(p), tp.detach().numpy(), rtol=1e-6, atol=1e-7
    )


def test_cli_sgd_label_smoothing_smoke():
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--synthetic-data", "--batch-size", "8",
            "--num-workers", "0", "--optimizer", "sgd", "--momentum", "0.9",
            "--learning-rate", "0.01", "--label-smoothing", "0.1",
            "--steps-per-epoch", "2",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "training finished" in result.output


def test_grad_clip_bounds_update():
    """--grad-clip's optax chain (clip -> coupled-L2 -> adam) must bound the
    effective gradient: a huge gradient and its clipped version produce the
    same parameter step."""
    import optax

    lr, wd, clip = 0.1, 1e-3, 1.0
    tx = optax.chain(
        optax.clip_by_global_norm(clip),
        optax.add_decayed_weights(wd),
        optax.scale_by_adam(),
        optax.scale_by_learning_rate(lr),
    )
    params = {"w": jnp.ones((4,))}
    huge = {"w": jnp.full((4,), 1e6)}
    norm = float(jnp.sqrt(jnp.sum(huge["w"] ** 2)))
    pre_clipped = {"w": huge["w"] * (clip / norm)}

    u1, _ = tx.update(huge, tx.init(params), params)
    u2, _ = tx.update(pre_clipped, tx.init(params), params)
    np.testing.assert_allclose(
        np.asarray(u1["w"]), np.asarray(u2["w"]), rtol=1e-6
    )


def test_checkpoint_restore_across_topologies(tmp_path, devices8):
    """Elastic/preemption restore (VERDICT r4 #6): save under an fsdp=2
    mesh, restore into (a) a single-device template and (b) a tp=2-mesh
    template.  Gathered params and optimizer slots must be bitwise equal
    and training must continue from the restored state in the new
    topology — the checkpoint is topology-free, the template's shardings
    are the contract."""
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models import create_model
    from pytorch_distributed_training_tpu.parallel.sharding import (
        shard_batch, tp_rules_for,
    )

    cfg = dict(num_layers=2, hidden_dim=32, num_heads=2, vocab_size=64,
               max_seq_len=16)
    model = create_model("gpt2", cfg_overrides=cfg)
    tokens = jnp.zeros((8, 16), jnp.int32)
    batch = {
        "tokens": np.random.default_rng(0).integers(0, 64, (8, 16)).astype(np.int32)
    }
    step = make_train_step(kind="lm")

    # --- save under fsdp=2 ---
    save_mesh = make_mesh(MeshConfig(data=4, fsdp=2))
    state = create_train_state(
        model, jax.random.PRNGKey(0), tokens, optax.adam(1e-3),
        mesh=save_mesh, rules=tp_rules_for("gpt2"),
        init_kwargs={"train": False},
    )
    with save_mesh:
        state, _ = step(state, shard_batch(batch, save_mesh))
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(state, wait=True)
    saved_params = jax.tree.map(np.asarray, state.params)
    saved_mu = jax.tree.map(np.asarray, state.opt_state[0].mu)

    def check(restored):
        assert int(restored.step) == 1
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
            restored.params, saved_params,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
            restored.opt_state[0].mu, saved_mu,
        )

    # --- (a) restore into a single-device template ---
    single = create_train_state(
        model, jax.random.PRNGKey(1), tokens, optax.adam(1e-3),
        init_kwargs={"train": False},
    )
    restored = mgr.restore_latest(single)
    check(restored)
    restored, m = step(restored, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(restored.step) == 2

    # --- (b) restore into a tp=2 template ---
    tp_mesh = make_mesh(MeshConfig(data=4, tensor=2))
    tp_template = create_train_state(
        model, jax.random.PRNGKey(2), tokens, optax.adam(1e-3),
        mesh=tp_mesh, rules=tp_rules_for("gpt2"),
        init_kwargs={"train": False},
    )
    restored_tp = mgr.restore_latest(tp_template)
    check(restored_tp)
    # Restored leaves carry the TP template's shardings, not the saver's.
    qkv = restored_tp.params["block_0"]["attn"]["qkv"]["kernel"]
    assert qkv.sharding == tp_template.params["block_0"]["attn"]["qkv"]["kernel"].sharding
    with tp_mesh:
        restored_tp, m = step(restored_tp, shard_batch(batch, tp_mesh))
    assert np.isfinite(float(m["loss"]))
    assert int(restored_tp.step) == 2

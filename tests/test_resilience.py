"""Resilience subsystem (resilience/): fault injection, jit-safe skip-step
policy, snapshot/rollback recovery, preemption checkpoints, verified
restores, and the deterministic mid-epoch resume they compose into.

Fast tests run in tier-1; the full supervised chaos scenarios (real child
processes, multiple relaunches) are marked ``slow``.
"""

import json
import os
import signal
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
from pytorch_distributed_training_tpu.resilience import (
    CRASH_EXIT_CODE,
    AnomalyPolicy,
    FaultInjector,
    Preempted,
    PreemptionHandler,
    RecoveryAborted,
    RecoveryConfig,
    RecoveryManager,
    init_resilience_state,
    parse_faults,
)
from pytorch_distributed_training_tpu.train import (
    Trainer,
    TrainerConfig,
    TrainState,
    make_train_step,
)

# ---------------------------------------------------------------------------
# Tiny fixture state: a linear-regression "model" through the custom loss_fn
# path — exercises the real guarded train step without a model compile.


def _loss_fn(state, params, batch, rng):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {"batch_stats": state.batch_stats}


def _state(policy_on: bool, seed: int = 0) -> TrainState:
    w = jax.random.normal(jax.random.PRNGKey(seed), (4, 2))
    params = {"w": w}
    tx = optax.adam(1e-2)
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), batch_stats={}, apply_fn=None, tx=tx,
        resilience=init_resilience_state() if policy_on else (),
    )


def _batch(rng, n=8):
    return {
        "x": jnp.asarray(rng.standard_normal((n, 4)), jnp.float32),
        "y": jnp.asarray(rng.standard_normal((n, 2)), jnp.float32),
    }


def _cpu_mesh():
    return make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# fault plan


def test_fault_plan_parse_and_defaults():
    faults = parse_faults(
        "crash@5, stall@3:0.5,nan_batch@2,spike_batch@4:10,ckpt_truncate@6,"
        "sigterm@7"
    )
    assert [(f.kind, f.step) for f in faults] == [
        ("crash", 5), ("stall", 3), ("nan_batch", 2), ("spike_batch", 4),
        ("ckpt_truncate", 6), ("sigterm", 7),
    ]
    assert faults[1].arg == 0.5
    assert faults[3].arg == 10.0
    # defaults
    assert parse_faults("stall@1")[0].arg == 3600.0
    assert parse_faults("spike_batch@1")[0].arg == 1e4
    with pytest.raises(ValueError):
        parse_faults("meteor@3")
    with pytest.raises(ValueError):
        parse_faults("crash@soon")


def test_fault_injector_fires_once_and_persists_markers(tmp_path):
    calls = []
    spec = "crash@5,nan_batch@2,sigterm@3,stall@4:0.01"
    inj = FaultInjector(
        parse_faults(spec), state_dir=str(tmp_path),
        _exit=lambda c: calls.append(("exit", c)),
        _kill=lambda p, s: calls.append(("kill", s)),
        _sleep=lambda s: calls.append(("sleep", s)),
    )
    b = inj.on_step(2, {"x": np.ones((2, 2), np.float32),
                        "i": np.ones((2,), np.int32)})
    assert np.isnan(np.asarray(b["x"])).all()
    assert (np.asarray(b["i"]) == 1).all()  # int leaves untouched
    inj.on_step(3, {})
    inj.on_step(4, {})
    inj.on_step(5, {})
    assert ("kill", signal.SIGTERM) in calls
    assert ("sleep", 0.01) in calls
    assert ("exit", CRASH_EXIT_CODE) in calls
    # Markers persist: a FRESH injector (the relaunched process) refires
    # nothing.
    calls2 = []
    inj2 = FaultInjector(
        parse_faults(spec), state_dir=str(tmp_path),
        _exit=lambda c: calls2.append(("exit", c)),
        _kill=lambda p, s: calls2.append(("kill", s)),
        _sleep=lambda s: calls2.append(("sleep", s)),
    )
    b2 = inj2.on_step(2, {"x": np.ones((2, 2), np.float32)})
    for step in (2, 3, 4, 5):
        inj2.on_step(step, {})
    assert calls2 == []
    assert not np.isnan(np.asarray(b2["x"])).any()


def test_spike_batch_scales_floats():
    inj = FaultInjector(parse_faults("spike_batch@1:100"))
    b = inj.on_step(1, {"x": np.ones((2,), np.float32)})
    np.testing.assert_allclose(np.asarray(b["x"]), 100.0)


# ---------------------------------------------------------------------------
# jit-safe skip policy


def test_guarded_step_skips_nan_and_spike_and_counts():
    step = make_train_step(
        kind="custom", loss_fn=_loss_fn,
        anomaly_policy=AnomalyPolicy(grad_norm_threshold=100.0),
    )
    rng = np.random.default_rng(0)
    good, nanb = _batch(rng), _batch(rng)
    nanb = {"x": jnp.full_like(nanb["x"], np.nan), "y": nanb["y"]}
    spike = {"x": good["x"] * 1e6, "y": good["y"]}

    s1, m1 = step(_state(True), good)
    assert int(m1["skipped"]) == 0 and int(m1["bad_streak"]) == 0
    w1 = np.array(s1.params["w"])  # host copy before s1's buffers donate
    mu1 = np.array(s1.opt_state[0].mu["w"])

    s2, m2 = step(s1, nanb)
    assert int(m2["skipped"]) == 1 and int(m2["bad_streak"]) == 1
    assert not np.isfinite(float(m2["loss"]))
    np.testing.assert_array_equal(np.asarray(s2.params["w"]), w1)
    np.testing.assert_array_equal(np.asarray(s2.opt_state[0].mu["w"]), mu1)
    assert int(s2.step) == 2  # the step counter still advances

    s3, m3 = step(s2, spike)  # finite but over the norm threshold
    assert np.isfinite(float(m3["loss"]))
    assert int(m3["skipped"]) == 1 and int(m3["bad_streak"]) == 2
    np.testing.assert_array_equal(np.asarray(s3.params["w"]), w1)

    s4, m4 = step(s3, good)
    assert int(m4["skipped"]) == 0 and int(m4["bad_streak"]) == 0
    assert int(m4["skipped_total"]) == 2
    assert not np.array_equal(np.asarray(s4.params["w"]), w1)


def test_guarded_step_requires_resilience_state():
    step = make_train_step(
        kind="custom", loss_fn=_loss_fn, anomaly_policy=AnomalyPolicy()
    )
    with pytest.raises(ValueError, match="resilience"):
        step(_state(False), _batch(np.random.default_rng(0)))


def test_no_fault_policy_is_bitwise_noop():
    """The acceptance pin: with nothing firing, policy-on and policy-off
    runs produce bitwise-identical loss trajectories AND end states
    (lax.cond, not where-selects — a select invites XLA to re-fuse the
    Adam update and drift a ULP within a couple of steps)."""
    off = make_train_step(kind="custom", loss_fn=_loss_fn)
    on = make_train_step(
        kind="custom", loss_fn=_loss_fn,
        anomaly_policy=AnomalyPolicy(grad_norm_threshold=1e9),
    )
    s_off, s_on = _state(False), _state(True)
    rng = np.random.default_rng(1)
    for i in range(30):
        b = _batch(rng)
        s_off, mo = off(s_off, b)
        s_on, mn = on(s_on, b)
        assert float(mo["loss"]) == float(mn["loss"]), i
    np.testing.assert_array_equal(
        np.asarray(s_off.params["w"]), np.asarray(s_on.params["w"])
    )
    np.testing.assert_array_equal(
        np.asarray(s_off.opt_state[0].mu["w"]),
        np.asarray(s_on.opt_state[0].mu["w"]),
    )


# ---------------------------------------------------------------------------
# recovery: snapshot / rollback / abort


def test_recovery_rollback_and_abort():
    state = _state(True)
    rec = RecoveryManager(RecoveryConfig(rollback_after=3, max_rollbacks=1))
    rec.stage(state, 10)
    w_snap = np.array(state.params["w"])

    drifted = state.replace(
        params={"w": state.params["w"] + 1.0},
        resilience=state.resilience.replace(
            bad_streak=jnp.asarray(5, jnp.int32)
        ),
    )
    # below threshold: untouched
    same = rec.observe(drifted, 11, bad_streak=2)
    assert same is drifted
    # at threshold: rolled back to the snapshot; the streak resets but
    # the run-cumulative skip counter must NOT (the trainer diffs it
    # against a host mirror — zeroing it would mask subsequent skips)
    drifted = drifted.replace(
        resilience=drifted.resilience.replace(
            skipped_total=jnp.asarray(7, jnp.int32)
        )
    )
    back = rec.observe(drifted, 12, bad_streak=3)
    np.testing.assert_array_equal(np.asarray(back.params["w"]), w_snap)
    assert int(back.resilience.bad_streak) == 0
    assert int(back.resilience.skipped_total) == 7
    assert rec.rollbacks == 1
    # budget exhausted: abort
    with pytest.raises(RecoveryAborted):
        rec.observe(drifted, 13, bad_streak=4)


def test_recovery_snapshot_cadence():
    state = _state(True)
    rec = RecoveryManager(RecoveryConfig(snapshot_every_steps=10))
    rec.maybe_stage(state, 0)
    assert rec._snapshot_step == 0
    rec.maybe_stage(state, 5)
    assert rec._snapshot_step == 0  # not due yet
    rec.maybe_stage(state, 10)
    assert rec._snapshot_step == 10


# ---------------------------------------------------------------------------
# trainer integration: nan fault -> skip -> run completes; recovery rollback


def test_trainer_skips_nan_fault_and_completes(tmp_path):
    from pytorch_distributed_training_tpu.obs import MetricsEmitter, read_events

    step = make_train_step(
        kind="custom", loss_fn=_loss_fn, anomaly_policy=AnomalyPolicy()
    )
    emitter = MetricsEmitter(str(tmp_path), rank=0, world=1)
    inj = FaultInjector(parse_faults("nan_batch@2"), emitter=emitter)
    trainer = Trainer(
        _state(True), step, _cpu_mesh(),
        TrainerConfig(progress=False, log_every=1, prefetch=0),
        emitter=emitter, faults=inj,
        recovery=RecoveryManager(RecoveryConfig(snapshot_every_steps=2)),
    )
    rng = np.random.default_rng(2)
    batches = [_batch(rng) for _ in range(6)]
    summary = trainer.run_epoch(batches)
    emitter.close()
    assert summary["skipped_total"] == 1.0
    assert np.isfinite(summary["loss"])
    events = read_events(emitter.path)
    kinds = [e.get("anomaly") for e in events if e["kind"] == "anomaly"]
    assert "fault_injected" in kinds
    assert "skip_step" in kinds and "nonfinite_loss" in kinds


def test_trainer_rollback_restores_snapshot_params():
    """Persistently bad data past ``rollback_after`` rolls params back to
    the staged snapshot (and the run continues, on the next batches)."""
    step = make_train_step(
        kind="custom", loss_fn=_loss_fn, anomaly_policy=AnomalyPolicy()
    )
    rec = RecoveryManager(
        RecoveryConfig(rollback_after=2, max_rollbacks=5,
                       snapshot_every_steps=1)
    )
    trainer = Trainer(
        _state(True), step, _cpu_mesh(),
        TrainerConfig(progress=False, log_every=1, prefetch=0),
        recovery=rec,
    )
    rng = np.random.default_rng(3)
    nan = {"x": jnp.full((8, 4), np.nan), "y": jnp.zeros((8, 2))}
    batches = [_batch(rng), _batch(rng), nan, nan, _batch(rng)]
    trainer.run_epoch(batches)
    assert rec.rollbacks == 1
    assert np.isfinite(np.asarray(trainer.state.params["w"])).all()


def test_trainer_abort_after_rollback_budget():
    step = make_train_step(
        kind="custom", loss_fn=_loss_fn, anomaly_policy=AnomalyPolicy()
    )
    trainer = Trainer(
        _state(True), step, _cpu_mesh(),
        TrainerConfig(progress=False, log_every=1, prefetch=0),
        recovery=RecoveryManager(
            RecoveryConfig(rollback_after=1, max_rollbacks=1,
                           snapshot_every_steps=1)
        ),
    )
    nan = {"x": jnp.full((8, 4), np.nan), "y": jnp.zeros((8, 2))}
    with pytest.raises(RecoveryAborted):
        trainer.run_epoch([_batch(np.random.default_rng(4))] + [nan] * 5)


# ---------------------------------------------------------------------------
# preemption


def test_preemption_handler_latches_and_restores():
    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionHandler() as h:
        assert not h.triggered
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.triggered
    assert signal.getsignal(signal.SIGTERM) is prev


def test_trainer_preemption_checkpoints_at_step_boundary():
    """sigterm fault mid-run -> the in-flight step completes, a SYNC
    checkpoint lands at the boundary, Preempted carries the step."""
    step = make_train_step(kind="custom", loss_fn=_loss_fn)
    saves = []
    inj = FaultInjector(
        parse_faults("sigterm@2"), _kill=os.kill
    )
    with PreemptionHandler() as handler:
        trainer = Trainer(
            _state(False), step, _cpu_mesh(),
            TrainerConfig(progress=False, log_every=100, prefetch=0),
            faults=inj, preemption=handler,
            checkpoint_fn=lambda s, wait=False: saves.append(
                (int(s.step), wait)
            ),
        )
        rng = np.random.default_rng(5)
        with pytest.raises(Preempted) as exc:
            trainer.run_epoch([_batch(rng) for _ in range(10)])
    # fault fires before step 2 dispatches; step 2 completes -> boundary 3
    assert exc.value.step == 3 and exc.value.saved
    assert saves == [(3, True)]


def test_trainer_step_checkpoint_cadence():
    step = make_train_step(kind="custom", loss_fn=_loss_fn)
    saves = []
    trainer = Trainer(
        _state(False), step, _cpu_mesh(),
        TrainerConfig(progress=False, log_every=100, prefetch=0,
                      checkpoint_every_steps=2),
        checkpoint_fn=lambda s, wait=False: saves.append(int(s.step)),
    )
    rng = np.random.default_rng(6)
    trainer.run_epoch([_batch(rng) for _ in range(7)])
    assert saves == [2, 4, 6]


# ---------------------------------------------------------------------------
# checkpoint manifest + verified restore


def _ckpt_state(step, val):
    params = {"w": jnp.full((64, 32), val, jnp.float32)}
    tx = optax.adam(1e-2)
    return TrainState(
        step=jnp.asarray(step, jnp.int32), params=params,
        opt_state=tx.init(params), batch_stats={}, apply_fn=None, tx=tx,
    )


def test_checkpoint_manifest_written_and_restore_verified(tmp_path):
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)
    manifest = tmp_path / "manifest-1.json"
    assert manifest.exists()
    leaves = json.loads(manifest.read_text())["leaves"]
    assert any("'w'" in k for k in leaves)
    assert all(
        {"crc32", "dtype", "shape"} <= set(rec) for rec in leaves.values()
    )
    restored = CheckpointManager(str(tmp_path)).restore_latest(
        _ckpt_state(0, 0.0)
    )
    assert int(restored.step) == 1
    assert float(np.asarray(restored.params["w"])[0, 0]) == 1.0


def test_corrupt_checkpoint_falls_back_to_older_step(tmp_path):
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_training_tpu.resilience.faults import (
        truncate_checkpoint,
    )

    anomalies = []
    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)
        mgr.save(_ckpt_state(2, 2.0), wait=True)
    truncate_checkpoint(str(tmp_path), 2)
    fresh = CheckpointManager(
        str(tmp_path),
        on_anomaly=lambda kind, **f: anomalies.append((kind, f)),
    )
    restored = fresh.restore_latest(_ckpt_state(0, 0.0))
    assert int(restored.step) == 1
    assert float(np.asarray(restored.params["w"])[0, 0]) == 1.0
    assert anomalies and anomalies[0][0] == "checkpoint_restore_failed"
    assert anomalies[0][1]["step"] == 2
    # A DESERIALIZE failure is not checksum-proven corruption, so the
    # step is NOT deleted (a template mismatch must never destroy
    # history) — but the resumed run's re-save at the same counter
    # REPLACES it instead of deduping against the unreadable bytes.
    assert fresh.all_steps() == [1, 2]
    fresh.save(_ckpt_state(2, 5.0), wait=True)
    assert fresh.all_steps() == [1, 2]
    replaced = CheckpointManager(str(tmp_path)).restore_latest(
        _ckpt_state(0, 0.0)
    )
    assert int(replaced.step) == 2
    assert float(np.asarray(replaced.params["w"])[0, 0]) == 5.0


def test_all_checkpoints_corrupt_raises_not_fresh_start(tmp_path):
    """Committed steps exist but NONE restores: that is a template
    mismatch or a dead disk, not bit-rot — silently retraining from
    scratch would retire the good checkpoints, so it must raise.  Only an
    EMPTY directory (fresh run) returns None."""
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_training_tpu.resilience.faults import (
        truncate_checkpoint,
    )

    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)
    truncate_checkpoint(str(tmp_path), 1)
    fresh = CheckpointManager(str(tmp_path), on_anomaly=lambda *a, **k: None)
    with pytest.raises(RuntimeError, match="no committed checkpoint"):
        fresh.restore_latest(_ckpt_state(0, 0.0))
    empty = CheckpointManager(str(tmp_path / "empty"))
    assert empty.restore_latest(_ckpt_state(0, 0.0)) is None


def test_checksum_catches_bitflip_not_just_truncation(tmp_path):
    """Flip one byte of the largest payload file (same size, valid enough
    to deserialize in the worst case) — the crc manifest must still
    reject the step."""
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)
        mgr.save(_ckpt_state(2, 2.0), wait=True)
    # flip a byte in step 2's largest file
    largest, size = None, -1
    for root, _, files in os.walk(str(tmp_path / "2")):
        for f in files:
            p = os.path.join(root, f)
            if os.path.getsize(p) > size:
                largest, size = p, os.path.getsize(p)
    with open(largest, "r+b") as f:
        f.seek(size // 2)
        byte = f.read(1)
        f.seek(size // 2)
        f.write(bytes([byte[0] ^ 0xFF]))
    fresh = CheckpointManager(str(tmp_path), on_anomaly=lambda *a, **k: None)
    restored = fresh.restore_latest(_ckpt_state(0, 0.0))
    assert restored is not None
    assert int(restored.step) == 1


def test_checksum_proven_corruption_drops_step(tmp_path):
    """When the restore DESERIALIZES but the bytes fail their crc32
    (bit-rot the storage layer missed), the step is deleted — proven-bad
    bytes must not shadow the good older step as "latest".  Forced
    deterministically by rewriting one manifest crc (same dtype/shape,
    so it cannot be mistaken for a template change)."""
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)
        mgr.save(_ckpt_state(2, 2.0), wait=True)
    manifest = tmp_path / "manifest-2.json"
    doc = json.loads(manifest.read_text())
    key = next(k for k in doc["leaves"] if "'w'" in k)
    doc["leaves"][key]["crc32"] ^= 0xFFFF
    manifest.write_text(json.dumps(doc))
    anomalies = []
    fresh = CheckpointManager(
        str(tmp_path), on_anomaly=lambda kind, **f: anomalies.append(f)
    )
    restored = fresh.restore_latest(_ckpt_state(0, 0.0))
    assert int(restored.step) == 1
    assert anomalies[0]["deleted"] is True
    assert fresh.all_steps() == [1]
    assert not manifest.exists()


def test_template_mismatch_never_deletes_history(tmp_path):
    """A resume with a CHANGED model config must fail loudly — and leave
    every committed checkpoint untouched (deleting good history on a
    config mistake would be unrecoverable)."""
    import optax

    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)
    wrong_params = {"w": jnp.zeros((8, 8)), "extra": jnp.zeros((3,))}
    tx = optax.adam(1e-2)
    wrong_template = TrainState(
        step=jnp.zeros((), jnp.int32), params=wrong_params,
        opt_state=tx.init(wrong_params), batch_stats={}, apply_fn=None,
        tx=tx,
    )
    fresh = CheckpointManager(str(tmp_path), on_anomaly=lambda *a, **k: None)
    with pytest.raises(RuntimeError, match="no committed checkpoint"):
        fresh.restore_latest(wrong_template)
    assert fresh.all_steps() == [1]  # history intact
    assert (tmp_path / "manifest-1.json").exists()


def test_async_save_stages_stable_copies_on_cpu():
    """Regression pin for the async-save tear the chaos harness caught:
    on the CPU backend jax "device" buffers ARE host memory, so orbax's
    background serializer read the LIVE training buffers — which the next
    donated train step overwrote mid-write, committing torn checkpoints.
    The staged tree must not alias the state's buffers."""
    from pytorch_distributed_training_tpu.checkpoint.manager import (
        _staged_arrays_of,
    )

    state = _ckpt_state(1, 1.0)
    staged = _staged_arrays_of(state)
    live = np.asarray(state.params["w"])
    assert isinstance(staged["params"]["w"], np.ndarray)
    assert not np.shares_memory(staged["params"]["w"], live)
    np.testing.assert_array_equal(staged["params"]["w"], live)
    assert staged["params"]["w"].dtype == live.dtype


def test_checkpoint_save_dedupes_same_step(tmp_path):
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(_ckpt_state(3, 1.0), wait=True)
        # step-cadence + epoch-end landing on the same optimizer step must
        # not raise (orbax rejects duplicate steps) nor rewrite bytes.
        mgr.save(_ckpt_state(3, 99.0), wait=True)
        assert mgr.all_steps() == [3]
    restored = CheckpointManager(str(tmp_path)).restore_latest(
        _ckpt_state(0, 0.0)
    )
    assert float(np.asarray(restored.params["w"])[0, 0]) == 1.0


def test_ckpt_truncate_fault_corrupts_committed_step(tmp_path):
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    inj = FaultInjector(
        parse_faults("ckpt_truncate@2"), state_dir=str(tmp_path / "fs")
    )
    with CheckpointManager(str(tmp_path / "ck"), fault_injector=inj) as mgr:
        mgr.save(_ckpt_state(1, 1.0), wait=True)   # below fault step: intact
        mgr.save(_ckpt_state(2, 2.0))              # async; fault waits + mangles
    anomalies = []
    fresh = CheckpointManager(
        str(tmp_path / "ck"),
        on_anomaly=lambda kind, **f: anomalies.append(kind),
    )
    restored = fresh.restore_latest(_ckpt_state(0, 0.0))
    assert int(restored.step) == 1
    assert anomalies == ["checkpoint_restore_failed"]
    # once-only: a fresh injector (relaunch) does not mangle step 3
    inj2 = FaultInjector(
        parse_faults("ckpt_truncate@2"), state_dir=str(tmp_path / "fs")
    )
    with CheckpointManager(
        str(tmp_path / "ck"), fault_injector=inj2
    ) as mgr2:
        mgr2.save(_ckpt_state(3, 3.0), wait=True)
    final = CheckpointManager(str(tmp_path / "ck")).restore_latest(
        _ckpt_state(0, 0.0)
    )
    assert int(final.step) == 3


# ---------------------------------------------------------------------------
# resume determinism: preempt mid-epoch, resume, bitwise-match the
# uninterrupted run (batch sequence AND final params)


def _det_loader(seed=0):
    from pytorch_distributed_training_tpu.data import DataLoader, DataLoaderConfig
    from pytorch_distributed_training_tpu.data.datasets import SyntheticImages

    ds = SyntheticImages(n=48, image_size=4, num_classes=10, seed=seed)
    return DataLoader(ds, DataLoaderConfig(batch_size=8, num_workers=0, seed=seed))


def _img_loss(state, params, batch, rng):
    flat = batch["image"].reshape(batch["image"].shape[0], -1)
    pred = flat @ params["w"]
    target = batch["label"].astype(jnp.float32)[:, None]
    return jnp.mean((pred - target) ** 2), {"batch_stats": state.batch_stats}


def _img_state():
    params = {"w": jax.random.normal(jax.random.PRNGKey(7), (48, 1)) * 0.01}
    tx = optax.adam(1e-3)
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), batch_stats={}, apply_fn=None, tx=tx,
    )


class _Tap:
    """Record a digest of every batch an iterator yields."""

    def __init__(self):
        self.digests = []

    def __call__(self, it):
        for b in it:
            self.digests.append(float(np.asarray(b["image"]).sum()))
            yield b


def test_preempt_resume_is_bitwise_deterministic(tmp_path):
    """Train 2 epochs x 6 steps uninterrupted; train again with a SIGTERM
    preemption at step 3 + step checkpoint + resume-with-skip; batch
    sequence and final params must match bitwise (the --ckpt-every-steps
    contract)."""
    import itertools

    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    step_fn = make_train_step(kind="custom", loss_fn=_img_loss)
    mesh = _cpu_mesh()
    epochs, per_epoch = 2, 6

    def run_epochs(trainer, loader, tap, start_epoch=0, skip=0):
        for epoch in range(start_epoch, epochs):
            loader.set_epoch(epoch)
            batches = iter(loader)
            s = skip if epoch == start_epoch else 0
            if s:
                batches = itertools.islice(batches, s, None)
            trainer.run_epoch(tap(batches), epoch=epoch)

    # --- uninterrupted reference ---
    ref_tap = _Tap()
    ref = Trainer(
        _img_state(), step_fn, mesh,
        TrainerConfig(progress=False, log_every=100, prefetch=0),
    )
    run_epochs(ref, _det_loader(), ref_tap)
    ref_w = np.asarray(ref.state.params["w"])

    # --- interrupted run: preempted before step 3 dispatches ---
    int_tap = _Tap()
    ck = CheckpointManager(str(tmp_path))
    with PreemptionHandler() as handler:
        t1 = Trainer(
            _img_state(), step_fn, mesh,
            TrainerConfig(progress=False, log_every=100, prefetch=0),
            faults=FaultInjector(parse_faults("sigterm@2"), _kill=os.kill),
            preemption=handler,
            checkpoint_fn=lambda s, wait=False: ck.save(s, wait=wait),
        )
        with pytest.raises(Preempted):
            run_epochs(t1, _det_loader(), int_tap)
    ck.close()

    # --- resume: restore, derive epoch+skip the way the CLI does ---
    resumed = CheckpointManager(str(tmp_path)).restore_latest(_img_state())
    assert resumed is not None
    resumed_step = int(resumed.step)
    assert resumed_step == 3  # sigterm@2 -> step 2 completed -> boundary 3
    start_epoch = resumed_step // per_epoch
    skip = resumed_step - start_epoch * per_epoch
    t2 = Trainer(
        resumed, step_fn, mesh,
        TrainerConfig(progress=False, log_every=100, prefetch=0),
    )
    run_epochs(t2, _det_loader(), int_tap, start_epoch=start_epoch, skip=skip)

    assert int_tap.digests == ref_tap.digests  # identical batch sequence
    np.testing.assert_array_equal(np.asarray(t2.state.params["w"]), ref_w)


# ---------------------------------------------------------------------------
# serving: deadline shedding


class _FakeEngine:
    """Minimal engine double for scheduler-policy tests (no compiles):
    one decode token per tick, retire at budget."""

    def __init__(self, slots=1):
        self.slots = slots
        self.active = {}

    @property
    def busy(self):
        return bool(self.active)

    @property
    def pool(self):
        return types.SimpleNamespace(num_active=len(self.active))

    def validate_request(self, prompt_len, max_new):
        pass

    def can_admit(self, prompt, max_new):
        return len(self.active) < self.slots

    def start(self, rid, prompt, max_new):
        self.active[rid] = max_new

    def live_requests(self):
        return list(self.active)

    def cancel(self, rid):
        del self.active[rid]
        return types.SimpleNamespace(
            request_id=rid, kind="finish", reason="cancelled"
        )

    def step(self):
        events = []
        for rid in list(self.active):
            events.append(types.SimpleNamespace(
                request_id=rid, kind="token", reason=None
            ))
            self.active[rid] -= 1
            if self.active[rid] <= 0:
                del self.active[rid]
                events.append(types.SimpleNamespace(
                    request_id=rid, kind="finish", reason="length"
                ))
        return events


def test_scheduler_sheds_expired_queued_requests(tmp_path):
    from pytorch_distributed_training_tpu.serve import (
        ContinuousScheduler, Request, VirtualClock, summarize_records,
    )
    from pytorch_distributed_training_tpu.utils.metrics import RequestLogger

    clock = VirtualClock()
    log = RequestLogger(str(tmp_path / "req.jsonl"), only_rank0=False)
    sched = ContinuousScheduler(
        _FakeEngine(slots=1), max_queue=8, clock=clock, request_logger=log,
    )
    p = np.arange(4, dtype=np.int32)
    assert sched.submit(Request(0, p, 5))                  # admitted tick 1
    assert sched.submit(Request(1, p, 5, deadline=0.5))    # will expire
    assert sched.submit(Request(2, p, 2, deadline=100.0))  # survives
    sched.tick()  # admits 0; queue: [1, 2]
    clock.advance(1.0)  # past request 1's deadline
    while not sched.idle:
        sched.tick()
        clock.advance(0.01)
    assert sched.shed == 1
    by_id = {r["id"]: r for r in sched.completed}
    assert by_id[1]["finish_reason"] == "shed"
    assert by_id[1]["generated"] == 0
    assert by_id[0]["finish_reason"] == "length"
    assert by_id[2]["finish_reason"] == "length"

    summary = summarize_records(sched.completed)
    assert summary["shed"] == 1
    assert summary["completed"] == 2  # shed excluded
    assert summary["finish_reasons"] == {"length": 2, "shed": 1}
    assert summary["generated_tokens"] == 7  # 5 + 2, nothing from the shed

    rows = log.read()
    shed_rows = [r for r in rows if r["finish_reason"] == "shed"]
    assert len(shed_rows) == 1 and shed_rows[0]["deadline"] == 0.5


def test_scheduler_no_deadline_never_sheds():
    from pytorch_distributed_training_tpu.serve import (
        ContinuousScheduler, Request, VirtualClock,
    )

    clock = VirtualClock()
    sched = ContinuousScheduler(_FakeEngine(slots=1), max_queue=8, clock=clock)
    p = np.arange(4, dtype=np.int32)
    for i in range(3):
        assert sched.submit(Request(i, p, 2))
    clock.advance(1e6)
    while not sched.idle:
        sched.tick()
        clock.advance(0.01)
    assert sched.shed == 0 and len(sched.completed) == 3


# ---------------------------------------------------------------------------
# supervised chaos scenarios (slow: real child processes + relaunches)


def _chaos_argv(ckpt, faults, steps_per_epoch=4, epochs=3, extra=()):
    import sys

    return [
        sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main",
        "--use-cpu", "--model", "resnet18", "--dataset", "synthetic-images",
        "--image-size", "8", "--batch-size", "8", "--num-workers", "0",
        "--learning-rate", "0.001", "--epochs", str(epochs),
        "--steps-per-epoch", str(steps_per_epoch),
        "--checkpoint-dir", str(ckpt), "--ckpt-every-steps", "2",
        "--skip-bad-steps", "--inject-faults", faults, *extra,
    ]


@pytest.mark.slow
def test_chaos_supervised_run_recovers_from_all_fault_classes(
    tmp_path, monkeypatch
):
    """One supervised run through every fault class: NaN batch (skipped),
    rank kill (restart), heartbeat stall (hung kill), SIGTERM preemption
    (free relaunch), corrupt committed checkpoint (verified-restore
    fallback) — and the run still reaches its final epoch."""
    from pytorch_distributed_training_tpu.utils.supervisor import supervise

    # Relaunched children share the CLI's own persistent compile cache,
    # so the heartbeat timeout prices the STALL, not XLA.
    ckpt = tmp_path / "ckpt"
    faults = "nan_batch@1,crash@3,stall@5:600,sigterm@8,ckpt_truncate@9"
    result = supervise(
        _chaos_argv(ckpt, faults),
        max_restarts=3,
        heartbeat_path=str(tmp_path / "hb"),
        # Must exceed a cold child's import+compile window (the trainer's
        # first beat lands after the first step compiles) while staying
        # far under the injected 600 s stall.
        heartbeat_timeout_s=60.0,
        poll_s=0.5,
        backoff_base_s=0.0,
        _print=lambda *a: None,
    )
    assert result.exit_code == 0
    assert result.restarts == 2      # crash + stall-kill
    assert result.hung_kills == 1
    assert result.preemptions == 1
    # every fault fired exactly once (markers persisted across relaunches)
    markers = sorted(os.listdir(ckpt / ".fault_state"))
    assert markers == [
        "ckpt_truncate_9", "crash_3", "nan_batch_1", "sigterm_8", "stall_5",
    ]
    # the final epoch's checkpoint committed (3 epochs x 4 steps)
    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager

    assert max(CheckpointManager(str(ckpt)).all_steps()) == 12

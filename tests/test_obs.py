"""Tests for the obs/ telemetry subsystem (ISSUE 3).

Covers the emitter's schema contract, the shared percentile helper, the
flight recorder's anomaly detectors and rank merge + straggler flagging,
the trainer's telemetry integration (per-step events, dedupe, profile-step
window), the analytic-DCN-counter match against ``dcn_bytes_per_sync``
for every --grad-sync mode, pinned MFU math, and the end-to-end CLI smoke
run that produces a schema-valid metrics dir tools/telemetry_report.py can
merge.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from click.testing import CliRunner

from pytorch_distributed_training_tpu.cli.main import main as cli_main
from pytorch_distributed_training_tpu.obs import (
    SCHEMA_VERSION,
    FlightRecorder,
    MetricsEmitter,
    collective_census,
    dcn_step_counters,
    load_rank_logs,
    merge_timeline,
    mfu,
    percentiles,
    read_events,
    step_cost_report,
    straggler_report,
    validate_events,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------- #
# percentiles + emitter
# ---------------------------------------------------------------------- #

def test_percentiles_matches_numpy_and_filters_none():
    xs = [5.0, None, 1.0, 3.0, None, 2.0, 4.0]
    out = percentiles(xs, (50, 99))
    clean = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert out["p50"] == pytest.approx(np.percentile(clean, 50))
    assert out["p99"] == pytest.approx(np.percentile(clean, 99))
    assert percentiles([], (50,)) == {"p50": None}
    # serve/metrics.percentile is the same implementation, fronted.
    from pytorch_distributed_training_tpu.serve.metrics import percentile

    assert percentile(xs, 50) == out["p50"]
    assert percentile([], 50) is None


def test_emitter_jsonl_schema_roundtrip(tmp_path):
    em = MetricsEmitter(str(tmp_path), rank=3, world=4, meta={"mode": "test"})
    em.set_step_counters({"dcn_bytes": 100.0})
    em.counter_add("tokens", 7)
    em.gauge("queue_depth", 2)
    em.observe("ttft_s", 0.5)
    em.observe("ttft_s", 1.5)
    em.phase("epoch_start", epoch=0)
    em.step(0, dt=0.1, loss=1.0)
    em.step(1, dt=0.2)
    em.heartbeat()
    em.anomaly("nonfinite_loss", step=1, loss=float("nan"))
    summary = em.summary()
    em.close()

    events = read_events(em.path)
    validate_events(events)  # schema-valid end to end
    assert os.path.basename(em.path) == "events.rank00003.jsonl"
    assert events[0]["kind"] == "meta"
    assert events[0]["schema"] == SCHEMA_VERSION
    assert events[0]["world"] == 4 and events[0]["mode"] == "test"
    steps = [e for e in events if e["kind"] == "step"]
    # step 0 carries the explicit counter_add AND the static per-step add;
    # step 1 only the static per-step add (deltas, not cumulative).
    assert steps[0]["counters"] == {"dcn_bytes": 100.0, "tokens": 7.0}
    assert steps[1]["counters"] == {"dcn_bytes": 100.0, "tokens": 0.0}
    assert steps[0]["loss"] == 1.0 and "loss" not in steps[1]
    # summary reduces histograms through the shared percentiles().
    assert summary["counters"]["dcn_bytes"] == 200.0
    assert summary["histograms"]["ttft_s"]["count"] == 2
    assert summary["histograms"]["ttft_s"]["p50"] == pytest.approx(1.0)
    assert summary["gauges"]["queue_depth"] == 2.0


def test_emitter_disabled_is_inert_and_cheap(tmp_path):
    em = MetricsEmitter(None)
    assert not em.enabled and em.path is None
    em.counter_add("x", 1)
    em.step(0, loss=1.0)
    assert em.summary() is None
    em.close()


def test_emitter_tsv_export(tmp_path):
    em = MetricsEmitter(str(tmp_path), rank=0, world=1, log_format="tsv")
    em.step(0, dt=0.25, loss=2.0)
    em.close()
    lines = open(em.path).read().splitlines()
    assert em.path.endswith(".tsv")
    assert lines[0].split("\t")[3] == "meta"
    step_cells = lines[1].split("\t")
    assert step_cells[3] == "step" and step_cells[4] == "0"
    assert "dt=0.25" in step_cells and "loss=2" in step_cells


def test_emitter_rejects_unknown_format(tmp_path):
    with pytest.raises(ValueError):
        MetricsEmitter(str(tmp_path), rank=0, log_format="csv")


def test_validate_events_rejects_malformed(tmp_path):
    em = MetricsEmitter(str(tmp_path), rank=0, world=1)
    em.step(0)
    em.close()
    good = read_events(em.path)
    validate_events(good)
    with pytest.raises(ValueError):  # no meta header
        validate_events(good[1:])
    with pytest.raises(ValueError):  # foreign rank in a per-rank file
        validate_events(good[:1] + [{**good[1], "rank": 9}])
    with pytest.raises(ValueError):  # unknown kind
        validate_events(good + [{**good[1], "kind": "nope"}])


# ---------------------------------------------------------------------- #
# flight recorder: anomalies, merge, stragglers
# ---------------------------------------------------------------------- #

def test_flight_recorder_anomalies(tmp_path):
    em = MetricsEmitter(str(tmp_path), rank=0, world=1)
    rec = FlightRecorder(em, grad_spike_z=4.0)
    rec.check_step(0, {"loss": float("nan")})
    for i in range(20):
        rec.check_step(i + 1, {"loss": 1.0, "grad_norm": 1.0 + 0.01 * i})
    rec.check_step(99, {"loss": 1.0, "grad_norm": 100.0})  # spike
    rec.check_queue(9, max_queue=10)   # >= 0.9 saturation
    rec.check_queue(1, max_queue=10)   # fine
    em.close()
    kinds = [
        e["anomaly"] for e in read_events(em.path) if e["kind"] == "anomaly"
    ]
    assert kinds == ["nonfinite_loss", "grad_norm_spike", "queue_saturation"]
    spike = [
        e for e in read_events(em.path)
        if e["kind"] == "anomaly" and e["anomaly"] == "grad_norm_spike"
    ][0]
    assert spike["step"] == 99 and spike["z"] > 4.0


def _write_rank_log(tmp_path, rank, dts, anomaly_at=None):
    clock = {"t": 100.0 * rank}  # per-rank clocks are NOT aligned

    def fake_clock():
        return clock["t"]

    em = MetricsEmitter(
        str(tmp_path), rank=rank, world=2, clock=fake_clock
    )
    em.set_step_counters({"dcn_bytes": 64.0})
    for step, dt in enumerate(dts):
        clock["t"] += dt
        em.step(step, dt=dt, loss=1.0)
        if anomaly_at == step:
            em.anomaly("nonfinite_loss", step=step, loss=float("nan"))
    em.summary()
    em.close()
    return em.path


def test_rank_merge_step_aligned_and_straggler_flagging(tmp_path):
    # rank 0 steps at 10 ms, rank 1 at 20 ms (the straggler), and rank 1
    # misses the final step (died / lagging).
    _write_rank_log(tmp_path, 0, [0.01] * 6)
    _write_rank_log(tmp_path, 1, [0.02] * 5, anomaly_at=3)
    logs = load_rank_logs(str(tmp_path))
    assert sorted(logs) == [0, 1]
    for events in logs.values():
        validate_events(events)
    timeline = merge_timeline(logs)
    assert [row["step"] for row in timeline] == list(range(6))
    assert timeline[2]["ranks"][0]["counters"]["dcn_bytes"] == 64.0
    assert timeline[5]["missing_ranks"] == [1]
    rep = straggler_report(timeline, skew_threshold=1.25)
    assert rep["stragglers"] == [1]
    assert rep["per_rank_median_dt_s"][1] == pytest.approx(0.02)
    assert rep["skew"][1] > 1.25 > rep["skew"][0]

    # The report tool merges the same logs end to end.
    from tools.telemetry_report import build_report

    report = build_report(str(tmp_path), skew_threshold=1.25)
    assert report["ranks"] == [0, 1] and report["steps"] == 6
    assert report["stragglers"]["stragglers"] == [1]
    assert report["counters_per_rank"]["dcn_bytes"] == {0: 384.0, 1: 320.0}
    assert [a["rank"] for a in report["anomalies"]] == [1]
    assert report["steps_missing_ranks"] == [{"step": 5, "missing": [1]}]


# ---------------------------------------------------------------------- #
# cost: MFU pinned, census, analytic DCN counters vs the model
# ---------------------------------------------------------------------- #

def test_mfu_pinned():
    assert mfu(1e12, 0.5, 4e12) == pytest.approx(0.5)
    assert mfu(1e12, 0.0, 4e12) is None
    assert mfu(1e12, 0.5, None) is None


def test_collective_census_reads_compiled_psum(devices8):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu.compat import shard_map

    mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))
    f = shard_map(
        lambda x: jax.lax.psum(x, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False,
    )
    x = jax.device_put(
        jnp.ones((8, 16), jnp.float32), NamedSharding(mesh, P("data"))
    )
    with mesh:
        hlo = jax.jit(f).lower(x).compile().as_text()
    census = collective_census(hlo)
    # An explicit 8-way psum must lower to at least one collective, and
    # the census must see nonzero f32 bytes on it.
    assert census, hlo[:400]
    total = sum(v["bytes"] for v in census.values())
    assert total > 0
    assert all(v["count"] >= 1 for v in census.values())
    assert any(
        v["by_dtype"].get("f32", 0) > 0 for v in census.values()
    )


@pytest.mark.parametrize("mode", [
    "flat", "hier", "hier-bf16", "hier-int8", "hier-int4", "hier-topk",
])
def test_dcn_step_counters_match_analytic_model(devices8, mode):
    """Acceptance pin: the per-step DCN byte counters the CLI attaches to
    step events equal the analytic dcn_bytes_per_sync model for every
    --grad-sync mode on the simulated 2-slice mesh — recomputed here from
    the same fields the grad_sync_model record carries (padded elems,
    slice split, bucket count, top-k fraction)."""
    from pytorch_distributed_training_tpu.comm import (
        GradSync, GradSyncConfig, MeshConfig, make_hybrid_mesh,
    )
    from pytorch_distributed_training_tpu.comm.hierarchical import (
        dcn_bytes_per_sync,
    )

    mesh = make_hybrid_mesh(
        MeshConfig(data=-1), devices=devices8, n_slices=2
    )
    params = {
        "w": jnp.zeros((64, 64), jnp.float32),
        "b": jnp.zeros((64,), jnp.float32),
    }
    accum = 3
    if mode == "flat":
        counters = dcn_step_counters(
            mesh=mesh, params=params, n_slices=2, num_microbatches=accum
        )
        n = 64 * 64 + 64
        assert counters["dcn_bytes"] == dcn_bytes_per_sync(n, 2, 4, "flat")
        assert counters["dcn_syncs"] == 1.0  # one implicit psum per step
    else:
        sync = GradSync(
            mesh, params,
            GradSyncConfig(
                mode=mode, n_slices=2, bucket_mb=0.004, topk_frac=0.25
            ),
        )
        counters = dcn_step_counters(grad_sync=sync, num_microbatches=accum)
        expect = dcn_bytes_per_sync(
            sync.layout.padded, 2, 4, mode,
            n_buckets=sync.layout.n_buckets, topk_frac=0.25,
        )
        # overlapped sync: one per microbatch, each at the model's bytes
        assert counters["dcn_syncs"] == accum
        assert counters["dcn_bytes"] == expect * accum


def test_pp_step_counters_match_boundary_model():
    """The --pp-compress face of the byte spine: pp_step_counters equals
    the stage-boundary model, and the DCN share is the crossing-edge
    fraction of the ring (0 on a single slice — the CPU default)."""
    from pytorch_distributed_training_tpu.comm.compress import (
        pp_boundary_bytes_per_step,
    )
    from pytorch_distributed_training_tpu.obs import pp_step_counters

    kw = dict(schedule="1f1b", num_stages=4, num_microbatches=8,
              microbatch_rows=2, seq_len=16, hidden=32, act_itemsize=4,
              mode="int8")
    total = pp_boundary_bytes_per_step(**kw)
    # Detected slice count on the CPU harness is 1: boundary traffic is
    # all-ICI, the DCN share must be zero.
    c = pp_step_counters(**kw)
    assert c["pp_boundary_bytes"] == total and c["pp_dcn_bytes"] == 0.0
    # Simulated 2-slice pipeline: 2 of the ring's 4 edges cross DCN.
    c2 = pp_step_counters(**kw, n_slices=2)
    assert c2["pp_dcn_bytes"] == total * 2 // 4
    # Compression shrinks the model the same way it shrinks the payload.
    none = pp_step_counters(**{**kw, "mode": "none"})
    bf16 = pp_step_counters(**{**kw, "mode": "bf16"})
    assert none["pp_boundary_bytes"] == 2 * bf16["pp_boundary_bytes"]
    assert bf16["pp_boundary_bytes"] > c["pp_boundary_bytes"]


def test_cli_pp_compress_metrics_dir_smoke(tmp_path):
    """End-to-end --pp-compress pin: a short pipelined train run with
    --metrics-dir emits a pp_compress_model record whose fields recompute
    to exactly the per-step pp_boundary_bytes counter on every step
    event."""
    from pytorch_distributed_training_tpu.comm.compress import (
        pp_boundary_bytes_per_step,
    )

    mdir = tmp_path / "metrics"
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=2,hidden_dim=32,num_heads=2,vocab_size=128",
            "--seq-len", "16", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "2", "--pipeline-parallel", "2",
            "--pp-compress", "int8", "--metrics-dir", str(mdir),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    events = load_rank_logs(str(mdir))[0]
    validate_events(events)
    rec = next(
        e for e in events
        if e["kind"] == "record" and e.get("record") == "pp_compress_model"
    )
    assert rec["mode"] == "int8" and rec["num_stages"] == 2
    expect = pp_boundary_bytes_per_step(**{
        k: rec[k] for k in (
            "schedule", "num_stages", "num_microbatches", "microbatch_rows",
            "seq_len", "hidden", "act_itemsize", "mode", "num_chunks",
        )
    })
    assert expect > 0
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 2
    assert {s["counters"]["pp_boundary_bytes"] for s in steps} == \
        {float(expect)}


# ---------------------------------------------------------------------- #
# trainer integration: dedupe, step field, per-step events, profile window
# ---------------------------------------------------------------------- #

def _tiny_trainer(tmp_path=None, *, log_every=2, steps=4, config=None):
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_training_tpu.parallel.sharding import DDP_RULES
    from pytorch_distributed_training_tpu.train import (
        Trainer, TrainerConfig, create_train_state, make_train_step,
    )

    cfg = GPT2Config(
        vocab_size=64, max_seq_len=8, num_layers=1, num_heads=2, hidden_dim=16
    )
    mesh = make_mesh(MeshConfig(data=-1))
    state = create_train_state(
        GPT2(cfg=cfg), jax.random.PRNGKey(0), jnp.zeros((8, 8), jnp.int32),
        optax.adam(1e-3), mesh=mesh, rules=DDP_RULES,
        init_kwargs={"train": False},
    )
    step = make_train_step(kind="lm")
    emitter = (
        MetricsEmitter(str(tmp_path), rank=0, world=1)
        if tmp_path is not None else None
    )
    trainer = Trainer(
        state, step, mesh,
        config or TrainerConfig(progress=False, log_every=log_every,
                                prefetch=0),
        emitter=emitter,
    )
    batch = {"tokens": np.random.default_rng(0).integers(
        0, 64, (8, 8), np.int32
    )}
    return trainer, emitter, [batch] * steps


def test_trainer_history_dedupe_and_step_field(tmp_path):
    # 4 steps with log_every=2: steps 0 and 2 log; the final step (3) was
    # NOT a log point, so the closing fetch appends it — 3 recorded losses.
    trainer, _, batches = _tiny_trainer(log_every=2, steps=4)
    s1 = trainer.run_epoch(batches, epoch=0)
    assert s1["step"] == 4  # global optimizer steps in the history record
    assert len(trainer.last_epoch_losses) == 3

    # Epoch length a multiple of log_every: every step logs, so the
    # closing fetch must NOT re-append the final loss (the pre-fix loop
    # duplicated the last logged value here).
    trainer2, emitter2, batches2 = _tiny_trainer(tmp_path, log_every=1,
                                                 steps=3)
    s2 = trainer2.run_epoch(batches2, epoch=0)
    assert s2["step"] == 3
    assert len(trainer2.last_epoch_losses) == 3  # was 4 before the dedupe
    assert s2["loss"] == trainer2.last_epoch_losses[-1]
    emitter2.close()
    steps = [
        e for e in read_events(emitter2.path) if e["kind"] == "step"
    ]
    assert len(steps) == 3
    assert all("loss" in e for e in steps)
    assert [e["step"] for e in steps] == [0, 1, 2]


def test_trainer_continues_global_step_across_epochs(tmp_path):
    trainer, emitter, batches = _tiny_trainer(tmp_path, log_every=2, steps=2)
    trainer.run_epoch(batches, epoch=0)
    trainer.run_epoch(batches, epoch=1)
    emitter.close()
    events = read_events(emitter.path)
    validate_events(events)
    steps = [e["step"] for e in events if e["kind"] == "step"]
    assert steps == [0, 1, 2, 3]  # global, not per-epoch
    phases = [e["phase"] for e in events if e["kind"] == "phase"]
    assert phases == ["epoch_start", "epoch_end"] * 2
    assert [e["epoch"] for e in trainer.history] == [0, 1]


def test_trainer_profile_steps_window(tmp_path, monkeypatch):
    """--profile-steps: the capture brackets exactly the requested global
    steps, the trace lands on disk, and the heartbeat is beaten on every
    captured step (a long capture is never mistaken for a hang)."""
    from pytorch_distributed_training_tpu.train import TrainerConfig
    from pytorch_distributed_training_tpu.utils import supervisor

    beats = {"n": 0}
    monkeypatch.setattr(
        supervisor.Heartbeat, "beat",
        lambda self: beats.__setitem__("n", beats["n"] + 1),
    )
    hb_file = tmp_path / "hb"
    monkeypatch.setenv(supervisor.HEARTBEAT_ENV, str(hb_file))

    prof_dir = tmp_path / "trace"
    cfg = TrainerConfig(
        progress=False, log_every=100, prefetch=0,
        profile_dir=str(prof_dir), profile_steps=(1, 3),
    )
    trainer, emitter, batches = _tiny_trainer(
        tmp_path / "m", steps=5, config=cfg
    )
    # Baseline beats: epoch start, the step-0 log point (0 % log_every ==
    # 0), epoch end = 3; the 2 captured steps (1 and 2) each add one.
    trainer.run_epoch(batches, epoch=0)
    emitter.close()
    assert beats["n"] == 3 + 2
    # The capture produced an xplane artifact under profile_dir.
    produced = [
        os.path.join(r, f)
        for r, _, fs in os.walk(prof_dir) for f in fs
    ]
    assert produced, "profile window produced no trace files"
    events = read_events(emitter.path)
    marks = [
        (e["phase"], e["step"]) for e in events
        if e["kind"] == "phase" and e["phase"].startswith("profile")
    ]
    assert marks == [("profile_start", 1), ("profile_stop", 2)]


def test_trainer_profile_window_truncates_at_data_end(tmp_path):
    """A window running past the epoch's data closes ONCE (truncated) and
    never restarts next epoch — one partial capture, not fragments."""
    from pytorch_distributed_training_tpu.train import TrainerConfig

    cfg = TrainerConfig(
        progress=False, log_every=100, prefetch=0,
        profile_dir=str(tmp_path / "trace"), profile_steps=(1, 10),
    )
    trainer, emitter, batches = _tiny_trainer(
        tmp_path / "m", steps=3, config=cfg
    )
    trainer.run_epoch(batches, epoch=0)
    trainer.run_epoch(batches, epoch=1)  # window range still open: 3..5 < 10
    emitter.close()
    marks = [
        {k: e[k] for k in ("phase", "step", "truncated") if k in e}
        for e in read_events(emitter.path)
        if e["kind"] == "phase" and e["phase"].startswith("profile")
    ]
    assert marks == [
        {"phase": "profile_start", "step": 1},
        {"phase": "profile_stop", "step": 3, "truncated": True},
    ]


def test_peak_flops_matches_real_v5e_device_kind():
    from pytorch_distributed_training_tpu.obs import peak_flops_for

    # jax reports v5e as "TPU v5 lite" — the MFU reference must hit it.
    assert peak_flops_for("TPU v5 lite") == 197e12
    assert peak_flops_for("TPU v5e") == 197e12
    assert peak_flops_for("cpu") is None


def test_cli_profile_steps_validation():
    runner = CliRunner()
    r = runner.invoke(
        cli_main,
        ["--use-cpu", "--synthetic-data", "--profile-steps", "2:4"],
    )
    assert r.exit_code != 0 and "--profile-dir" in r.output
    r = runner.invoke(
        cli_main,
        ["--use-cpu", "--synthetic-data", "--profile-dir", "/tmp/x",
         "--profile-steps", "nope"],
    )
    assert r.exit_code != 0 and "START:STOP" in r.output
    r = runner.invoke(
        cli_main,
        ["--use-cpu", "--synthetic-data", "--profile-dir", "/tmp/x",
         "--profile-steps", "4:2"],
    )
    assert r.exit_code != 0 and "START < STOP" in r.output


# ---------------------------------------------------------------------- #
# end-to-end CLI smoke: --metrics-dir produces a valid, mergeable log
# ---------------------------------------------------------------------- #

def test_cli_train_metrics_dir_smoke(tmp_path):
    """Tier-1 smoke (satellite): a short train run with --metrics-dir
    emits schema-valid events — meta, compiled_cost (with FLOPs), per-step
    records with analytic DCN counters, and a summary — and the report
    tool merges them with MFU computed from cost_analysis()."""
    mdir = tmp_path / "metrics"
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=1,hidden_dim=32,num_heads=2,vocab_size=128",
            "--seq-len", "16", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "4", "--grad-sync", "hier",
            "--grad-sync-slices", "2",
            "--metrics-dir", str(mdir),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    logs = load_rank_logs(str(mdir))
    assert sorted(logs) == [0]
    events = logs[0]
    validate_events(events)
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "meta" and kinds[-1] == "summary"
    assert "compiled_cost" in kinds
    cost = next(e for e in events if e["kind"] == "compiled_cost")
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    # ... and from the probe's own compile the census of the step's scopes
    # (PR 36): instructions by the innermost obs.trace.PHASES name.  Only
    # the names older than the session's compile cache can be are asked
    # for: the cache's key leaves op_name out, so the executable the probe
    # LOADS may carry an older tree's scopes (train/step.py::step_scopes).
    scopes = next(
        e for e in events
        if e["kind"] == "record" and e.get("record") == "step_scopes"
    )["scopes"]
    assert {"train/optimizer", "train/loss", "none"} <= set(scopes)
    assert all(isinstance(n, int) and n > 0 for n in scopes.values())
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 4

    # The per-step DCN counter equals the analytic model, recomputed
    # INDEPENDENTLY from the grad_sync_model record's fields (hier over 2
    # simulated slices, one sync per step at accum=1).
    from pytorch_distributed_training_tpu.comm.hierarchical import (
        dcn_bytes_per_sync,
    )

    meta = events[0]
    assert meta["grad_sync"] == "hier" and meta["mode"] == "train"
    model_rec = next(
        e for e in events
        if e["kind"] == "record" and e.get("record") == "grad_sync_model"
    )
    expect = dcn_bytes_per_sync(
        model_rec["n_elems_padded"], model_rec["n_slices"],
        model_rec["ici"], "hier",
    ) * model_rec["syncs_per_step"]
    assert expect > 0
    assert model_rec["n_slices"] == 2
    got = {s["counters"]["dcn_bytes"] for s in steps}
    assert got == {expect}

    from tools.telemetry_report import build_report

    report = build_report(str(mdir), peak_flops=1e12)
    assert report["steps"] == 4
    assert report["compiled_cost"]["mfu"] is not None
    assert report["compiled_cost"]["mfu"] == pytest.approx(
        cost["flops"] / report["step_time_s"]["p50"] / 1e12
    )


def test_cli_train_striped_metrics_and_report(tmp_path):
    """Striped+overlapped leg of the telemetry spine: every step's
    per-FABRIC byte counters (dcn_bytes crosses slices, ici_bytes stays
    inside one) are counter-exact vs the grad_sync_model record's
    analytic per-sync models, the record carries the sum-vs-max walls,
    and the report tool surfaces both in its grad_sync section."""
    mdir = tmp_path / "metrics"
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--dataset", "synthetic-tokens",
            "--model-overrides",
            "num_layers=1,hidden_dim=32,num_heads=2,vocab_size=128",
            "--seq-len", "16", "--batch-size", "8", "--num-workers", "0",
            "--steps-per-epoch", "3", "--grad-sync", "hier-int8",
            "--grad-sync-slices", "2", "--grad-sync-bucket-mb", "0.01",
            "--grad-sync-stripe", "2", "--grad-sync-overlap", "on",
            "--metrics-dir", str(mdir),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    logs = load_rank_logs(str(mdir))
    events = logs[0]
    validate_events(events)
    rec = next(
        e for e in events
        if e["kind"] == "record" and e.get("record") == "grad_sync_model"
    )
    assert rec["stripe"] == 2 and rec["phase_overlap"] is True
    # Pipelined schedule: depth == bucket count (the sizer's floor is 3).
    assert rec["overlap_depth"] == rec["n_buckets"] > 1
    # sum-vs-max: the pipelined wall never exceeds the serial one, and
    # the reported wall IS the overlapped wall when overlap is on.
    assert rec["wall_overlap_s"] <= rec["wall_serial_s"]
    assert rec["wall_s"] == rec["wall_overlap_s"]
    assert rec["bubble_s"] > 0
    assert rec["overlap_ratio"] == pytest.approx(
        rec["wall_serial_s"] / rec["wall_overlap_s"]
    )

    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 3
    for s in steps:
        assert s["counters"]["dcn_bytes"] == (
            rec["dcn_bytes_per_sync"] * rec["syncs_per_step"]
        )
        assert s["counters"]["ici_bytes"] == (
            rec["ici_bytes_per_sync"] * rec["syncs_per_step"]
        )

    from tools.telemetry_report import build_report

    report = build_report(str(mdir))
    gs = report["grad_sync"]
    assert gs["dcn_bytes_per_sync"] == rec["dcn_bytes_per_sync"]
    assert gs["ici_bytes_per_sync"] == rec["ici_bytes_per_sync"]
    assert gs["dcn_counter_model_abs_err"] == 0
    assert gs["ici_counter_model_abs_err"] == 0
    assert gs["model"]["stripe"] == 2
    assert gs["model"]["wall_overlap_s"] <= gs["model"]["wall_serial_s"]


def test_cli_serve_metrics_dir_smoke(tmp_path):
    """Serve leg of the spine: --serve --metrics-dir produces a valid
    event log with TTFT/TPOT histograms and a serve summary."""
    mdir = tmp_path / "metrics"
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--model", "gpt2", "--serve",
            "--model-overrides",
            "num_layers=1,hidden_dim=32,num_heads=2,vocab_size=128,"
            "max_seq_len=48",
            "--serve-requests", "3", "--serve-slots", "2",
            "--serve-max-new", "4", "--serve-prefill-chunk", "4",
            "--metrics-dir", str(mdir),
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    logs = load_rank_logs(str(mdir))
    events = logs[0]
    validate_events(events)
    assert events[0]["mode"] == "serve"
    summary = next(e for e in events if e["kind"] == "summary")
    assert summary["serve"]["completed"] == 3
    assert summary["histograms"]["ttft_s"]["count"] == 3
    assert summary["counters"]["generated_tokens"] > 0
    finishes = [e for e in events if e["kind"] == "record"]
    assert len(finishes) == 3


def test_step_cost_report_on_compiled_step():
    trainer, _, batches = _tiny_trainer()
    with trainer.mesh:
        compiled = trainer.train_step.lower(
            trainer.state, batches[0]
        ).compile()
    report = step_cost_report(compiled)
    assert report["flops"] > 0
    assert report["bytes_accessed"] > 0
    assert "peak_flops" in report  # None on CPU, a number on TPU

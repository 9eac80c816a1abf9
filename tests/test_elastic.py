"""Tests for elastic world resizing (ISSUE 20).

The membership plane's contract is exactness, so the scripted episode is
pinned the way the goodput ledger is: the peer restore BIT-identical to
the committed snapshot, the consumed-batch schedule identical to the
global-step oracle at every world size, every ledger category an exact
integer-ns total with ``sum == wall``, the shrink window's re-executed
steps classified as rework, and the three independent accountings of the
episode — host counters, transition records, restore-provenance records
— agreeing exactly through the telemetry report.  Also covered: the
elastic fault grammar (and ``--inject-faults`` refusing it loudly), the
heartbeat-staleness monitor with the ``host_hang`` stall band, the
PeerSnapshotStore's buddy/drop/restore machinery and its corruption
refusals, the ``/slo`` ``elastic`` block, and run-twice determinism.
"""

import json
import urllib.request

import numpy as np
import pytest

from pytorch_distributed_training_tpu.obs import (
    LiveAggregator,
    OpsServer,
)
from pytorch_distributed_training_tpu.resilience import (
    ELASTIC_FAULT_KINDS,
    ElasticConfig,
    ElasticWorld,
    PeerSnapshotStore,
    SliceHealthMonitor,
    oracle_batch_digests,
    parse_elastic_faults,
)
from pytorch_distributed_training_tpu.resilience.faults import parse_faults

NS = 1_000_000_000

EPISODE_FAULTS = "slice_lost@4:1,slice_return@9"
EPISODE_STEPS = 12


def _run_episode(faults, n_steps, metrics_dir=None):
    """Run one scripted episode and return its report, JSON round-tripped
    (every pin below is ints/strs/bools) — the form the run-twice
    determinism pin compares."""
    from pytorch_distributed_training_tpu.obs import MetricsEmitter
    from pytorch_distributed_training_tpu.resilience import (
        run_elastic_episode,
    )

    emitter = (
        MetricsEmitter(metrics_dir, rank=0, world=1) if metrics_dir else None
    )
    report = run_elastic_episode(
        faults=faults, n_steps=n_steps, emitter=emitter,
    )
    if emitter is not None:
        emitter.summary()
        emitter.close()
    return json.loads(json.dumps(report))


@pytest.fixture(scope="module")
def episode(tmp_path_factory):
    """One scripted loss-and-return episode, emitting telemetry — the
    shared artifact most pins below read (the episode is deterministic,
    so sharing it loses no coverage)."""
    metrics_dir = str(tmp_path_factory.mktemp("elastic-metrics"))
    report = _run_episode(
        EPISODE_FAULTS, EPISODE_STEPS, metrics_dir=metrics_dir
    )
    return report, metrics_dir


# ---------------------------------------------------------------------- #
# fault grammar
# ---------------------------------------------------------------------- #

def test_parse_elastic_faults_grammar():
    faults = parse_elastic_faults("slice_lost@4:1,slice_return@9,host_hang@2")
    assert [(f.kind, f.step, f.arg) for f in faults] == [
        ("slice_lost", 4, 1), ("slice_return", 9, None), ("host_hang", 2, 8),
    ]
    assert parse_elastic_faults("host_hang@2:3")[0].arg == 3
    with pytest.raises(ValueError):   # slice_lost needs the slice index
        parse_elastic_faults("slice_lost@4")
    with pytest.raises(ValueError):   # slice_return takes no argument
        parse_elastic_faults("slice_return@9:1")
    with pytest.raises(ValueError):   # hang length must be >= 1
        parse_elastic_faults("host_hang@2:0")
    with pytest.raises(ValueError):   # training faults stay in their plan
        parse_elastic_faults("crash@5")


def test_inject_faults_rejects_elastic_kinds_loudly():
    for kind in ELASTIC_FAULT_KINDS:
        arg = ":1" if kind == "slice_lost" else ""
        with pytest.raises(ValueError, match="--elastic-resize"):
            parse_faults(f"{kind}@3{arg}")


# ---------------------------------------------------------------------- #
# heartbeat-staleness monitor (detection is never exit codes)
# ---------------------------------------------------------------------- #

def _beat(mon, step, ranks):
    for r in ranks:
        mon.ingest({"kind": "heartbeat", "step": step, "hb_rank": r})


def test_monitor_declares_slice_lost_past_patience():
    mon = SliceHealthMonitor(8, 2, patience_steps=3, stall_flag_after=1)
    for g in range(4):
        _beat(mon, g, range(8))
    # Slice 1 (ranks 4-7) goes silent after step 3.
    for g in range(4, 8):
        _beat(mon, g, range(4))
        verdict = mon.observe(g)
        if g - 3 > 3:
            assert verdict["lost_slices"] == [1]
        else:
            assert verdict["lost_slices"] == []
    assert mon.observe(7)["lost_slices"] == [1]


def test_monitor_flags_host_stall_once_per_episode():
    mon = SliceHealthMonitor(8, 2, patience_steps=3, stall_flag_after=1)
    _beat(mon, 0, range(8))
    # Rank 3 misses two boundaries: inside patience, past the flag
    # threshold — one host_stall anomaly, not one per boundary.
    _beat(mon, 1, [r for r in range(8) if r != 3])
    _beat(mon, 2, [r for r in range(8) if r != 3])
    assert mon.observe(2)["stalled_ranks"] == [3]
    assert mon.observe(2)["stalled_ranks"] == [3]
    assert mon.host_stalls == 1
    # Recovery clears the flag; a later stall counts again.
    _beat(mon, 3, range(8))
    assert mon.observe(3)["stalled_ranks"] == []
    _beat(mon, 4, [r for r in range(8) if r != 3])
    _beat(mon, 5, [r for r in range(8) if r != 3])
    assert mon.observe(5)["stalled_ranks"] == [3]
    assert mon.host_stalls == 2


def test_monitor_validates_shape():
    with pytest.raises(ValueError):
        SliceHealthMonitor(7, 2)
    with pytest.raises(ValueError):
        SliceHealthMonitor(8, 2, patience_steps=2, stall_flag_after=3)


# ---------------------------------------------------------------------- #
# PeerSnapshotStore: buddy mapping, drop, bit-identical restore
# ---------------------------------------------------------------------- #

class _FakeState:
    """Just the snapshot fields, as host trees with mixed dtypes — the
    bit-identity pin must survive non-f32 leaves byte-exactly."""

    def __init__(self, seed=0):
        rng = np.random.default_rng(seed)
        self.params = {"w": rng.standard_normal((5, 3)).astype(np.float32)}
        self.opt_state = {"mu": rng.standard_normal(7).astype(np.float32),
                          "count": np.asarray(3, np.int32)}
        self.batch_stats = {"mean": rng.standard_normal(4).astype(np.float64)}
        self.grad_sync_residual = {
            "r": rng.standard_normal(6).astype(np.float32)
        }


def _tree_bytes(tree):
    import jax

    return [np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(tree)]


def test_peer_store_buddy_is_same_position_next_slice():
    store = PeerSnapshotStore(8, 2)
    assert store.buddy(0) == 4 and store.buddy(4) == 0
    assert store.buddy(3) == 7 and store.buddy(7) == 3
    # Degraded to one slice: no peer tier.
    assert store.buddy(0, ranks=[0, 1, 2, 3]) is None


def test_peer_store_rejects_lossy_codecs():
    for codec in ("bf16", "int8", "int4", "topk"):
        with pytest.raises(ValueError, match="bit-identity"):
            PeerSnapshotStore(8, 2, codec=codec)


def test_peer_store_restore_survives_slice_loss_bit_identically():
    store = PeerSnapshotStore(8, 2)
    state = _FakeState()
    wire = store.put(3, state)
    assert wire > 0 and store.total_wire_bytes == wire
    store.drop_slice(1)
    step, tree = store.restore()
    assert step == 3
    for field in ("params", "opt_state", "batch_stats",
                  "grad_sync_residual"):
        assert _tree_bytes(tree[field]) == \
            _tree_bytes(getattr(state, field))


def test_peer_store_refuses_when_both_copies_die():
    store = PeerSnapshotStore(8, 2)
    store.put(3, _FakeState())
    store.drop_slice(0)
    store.drop_slice(1)
    with pytest.raises(RuntimeError, match="disk tier"):
        store.restore()


def test_peer_store_refuses_digest_mismatch():
    store = PeerSnapshotStore(8, 2)
    store.put(3, _FakeState())
    rank0 = store._primary[0]
    store._primary[0] = bytes(len(rank0))  # corrupt one row in place
    with pytest.raises(RuntimeError, match="digest"):
        store.restore()
    with pytest.raises(RuntimeError, match="no committed"):
        PeerSnapshotStore(8, 2).restore()


# ---------------------------------------------------------------------- #
# the scripted episode: the acceptance pins
# ---------------------------------------------------------------------- #

def test_episode_shrinks_restores_and_grows_back(episode):
    report, _ = episode
    assert report["world"] == {"initial": 8, "final": 8, "n_slices": 2}
    assert report["final_step"] == EPISODE_STEPS
    # Peer restore is BIT-identical to the last committed snapshot.
    assert report["restore_bit_identical"] is True
    # Loss at 4, patience 3: detection at boundary 7, resumed from the
    # step-6 snapshot; grow-back at the scripted return boundary.
    kinds = [
        (t["transition"], t["step"], t["world_from"], t["world_to"])
        for t in report["transitions"]
    ]
    assert kinds == [
        ("shrink", 7, 8, 4), ("peer_restore", 7, 4, 4), ("grow", 9, 4, 8),
    ]
    assert report["transitions"][0]["lost_slice"] == 1
    assert report["transitions"][0]["resumed_from_step"] == 6
    assert report["transitions"][1]["restore_source"] == "peer"
    assert report["transitions"][2]["returned_slice"] == 1
    assert report["counters"] == {
        "elastic_shrinks": 1,
        "elastic_grows": 1,
        "elastic_peer_restores": 1,
        "elastic_peer_snapshot_bytes":
            report["peer_snapshot_wire_bytes"],
        "elastic_host_stalls": report["host_stalls"],
    }
    assert report["peer_snapshot_wire_bytes"] > 0


def test_episode_preserves_the_global_batch_schedule(episode):
    """The consumed-batch oracle: at EVERY world size the run consumes
    the identical global batch at global step N — shrink re-partitions
    by scaling accumulation, never by changing the batch."""
    report, _ = episode
    oracle = oracle_batch_digests(EPISODE_STEPS)
    steps = report["steps"]
    for row in steps:
        assert row["digest"] == oracle[row["step"]]
        assert row["global_rows"] == 16
        # Half the world, double the microbatches: 16 rows over 4 ranks.
        assert row["accum"] == (4 if row["world"] == 4 else 2)
    # Step 6 ran twice (the discarded original and its replay after the
    # rollback); the executed global sequence is the oracle's 0..11.
    executed = [row["step"] for row in steps]
    assert executed == [0, 1, 2, 3, 4, 5, 6, 6, 7, 8, 9, 10, 11]
    assert {row["world"] for row in steps} == {4, 8}


def test_episode_ledger_attribution_exact(episode):
    """Integer-ns category pins for the whole episode, hand-derived from
    the virtual-clock constants: identity EXACT, shrink-window originals
    + replays classified rework, peer restore under ckpt_restore."""
    report, _ = episode
    led = report["ledger"]
    assert led["identity_ok"]
    cats = led["categories_ns"]
    assert sum(cats.values()) == led["wall_ns"] == int(12.5 * NS)
    # COMPILE 2.0 + the first step's interval 0.375 + two reshape
    # recompiles (shrink + grow) at 0.5 each.
    assert cats["compile"] == int(3.375 * NS)
    assert cats["step_compute"] == int(3.75 * NS)   # 10 fresh steps
    assert cats["data_wait"] == int(1.75 * NS)      # 14 batch pulls
    assert cats["ckpt_save"] == int(1.75 * NS)      # 7 commits
    assert cats["ckpt_restore"] == int(0.25 * NS)   # the one peer hop
    # Step 6's discarded original AND its replay: 2 x (0.25 + 0.125).
    assert cats["rework"] == int(0.75 * NS)
    assert cats["supervisor_backoff"] == int(0.5 * NS)
    assert cats["other"] == int(0.375 * NS)         # grow sync + tail
    assert cats["grad_sync"] == 0
    # 13 dispatches: 1 compile-classified, 10 fresh, and step 6 twice as
    # rework (the rolled-back original + its watermark-classified replay).
    assert led["step_intervals"]["compile"] == 1
    assert led["step_intervals"]["step_compute"] == 10
    assert led["step_intervals"]["rework"] == 2


def test_episode_is_deterministic_run_to_run(episode):
    report, _ = episode
    again = _run_episode(EPISODE_FAULTS, EPISODE_STEPS)
    # The emitter is a pure side channel: the report — transitions,
    # counters, digests, ledger integers — replays identically without
    # one attached, from a different process.
    assert again == report


def test_episode_counters_match_telemetry_and_report(episode):
    """The three-way pin: ElasticWorld's host counters == the emitted
    telemetry == tools/telemetry_report.py's elastic section, and the
    report's own counter-vs-record cross-check passes."""
    from tools.telemetry_report import _format_text, build_report

    report, metrics_dir = episode
    tr = build_report(metrics_dir)
    el = tr["elastic"]
    assert el["counters"] == report["counters"]
    assert all(el["counter_record_check"].values())
    assert el["restore_sources"] == {"peer": 1, "disk": 0}
    assert [t["transition"] for t in el["transitions"]] == \
        ["shrink", "peer_restore", "grow"]
    assert el["world_size_last"] == 8
    text = _format_text(tr)
    assert "elastic: 1 shrink(s) 1 grow(s)" in text
    assert "COUNTERS != RECORDS" not in text


def test_host_hang_flags_stall_without_shrinking():
    """Satellite (a): a stall-without-crash chaos-tests the staleness
    detector's flag band — anomalies and counters fire, nothing dies,
    the world never resizes."""
    report = _run_episode("host_hang@2:2", 6)
    assert report["transitions"] == []
    assert report["world"]["final"] == 8
    assert report["final_step"] == 6
    assert report["host_stalls"] == 1
    assert report["counters"]["elastic_host_stalls"] == 1
    assert report["counters"]["elastic_shrinks"] == 0
    assert report["ledger"]["identity_ok"]
    assert report["ledger"]["categories_ns"]["rework"] == 0


# ---------------------------------------------------------------------- #
# /slo elastic block (satellite b)
# ---------------------------------------------------------------------- #

def test_slo_elastic_block_next_to_goodput():
    ew = ElasticWorld(8, 2)
    ew.count("elastic_shrinks")
    ew.transition("shrink", step=7, world_to=4, lost_slice=1)
    srv = OpsServer(LiveAggregator(), None, port=0, elastic=ew).start()
    try:
        body = urllib.request.urlopen(srv.url + "/slo", timeout=5.0).read()
        el = json.loads(body)["elastic"]
        assert el["world_size"] == 4
        assert el["initial_world_size"] == 8
        assert el["counters"]["elastic_shrinks"] == 1
        assert el["transitions"][0]["transition"] == "shrink"
    finally:
        srv.stop()
    with pytest.raises(ValueError):
        ew.transition("explode", step=0, world_to=8)


def test_elastic_config_defaults_round_trip():
    cfg = ElasticConfig()
    assert cfg.n_slices == 2 and cfg.patience_steps == 3
    assert cfg.stall_flag_after == 1 and cfg.snapshot_every_steps == 2

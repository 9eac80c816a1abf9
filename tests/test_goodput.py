"""Tests for the training goodput ledger (ISSUE 18).

The ledger's contract is exactness, so almost everything here drives a
virtual clock and asserts integer equality, not closeness: per-rank
``sum(categories) == wall`` to the nanosecond, the scripted fault trace
reproducing the exact rework/restore/backoff attribution twice, the
fleet merge's idle-residual identity, and the live ``goodput_fraction``
gauge equal to the post-hoc record because finalize emits both from one
snapshot.  Also covered: the metric-name schema registry + its lint
rule, the telemetry report's goodput section and graceful degradation
when an optional event stream is absent, the flight recorder's merge
edge cases, the ephemeral ``--metrics-port 0`` + ``/slo`` goodput
block, and (slow) the supervised crash-chaos run end to end.
"""

import json
import os
import textwrap
import urllib.request

import pytest

from pytorch_distributed_training_tpu.analysis import lint_source
from pytorch_distributed_training_tpu.analysis.ledger_audit import (
    expected_final_categories_ns, run_ledger_audit,
)
from pytorch_distributed_training_tpu.obs import (
    GoodputLedger,
    LiveAggregator,
    MetricsEmitter,
    OpsServer,
    check_metric_name,
    fleet_ledger,
    load_rank_logs,
    merge_timeline,
    read_events,
    straggler_report,
)
from pytorch_distributed_training_tpu.utils.supervisor import BACKOFF_ENV


class Clock:
    """Virtual monotonic clock; every duration below is a multiple of
    2^-3 s so ns conversion is exact."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


NS = 1_000_000_000


# ---------------------------------------------------------------------- #
# ledger core: identity, quota split, brackets, rework, backoff
# ---------------------------------------------------------------------- #

def test_identity_exact_and_quota_split():
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.set_grad_sync_model(0.25, ici_share=0.5)
    batches = iter([None] * 3)

    def pulls():
        for b in batches:
            clock.advance(0.125)   # data_wait
            yield b

    step = 0
    for _ in led.wrap_batches(pulls()):
        clock.advance(0.5)         # batch-ready -> dispatch
        led.begin_step(step)
        clock.advance(0.25)        # host tail
        step += 1
    clock.advance(0.5)             # epoch tail -> other
    snap = led.finalize()

    cats = snap["categories_ns"]
    assert sum(cats.values()) == snap["wall_ns"]
    assert snap["identity_ok"]
    # step 0 is compile (first dispatched step), steps 1-2 split against
    # the 0.25 s/step quota: grad_sync 0.25 (ICI 0.125 / DCN 0.125),
    # step_compute the remaining 0.5.
    assert cats["compile"] == int(0.75 * NS)
    assert cats["grad_sync"] == int(0.5 * NS)
    assert snap["grad_sync_ici_ns"] == int(0.25 * NS)
    assert snap["grad_sync_dcn_ns"] == int(0.25 * NS)
    assert cats["step_compute"] == int(1.0 * NS)
    assert cats["data_wait"] == int(0.375 * NS)
    assert cats["other"] == int(0.5 * NS)
    assert snap["step_intervals"] == {
        "compile": 1, "step_compute": 2, "rework": 0,
    }
    assert snap["goodput_fraction"] == (
        (cats["step_compute"] + cats["grad_sync"]) / snap["wall_ns"]
    )


def test_bracket_nesting_resumes_interrupted_step_class():
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.begin_step(0)              # compile class
    clock.advance(0.25)
    with led.bracket("ckpt_save"):
        clock.advance(1.0)
    clock.advance(0.125)           # tail resumes the step's class
    snap = led.finalize()
    cats = snap["categories_ns"]
    assert cats["ckpt_save"] == int(1.0 * NS)
    assert cats["compile"] == int(0.375 * NS)
    assert sum(cats.values()) == snap["wall_ns"]
    with pytest.raises(ValueError):
        led.bracket("not_a_category")


def test_rollback_moves_recorded_charges_to_rework():
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.set_grad_sync_model(0.25, ici_share=0.5)
    led.begin_step(0)              # compile
    clock.advance(0.5)
    for step in (1, 2, 3):
        led.begin_step(step)
        clock.advance(0.75)
    before = led.snapshot()
    assert before["categories_ns"]["grad_sync"] == int(0.75 * NS)
    # Anomaly rollback to the snapshot at step 2: the recorded charges
    # of steps >= 2 move to rework (re-classified, never re-counted) and
    # the open step-3 tail re-classes too.  begin_step(k) charges the
    # interval since the previous boundary to step k, so step 1 owns the
    # 0.5 s that elapsed after begin_step(0): grad_sync 0.25 + 0.25
    # step_compute; steps 2 and 3 own 0.75 each, and the 0.75 pending
    # tail plus the 0.25 decision tail land in rework.
    led.note_rollback(2, 3)
    clock.advance(0.25)            # tail after the rollback decision
    snap = led.finalize()
    cats = snap["categories_ns"]
    assert sum(cats.values()) == snap["wall_ns"]
    assert cats["grad_sync"] == int(0.25 * NS)
    assert cats["step_compute"] == int(0.25 * NS)
    assert cats["rework"] == int((0.75 * 2 + 0.75 + 0.25) * NS)
    assert snap["step_intervals"] == {
        "compile": 1, "step_compute": 1, "rework": 2,
    }


def test_restart_watermark_first_step_is_compile_not_rework():
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.set_rework_until(5)
    for step in (3, 4, 5):
        led.begin_step(step)
        clock.advance(0.5)
    snap = led.finalize()
    # step 3: compile takes precedence (the restart recompiles there);
    # step 4 < 5: rework; step 5: fresh.
    assert snap["step_intervals"] == {
        "compile": 1, "step_compute": 1, "rework": 1,
    }
    assert snap["categories_ns"]["rework"] == int(0.5 * NS)


def test_inherited_backoff_widens_wall_and_category(monkeypatch):
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=2.5)
    clock.advance(1.0)
    snap = led.finalize()
    assert snap["inherited_backoff_ns"] == int(2.5 * NS)
    assert snap["categories_ns"]["supervisor_backoff"] == int(2.5 * NS)
    assert snap["wall_ns"] == int(3.5 * NS)
    assert snap["identity_ok"]
    # Default: read from the supervisor's env hand-off.
    monkeypatch.setenv(BACKOFF_ENV, repr(0.25))
    led2 = GoodputLedger(clock=Clock())
    assert led2.inherited_backoff_ns == int(0.25 * NS)


def test_snapshot_is_pure_and_finalize_idempotent(tmp_path):
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.begin_step(0)
    clock.advance(0.5)
    a = led.snapshot()
    b = led.snapshot()
    assert a == b                  # no state advanced by reading
    first = led.finalize()
    clock.advance(10.0)            # after finalize the clock is frozen
    assert led.finalize() == first
    assert led.snapshot()["wall_ns"] == first["wall_ns"]


def test_finalize_emits_gauges_and_record_from_one_snapshot(tmp_path):
    clock = Clock()
    em = MetricsEmitter(str(tmp_path), rank=0, world=1, clock=clock)
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.begin_step(0)
    clock.advance(0.5)
    snap = led.finalize(em)
    em.summary()
    em.close()
    evs = read_events(em.path)
    rec = [e for e in evs if e.get("record") == "goodput_ledger"][0]
    summ = [e for e in evs if e["kind"] == "summary"][0]
    assert rec["goodput_fraction"] == snap["goodput_fraction"]
    assert summ["gauges"]["goodput_fraction"] == snap["goodput_fraction"]
    assert summ["gauges"]["ledger_compile_s"] == snap["seconds"]["compile"]
    assert sum(rec["categories_ns"].values()) == rec["wall_ns"]


def test_progress_file_roundtrip(tmp_path):
    path = str(tmp_path / ".progress")
    led = GoodputLedger(clock=Clock(), progress_path=path,
                        inherited_backoff_s=0.0)
    led.note_progress(3)
    led.note_progress(7)           # in-place rewrite, not append
    led.finalize()
    assert GoodputLedger.read_progress(path) == 7
    assert GoodputLedger.read_progress(str(tmp_path / "nope")) is None
    assert GoodputLedger.read_progress(None) is None


def test_fleet_ledger_identity_and_straggler_attribution():
    def rank_record(wall_s, compute_s):
        return {
            "wall_ns": int(wall_s * NS),
            "categories_ns": {
                "step_compute": int(compute_s * NS),
                "other": int((wall_s - compute_s) * NS),
            },
            "grad_sync_ici_ns": 0,
            "grad_sync_dcn_ns": 0,
        }

    records = {0: rank_record(10.0, 8.0), 1: rank_record(12.0, 8.0)}
    fleet = fleet_ledger(records)
    assert fleet["fleet_wall_ns"] == 2 * int(12.0 * NS)
    assert fleet["idle_gap_ns"] == {0: int(2.0 * NS), 1: 0}
    assert fleet["identity_ok"]
    assert fleet["idle_attributed_to"] == 1  # longest wall by default
    # An explicit straggler (the flight recorder's skew report) wins.
    assert fleet_ledger(records, straggler_rank=0)[
        "idle_attributed_to"] == 0
    with pytest.raises(ValueError):
        fleet_ledger({})


def test_fleet_ledger_ranks_disagree_on_wall_after_elastic_shrink():
    """An elastic shrink (ISSUE 20) leaves the fleet's ranks with
    honestly different wall clocks: a survivor carries the whole run
    (restore + rework + backoff included) while a rank on the returned
    slice only accounts from its re-entry.  The merge must still close
    its identity EXACTLY — every rank's gap to the longest wall is idle
    residual, attributed to the straggler."""
    survivor = {
        "wall_ns": int(20.0 * NS),
        "categories_ns": {
            "step_compute": int(14.0 * NS),
            "ckpt_restore": int(0.25 * NS),
            "rework": int(0.75 * NS),
            "supervisor_backoff": int(0.5 * NS),
            "other": int(4.5 * NS),
        },
        "grad_sync_ici_ns": 0,
        "grad_sync_dcn_ns": 0,
    }
    returned = {   # re-entered mid-run: a much shorter wall, no badput
        "wall_ns": int(6.0 * NS),
        "categories_ns": {
            "step_compute": int(5.5 * NS),
            "other": int(0.5 * NS),
        },
        "grad_sync_ici_ns": 0,
        "grad_sync_dcn_ns": 0,
    }
    fleet = fleet_ledger({0: survivor, 1: survivor, 2: returned})
    assert fleet["identity_ok"]
    assert fleet["fleet_wall_ns"] == 3 * int(20.0 * NS)
    # The returned rank's 14 s gap is idle residual, not invented work.
    assert fleet["idle_gap_ns"] == {0: 0, 1: 0, 2: int(14.0 * NS)}
    assert fleet["idle_gap_total_ns"] == int(14.0 * NS)
    assert sum(fleet["categories_ns"].values()) \
        + fleet["idle_gap_total_ns"] == fleet["fleet_wall_ns"]
    # Survivor badput categories sum across ranks, the returned rank
    # contributing none of them.
    assert fleet["categories_ns"]["rework"] == 2 * int(0.75 * NS)
    assert fleet["categories_ns"]["ckpt_restore"] == 2 * int(0.25 * NS)
    # Longest-wall attribution: a survivor, not the short-wall rank.
    assert fleet["idle_attributed_to"] == 0


# ---------------------------------------------------------------------- #
# the scripted fault-trace audit (graftcheck ledger pass)
# ---------------------------------------------------------------------- #

def test_ledger_audit_fault_trace_exact_and_deterministic():
    findings, report = run_ledger_audit()
    assert findings == []
    assert report["determinism_ok"] and report["identity_ok"]
    assert report["fleet_identity_ok"]
    # The audited run reproduces the hand-derived expectation table
    # EXACTLY (both sides integer ns; compared here in exact seconds).
    expected = {k: v / 1e9 for k, v in expected_final_categories_ns().items()}
    assert report["got_s"] == expected
    assert report["got_s"]["rework"] == 0.75
    assert report["got_s"]["ckpt_restore"] == 2.0
    assert report["got_s"]["supervisor_backoff"] == 2.5


def test_graftcheck_ledger_pass_wired():
    from tools.graftcheck import ALL_PASSES, main as graftcheck_main

    assert "ledger" in ALL_PASSES
    assert graftcheck_main(["--ledger"]) == 0


# ---------------------------------------------------------------------- #
# metric-name schema registry + lint rule (satellite 1)
# ---------------------------------------------------------------------- #

def test_check_metric_name_registry():
    assert check_metric_name("mfu_live", "gauge") is None
    assert check_metric_name("goodput_fraction", "gauge") is None
    assert check_metric_name("mfu-live", "gauge") is not None   # typo
    # wrong instrument for a declared name
    assert check_metric_name("mfu_live", "counter_add") is not None
    # labeled names check their bracket-free base
    assert check_metric_name("ttft_s[tenant=a]", "observe") is None
    # a label suffix on a non-labeled metric is itself a violation
    assert check_metric_name("mfu_live[x=y]", "gauge") is not None
    # dynamic prefixes: a declared-name prefix passes, garbage fails
    assert check_metric_name("ledger_", "gauge", dynamic=True) is None
    assert check_metric_name("bogus_", "gauge", dynamic=True) is not None


def _lint(snippet):
    return lint_source(textwrap.dedent(snippet), "fixture.py")


def test_metric_name_lint_rule_fires_and_passes():
    fired = _lint("""
        def run(emitter):
            emitter.gauge("mfu-live", 0.5)
    """)
    assert [f.rule for f in fired] == ["metric-name"]

    assert _lint("""
        def run(emitter):
            emitter.gauge("mfu_live", 0.5)
            emitter.gauge(f"ledger_{cat}_s", 1.0)
            emitter.observe(labeled("ttft_s", tenant="a"), 0.1)
            emitter.gauge(name, 0.5)   # variable: not statically checkable
    """) == []

    fired = _lint("""
        def run(emitter):
            emitter.gauge(f"bogus_{k}", 1.0)
    """)
    assert [f.rule for f in fired] == ["metric-name"]

    assert _lint("""
        def run(emitter):
            emitter.gauge("mfu-live", 0.5)  # graftcheck: disable=metric-name
    """) == []


# ---------------------------------------------------------------------- #
# telemetry report: goodput section + graceful degradation (satellite 2)
# ---------------------------------------------------------------------- #

def _write_goodput_log(tmp_path, rank, *, extra_step_s=0.0, world=2):
    clock = Clock(100.0 * rank)    # per-rank clocks are NOT aligned
    em = MetricsEmitter(str(tmp_path), rank=rank, world=world, clock=clock)
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.set_grad_sync_model(
        0.25, ici_share=0.5, model={"per_step_s": 0.25}
    )
    for step in range(4):
        led.begin_step(step)
        clock.advance(0.5 + extra_step_s)
        em.step(step, dt=0.5 + extra_step_s, loss=1.0)
    led.finalize(em)
    em.summary()
    em.close()
    return em.path


def test_report_goodput_section_exact(tmp_path):
    _write_goodput_log(tmp_path, 0)
    _write_goodput_log(tmp_path, 1, extra_step_s=0.5)  # the straggler
    from tools.telemetry_report import _format_text, build_report

    report = build_report(str(tmp_path))
    gp = report["goodput"]
    for rank in (0, 1):
        rec = gp["per_rank"][rank]
        assert rec["identity_ok"]
        assert rec["record_fraction_exact"]
        assert rec["live_gauge_exact"]
        chk = rec["grad_sync_model_check"]
        assert chk["charged_s"] <= chk["modeled_s"]
    fleet = gp["fleet"]
    assert fleet["identity_ok"] and fleet["n_ranks"] == 2
    # rank 1 is both the skew straggler and the longest wall: the idle
    # residual (rank 0's gap to it) is attributed there.
    assert fleet["idle_attributed_to"] == 1
    assert fleet["idle_gap_s"][0] == pytest.approx(2.0)
    text = _format_text(report)
    assert "goodput: fleet fraction=" in text
    assert "IDENTITY BROKEN" not in text


def test_report_degrades_when_optional_stream_breaks(tmp_path, monkeypatch):
    _write_goodput_log(tmp_path, 0, world=1)
    import tools.telemetry_report as tr

    def boom(*a, **k):
        raise RuntimeError("stream absent")

    monkeypatch.setattr(tr, "span_events", boom)
    monkeypatch.setattr(tr, "merge_timeline", boom)
    report = tr.build_report(str(tmp_path))
    # The broken streams' sections are omitted with a note each; the
    # goodput section (a different stream) still builds.
    assert "spans" not in report
    assert report["steps"] == 0
    notes = report["notes"]
    assert any(n.startswith("spans:") for n in notes)
    assert any(n.startswith("flight timeline:") for n in notes)
    assert report["goodput"]["per_rank"][0]["identity_ok"]
    assert "note: spans:" in tr._format_text(report)


# ---------------------------------------------------------------------- #
# flight recorder merge edge cases (satellite 4)
# ---------------------------------------------------------------------- #

def _write_flight_log(tmp_path, rank, steps, dt, world=2):
    clock = Clock(50.0 * rank)
    em = MetricsEmitter(str(tmp_path), rank=rank, world=world, clock=clock)
    for step in steps:
        clock.advance(dt)
        em.step(step, dt=dt, loss=1.0)
    em.summary()
    em.close()
    return em.path


def test_flight_merge_single_rank(tmp_path):
    _write_flight_log(tmp_path, 0, range(5), 0.01, world=1)
    logs = load_rank_logs(str(tmp_path))
    timeline = merge_timeline(logs)
    assert [row["step"] for row in timeline] == list(range(5))
    assert all(not row["missing_ranks"] for row in timeline)
    rep = straggler_report(timeline, skew_threshold=1.25)
    # One rank defines the fleet median: it cannot straggle vs itself.
    assert rep["stragglers"] == []


def test_flight_merge_disjoint_step_ranges(tmp_path):
    _write_flight_log(tmp_path, 0, range(0, 4), 0.01)
    _write_flight_log(tmp_path, 1, range(10, 14), 0.01)
    logs = load_rank_logs(str(tmp_path))
    timeline = merge_timeline(logs)
    steps = [row["step"] for row in timeline]
    assert steps == sorted(steps) and set(steps) == set(range(0, 4)) | set(
        range(10, 14)
    )
    for row in timeline:
        assert row["missing_ranks"] == ([1] if row["step"] < 10 else [0])
    # Equal per-step durations: disjoint ranges must NOT read as skew.
    rep = straggler_report(timeline, skew_threshold=1.25)
    assert rep["stragglers"] == []
    assert rep["skew"][0] == pytest.approx(1.0)
    assert rep["skew"][1] == pytest.approx(1.0)


def test_flight_merge_tolerates_truncated_rank_log(tmp_path):
    _write_flight_log(tmp_path, 0, range(4), 0.01)
    path1 = _write_flight_log(tmp_path, 1, range(4), 0.01)
    # Tear rank 1's log mid-final-event (a crashed writer).
    raw = open(path1, "rb").read()
    with open(path1, "wb") as f:
        f.write(raw[: raw.rindex(b"\n{") + 10])
    logs = load_rank_logs(str(tmp_path))
    assert sorted(logs) == [0, 1]
    timeline = merge_timeline(logs)
    rep = straggler_report(timeline, skew_threshold=1.25)
    # The torn tail drops at most the final event; the surviving steps
    # still merge and identical durations still read as no skew.
    assert rep["stragglers"] == []


# ---------------------------------------------------------------------- #
# ephemeral --metrics-port 0 + /slo goodput block (satellite 3)
# ---------------------------------------------------------------------- #

def test_ops_server_port_zero_and_slo_goodput_block():
    clock = Clock()
    led = GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    led.begin_step(0)
    clock.advance(0.5)
    agg = LiveAggregator(clock=clock)
    srv = OpsServer(agg, None, port=0, ledger=led).start()
    try:
        # Port 0 binds an ephemeral port, exposed on the server object
        # (and therefore in the CLI's startup line).
        assert srv.port > 0
        assert f":{srv.port}" in srv.url
        body = urllib.request.urlopen(srv.url + "/slo", timeout=5.0).read()
        gp = json.loads(body)["goodput"]
        assert gp["identity_ok"]
        assert sum(gp["categories_ns"].values()) == gp["wall_ns"]
        assert gp["categories_ns"]["compile"] == int(0.5 * NS)
    finally:
        srv.stop()


# ---------------------------------------------------------------------- #
# supervised crash chaos (slow: real child processes)
# ---------------------------------------------------------------------- #

@pytest.mark.slow
def test_chaos_crash_restart_exact_badput_attribution(tmp_path, monkeypatch):
    """Scripted fault trace through REAL processes: crash before step 5,
    one supervised restart with a pinned 0.25 s backoff (jitter 0), then
    run to completion.  The surviving attempt's ledger must attribute
    exactly: 1 compile + 4 rework + 3 fresh step intervals (progress was
    5; the restarted epoch re-executes 0-4, the first being compile),
    the backoff's 250_000_000 ns to supervisor_backoff, a nonzero
    ckpt_restore, the ns identity, and the live gauge == the record."""
    import sys

    from pytorch_distributed_training_tpu.utils.supervisor import supervise

    ckpt = tmp_path / "ckpt"
    metrics = tmp_path / "metrics"
    argv = [
        sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main",
        "--use-cpu", "--model", "resnet18", "--dataset", "synthetic-images",
        "--image-size", "8", "--batch-size", "8", "--num-workers", "0",
        "--learning-rate", "0.001", "--epochs", "1",
        "--steps-per-epoch", "8", "--checkpoint-dir", str(ckpt),
        "--ckpt-every-steps", "3", "--skip-bad-steps",
        "--inject-faults", "crash@5",
        "--metrics-dir", str(metrics), "--goodput",
    ]
    result = supervise(
        argv,
        max_restarts=2,
        heartbeat_path=str(tmp_path / "hb"),
        heartbeat_timeout_s=120.0,
        poll_s=0.5,
        backoff_base_s=0.25,
        backoff_jitter=0.0,
        _print=lambda *a: None,
    )
    assert result.exit_code == 0 and result.restarts == 1

    evs = read_events(
        str(metrics / "events.rank00000.jsonl"), allow_truncated=True
    )
    rec = [e for e in evs if e.get("record") == "goodput_ledger"][-1]
    summ = [e for e in evs if e["kind"] == "summary"][-1]
    # Exact fault attribution, deterministic across runs: 5 steps were
    # lost to the crash, the restart re-executes them (first = compile).
    assert rec["step_intervals"] == {
        "compile": 1, "rework": 4, "step_compute": 3,
    }
    assert rec["categories_ns"]["supervisor_backoff"] == 250_000_000
    assert rec["inherited_backoff_ns"] == 250_000_000
    assert rec["categories_ns"]["ckpt_restore"] > 0
    assert sum(rec["categories_ns"].values()) == rec["wall_ns"]
    # The live gauge and the post-hoc record are one snapshot.
    assert summ["gauges"]["goodput_fraction"] == rec["goodput_fraction"]
    assert GoodputLedger.read_progress(str(ckpt / ".progress")) == 8

"""Request-scoped tracing (ISSUE 11): span spine, exporter, schema v3.

Contracts pinned here:

1. ``SpanRecorder`` invariants — implicit nesting parents correctly,
   serialization is deferred to flush boundaries, open spans never emit,
   double-end raises, and a disabled recorder is inert end to end.
2. Sampling is deterministic PER CORRELATION ID: two recorders agree
   decision-for-decision over the same ids, and a request either records
   its whole chain or nothing (no partial traces).
3. A real scheduler+engine run correlates: every finished request's
   queued→prefill→decode chain is complete, causally ordered, parented
   under one ``serve/request`` root, and its boundaries EQUAL the SLO
   record's timestamps (span math and histogram math share a source).
4. Spans vs counters: decode/verify tick spans == the engine's
   ``decode_ticks`` counter, in-memory and through the summary event.
5. Exporter roundtrip: the Chrome-trace JSON survives a dump/load cycle
   byte-equal, validates structurally (the stand-in for "loads in
   Perfetto"), and its flow events bind each request's queue span to the
   slot ticks that computed for it.
6. Schema back-compat: a checked-in v2 fixture (and a synthesized v1
   log) still read, validate, and report; span events in a pre-v3 log
   are rejected.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import gpt2_124m
from pytorch_distributed_training_tpu.obs import (
    SCHEMA_VERSION,
    SUPPORTED_SCHEMA_VERSIONS,
    MetricsEmitter,
    SpanRecorder,
    read_events,
    span_events,
    ttft_decomposition,
    validate_events,
)
from pytorch_distributed_training_tpu.serve import (
    ContinuousScheduler,
    Request,
    ServingEngine,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

SHRINK = dict(num_layers=2, hidden_dim=32, num_heads=2, vocab_size=61,
              max_seq_len=32)


class _Clock:
    """Hand-advanced clock so span timestamps are script-exact."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _recorder(tmp_path, **kw):
    em = MetricsEmitter(str(tmp_path), rank=0, world=1)
    clock = kw.pop("clock", _Clock())
    return SpanRecorder(em, clock=clock, **kw), em, clock


# --------------------------------------------------------------------- #
# recorder invariants
# --------------------------------------------------------------------- #


def test_span_nesting_parents_implicitly(tmp_path):
    rec, em, clock = _recorder(tmp_path)
    with rec.span("serve/request", corr="r1", tenant="t0") as root:
        clock.advance(1.0)
        with rec.span("request/prefill", corr="r1") as inner:
            clock.advance(0.5)
        clock.advance(0.25)
        sib = rec.start_span("request/decode", corr="r1")
        clock.advance(0.25)
        rec.end_span(sib, extra="x")
    rec.close()
    em.close()
    events = read_events(em.path)
    validate_events(events)
    spans = {e["span"]: e for e in span_events(events)}
    root_ev = spans["serve/request"]
    assert "parent" not in root_ev
    assert root_ev["attrs"] == {"tenant": "t0"}
    assert root_ev["corr"] == "r1"
    # Both children — the lexical nest and the start/end pair opened
    # inside the with-block — parent to the root via the implicit stack.
    assert spans["request/prefill"]["parent"] == root_ev["sid"]
    assert spans["request/decode"]["parent"] == root_ev["sid"]
    assert spans["request/decode"]["attrs"] == {"extra": "x"}
    # Durations are exact under the scripted clock; the root brackets
    # both children.
    assert spans["request/prefill"]["dur"] == pytest.approx(0.5)
    assert root_ev["dur"] == pytest.approx(2.0)
    assert root_ev["t0"] <= spans["request/prefill"]["t0"]
    assert spans["request/decode"]["t1"] <= root_ev["t1"]
    assert inner.sid != sib.sid != root.sid


def test_explicit_parent_and_timestamps(tmp_path):
    rec, em, _ = _recorder(tmp_path)
    root = rec.start_span("serve/request", corr=7, t0=10.0)
    child = rec.record_span(
        "request/queued", 10.0, 12.5, corr=7, parent=root
    )
    rec.end_span(root, t1=20.0)
    assert child.parent == root.sid
    assert child.dur == pytest.approx(2.5)
    assert root.dur == pytest.approx(10.0)
    # A raw sid works as parent too (cross-object correlation).
    other = rec.record_span("request/decode", 12.5, 20.0, parent=root.sid)
    assert other.parent == root.sid
    em.close()


def test_deferred_serialization_flushes_at_boundaries(tmp_path):
    rec, em, clock = _recorder(tmp_path, flush_every=3)
    for i in range(2):
        rec.record_span("serve/decode", float(i), i + 0.5)
    # Two buffered spans: the log holds only the meta header so far —
    # recording never writes.
    assert span_events(read_events(em.path)) == []
    rec.flush()
    assert len(span_events(read_events(em.path))) == 2
    # flush_every triggers the deferred write on its own.
    for i in range(3):
        rec.record_span("serve/decode", float(i), i + 0.5)
    assert len(span_events(read_events(em.path))) == 5
    em.close()


def test_end_twice_raises_and_close_drops_open(tmp_path):
    rec, em, _ = _recorder(tmp_path)
    s = rec.start_span("train/step")
    rec.end_span(s)
    with pytest.raises(ValueError, match="already ended"):
        rec.end_span(s)
    dangling = rec.start_span("train/host_sync")
    rec.close()
    em.close()
    emitted = {e["sid"] for e in span_events(read_events(em.path))}
    assert s.sid in emitted
    assert dangling.sid not in emitted  # no t1 -> no defined duration


def test_disabled_recorder_is_inert(tmp_path):
    # Disabled emitter and rate 0 both produce an inert recorder: every
    # call returns immediately, so call sites thread one object
    # unconditionally.
    for rec in (
        SpanRecorder(MetricsEmitter(None)),
        SpanRecorder(
            MetricsEmitter(str(tmp_path), rank=0, world=1), sample_rate=0.0
        ),
    ):
        assert not rec.enabled
        assert rec.start_span("train/step") is None
        with rec.span("serve/request", corr=1) as s:
            assert s is None
        rec.end_span(None)
        rec.close()
        assert rec.recorded == 0
    with pytest.raises(ValueError, match="sample_rate"):
        SpanRecorder(MetricsEmitter(None), sample_rate=1.5)


# --------------------------------------------------------------------- #
# sampling
# --------------------------------------------------------------------- #


def test_sampling_deterministic_per_corr(tmp_path):
    rec1, em1, _ = _recorder(tmp_path / "a", sample_rate=0.5)
    rec2, em2, _ = _recorder(tmp_path / "b", sample_rate=0.5)
    ids = [f"req-{i}" for i in range(400)]
    d1 = [rec1.sampled(i) for i in ids]
    d2 = [rec2.sampled(i) for i in ids]
    # Hash of the id, not a coin flip: two recorders (two runs, two
    # processes) agree decision-for-decision.
    assert d1 == d2
    assert 0.35 < sum(d1) / len(d1) < 0.65
    # corr=None (tick/step anatomy) always records; rate 1.0 records all.
    assert rec1.sampled(None)
    full, em3, _ = _recorder(tmp_path / "c", sample_rate=1.0)
    assert all(full.sampled(i) for i in ids)
    for em in (em1, em2, em3):
        em.close()


def test_sampling_is_all_or_nothing_per_request(tmp_path):
    rec, em, _ = _recorder(tmp_path, sample_rate=0.5)
    ids = [f"req-{i}" for i in range(64)]
    kept = [i for i in ids if rec.sampled(i)]
    dropped = [i for i in ids if not rec.sampled(i)]
    assert kept and dropped
    for rid in (kept[0], dropped[0]):
        for name in ("serve/request", "request/queued", "request/decode"):
            rec.record_span(name, 0.0, 1.0, corr=rid)
    rec.close()
    em.close()
    by_corr = {}
    for ev in span_events(read_events(em.path)):
        by_corr.setdefault(ev["corr"], []).append(ev["span"])
    # The sampled request recorded its WHOLE chain; the unsampled one
    # recorded nothing (and was counted, not silently lost).
    assert sorted(by_corr) == [kept[0]]
    assert len(by_corr[kept[0]]) == 3
    assert rec.sampled_out == 3


# --------------------------------------------------------------------- #
# scheduler + engine correlation (one traced serving run, shared)
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def traced_serve(tmp_path_factory):
    m = gpt2_124m(cfg_overrides=SHRINK)
    params = m.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32), train=False
    )["params"]
    engine = ServingEngine(
        m, params, num_slots=3, max_len=32, prefill_chunk=4, temperature=0.0
    )
    td = tmp_path_factory.mktemp("traced_serve")
    emitter = MetricsEmitter(str(td), rank=0, world=1, meta={"mode": "serve"})
    spans = SpanRecorder(emitter)
    sched = ContinuousScheduler(engine, emitter=emitter, spans=spans)
    rng = np.random.default_rng(7)
    for i, budget in enumerate((6, 4, 8, 5, 7)):
        prompt = rng.integers(
            0, 61, (int(rng.integers(3, 10)),)
        ).astype(np.int32)
        sched.submit(Request(
            i, prompt, budget, arrival_time=time.monotonic(),
            tenant="a" if i % 2 else "b",
        ))
    while not sched.idle:
        sched.tick()
    spans.close()
    summary = emitter.summary()
    emitter.close()
    return str(td), sched, engine, summary


def test_request_chains_complete_and_match_records(traced_serve):
    td, sched, _, _ = traced_serve
    events = read_events(os.path.join(td, "events.rank00000.jsonl"))
    validate_events(events)
    # Spans were introduced at v3; the current writer version has moved
    # on (v4 added alerts) but stays in the supported matrix.
    assert events[0]["schema"] == SCHEMA_VERSION
    assert SCHEMA_VERSION >= 3
    by_corr: dict = {}
    for ev in span_events(events):
        if ev.get("corr") is not None:
            by_corr.setdefault(ev["corr"], {})[ev["span"]] = ev
    assert len(sched.completed) == 5
    for rec in sched.completed:
        chain = by_corr[rec["id"]]
        root = chain["serve/request"]
        q, p, d = (
            chain["request/queued"], chain["request/prefill"],
            chain["request/decode"],
        )
        # Boundaries EQUAL the SLO record's own timestamps — the spans
        # are derived from them, so the two layers cannot disagree.
        assert q["t0"] == rec["arrival"] and q["t1"] == rec["admitted"]
        assert p["t0"] == rec["admitted"] and p["t1"] == rec["first_token"]
        assert d["t0"] == rec["first_token"] and d["t1"] == rec["finish"]
        assert root["t0"] == rec["arrival"] and root["t1"] == rec["finish"]
        assert all(ev["parent"] == root["sid"] for ev in (q, p, d))
        assert root["attrs"]["tenant"] == rec["tenant"]
        assert root["attrs"]["finish_reason"] == rec["finish_reason"]


def test_tick_spans_carry_slot_attribution(traced_serve):
    td, sched, _, _ = traced_serve
    events = read_events(os.path.join(td, "events.rank00000.jsonl"))
    ticks = [
        e for e in span_events(events)
        if e["span"] in ("serve/prefill", "serve/decode")
    ]
    assert any(e["span"] == "serve/prefill" for e in ticks)
    served = set()
    for ev in ticks:
        slots = ev["attrs"]["slots"]
        assert slots, ev
        for entry in slots:
            assert 0 <= entry[0] < 3  # slot index within the pool
            served.add(entry[1])
    # Every request's compute is attributed to at least one tick span.
    assert served == {rec["id"] for rec in sched.completed}


def test_decode_tick_spans_equal_counter(traced_serve):
    td, _, engine, summary = traced_serve
    events = read_events(os.path.join(td, "events.rank00000.jsonl"))
    tick_spans = [
        e for e in span_events(events)
        if e["span"] in ("serve/decode", "serve/verify")
    ]
    assert len(tick_spans) == engine.decode_ticks
    assert len(tick_spans) == summary["counters"]["decode_ticks"]


def test_ttft_decomposition_sums_and_matches_histogram(traced_serve):
    td, _, _, summary = traced_serve
    events = read_events(os.path.join(td, "events.rank00000.jsonl"))
    dc = ttft_decomposition(span_events(events))
    assert dc["requests"] == 5
    # queue + prefill + sched == TTFT by construction, means included.
    total = (
        dc["queue_wait_s"]["mean"] + dc["prefill_compute_s"]["mean"]
        + dc["sched_delay_s"]["mean"]
    )
    assert total == pytest.approx(dc["ttft_s"]["mean"], abs=1e-12)
    # Span-side p50 vs the histogram the scheduler reduced independently:
    # exact at full sampling (same record timestamps, same percentile fn).
    assert dc["ttft_s"]["p50"] == pytest.approx(
        summary["histograms"]["ttft_s"]["p50"], abs=1e-9
    )
    assert sorted(dc["per_tenant"]) == ["a", "b"]
    assert sum(
        sub["requests"] for sub in dc["per_tenant"].values()
    ) == 5


def test_ttft_decomposition_empty_and_shed():
    assert ttft_decomposition([]) is None
    # A shed request (queued leg only, no prefill window) contributes no
    # row — the histograms exclude it too, so the cross-check stays exact.
    shed_only = [
        {"kind": "span", "span": "serve/request", "sid": 1, "corr": "r",
         "t0": 0.0, "t1": 1.0, "dur": 1.0,
         "attrs": {"finish_reason": "shed"}},
        {"kind": "span", "span": "request/queued", "sid": 2, "corr": "r",
         "t0": 0.0, "t1": 1.0, "dur": 1.0, "parent": 1},
    ]
    assert ttft_decomposition(shed_only) is None


# --------------------------------------------------------------------- #
# exporter
# --------------------------------------------------------------------- #


def test_exporter_roundtrip_and_flows_bind(traced_serve, tmp_path):
    from tools.trace_export import export_trace, validate_chrome_trace

    td, sched, _, _ = traced_serve
    out = str(tmp_path / "trace.json")
    trace = export_trace(td, out)
    # Golden-file roundtrip: the written JSON reloads byte-equivalent and
    # still validates — what Perfetto/chrome://tracing will parse.
    with open(out) as f:
        loaded = json.load(f)
    assert loaded == trace
    validate_chrome_trace(loaded)
    events = trace["traceEvents"]
    # One flow per computed request, binding its queue span to slot ticks.
    flow_ids = {e["id"] for e in events if e.get("ph") == "s"}
    assert len(flow_ids) == len(sched.completed) == 5
    # Track metadata: the rank process row, per-slot tracks, and one
    # request lane per traced request.
    names = {
        (e["name"], e["args"]["name"])
        for e in events if e.get("ph") == "M"
    }
    assert ("process_name", "rank 0") in names
    assert ("thread_name", "slot 0") in names
    assert sum(
        1 for kind, label in names
        if kind == "thread_name" and label.startswith("request ")
    ) == 5
    # Slot slices carry the request attribution the flow arrows follow.
    slot_slices = [
        e for e in events if e.get("ph") == "X" and e.get("cat") == "engine"
    ]
    assert slot_slices
    assert all("request" in e["args"] for e in slot_slices)


def test_router_route_spans_and_replica_rows(tmp_path):
    from pytorch_distributed_training_tpu.serve import ReplicaRouter
    from tools.trace_export import build_trace, validate_chrome_trace

    m = gpt2_124m(cfg_overrides=SHRINK)
    params = m.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32), train=False
    )["params"]
    engines = [
        ServingEngine(
            m, params, num_slots=2, max_len=32, prefill_chunk=4,
            temperature=0.0,
        )
        for _ in range(2)
    ]
    emitter = MetricsEmitter(str(tmp_path), rank=0, world=1,
                             meta={"mode": "serve"})
    spans = SpanRecorder(emitter)
    router = ReplicaRouter(
        engines, max_queue=8, emitter=emitter, affinity=False, spans=spans,
    )
    rng = np.random.default_rng(3)
    for i in range(4):
        prompt = rng.integers(0, 61, (5,)).astype(np.int32)
        router.submit(Request(i, prompt, 4, arrival_time=time.monotonic()))
    while not router.idle:
        router.tick()
    spans.close()
    emitter.summary()
    emitter.close()
    events = read_events(emitter.path)
    validate_events(events)
    all_spans = span_events(events)
    # One route-decision span per submitted request, first link of the
    # chain: which replica, by which rule, and that the queue took it.
    routes = {e["corr"]: e for e in all_spans if e["span"] == "router/route"}
    assert sorted(routes) == [0, 1, 2, 3]
    for ev in routes.values():
        assert ev["attrs"]["decision"] == "least_loaded"
        assert ev["attrs"]["accepted"] is True
        assert ev["attrs"]["replica"] in (0, 1)
    # Least-loaded over two idle replicas spreads 4 requests 2/2 — both
    # replicas computed, so BOTH must appear as replica-attributed tick
    # spans (two replicas' slot 0 must never collide on one track).
    tick_replicas = {
        ev["attrs"]["replica"] for ev in all_spans
        if ev["span"] in ("serve/prefill", "serve/decode")
    }
    assert tick_replicas == {0, 1}
    # Lifecycle roots carry the replica too (the scheduler stamps its
    # records), so request lanes group under replica process rows.
    roots = [e for e in all_spans if e["span"] == "serve/request"]
    assert {e["attrs"]["replica"] for e in roots} == {0, 1}
    trace = build_trace(str(tmp_path))
    validate_chrome_trace(trace)
    process_names = {
        e["args"]["name"] for e in trace["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert {"replica 0", "replica 1"} <= process_names


def test_exporter_validator_rejects_unbound_flow():
    from tools.trace_export import validate_chrome_trace

    good = {"traceEvents": [
        {"ph": "X", "name": "q", "cat": "request", "pid": 1, "tid": 1,
         "ts": 0.0, "dur": 5.0, "args": {}},
        {"ph": "X", "name": "tick", "cat": "engine", "pid": 1, "tid": 2,
         "ts": 6.0, "dur": 2.0, "args": {}},
        {"ph": "s", "id": 1, "cat": "request", "name": "request",
         "pid": 1, "tid": 1, "ts": 4.0},
        {"ph": "f", "bp": "e", "id": 1, "cat": "request", "name": "request",
         "pid": 1, "tid": 2, "ts": 7.0},
    ]}
    validate_chrome_trace(good)
    # An arrow endpoint outside every slice on its row is exactly the
    # failure mode that renders as a dangling arrow in the UI.
    bad = json.loads(json.dumps(good))
    bad["traceEvents"][3]["ts"] = 9.5
    with pytest.raises(ValueError, match="binds to no slice"):
        validate_chrome_trace(bad)
    # Flows must open with 's' before their steps/finish.
    headless = {"traceEvents": good["traceEvents"][:2] + [
        {"ph": "f", "bp": "e", "id": 2, "cat": "request", "name": "request",
         "pid": 1, "tid": 2, "ts": 7.0},
    ]}
    with pytest.raises(ValueError, match="start with one 's'"):
        validate_chrome_trace(headless)


# --------------------------------------------------------------------- #
# trainer integration
# --------------------------------------------------------------------- #


def _tiny_trainer(emitter=None, spans=None, *, log_every=2, microbatches=1,
                  train_step=None, anatomy=None):
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models.gpt2 import (
        GPT2, GPT2Config,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import DDP_RULES
    from pytorch_distributed_training_tpu.train import (
        Trainer, TrainerConfig, create_train_state, make_train_step,
    )

    cfg = GPT2Config(
        vocab_size=64, max_seq_len=8, num_layers=1, num_heads=2,
        hidden_dim=16,
    )
    mesh = make_mesh(MeshConfig(data=-1))
    state = create_train_state(
        GPT2(cfg=cfg), jax.random.PRNGKey(0), jnp.zeros((8, 8), jnp.int32),
        optax.adam(1e-3), mesh=mesh, rules=DDP_RULES,
        init_kwargs={"train": False},
    )
    trainer = Trainer(
        state,
        train_step or make_train_step(kind="lm", num_microbatches=microbatches),
        mesh, TrainerConfig(progress=False, log_every=log_every, prefetch=0),
        emitter=emitter, spans=spans, anatomy=anatomy,
    )
    batch = {"tokens": np.random.default_rng(0).integers(
        0, 64, (8, 8), np.int32
    )}
    return trainer, batch


def test_trainer_step_spans_and_anatomy(tmp_path):
    emitter = MetricsEmitter(str(tmp_path), rank=0, world=1)
    spans = SpanRecorder(emitter)
    anatomy = {
        "microbatches": 2, "grad_sync": "hier",
        "sync_tiers": ["grad_sync/rs_ici", "grad_sync/ar_dcn",
                       "grad_sync/ag_ici"],
    }
    trainer, batch = _tiny_trainer(emitter, spans, log_every=1,
                                   anatomy=anatomy)
    trainer.run_epoch([batch] * 3, epoch=0)
    spans.close()
    emitter.close()
    events = read_events(emitter.path)
    validate_events(events)
    spans_by_name: dict = {}
    for ev in span_events(events):
        spans_by_name.setdefault(ev["span"], []).append(ev)
    steps = spans_by_name["train/step"]
    assert [e["corr"] for e in steps] == [0, 1, 2]
    # The step span carries the compiled-in anatomy (what ONE program
    # contains) — measured sub-phase timelines stay xprof's job.
    for ev in steps:
        assert ev["attrs"]["microbatches"] == 2
        assert ev["attrs"]["sync_tiers"] == anatomy["sync_tiers"]
    # log_every=1: every step's loss fetch is a host_sync child of its
    # own step span, and so is the pull of its batch; the fetch that
    # closes the epoch comes after the last step span and has no parent.
    step_sids = {e["corr"]: e["sid"] for e in steps}
    *syncs, closing = spans_by_name["train/host_sync"]
    assert len(syncs) == 3
    assert all(e["parent"] == step_sids[e["corr"]] for e in syncs)
    assert "parent" not in closing and closing["corr"] == 2
    *waits, exhausted = spans_by_name["train/input_wait"]
    assert [e["corr"] for e in waits] == [0, 1, 2]
    assert all(e["parent"] == step_sids[e["corr"]] for e in waits)
    # the pull that found the loader empty: input time, but no step followed
    assert exhausted["corr"] == 3 and "parent" not in exhausted
    by_sid = {e["sid"]: e for e in steps}
    assert all(by_sid[e["parent"]]["t0"] <= e["t0"] and e["t1"] <= by_sid[e["parent"]]["t1"]
               for e in syncs + waits)


# --------------------------------------------------------------------- #
# schema back-compat
# --------------------------------------------------------------------- #


def test_v2_fixture_reads_validates_and_reports():
    from tools.telemetry_report import build_report

    path = os.path.join(FIXTURES, "v2_metrics_dir",
                        "events.rank00000.jsonl")
    events = read_events(path)
    validate_events(events)  # v2 is a supported reader version
    assert events[0]["schema"] == 2
    assert 2 in SUPPORTED_SCHEMA_VERSIONS
    report = build_report(os.path.join(FIXTURES, "v2_metrics_dir"))
    assert report["ranks"] == [0]
    assert report["counters_per_rank"]["dcn_bytes"][0] == 2048.0
    # No spans in a v2 log: the decomposition section must not appear.
    assert "spans" not in report
    assert "ttft_decomposition" not in report.get("serving", {})


def test_v1_log_still_validates(tmp_path):
    path = os.path.join(FIXTURES, "v2_metrics_dir",
                        "events.rank00000.jsonl")
    events = read_events(path)
    v1 = [dict(ev, v=1) for ev in events]
    v1[0]["schema"] = 1
    validate_events(v1)


def test_span_events_rejected_in_pre_v3_logs():
    path = os.path.join(FIXTURES, "v2_metrics_dir",
                        "events.rank00000.jsonl")
    events = read_events(path)
    spanned = events + [{
        "v": 2, "t": events[-1]["t"] + 1.0, "rank": 0, "kind": "span",
        "span": "serve/request", "sid": 1, "t0": 0.0, "t1": 1.0, "dur": 1.0,
    }]
    with pytest.raises(ValueError, match="spans are v3"):
        validate_events(spanned)


def test_validate_events_rejects_malformed_spans(tmp_path):
    em = MetricsEmitter(str(tmp_path), rank=0, world=1)
    em.close()
    meta = read_events(em.path)
    for bad, msg in (
        ({"span": "x", "sid": "not-int", "t0": 0.0, "t1": 1.0, "dur": 1.0},
         "str span name / int sid"),
        ({"span": "x", "sid": 1, "t0": 0.0, "dur": 1.0}, "not numeric"),
        ({"span": "x", "sid": 1, "t0": 2.0, "t1": 1.0, "dur": -1.0},
         "t1 < t0"),
    ):
        ev = {"v": 3, "t": meta[-1]["t"] + 1.0, "rank": 0, "kind": "span",
              **bad}
        with pytest.raises(ValueError, match=msg):
            validate_events(meta + [ev])


# --------------------------------------------------------------------- #
# the program's own instruments (ISSUE 26): compile events, kernel names,
# the loop's host boundaries on the profiler's clock, the step's scopes,
# the step time a host sync closes
# --------------------------------------------------------------------- #


def test_compile_events_name_the_function_and_the_open_phase():
    from pytorch_distributed_training_tpu.utils import compile_cache as cc

    cc.enable_compile_cache()       # the session did already: registers once

    @jax.jit
    def issue26_probe(x):
        return (x @ x).sum()

    x = jnp.ones((16, 16))          # made outside the phase: its own compiles are not the probe's
    before = len(cc.compile_events())
    with cc.compile_phase("train/epoch", epoch=7):
        issue26_probe(x).block_until_ready()
    mine = [e for e in cc.compile_events(before)
            if e["fun_name"] in ("issue26_probe", "jit(issue26_probe)")]
    assert [e["what"] for e in mine] == ["trace", "lower", "backend_compile"]
    assert all(e["phase"] == "train/epoch" and e["epoch"] == 7 for e in mine)
    assert all(e["seconds"] > 0 and e["t_end"] > 0 for e in mine)
    totals = cc.compile_totals(mine)
    assert totals["compiles"] == 1
    # the three spans follow one another, so their union is their sum
    assert totals["compile_s"] == pytest.approx(sum(e["seconds"] for e in mine), rel=1e-6)
    # a second call compiles nothing, and a record made outside any phase says so
    after = len(cc.compile_events())
    issue26_probe(x).block_until_ready()
    assert cc.compile_events(after) == []
    with pytest.raises(ValueError):
        with cc.compile_phase("train/typo"):
            pass


def test_compile_totals_take_the_union_of_nested_spans():
    from pytorch_distributed_training_tpu.utils.compile_cache import (
        compile_totals,
    )

    def ev(what, t0, t1):
        return {"what": what, "fun_name": None, "seconds": t1 - t0,
                "t_end": t1, "phase": None}

    events = [ev("trace", 0.0, 10.0), ev("trace", 2.0, 5.0),     # an inner function's trace
              ev("lower", 10.0, 11.0), ev("backend_compile", 12.0, 15.0),
              ev("cache_retrieval", 12.5, 13.0), ev("cache_hit", 13.0, 13.0),
              ev("cache_miss", 14.0, 14.0), ev("cache_miss", 14.0, 14.0)]
    assert compile_totals(events) == {
        "compiles": 1, "compile_s": 14.0, "cache_hits": 1, "cache_misses": 2,
    }


def test_run_epoch_counts_its_own_compiles():
    trainer, batch = _tiny_trainer()
    first = trainer.run_epoch([batch] * 2, epoch=0)
    second = trainer.run_epoch([batch] * 2, epoch=1)
    assert first["compiles"] > 0 and first["compile_s"] > 0
    assert second["compiles"] == 0 and second["compile_s"] == 0.0
    assert "rolling_examples_per_sec" not in first


def _pallas_call_names(tree):
    """``(lineno, name node)`` of every ``pl.pallas_call(...)`` and of every
    call of a launcher that takes the kernel's name as an argument."""
    import ast

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_pallas = isinstance(f, ast.Attribute) and f.attr == "pallas_call"
        is_launcher = isinstance(f, ast.Name) and f.id == "_paged_multi_call"
        if is_pallas or is_launcher:
            named = [k.value for k in node.keywords if k.arg == "name"]
            yield node.lineno, is_pallas, (named[0] if named else None)


def test_every_pallas_call_has_a_role_name():
    import ast
    import inspect

    from pytorch_distributed_training_tpu.ops import pallas_attention as pa

    tree = ast.parse(inspect.getsource(pa))
    sites = list(_pallas_call_names(tree))
    assert sum(is_pallas for _, is_pallas, _ in sites) == 15
    seen = set()
    for lineno, is_pallas, name in sites:
        assert name is not None, f"pallas_attention.py:{lineno}: no name="
        if isinstance(name, ast.Constant):
            assert name.value in pa.KERNEL_NAMES, (lineno, name.value)
            seen.add(name.value)
        elif isinstance(name, ast.IfExp):
            # the tabled multi-tile launchers: one name under the
            # block-diffusion mask, the causal kernels' own otherwise
            for branch in (name.body, name.orelse):
                assert isinstance(branch, ast.Constant) and branch.value in pa.KERNEL_NAMES, lineno
                seen.add(branch.value)
        else:
            # only the shared paged launcher forwards its caller's name
            assert is_pallas and isinstance(name, ast.Name) and name.id == "name", lineno
    assert seen == set(pa.KERNEL_NAMES)      # no name in the tuple that no call uses


def test_flash_kernels_lower_under_their_names():
    from pytorch_distributed_training_tpu.ops import pallas_attention as pa

    q = jnp.ones((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, causal=True) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q).as_text(debug_info=True)
    assert "flash_fwd" in text and "flash_bwd" in text


def test_traced_epoch_puts_the_loops_boundaries_on_the_profilers_clock(tmp_path):
    """A capture of a tiny epoch on the CPU: the host plane holds the step
    marker, the batch pulls and the loss fetches under their names."""
    from benchmark import tracered

    trainer, batch = _tiny_trainer(log_every=1)
    trainer.run_epoch([batch] * 2, epoch=0)          # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.run_epoch([batch] * 3, epoch=1)
    finally:
        jax.profiler.stop_trace()
    host = tracered.host_events(tracered.load_events(str(tmp_path)))
    count = {n: sum(e[0] == n for e in host)
             for n in ("train", "train/input_wait", "train/host_sync")}
    assert count["train"] == 3
    assert count["train/input_wait"] == 4        # three batches and the pull that found none
    assert count["train/host_sync"] == 4         # three log points and the closing fetch


def test_compiled_step_names_its_phases():
    trainer, batch = _tiny_trainer(microbatches=2)
    with trainer.mesh:
        text = trainer.train_step.lower(trainer.state, batch).compile().as_text()
    import re

    op_names = re.findall(r'op_name="([^"]+)"', text)
    for phase in ("train/loss", "train/optimizer", "grad_accum/microbatch"):
        assert any(phase in name for name in op_names), phase
    # the loss runs inside a microbatch; the optimizer after all of them
    assert any("grad_accum/microbatch" in n and "train/loss" in n for n in op_names)
    assert not any("grad_accum/microbatch" in n and "train/optimizer" in n for n in op_names)


def test_mfu_live_is_the_step_time_a_sync_closes(tmp_path):
    """A stub step that takes 30 ms on the host: dispatch and "device" are
    one here, so the sync-closed step time is 30 ms; before the second
    sync there is no value."""
    sleep_s, flops, peak = 0.03, 3.0e9, 1.0e12

    def stub_step(state, batch):
        time.sleep(sleep_s)
        return state, {"loss": np.float32(1.0)}

    emitter = MetricsEmitter(str(tmp_path), rank=0, world=1)
    trainer, batch = _tiny_trainer(emitter, log_every=2, train_step=stub_step)
    trainer.step_flops, trainer.peak_flops = flops, peak
    trainer.run_epoch([batch], epoch=0)              # one sync at step 0, the closing one on the same step
    assert "mfu_live" not in emitter._gauges
    trainer.run_epoch([batch] * 5, epoch=1)          # syncs at 0, 2, 4: two closed intervals of two steps
    live = emitter._gauges["mfu_live"]
    assert live == pytest.approx(flops / sleep_s / peak, rel=0.25)
    assert live < flops / sleep_s / peak             # a step cannot be closed faster than it ran
    emitter.close()
    steps = [e for e in read_events(emitter.path) if e["kind"] == "step"]
    assert [("steps_per_sec" in e) for e in steps] == [False, False, False, True, False, True]
    assert all(e["steps_per_sec"] < 1 / sleep_s for e in steps if "steps_per_sec" in e)
    assert all("dt" in e for e in steps)             # the dispatch interval stays what it was


def _vocabulary(name):
    from pytorch_distributed_training_tpu.obs import trace as obs_trace, spans as obs_spans
    from pytorch_distributed_training_tpu.ops import pallas_attention
    from pytorch_distributed_training_tpu.utils import compile_cache

    return {"PHASES": obs_trace.PHASES, "SPAN_NAMES": obs_spans.SPAN_NAMES,
            "COMPILE_PHASES": compile_cache.COMPILE_PHASES,
            "KERNEL_NAMES": pallas_attention.KERNEL_NAMES}[name]


@pytest.mark.parametrize("name, members", [
    # Renaming an xprof phase, a span, a compile phase or a kernel
    # invalidates saved traces, the README tables and the benchmark's
    # readers; make it a deliberate act.
    ("PHASES", {
        "train/step", "train/input_wait", "train/host_sync", "train/loss",
        "train/optimizer", "train/noise", "attn/block_diffusion",
        "attn/mla", "moe/route", "moe/experts", "moe/shared", "train/mtp",
        "ssm/conv", "ssm/scan", "ssm/gate",
        # PR 36: where the time is and no name was (the scope table's shares)
        "train/head", "block/norm", "block/mlp", "attn/proj", "attn/core", "ssm/proj",
        # the Gated DeltaNet mixer's four
        "gdn/proj", "gdn/conv", "gdn/delta", "gdn/gate",
        "grad_accum/microbatch",
        "grad_sync/rs_ici", "grad_sync/ar_dcn", "grad_sync/ag_ici",
        "grad_sync/stripe",
        "pipeline/tick", "serve/prefill", "serve/decode", "serve/verify",
    }),
    ("SPAN_NAMES", {
        "serve/request", "request/queued", "request/prefill",
        "request/decode", "router/route",
        "serve/prefill", "serve/decode", "serve/verify",
        "train/step", "train/input_wait", "train/host_sync",
        "train/snapshot", "train/checkpoint",
    }),
    ("COMPILE_PHASES", {
        "startup/state", "startup/restore", "startup/step", "train/epoch",
        "trace/scopes",
    }),
    ("KERNEL_NAMES", {
        "flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv",
        "flash_bd_fwd", "flash_bd_bwd",
        "decode_attn", "decode_multi_attn", "paged_decode_attn",
        "paged_verify_attn", "paged_prefill_attn",
    }),
])
def test_vocabulary_is_stable(name, members):
    vocabulary = _vocabulary(name)
    assert set(vocabulary) == members and len(vocabulary) == len(members)

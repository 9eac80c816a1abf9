"""Iteration-level continuous batching: admit into freed slots every tick.

The scheduler is the host-side control loop around ``ServingEngine``:

- **FIFO queue + admission control**: ``submit`` enqueues (or refuses — a
  bounded queue is the backpressure signal a front-end needs to shed load
  instead of silently building unbounded latency), and every ``tick``
  drains the queue head into freed slots BEFORE stepping the engine — a
  request admitted the same tick a slot frees is what keeps decode slots
  full (the whole point: GEN_ROOFLINE (deleted: not measured on the current
  machine) shows throughput scales with
  live batch).
- **One engine tick per scheduler tick**: a prefill chunk for loading
  slots interleaved with a decode token for generating slots.
- **SLO record keeping**: per-request arrival/admission/first-token/finish
  timestamps and queue-depth samples, finalized into TTFT/TPOT records
  (serve/metrics.py) and optionally appended as per-request JSONL
  (utils/metrics.py::RequestLogger).

Time is injected (``clock``) so scripted traces run deterministically in
tests (``VirtualClock``) while the bench uses the wall clock.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import numpy as np

from ..obs import labeled
from .engine import ServingEngine
from .metrics import finalize_record


@dataclasses.dataclass
class Request:
    id: Any
    prompt: np.ndarray  # (P,) int32 token ids
    max_new_tokens: int
    arrival_time: float = 0.0
    # Absolute admission deadline (scheduler-clock seconds): a request
    # still queued past it is SHED at the next tick instead of admitted —
    # the load-shedding half of the backpressure contract (a bounded
    # queue refuses new work; a deadline drops work that went stale
    # waiting).  None = wait forever.
    deadline: float | None = None
    # Fair-admission class (hashable; None = the shared default class).
    # Admission pops ROUND-ROBIN across the tenants present in the queue,
    # FIFO within each tenant — one tenant's burst ahead of another
    # tenant's request no longer starves it behind the whole burst
    # (serving QoS).  Single-tenant queues reduce exactly to plain FIFO.
    tenant: Any = None


# Initial rotation sentinel: distinct from every legal tenant value
# (None included — it is the default tenant class).
_NO_TENANT = object()


class VirtualClock:
    """Deterministic clock for scripted traces: time moves only when the
    test advances it."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


class ContinuousScheduler:
    def __init__(
        self,
        engine: ServingEngine,
        *,
        max_queue: int = 64,
        clock: Callable[[], float] = time.monotonic,
        request_logger=None,
        emitter=None,
        replica: int | None = None,
        spans=None,
        slo=None,
        policy=None,
    ):
        self.engine = engine
        # Admission policy (serve/policy.py): when present, the
        # weighted-deficit pop replaces the unweighted tenant rotation
        # in _admit_candidate (per-queue deficit state lives on this
        # scheduler; the policy object is shared tier-wide).
        self.policy = policy
        self.max_queue = max_queue
        self.clock = clock
        self.request_logger = request_logger
        # Live SLO plane (obs/slo.py): evaluated once per tick AFTER the
        # tick's records land, so burn-rate transitions are a
        # deterministic function of the scripted trace (the policy never
        # runs its own thread).  None = no policy, zero cost.
        self.slo = slo
        # Request-scoped tracing (obs/spans.py): the scheduler owns the
        # lifecycle chain — serve/request root with queued/prefill/decode
        # children, derived from the SAME record timestamps the TTFT/TPOT
        # histograms reduce, so span math and histogram math cannot
        # disagree — and hands the recorder to the engine for the
        # slot-attributed tick spans.  None = tracing off, zero cost.
        self.spans = spans
        if spans is not None:
            engine.spans = spans
            # Replica id rides the engine's tick spans so the exporter
            # groups slot tracks under the owning replica's process row
            # (two replicas' slot 0 must not collide on one track).
            engine.spans_replica = replica
        # Replica id under a data-parallel router (serve/router.py):
        # stamped on every record (and through it every RequestLogger
        # JSONL line and metrics summary) so multi-replica runs stay
        # attributable after the records merge.
        self.replica = replica
        self.queue: deque[Request] = deque()
        # Brown-out shedding margin (serve/failover.py): while the tier
        # runs under capacity after a replica death, the failover
        # controller raises this above zero and queued requests shed
        # this many seconds BEFORE their deadline — refusing work that
        # will miss its SLO anyway instead of letting the queue grow
        # unboundedly on the survivors.  0.0 = the normal contract.
        self.brownout_margin = 0.0
        # Round-robin fair admission: the tenant admitted most recently
        # (the rotation resumes AFTER it next tick).  A private sentinel,
        # NOT None — None is a legal tenant (the default class), and
        # seeding the rotation with it would let the first mixed-tenant
        # tick skip past older default-class requests as if a turn had
        # already been taken.
        self._last_tenant: Any = _NO_TENANT
        # Tenants currently queued -> queued-request count: the
        # single-tenant fast path key (the common no-QoS case admits at
        # the old O(1) popleft instead of scanning the deque).
        self._tenant_counts: dict = {}
        self.records: dict[Any, dict] = {}
        self.completed: list[dict] = []
        self.rejected = 0
        self.shed = 0
        self.cancelled = 0
        self.queue_depth_samples: list[int] = []
        self.active_slot_samples: list[int] = []
        self._last_stats: dict = {}
        # Telemetry spine (obs/): per-tick queue-depth gauge + saturation
        # anomalies via the flight recorder, TTFT/TPOT histograms on finish.
        self.recorder = None
        if emitter is not None:
            from ..obs import FlightRecorder

            self.emitter = emitter
            self.recorder = FlightRecorder(emitter)
        else:
            self.emitter = None

    # ------------------------------------------------------------------ #

    def submit(self, request: Request, *, force: bool = False) -> bool:
        """Enqueue a request; False = refused (queue full — backpressure).
        A request that could NEVER be admitted (over the position bound,
        or a worst-case span beyond the whole paged block pool) raises —
        queueing it would head-of-line-block every request behind it
        forever.  ``force=True`` (failover requeue, serve/router.py)
        enqueues past the bounded-queue check: migrated work was already
        admitted once, and backpressure belongs at the tier edge, not
        between replicas."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        try:
            self.engine.validate_request(
                prompt.size, request.max_new_tokens
            )
        except ValueError as e:
            raise ValueError(f"request {request.id}: {e}") from None
        if len(self.queue) >= self.max_queue and not force:
            self.rejected += 1
            if self.emitter is not None:
                # Backpressure is an SLO event: refusals join shed and
                # cancelled requests as the goodput objective's bad set.
                self.emitter.counter_add("rejected_requests", 1)
            return False
        self.queue.append(request)
        self._tenant_counts[request.tenant] = (
            self._tenant_counts.get(request.tenant, 0) + 1
        )
        self.records[request.id] = {
            "id": request.id,
            "prompt_len": int(prompt.size),
            "max_new_tokens": int(request.max_new_tokens),
            "arrival": float(request.arrival_time),
            "deadline": (
                float(request.deadline) if request.deadline is not None
                else None
            ),
            "tenant": request.tenant,
            "replica": self.replica,
            "admitted": None,
            "first_token": None,
            "finish": None,
            "finish_reason": None,
            "generated": 0,
            # Failover provenance (serve/failover.py): how many times
            # this request was re-placed after a replica death, and every
            # replica that held it, in order.  The controller overwrites
            # both on a requeue; a never-retried request reads 0 / its
            # one placement.
            "retries": 0,
            "replica_history": (
                [self.replica] if self.replica is not None else []
            ),
        }
        return True

    @property
    def idle(self) -> bool:
        return not self.queue and not self.engine.busy

    def tick(self) -> list:
        """Shed/cancel → admit → step → record.  Returns the engine events.

        Shedding first: a queued request whose deadline passed would burn
        prefill + decode ticks producing tokens its caller already timed
        out on — goodput poison.  It is dropped with finish reason
        ``"shed"``, counted in :attr:`shed` and the serve metrics, and
        logged through the RequestLogger like any finished request.

        Cancellation second (the other half of the deadline contract): an
        IN-FLIGHT request past its deadline mid-decode is retired at this
        tick with finish reason ``"cancelled"`` — its slot (and paged
        blocks) free immediately for the admission sweep below instead of
        finishing a response the caller already timed out on.  Cancelled
        requests join shed ones outside the goodput/latency figures.

        Admission is by ``engine.can_admit`` — free-slot count for the
        contiguous pool, AVAILABLE-BLOCK count (net of prefix-cache hits
        and live reservations) for the paged pool — round-robin across
        tenants (``Request.tenant``; FIFO within one, and a one-tenant
        queue IS plain FIFO) with head-of-line blocking per rotation: a
        too-big candidate waits rather than being jumped."""
        now = self.clock()
        if any(r.deadline is not None for r in self.queue):
            # Brown-out (serve/failover.py): under tier capacity loss the
            # margin rises above zero and queued work sheds EARLY — a
            # request that cannot finish by its deadline anyway is
            # goodput poison on a degraded tier.
            horizon = now + self.brownout_margin
            alive: deque[Request] = deque()
            for r in self.queue:
                if r.deadline is not None and r.deadline <= horizon:
                    self._shed(r, now)
                else:
                    alive.append(r)
            self.queue = alive
        cancel_events = []
        for rid in self.engine.live_requests():
            deadline = self.records[rid].get("deadline")
            if deadline is not None and deadline <= now:
                cancel_events.append(self.engine.cancel(rid))
        while self.queue:
            r = self._admit_candidate()
            if not self.engine.can_admit(r.prompt, r.max_new_tokens):
                break
            if r is self.queue[0]:
                self.queue.popleft()  # the fast path pops O(1)
            else:
                self.queue.remove(r)
            self._drop_tenant_count(r.tenant)
            self._last_tenant = r.tenant
            if self.policy is not None:
                # Settle the weighted-deficit round the pop consumed —
                # only a SUCCESSFUL admission spends credit, so a
                # blocked head-of-line candidate keeps its turn.
                self.policy.on_admit(self, r)
            self.engine.start(r.id, r.prompt, r.max_new_tokens)
            rec = self.records[r.id]
            if rec["admitted"] is None:
                # A failover requeue restores the request's ORIGINAL
                # admission stamp (serve/failover.py) — re-stamping here
                # would put admitted after the restored first_token and
                # flip the request/prefill span negative.
                rec["admitted"] = self.clock()
        self.queue_depth_samples.append(len(self.queue))
        self.active_slot_samples.append(self.engine.pool.num_active)
        if self.recorder is not None:
            self.recorder.check_queue(len(self.queue), self.max_queue)
        events = cancel_events + self.engine.step()
        if self.emitter is not None:
            self._emit_engine_stats()
        now = self.clock()
        for ev in events:
            rec = self.records[ev.request_id]
            if ev.kind == "token":
                rec["generated"] += 1
                if rec["first_token"] is None:
                    rec["first_token"] = now
            elif ev.reason == "cancelled":
                # Mid-decode deadline expiry: finalized like a finish but
                # kept out of the SLO histograms and the goodput token
                # count — whatever it generated, nobody was waiting for.
                self.cancelled += 1
                rec["finish"] = now
                rec["finish_reason"] = "cancelled"
                finalize_record(rec)
                self._record_request_spans(rec)
                self.completed.append(rec)
                if self.request_logger is not None:
                    self.request_logger.log(rec)
                if self.emitter is not None:
                    self.emitter.counter_add("cancelled_requests", 1)
                    self.emitter.emit("record", {
                        "record": "request_cancelled", "id": rec["id"],
                        "generated": rec["generated"],
                        "overdue_s": now - rec["deadline"],
                    })
            else:  # finish
                rec["finish"] = now
                rec["finish_reason"] = ev.reason
                finalize_record(rec)
                self._record_request_spans(rec)
                self.completed.append(rec)
                if self.request_logger is not None:
                    self.request_logger.log(rec)
                if self.emitter is not None:
                    # The plain names are the SLO objective inputs and
                    # the tier totals; the labeled variants are the
                    # per-tenant / per-replica views the live plane
                    # exposes as Prometheus labels (obs/live.py
                    # parse_metric_name decodes them back).
                    views = [{}]
                    if rec["tenant"] is not None:
                        views.append({"tenant": rec["tenant"]})
                    if rec["replica"] is not None:
                        views.append({"replica": rec["replica"]})
                    for view in views:
                        if rec.get("ttft") is not None:
                            self.emitter.observe(
                                labeled("ttft_s", **view), rec["ttft"]
                            )
                        if rec.get("tpot") is not None:
                            self.emitter.observe(
                                labeled("tpot_s", **view), rec["tpot"]
                            )
                        self.emitter.counter_add(
                            labeled("generated_tokens", **view),
                            rec["generated"],
                        )
                        self.emitter.counter_add(
                            labeled("finished_requests", **view), 1
                        )
                    self.emitter.emit("record", {
                        "record": "request_finish",
                        "id": rec["id"],
                        "finish_reason": rec["finish_reason"],
                        "generated": rec["generated"],
                    })
        if self.slo is not None:
            # After the tick's records landed, so this tick's samples are
            # in-window for the burn rates it evaluates.
            self.slo.evaluate(now)
        if self.spans is not None:
            # Deferred serialization drains at the tick boundary — never
            # on the span record path.
            self.spans.flush()
        return events

    def _record_request_spans(self, rec: dict) -> None:
        """The finished request's lifecycle chain, from the record's own
        timestamps: ``serve/request`` root (arrival → finish) parenting
        ``request/queued`` (arrival → admitted), ``request/prefill``
        (admitted → first token), ``request/decode`` (first token →
        finish).  Shed requests carry only the queued leg (nothing ran);
        a cancellation before the first token carries queued alone too.
        Sampling is per request id, so the chain records whole or not at
        all."""
        if self.spans is None or not self.spans.enabled:
            return
        corr = rec["id"]
        root = self.spans.start_span(
            "serve/request", corr=corr, t0=rec["arrival"],
            tenant=rec["tenant"], replica=rec["replica"],
            prompt_len=rec["prompt_len"],
        )
        if root is None:  # not sampled — no partial chains
            return
        queued_end = (
            rec["admitted"] if rec["admitted"] is not None else rec["finish"]
        )
        # Replica id rides EVERY chain link (not just the root): the
        # exporter groups spans into process rows by their own replica
        # attr, and one request's lane must not split across rows.
        extra = (
            {"replica": rec["replica"]} if rec["replica"] is not None else {}
        )
        self.spans.record_span(
            "request/queued", rec["arrival"], queued_end,
            corr=corr, parent=root, **extra,
        )
        if rec["admitted"] is not None and rec["first_token"] is not None:
            self.spans.record_span(
                "request/prefill", rec["admitted"], rec["first_token"],
                corr=corr, parent=root, **extra,
            )
            self.spans.record_span(
                "request/decode", rec["first_token"], rec["finish"],
                corr=corr, parent=root, **extra,
            )
        self.spans.end_span(
            root, t1=rec["finish"], generated=rec["generated"],
            finish_reason=rec["finish_reason"],
        )

    def _drop_tenant_count(self, tenant) -> None:
        n = self._tenant_counts.get(tenant, 0) - 1
        if n > 0:
            self._tenant_counts[tenant] = n
        else:
            self._tenant_counts.pop(tenant, None)

    def _admit_candidate(self) -> Request:
        """Next request to TRY admitting: round-robin across the tenants
        currently queued (rotation resumes after the tenant admitted
        last), FIFO within a tenant.  A single-tenant queue reduces to
        the plain FIFO head — O(1) via the tenant-count fast path, no
        deque scan.  Head-of-line semantics are per ROTATION, not per
        queue: when the selected tenant's oldest request cannot be
        admitted, admission stops for this tick — a too-big request
        waits rather than being jumped, exactly as before, but one
        tenant's burst can no longer park an entire queue's worth of its
        own requests ahead of everyone else's head.

        With an admission policy bound (serve/policy.py), the weighted-
        deficit pop replaces the rotation: same head-of-line semantics,
        weighted shares instead of equal turns."""
        if len(self._tenant_counts) <= 1:
            return self.queue[0]
        if self.policy is not None:
            return self.policy.admit_candidate(self)
        order: list = []
        seen: set = set()
        for r in self.queue:
            if r.tenant not in seen:
                seen.add(r.tenant)
                order.append(r.tenant)
        if self._last_tenant in seen:
            i = order.index(self._last_tenant)
            order = order[i + 1:] + order[:i + 1]
        tenant = order[0]
        return next(r for r in self.queue if r.tenant == tenant)

    def _shed(self, request: Request, now: float) -> None:
        """Finalize a deadline-expired queued request without admitting
        it: zero generated tokens, finish reason ``"shed"``."""
        self._drop_tenant_count(request.tenant)
        self.shed += 1
        rec = self.records[request.id]
        rec["finish"] = now
        rec["finish_reason"] = "shed"
        finalize_record(rec)
        self._record_request_spans(rec)
        self.completed.append(rec)
        if self.request_logger is not None:
            self.request_logger.log(rec)
        if self.emitter is not None:
            self.emitter.counter_add("shed_requests", 1)
            self.emitter.emit("record", {
                "record": "request_shed", "id": rec["id"],
                "queued_s": now - rec["arrival"],
            })

    def _emit_engine_stats(self) -> None:
        """Per-tick paged/prefill accounting into the obs spine: gauges
        for pool occupancy, counter DELTAS for the monotonic engine stats
        (the emitter's counters are cumulative adds) — prefix-cache hit
        rate, blocks evicted, and prefill work then ride the same
        events.rank*.jsonl the TTFT/TPOT histograms live on
        (tools/telemetry_report.py surfaces them)."""
        st = self.engine.stats()
        # Gauges are last-write-wins per NAME: under a multi-replica
        # router every scheduler shares one emitter, so replica-tagged
        # schedulers suffix their engine gauges (replica 1's empty pool
        # must not overwrite replica 0's full one).  Counters stay
        # un-suffixed — cumulative adds sum correctly across replicas
        # into tier totals.
        sfx = f"_r{self.replica}" if self.replica is not None else ""
        self.emitter.gauge(f"serve_slots_active{sfx}", st["slots_active"])
        if "prefill_slots_active" in st:
            # Disaggregated tier (serve/disagg.py): per-ROLE occupancy —
            # the two pools' load is the signal role sizing reads.
            self.emitter.gauge(
                f"serve_prefill_slots_active{sfx}",
                st["prefill_slots_active"],
            )
            self.emitter.gauge(
                f"serve_decode_slots_active{sfx}",
                st["decode_slots_active"],
            )
        if "blocks_in_use" in st:
            self.emitter.gauge(
                f"kv_blocks_in_use{sfx}", st["blocks_in_use"]
            )
            self.emitter.gauge(
                f"kv_blocks_cached{sfx}", st["blocks_cached"]
            )
            self.emitter.gauge(
                f"kv_block_occupancy{sfx}", st["block_occupancy"]
            )
        if "host_blocks" in st:
            # Host KV tier (serve/kv_store.py): per-TIER occupancy, the
            # other half of the cache-hierarchy accounting.  The per-
            # block byte price rides along so the report can pin the
            # ledger identity host_bytes == host_blocks x kv_block_bytes
            # under ANY --serve-kv-dtype (the quantized model:
            # obs.cost.kv_block_model_bytes(dtype=...)).
            self.emitter.gauge(f"kv_host_blocks{sfx}", st["host_blocks"])
            self.emitter.gauge(f"kv_host_bytes{sfx}", st["host_bytes"])
            if "kv_block_bytes" in st:
                self.emitter.gauge(
                    f"kv_block_bytes{sfx}", st["kv_block_bytes"]
                )
        for name in (
            "prefill_tokens_computed", "prefill_tokens_offered",
            "prefix_hit_tokens", "prefix_lookup_tokens", "blocks_evicted",
            "cow_copies", "decode_ticks", "decode_slot_ticks",
            "decode_tokens",
            "spec_drafted_tokens", "spec_accepted_tokens",
            "blocks_spilled", "blocks_restored", "blocks_sibling_fetched",
            "host_dropped_blocks", "handoffs",
        ):
            if name in st:
                delta = st[name] - self._last_stats.get(name, 0)
                if delta:
                    self.emitter.counter_add(name, delta)
        # Speculation histograms (spec engines only): per-tick acceptance
        # rate over drafted tokens, and effective tokens per decode tick —
        # the two distributions that say whether the drafter is earning
        # its verify width (tools/telemetry_report.py reduces the counter
        # totals to the same headline numbers).
        if "spec_drafted_tokens" in st:
            drafted = (
                st["spec_drafted_tokens"]
                - self._last_stats.get("spec_drafted_tokens", 0)
            )
            if drafted:
                acc = (
                    st["spec_accepted_tokens"]
                    - self._last_stats.get("spec_accepted_tokens", 0)
                )
                self.emitter.observe("spec_acceptance_rate", acc / drafted)
            slot_ticks = (
                st["decode_slot_ticks"]
                - self._last_stats.get("decode_slot_ticks", 0)
            )
            if slot_ticks:
                toks = (
                    st["decode_tokens"]
                    - self._last_stats.get("decode_tokens", 0)
                )
                self.emitter.observe(
                    "spec_tokens_per_slot_tick", toks / slot_ticks
                )
        self._last_stats = st

    # ------------------------------------------------------------------ #

    def run(
        self,
        requests: list[Request],
        *,
        sleep: Callable[[float], None] | None = None,
    ) -> list[dict]:
        """Drive a full trace: requests are submitted when the clock
        reaches their ``arrival_time`` (FIFO by arrival), ticking until
        everything submitted has finished.  ``sleep`` bridges idle gaps
        before the next arrival (defaults to ``time.sleep`` for real
        clocks; pass the virtual clock's ``advance`` for scripted runs).
        Refused submissions (backpressure) are counted, not retried.
        Returns the completed per-request records."""
        if sleep is None:
            sleep = time.sleep
        pending = sorted(requests, key=lambda r: r.arrival_time)
        i = 0
        while i < len(pending) or not self.idle:
            now = self.clock()
            while i < len(pending) and pending[i].arrival_time <= now:
                self.submit(pending[i])
                i += 1
            if not self.idle:
                self.tick()
            elif i < len(pending):
                sleep(max(pending[i].arrival_time - now, 0.0))
        return self.completed

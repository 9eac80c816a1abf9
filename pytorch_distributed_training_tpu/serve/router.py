"""Data-parallel serving router: N engine replicas behind one admission
point.

One TP-sharded engine caps out at one (sub)mesh's throughput; the next
rung of serving scale is REPLICATION — N independent engines, each with
its own compiled programs, KV pool, and scheduler, spread over disjoint
device sets (``parallel/sharding.serve_tp_mesh`` per replica — the MPMD
program-per-role decomposition: heterogeneous-placement programs running
side by side, coordinated only by host logic).  The router is that host
logic: every request enters through :meth:`submit`, which picks a replica
by

1. **Prefix-cache affinity** (paged replicas): the request's hash-chained
   prefix key (serve/kv_pool.py) is looked up against every replica's
   block cache WITHOUT claiming; the replica with the deepest hit serves
   it — the K/V bytes for the shared prefix already sit in that replica's
   pool, so prefill skips them.  Routing elsewhere would recompute the
   prefix from scratch: affinity is worth exactly the prefix-cache win,
   which is why it yields when the hot replica is SATURATED (its queue
   deeper than ``affinity_queue_cap``) — at that point queue wait
   dominates the recompute and the request falls back to rule 2, counted
   as a rebalance.
2. **Least-loaded**: minimal (queued + live-slot) occupancy, ties broken
   by lowest replica index — deterministic, so scripted traces replay.

Cross-replica sharing: all replicas' prompt-lookup drafters feed ONE
:class:`~.draft.NgramIndex` (a prompt admitted on replica 0 makes its
continuation draftable on replica 3 — the index is host-side text, no
K/V), and per-replica schedulers stamp their ``replica`` id on every
record so the merged metrics stay attributable.

Router accounting rides the obs spine (per-replica queue-depth/occupancy
gauges, routed/affinity-hit/rebalance counters) and is surfaced by
``tools/telemetry_report.py``.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable

import numpy as np

from .draft import NgramIndex
from .engine import ServingEngine
from .scheduler import ContinuousScheduler, Request

# Rolling per-replica tick-completion window the failover controller's
# straggler-skew detector reads (serve/failover.py).
_TICK_LOG_WINDOW = 16


class ReplicaRouter:
    """Admission point over N ``ServingEngine`` replicas.

    ``engines`` should be interchangeable (same model/params/decoding
    config) — the router assumes any replica can serve any request.
    ``affinity_queue_cap`` is the per-replica queue depth at which an
    affinity target counts as saturated; it defaults to the replica's
    slot count (a queue deeper than the slots it feeds means waiting
    costs more than recomputing the prefix elsewhere).
    """

    def __init__(
        self,
        engines: list[ServingEngine],
        *,
        max_queue: int = 64,
        clock: Callable[[], float] = time.monotonic,
        request_logger=None,
        emitter=None,
        affinity: bool = True,
        affinity_queue_cap: int | None = None,
        share_ngram_index: bool = True,
        sibling_fetch: bool = True,
        spans=None,
        slo=None,
        chaos=None,
        failover=None,
        autoscale=None,
        policy=None,
    ):
        if not engines:
            raise ValueError("need at least one engine replica")
        self.affinity = affinity
        self.affinity_queue_cap = affinity_queue_cap
        # Sibling prefix fetch (serve/kv_store.py): when the routing
        # decision lands a request AWAY from the replica holding its
        # prefix hot (saturation rebalance, or a deeper hit elsewhere),
        # the hot replica's prefix blocks are copied into the target's
        # HOST tier first — the target's admission then RESTORES them
        # instead of recomputing the prefix.  Requires host tiers on the
        # pools; silently inert without them.
        self.sibling_fetch = sibling_fetch
        self.emitter = emitter
        # One shared span recorder across the tier (obs/spans.py): every
        # replica's scheduler + engine record into the same buffer, and
        # the router stamps its routing decision as a span on the same
        # request correlation id — the exporter links a request's route →
        # queue wait → slot ticks across replicas through it.  Route
        # spans are stamped with the ROUTER's injected clock — the same
        # timebase the replicas' SLO records (and so every lifecycle
        # span) use, scripted VirtualClock runs included.
        self.spans = spans
        # Live SLO plane (obs/slo.py): ONE policy for the tier, evaluated
        # once per router tick — the per-replica schedulers share the
        # emitter (and so the aggregator), so a tier-level objective sees
        # every replica's samples; replica schedulers get slo=None to
        # avoid N evaluations per tick.
        self.slo = slo
        self.clock = clock
        self.replicas = [
            ContinuousScheduler(
                eng, max_queue=max_queue, clock=clock,
                request_logger=request_logger, emitter=emitter, replica=k,
                spans=spans, policy=policy,
            )
            for k, eng in enumerate(engines)
        ]
        # Admission policy (serve/policy.py): ONE weighted-deficit
        # policy shared by every replica scheduler (per-queue deficit
        # state lives on the scheduler), surfaced for /slo.
        self.policy = policy
        # One shared cross-request n-gram index: replica 0's index becomes
        # everyone's (engine.reset() clears it IN PLACE, so resets on any
        # replica never fork the sharing).
        self.shared_index: NgramIndex | None = None
        if share_ngram_index:
            drafters = [
                e.drafter for e in engines
                if e.drafter is not None and e.drafter.index is not None
            ]
            if drafters:
                self.shared_index = drafters[0].index
                for d in drafters[1:]:
                    d.index = self.shared_index
        # Routing accounting (host-side source of truth; the emitted
        # telemetry is pinned equal to these in tests).
        self.routed = [0] * len(engines)
        self.affinity_hits = 0      # routed to the deepest-prefix replica
        self.rebalanced = 0         # affinity target saturated -> fallback
        self.rejected = 0           # chosen replica's queue full
        self.sibling_fetches = 0        # fetch events (requests helped)
        self.sibling_fetch_blocks = 0   # blocks copied across pools
        self._last_emitted: dict = {}
        # Chaos + failover plane (resilience/faults.py::ServeFaultInjector
        # / serve/failover.py::FailoverController).  The router owns the
        # raw fault/fence state either way, so a CHAOS-ONLY run (the
        # no-failover control) still presents a dead replica honestly:
        # its scheduler stops being ticked, its work strands, its
        # heartbeat gauges go stale — nothing recovers it.
        self.tick_index = 0
        self.chaos = chaos
        self.failover = failover
        self.request_logger = request_logger
        n = len(engines)
        self._faults: dict[int, dict] = {}   # k -> {"kind", "until"/"period"}
        self._fenced: set[int] = set()       # declared dead by failover
        self._missed = [0] * n               # consecutive unanswered ticks
        self._tick_log = [
            deque(maxlen=_TICK_LOG_WINDOW) for _ in range(n)
        ]
        if chaos is not None:
            # Fail fast on out-of-range replica indices: a fault that
            # raised at FIRE time would already have written its marker,
            # and a supervised relaunch would silently skip it.
            chaos.validate(n)
        if failover is not None:
            failover.bind(self)
        # Closed-loop control plane (serve/autoscale.py): binds AFTER
        # failover (its scale actions are the failover controller's
        # park/unpark machinery) and may park initial spares here.
        self.autoscale = autoscale
        if autoscale is not None:
            autoscale.bind(self)

    # ------------------------------------------------------------------ #
    # routing
    # ------------------------------------------------------------------ #

    def _load(self, k: int) -> int:
        s = self.replicas[k]
        return len(s.queue) + s.engine.pool.num_active

    def _affinity_cap(self, k: int) -> int:
        if self.affinity_queue_cap is not None:
            return self.affinity_queue_cap
        return self.replicas[k].engine.num_slots

    def _eligible(self) -> list[int]:
        """Replicas new work may land on: all of them without a failover
        controller; the controller's ``up`` set with one (dead replicas
        are fenced, degraded stragglers take nothing new)."""
        if self.failover is None:
            return list(range(len(self.replicas)))
        return self.failover.eligible()

    def _readable(self) -> set[int]:
        """Replicas whose pools may serve prefix lookups / sibling-fetch
        sources — a dead replica's device bytes are gone and must not be
        read back to life."""
        if self.failover is None:
            return set(range(len(self.replicas)))
        return set(self.failover.readable())

    def route(self, request: Request) -> int | None:
        """Replica index for ``request`` (no side effects beyond the
        routing counters — :meth:`submit` does the enqueue); None when
        no replica is eligible (tier fully dead/degraded)."""
        return self._route_decision(request)[0]

    def _route_decision(self, request: Request) -> tuple[int | None, str]:
        """(replica index, decision kind) — ``"affinity"`` (deepest
        prefix hit, unsaturated), ``"rebalanced"`` (hit target saturated,
        fell back to least-loaded), or ``"least_loaded"``.

        Whenever the decision lands the request on a replica with a
        SHALLOWER prefix hit than the best sibling's (a rebalance, or a
        least-loaded placement while some replica is warm), the sibling
        fetch copies the missing prefix blocks into the chosen replica's
        host KV tier first — admission there restores them instead of
        recomputing the prefix (serve/kv_store.py)."""
        cand = self._eligible()
        if not cand:
            return None, "no_replica"
        decision = "least_loaded"
        hits = None
        if len(self.replicas) > 1 and (self.affinity or self.sibling_fetch):
            # Per-replica prefix depths feed BOTH affinity routing and
            # the sibling fetch — with affinity off, the lookup still
            # runs so a warm sibling's blocks can chase the least-loaded
            # placement (the fetch is the consolation prize for not
            # routing to the warm replica).  Unreadable (dead) replicas
            # score zero: their bytes are gone.
            prompt = np.asarray(request.prompt, np.int32).reshape(-1)
            readable = self._readable()
            hits = [
                s.engine.pool.lookup(prompt)
                if k in readable and s.engine.paged
                and s.engine.pool.prefix_cache_enabled
                else 0
                for k, s in enumerate(self.replicas)
            ]
            best = max(cand, key=lambda k: (hits[k], -k))
            if self.affinity and hits[best] > 0:
                s_best = self.replicas[best]
                # Saturation is the affinity cap OR the hard queue bound,
                # whichever bites first: routing an affinity hit into a
                # FULL queue would bounce the request off backpressure
                # while another replica had room.
                cap = min(self._affinity_cap(best), s_best.max_queue)
                if len(s_best.queue) < cap:
                    self.affinity_hits += 1
                    return best, "affinity"
                self.rebalanced += 1
                decision = "rebalanced"
        chosen = min(cand, key=lambda k: (self._load(k), k))
        if (
            self.sibling_fetch and hits is not None
            and max(hits) > hits[chosen]
        ):
            self._sibling_fetch(request, chosen, hits)
        return chosen, decision

    def _sibling_fetch(
        self, request: Request, chosen: int, hits: list[int]
    ) -> None:
        """Copy warm siblings' prefix blocks into ``chosen``'s host tier
        (no-op without host tiers on both pools).  Every replica whose
        prefix is deeper than ``chosen``'s contributes as a stripe lane —
        the missing chain is pulled round-robin across all of them
        (``kv_store.sibling_fetch_striped``), deepest lane first, so one
        hot sibling's copy path is no longer the serialized bottleneck.
        With a single warm sibling this is exactly the old single-source
        fetch."""
        from .kv_store import sibling_fetch_striped

        dst = getattr(self.replicas[chosen].engine.pool, "blocks", None)
        if dst is None or dst.host is None:
            return
        warm = sorted(
            (k for k in range(len(self.replicas)) if hits[k] > hits[chosen]),
            key=lambda k: (-hits[k], k),
        )
        srcs = [
            src for k in warm
            if (src := getattr(self.replicas[k].engine.pool, "blocks", None))
            is not None and src is not dst
        ]
        if not srcs:
            return
        fetched = sibling_fetch_striped(dst, srcs, request.prompt)
        if fetched:
            self.sibling_fetches += 1
            self.sibling_fetch_blocks += fetched

    def submit(self, request: Request) -> bool:
        """Route + enqueue; False = the chosen replica's bounded queue
        refused it (backpressure — same contract as the single-replica
        scheduler's submit), or no replica is eligible at all (the tier
        is fully dead/degraded — refusing IS the graceful degradation)."""
        k, decision = self._route_decision(request)
        if k is None:
            self.rejected += 1
            if self.emitter is not None:
                # Tier-level refusal joins the schedulers' queue-full
                # refusals in the goodput objective's bad set.
                self.emitter.counter_add("rejected_requests", 1)
            return False
        ok = self.replicas[k].submit(request)
        if ok:
            self.routed[k] += 1
            if self.failover is not None:
                self.failover.track(request, k)
        else:
            self.rejected += 1
        if self.spans is not None and self.spans.enabled:
            # The route decision as a zero-width span on the request's
            # correlation id: which replica, by which rule, and whether
            # the bounded queue took it — the first link of the chain.
            now = self.clock()
            self.spans.record_span(
                "router/route", now, now, corr=request.id,
                decision=decision, replica=k, accepted=ok,
            )
        return ok

    def _submit_requeue(self, request: Request) -> int | None:
        """Failover requeue placement (serve/failover.py): route the
        rebuilt request through the normal decision (affinity + sibling
        fetch against the SURVIVORS) but enqueue past the bounded-queue
        check — this work was already admitted once, and bouncing it off
        backpressure would turn a replica death into silent request
        loss.  Returns the chosen replica, or None when nothing is
        eligible (the controller parks it until capacity returns)."""
        k, decision = self._route_decision(request)
        if k is None:
            return None
        self.replicas[k].submit(request, force=True)
        self.routed[k] += 1
        if self.spans is not None and self.spans.enabled:
            now = self.clock()
            self.spans.record_span(
                "router/route", now, now, corr=request.id,
                decision="failover", replica=k, accepted=True,
            )
        return k

    # ------------------------------------------------------------------ #
    # driving
    # ------------------------------------------------------------------ #

    @property
    def idle(self) -> bool:
        return all(s.idle for s in self.replicas) and (
            self.failover is None or self.failover.pending == 0
        )

    # ---- chaos-plane surface (resilience/faults.py) -------------------- #

    def set_fault(
        self, k: int, kind: str, *, until_tick: int | None = None,
        period: int | None = None,
    ) -> None:
        """Arm a replica fault: ``"crash"`` (never responds again),
        ``"stall"`` (misses ticks until ``until_tick``), ``"slow"``
        (responds once per ``period`` router ticks).  The router only
        SIMULATES the failure mode — detection and recovery are the
        failover controller's job, from the observable signals alone."""
        if not 0 <= k < len(self.replicas):
            raise ValueError(f"no replica {k}")
        if kind not in ("crash", "stall", "slow"):
            raise ValueError(f"unknown replica fault kind {kind!r}")
        self._faults[k] = {
            "kind": kind, "until": until_tick, "period": period,
        }

    def inject_role_death(self, k: int, role: str) -> None:
        """Kill one role pool of a disaggregated replica (the finer
        failure unit MPMD decomposition buys): the engine reclaims the
        role's slots and the failover controller (when present) requeues
        the stranded requests; without one they simply strand — the
        no-failover control behavior."""
        eng = self.replicas[k].engine
        if not hasattr(eng, "fail_role"):
            raise ValueError(
                f"replica {k} is not disaggregated — role faults need a "
                "DisaggServingEngine"
            )
        if role in eng.dead_roles:
            return  # already dead: not a second death
        stranded = eng.fail_role(role)
        if self.failover is not None:
            self.failover.on_role_death(
                k, role, stranded, self.tick_index, self.clock()
            )

    def drop_handoff(self) -> Any | None:
        """Drop one parked prefill→decode handoff somewhere in the tier
        (the lost-message chaos scenario); returns the dropped request
        id or None when nothing is parked."""
        for s in self.replicas:
            dropper = getattr(s.engine, "drop_handoff", None)
            if dropper is not None:
                rid = dropper()
                if rid is not None:
                    return rid
        return None

    def _tickable(self, k: int) -> bool:
        fault = self._faults.get(k)
        if fault is None:
            return True
        if fault["kind"] == "crash":
            return False
        if fault["kind"] == "stall":
            if self.tick_index < fault["until"]:
                return False
            del self._faults[k]  # stall over: the program responds again
            return True
        return self.tick_index % fault["period"] == 0  # slow

    def tick(self) -> list:
        """One tick of every RESPONSIVE replica (idle replicas no-op
        cheaply); returns the merged engine events.

        The chaos plane fires first (faults arm at tick boundaries);
        then each replica either ticks or — crashed/stalled/fenced —
        misses, which is the failover controller's raw detection signal
        (``_missed`` streaks, the rolling ``_tick_log`` the straggler
        detector reads, and the heartbeat gauges that simply stop).  The
        controller evaluates AFTER the replica sweep, so a declared
        death drains and requeues within the same tick — pinned
        tick-exact in tests."""
        self.tick_index += 1
        if self.chaos is not None:
            self.chaos.on_tick(self.tick_index, self)
        events: list = []
        for k, s in enumerate(self.replicas):
            fenced = k in self._fenced
            if fenced or not self._tickable(k):
                # A silent replica — fenced (known dead: a zombie coming
                # back from a stall can never emit) or faulted — still
                # contributes its queue depth and occupancy, so the
                # tier's per-tick samples stay rectangular.  Only the
                # UNfenced silence feeds detection: a fenced corpse has
                # already been declared.
                if not fenced:
                    self._missed[k] += 1
                    self._tick_log[k].append(0)
                s.queue_depth_samples.append(len(s.queue))
                s.active_slot_samples.append(s.engine.pool.num_active)
                continue
            self._missed[k] = 0
            self._tick_log[k].append(1)
            ev = s.tick()
            if self.failover is not None:
                self.failover.observe_events(k, ev)
            events.extend(ev)
        if self.failover is not None:
            self.failover.evaluate(self.tick_index, self.clock())
        if self.autoscale is not None:
            # The control plane runs after the failover pass (health
            # states settled, failure drains done) and before the
            # telemetry flush, so an action's counters and its effects
            # land in the same tick's emission — pinned tick-exact.
            self.autoscale.evaluate(self.tick_index, self.clock())
        if self.emitter is not None:
            self._emit_stats()
        if self.slo is not None:
            self.slo.evaluate(self.clock())
        return events

    def run(
        self,
        requests: list[Request],
        *,
        sleep: Callable[[float], None] | None = None,
    ) -> list[dict]:
        """Drive a full trace through the tier: requests are routed at
        their arrival time (affinity decisions see exactly the cache
        state a live front-end would), ticking all replicas until idle.
        Returns the merged completed records, each stamped with its
        replica id."""
        if sleep is None:
            sleep = time.sleep
        clock = self.replicas[0].clock
        pending = sorted(requests, key=lambda r: r.arrival_time)
        i = 0
        while i < len(pending) or not self.idle:
            now = clock()
            while i < len(pending) and pending[i].arrival_time <= now:
                self.submit(pending[i])
                i += 1
            if not self.idle:
                self.tick()
            elif i < len(pending):
                sleep(max(pending[i].arrival_time - now, 0.0))
        return self.completed

    @property
    def completed(self) -> list[dict]:
        """Merged per-request records across replicas (plus the failover
        controller's ``"failed"`` retirements), finish-time ordered
        (each record carries its ``replica`` id)."""
        out = [r for s in self.replicas for r in s.completed]
        if self.failover is not None:
            out.extend(self.failover.completed)
        out.sort(key=lambda r: (r.get("finish") is None, r.get("finish")))
        return out

    # ------------------------------------------------------------------ #
    # accounting
    # ------------------------------------------------------------------ #

    def stats(self) -> dict:
        """Router-level accounting plus per-replica occupancy — the
        source of truth the emitted telemetry must match."""
        return {
            "replicas": len(self.replicas),
            "routed": list(self.routed),
            "affinity_hits": self.affinity_hits,
            "rebalanced": self.rebalanced,
            "rejected": self.rejected,
            "sibling_fetches": self.sibling_fetches,
            "sibling_fetch_blocks": self.sibling_fetch_blocks,
            "queue_depths": [len(s.queue) for s in self.replicas],
            "slots_active": [
                s.engine.pool.num_active for s in self.replicas
            ],
            **(
                {"failover": self.failover.stats()}
                if self.failover is not None else {}
            ),
        }

    def queue_depth_samples(self) -> list[int]:
        """Tier-wide queue depth per tick (summed across replicas) — the
        summarize_records input."""
        per = [s.queue_depth_samples for s in self.replicas]
        n = min((len(p) for p in per), default=0)
        return [sum(p[i] for p in per) for i in range(n)]

    def active_slot_samples(self) -> list[int]:
        per = [s.active_slot_samples for s in self.replicas]
        n = min((len(p) for p in per), default=0)
        return [sum(p[i] for p in per) for i in range(n)]

    def engine_stats(self) -> dict:
        """Summed engine counters across replicas (the fields are all
        monotonic counts, so the tier total is just the sum), for
        ``summarize_records(engine_stats=...)``."""
        total: dict = {}
        for s in self.replicas:
            for name, v in s.engine.stats().items():
                if not isinstance(v, (int, np.integer)):
                    continue
                if name == "kv_block_bytes":
                    # A per-block PRICE (identical on every replica of
                    # one tier), not a monotonic count — summing it
                    # would report replicas x the real block size.
                    total[name] = int(v)
                else:
                    total[name] = total.get(name, 0) + int(v)
        return total

    def _emit_stats(self) -> None:
        """Router counters/gauges into the obs spine: per-replica queue
        depth + occupancy gauges, counter DELTAS for the monotonic
        routing totals (the emitter's counters are cumulative adds) —
        tools/telemetry_report.py reduces them back to the affinity-hit
        rate and per-replica spread."""
        for k, s in enumerate(self.replicas):
            self.emitter.gauge(f"router_queue_depth_r{k}", len(s.queue))
            self.emitter.gauge(
                f"router_slots_active_r{k}", s.engine.pool.num_active
            )
        totals = {
            "router_routed_requests": sum(self.routed),
            "router_affinity_hits": self.affinity_hits,
            "router_rebalanced": self.rebalanced,
            "router_rejected": self.rejected,
            "router_sibling_fetches": self.sibling_fetches,
            "router_sibling_fetch_blocks": self.sibling_fetch_blocks,
        }
        for k in range(len(self.replicas)):
            totals[f"router_routed_r{k}"] = self.routed[k]
        for name, total in totals.items():
            delta = total - self._last_emitted.get(name, 0)
            if delta:
                self.emitter.counter_add(name, delta)
        self._last_emitted = totals

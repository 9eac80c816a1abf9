"""Disaggregated prefill/decode serving: role pools with KV handoff.

The interleaved engine runs one (S, C) prefill program plus one decode
program per tick over ONE slot array — so a burst of long prompts makes
EVERY decode tick pay a full-width prefill forward, inflating all
co-scheduled requests' TPOT (the interference DistServe/Splitwise
split serving to remove).  This module splits the engine into two role
pools, the MPMD program-per-role decomposition (PAPERS.md "Scaling Deep
Learning Training with MPMD Pipeline Parallelism" is the compilation
story):

- a **prefill-role** :class:`~.engine.ServingEngine` (``role="prefill"``,
  typically FEW slots) compiles only the chunked-prefill program; it
  admits raw prompts, samples each request's first token (the TTFT
  moment stays on this side), and parks the finished request for
  handoff;
- a **decode-role** engine (``role="decode"``) compiles only the decode
  (+ speculative verify) programs; its slot array holds ONLY decoding
  requests, so its per-tick cost never includes a prefill forward wider
  than the prefill pool — under a long-prompt burst the decode pool's
  TPOT rides a (P, C) prefill instead of the interleaved (S, C) one,
  with P << S.

**KV handoff.**  Paged (the tentpole): both role pools are slot VIEWS
over one shared :class:`~.kv_pool.BlockPool` — the prefill engine fills
physical blocks and registers full prompt blocks in the hash chain, and
the handoff moves only the block-table ROW (``SlotExport``); the decode
engine adopts it without touching a byte, and the recompile guard pins
zero new compiles across the handoff.  Contiguous: the pools have
separate caches, so adoption device-copies the slot's K/V rows — the
same handoff contract at the cost the reservation-per-slot layout
already implies.  Either way the decode-side output is greedy
TOKEN-EXACT vs the single interleaved engine (pinned by
tests/test_serve_disagg.py).

:class:`DisaggServingEngine` quacks like a ``ServingEngine`` for the
iteration-level scheduler and the replica router (submit/step/cancel/
stats), so disaggregation composes with everything above it: tenant-fair
admission, deadlines, tracing, and the data-parallel tier — a
``ReplicaRouter`` over N disaggregated replicas is role-aware placement
for free (every raw prompt lands in a prefill pool; decode pools only
ever adopt).
"""

from __future__ import annotations

from collections import deque
from typing import Any

import numpy as np

from .engine import Event, Handoff, ServingEngine
from .kv_pool import BlockPool
from .kv_store import HostKVStore


class _TierPool:
    """The scheduler/router-facing pool view of the tier: occupancy is
    the sum over both role pools; prefix lookups answer from the shared
    substrate (either view sees the same hash chain)."""

    def __init__(self, tier: "DisaggServingEngine"):
        self._tier = tier

    @property
    def num_active(self) -> int:
        return (
            self._tier.prefill_engine.pool.num_active
            + self._tier.decode_engine.pool.num_active
        )

    @property
    def prefix_cache_enabled(self) -> bool:
        pool = self._tier.prefill_engine.pool
        return bool(getattr(pool, "prefix_cache_enabled", False))

    def lookup(self, prompt) -> int:
        return self._tier.prefill_engine.pool.lookup(prompt)

    @property
    def blocks(self):
        return self._tier.blocks


class DisaggServingEngine:
    """Prefill-role + decode-role engine pools behind one engine-shaped
    surface.

    ``prefill_slots`` sizes the prefill pool (small: its program width is
    the per-tick prefill tax every decode tick pays on shared hardware);
    ``decode_slots`` sizes the decode pool (the live-batch width decode
    throughput scales with).  ``kv_host_mb`` adds the host-RAM KV tier
    on the shared block pool (paged only): evicted prefix blocks spill
    there and restore on a hash-chain hit instead of recomputing.
    """

    def __init__(
        self,
        model,
        params,
        *,
        prefill_slots: int = 2,
        decode_slots: int = 4,
        max_len: int | None = None,
        prefill_chunk: int = 16,
        temperature: float = 0.0,
        top_k: int | None = None,
        exact_top_k: bool = False,
        eos_token_id: int | None = None,
        seed: int = 0,
        stream_cb=None,
        paged: bool = True,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefix_cache: bool = True,
        kv_host_mb: float | None = None,
        spec_k: int = 0,
        spec_ngram: int = 4,
        tp_mesh=None,
        kv_dtype: str = "bf16",
    ):
        if prefill_slots < 1 or decode_slots < 1:
            raise ValueError(
                "prefill_slots and decode_slots must both be >= 1"
            )
        if kv_host_mb is not None and not paged:
            raise ValueError(
                "the host KV tier spills paged blocks — pass paged=True"
            )
        if kv_dtype != "bf16" and not paged:
            raise ValueError(
                "quantized KV storage lives in the paged block pool — "
                "pass paged=True with kv_dtype int8/int4"
            )
        self.paged = paged
        self.blocks: BlockPool | None = None
        common = dict(
            max_len=max_len, temperature=temperature, top_k=top_k,
            exact_top_k=exact_top_k, eos_token_id=eos_token_id, seed=seed,
            stream_cb=stream_cb, tp_mesh=tp_mesh, kv_dtype=kv_dtype,
        )
        if paged:
            cap = max_len or model.cfg.max_seq_len
            host = (
                HostKVStore(int(kv_host_mb * 2**20))
                if kv_host_mb is not None else None
            )
            # The shared substrate both role views attach to — sized by
            # default like one interleaved engine over ALL the slots, so
            # disaggregation alone never shrinks the byte budget.  The
            # substrate's decoder carries the SAME kv_quant as the role
            # views: the physical blocks are quantized once, and the
            # handoff (a block-table row) moves compressed bytes only.
            clone_kw: dict = dict(decode=True, tp_mesh=tp_mesh)
            if kv_dtype != "bf16":
                clone_kw["kv_quant"] = kv_dtype
            decoder = model.clone(**clone_kw)
            self.blocks = BlockPool(
                decoder,
                num_blocks=num_blocks or (
                    (prefill_slots + decode_slots)
                    * (-(-cap // block_size))
                ),
                block_size=block_size, host_store=host,
            )
            common.update(
                paged=True, block_pool=self.blocks,
                prefix_cache=prefix_cache,
            )
        self.prefill_engine = ServingEngine(
            model, params, num_slots=prefill_slots, role="prefill",
            prefill_chunk=prefill_chunk, **common,
        )
        self.decode_engine = ServingEngine(
            model, params, num_slots=decode_slots, role="decode",
            prefill_chunk=prefill_chunk, spec_k=spec_k,
            spec_ngram=spec_ngram, **common,
        )
        self.prefill_slots = prefill_slots
        self.decode_slots = decode_slots
        self.max_len = self.decode_engine.max_len
        self.num_slots = prefill_slots + decode_slots
        self.eos_token_id = eos_token_id
        self._handoffs: deque[Handoff] = deque()
        self.handoffs = 0  # completed adoptions (obs spine)
        self.handoffs_dropped = 0  # chaos plane: lost handoff messages
        # Role-death state (serve/failover.py): a dead role pool stops
        # stepping and admitting/adopting until revive_role.
        self._dead_roles: set[str] = set()
        self.pool = _TierPool(self)

    # ------------------------------------------------------------------ #
    # engine-shaped surface (ContinuousScheduler / ReplicaRouter)
    # ------------------------------------------------------------------ #

    @property
    def drafter(self):
        """The decode side owns speculation (the router's shared-index
        plumbing reads this)."""
        return self.decode_engine.drafter

    @property
    def params(self):
        """The weights both role engines were built with and placed."""
        return self.decode_engine.params

    @property
    def mosaic_kernels(self) -> dict[str, dict[str, int]]:
        """Per-program kernel counts of both roles, by kernel name (each
        role compiles only its own programs, so the names do not
        collide)."""
        return {
            **self.prefill_engine.mosaic_kernels,
            **self.decode_engine.mosaic_kernels,
        }

    @property
    def stream_cb(self):
        return self.prefill_engine.stream_cb

    @stream_cb.setter
    def stream_cb(self, cb) -> None:
        self.prefill_engine.stream_cb = cb
        self.decode_engine.stream_cb = cb

    @property
    def spans(self):
        return self.prefill_engine.spans

    @spans.setter
    def spans(self, value) -> None:
        self.prefill_engine.spans = value
        self.decode_engine.spans = value

    @property
    def spans_replica(self):
        return self.prefill_engine.spans_replica

    @spans_replica.setter
    def spans_replica(self, value) -> None:
        self.prefill_engine.spans_replica = value
        self.decode_engine.spans_replica = value

    @property
    def program_signatures(self) -> dict[str, str]:
        """Per-program abstract-signature hashes across both roles (the
        role program sets are disjoint: prefill | decode+verify)."""
        return {
            **self.prefill_engine.program_signatures,
            **self.decode_engine.program_signatures,
        }

    @property
    def has_free_slot(self) -> bool:
        return self.prefill_engine.has_free_slot

    @property
    def busy(self) -> bool:
        return (
            self.prefill_engine.busy or self.decode_engine.busy
            or bool(self._handoffs)
        )

    def validate_request(self, prompt_len: int, max_new: int) -> None:
        self.prefill_engine.validate_request(prompt_len, max_new)

    def can_admit(self, prompt, max_new: int) -> bool:
        """Admission is by the PREFILL pool: a free prefill slot plus —
        paged — the shared block budget (which already accounts every
        decode-side and in-flight-handoff reservation, so an admitted
        request can always run to completion on the decode side).  With
        EITHER role dead the tier admits nothing: no prefill program to
        consume the prompt, or no decode pool for it to ever land on."""
        if self._dead_roles:
            return False
        return self.prefill_engine.can_admit(prompt, max_new)

    def start(self, request_id, prompt, max_new: int) -> int:
        return self.prefill_engine.start(request_id, prompt, max_new)

    def live_requests(self) -> list:
        return (
            self.prefill_engine.live_requests()
            + [h.request_id for h in self._handoffs]
            + self.decode_engine.live_requests()
        )

    def cancel(self, request_id) -> Event:
        """Retire an in-flight request wherever it currently lives:
        still prefilling, parked in the handoff queue, or decoding.
        Only PAGED exports ever park in the queue (contiguous handoffs
        export and adopt in the same ``_move_handoffs`` call), so the
        queued release always goes through the decode view."""
        for h in list(self._handoffs):
            if h.request_id == request_id:
                self._handoffs.remove(h)
                self.decode_engine.pool.release_export(h.export)
                return Event("finish", request_id, reason="cancelled")
        try:
            return self.prefill_engine.cancel(request_id)
        except KeyError:
            return self.decode_engine.cancel(request_id)

    def _move_handoffs(self) -> None:
        """Pull finished prefills toward the decode pool.  Paged exports
        detach EAGERLY (the freed prefill slot takes the next prompt
        immediately; the blocks ride the export's refcounts); contiguous
        exports detach lazily — the source slot must stay intact until
        the adoption row-copy, so it waits for a decode slot."""
        pre, dec = self.prefill_engine, self.decode_engine
        if self.paged:
            for slot in pre.handoff_ready():
                self._handoffs.append(pre.export_handoff(slot))
        while self._handoffs and dec.can_adopt():
            dec.adopt(self._handoffs.popleft())
            self.handoffs += 1
        if not self.paged:
            while dec.can_adopt() and pre.handoff_ready():
                dec.adopt(pre.export_handoff(pre.handoff_ready()[0]))
                self.handoffs += 1

    def step(self) -> list[Event]:
        """One tier tick: a prefill chunk on the prefill pool, handoffs,
        then a decode/verify batch on the decode pool.  The decode batch
        never waits on a wide interleaved prefill — its prefill tax is
        the (prefill_slots, C) program, not (all_slots, C) — and a
        request handed off this tick decodes this tick.  A dead role's
        half simply doesn't run (its sibling keeps draining: a dead
        prefill pool's already-exported handoffs still adopt off the
        shared substrate)."""
        events: list[Event] = []
        if "prefill" not in self._dead_roles:
            events += self.prefill_engine.step()
        if "decode" not in self._dead_roles:
            self._move_handoffs()
            events += self.decode_engine.step()
        return events

    # ------------------------------------------------------------------ #
    # role death (serve/failover.py + resilience chaos plane)
    # ------------------------------------------------------------------ #

    def fail_role(self, role: str) -> list:
        """Kill one role pool: reclaim its slots (host bookkeeping on
        the SURVIVING shared substrate — the control plane revoking a
        dead program's leases; no compiled program runs) and return the
        stranded request ids for the failover controller to requeue.

        Prefill death strands only the mid-prefill slots — queued
        handoffs already detached onto the shared block pool and keep
        adopting into the live decode pool.  Decode death strands
        everything: its live decodes, the parked handoffs it will never
        adopt, and the prefilling requests that could only ever land on
        it."""
        if role not in ("prefill", "decode"):
            raise ValueError(
                f"role must be 'prefill' or 'decode', got {role!r}"
            )
        if role in self._dead_roles:
            return []
        self._dead_roles.add(role)
        stranded: list = []
        if role == "prefill":
            for rid in list(self.prefill_engine.live_requests()):
                stranded.append(rid)
                self.prefill_engine.cancel(rid)
        else:
            for rid in list(self.decode_engine.live_requests()):
                stranded.append(rid)
                self.decode_engine.cancel(rid)
            for h in self._handoffs:
                stranded.append(h.request_id)
                self.decode_engine.pool.release_export(h.export)
            self._handoffs.clear()
            for rid in list(self.prefill_engine.live_requests()):
                stranded.append(rid)
                self.prefill_engine.cancel(rid)
        return stranded

    def revive_role(self, role: str) -> None:
        """Respawn a dead role pool: its compiled programs were never
        lost (the MPMD artifacts are per-role), its slots were reclaimed
        at death — the role just starts taking work again."""
        self._dead_roles.discard(role)

    # ------------------------------------------------------------------ #
    # role re-splitting (serve/autoscale.py)
    # ------------------------------------------------------------------ #

    def resplit(self, prefill_cap: int, decode_cap: int) -> None:
        """Re-bias the tier's P:D split without touching a program: cap
        each role pool's ADMISSION width below its compiled width.  The
        graceful half of the ``fail_role`` role flip — where a role
        death reclaims every slot at once (cap 0 + strand), a re-split
        lets slots over the new cap drain naturally and simply stops
        refilling them, so in-flight work is untouched and output stays
        token-exact.  Capping prefill throttles concurrent prompt
        consumption (and, paged, its worst-case block reservations —
        the pressure that inflates decode TPOT on the shared
        substrate); capping decode throttles handoff adoption so the
        freed block budget favors prompt admission.  Compiled program
        widths never change — excess rows idle-mask exactly as a
        half-empty pool's do, and the recompile guard pins zero new
        compiles across a re-split."""
        if not 1 <= prefill_cap <= self.prefill_slots:
            raise ValueError(
                f"prefill_cap must be in [1, {self.prefill_slots}], "
                f"got {prefill_cap} (a 0-width role is fail_role's job)"
            )
        if not 1 <= decode_cap <= self.decode_slots:
            raise ValueError(
                f"decode_cap must be in [1, {self.decode_slots}], "
                f"got {decode_cap} (a 0-width role is fail_role's job)"
            )
        self.prefill_engine.slot_cap = (
            None if prefill_cap == self.prefill_slots else int(prefill_cap)
        )
        self.decode_engine.slot_cap = (
            None if decode_cap == self.decode_slots else int(decode_cap)
        )

    @property
    def role_split(self) -> tuple[int, int]:
        """The EFFECTIVE (prefill, decode) admission widths — compiled
        widths unless a re-split capped them."""
        return (
            self.prefill_engine.effective_slots,
            self.decode_engine.effective_slots,
        )

    @property
    def dead_roles(self) -> tuple:
        return tuple(sorted(self._dead_roles))

    def drop_handoff(self):
        """Chaos hook (``handoff_drop@T``): lose one parked handoff —
        its export is released (the blocks' in-flight reservation dies
        with the message) and nobody tells the scheduler, which is
        exactly the orphan the failover sweep must notice.  Returns the
        dropped request id, or None when nothing is parked."""
        if not self._handoffs:
            return None
        h = self._handoffs.popleft()
        self.decode_engine.pool.release_export(h.export)
        self.handoffs_dropped += 1
        return h.request_id

    # ------------------------------------------------------------------ #
    # accounting
    # ------------------------------------------------------------------ #

    def stats(self) -> dict:
        """Tier accounting: role-attributed occupancy, the merged
        prefill/decode counters (each role owns its half), the shared
        block/host-tier stats once, and the handoff count."""
        pre, dec = self.prefill_engine, self.decode_engine
        out = {
            "slots_active": self.pool.num_active,
            "prefill_slots_active": pre.pool.num_active,
            "decode_slots_active": dec.pool.num_active,
            "prefill_slot_cap": pre.effective_slots,
            "decode_slot_cap": dec.effective_slots,
            "handoffs_queued": len(self._handoffs),
            "handoffs": self.handoffs,
            "handoffs_dropped": self.handoffs_dropped,
            "prefill_tokens_computed": pre.prefill_tokens_computed,
            "prefill_tokens_offered": pre.prefill_tokens_offered,
            "decode_ticks": dec.decode_ticks,
            "decode_slot_ticks": dec.decode_slot_ticks,
            "decode_tokens": dec.decode_tokens,
        }
        if dec.spec_k > 0:
            out["spec_drafted_tokens"] = dec.spec_drafted_tokens
            out["spec_accepted_tokens"] = dec.spec_accepted_tokens
        if self.paged:
            # View-local prefix counters live on the prefill view (all
            # admissions land there); block/host stats are the shared
            # substrate's, counted once.
            out["prefix_hit_tokens"] = (
                pre.pool.prefix_hit_tokens + dec.pool.prefix_hit_tokens
            )
            out["prefix_lookup_tokens"] = (
                pre.pool.prefix_lookup_tokens
                + dec.pool.prefix_lookup_tokens
            )
            out.update(self.blocks.stats())
        return out

    def check_invariants(self) -> None:
        if self.blocks is not None:
            self.blocks.check_invariants()

    def reset(self) -> None:
        """Drop all in-flight requests on both roles, the handoff queue,
        and (paged) the shared substrate — same leg-isolation contract
        as ``ServingEngine.reset``."""
        for h in self._handoffs:
            # Queued handoffs are always paged (see cancel()).
            self.decode_engine.pool.release_export(h.export)
        self._handoffs.clear()
        self.prefill_engine.reset()
        self.decode_engine.reset()
        if self.blocks is not None:
            self.blocks.reset()
        self.handoffs = 0
        self.handoffs_dropped = 0
        self._dead_roles.clear()

    def memory_model(self, program: str) -> dict[str, int]:
        """Per-program HBM model, delegated to the owning role engine
        (graftcheck pass 3 audits the role programs individually)."""
        if program == "prefill":
            return self.prefill_engine.memory_model(program)
        return self.decode_engine.memory_model(program)

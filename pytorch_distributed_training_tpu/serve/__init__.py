"""Continuous-batching serving engine (the inference counterpart of the
training stack).

The static path (``models/generate.py``) is a fixed-batch, run-to-completion
scan: every request shares one ``max_new_tokens`` budget and finished rows
burn compute until the longest row ends.  GEN_ROOFLINE (deleted: not measured
on the current machine) shows decode
throughput scales with batch toward the byte bound — so the serving win is
keeping decode slots FULL under a live request stream.  This package is the
Orca/vLLM-class iteration-level answer, built on the same trained-checkpoint
artifact and the same flax ``cache`` collection:

- ``kv_pool``   — KV-cache pools: the contiguous slot pool (per-slot
  lengths, allocate/release, idle-slot sentinel positions) and the paged
  block pool (``PagedKVCachePool``: fixed-size physical blocks + per-slot
  block tables, on-demand allocation bounded by the GLOBAL pool, and
  hash-addressed prefix caching with refcounts/COW/LRU eviction); ragged
  live sequences coexist in one jitted step via the per-row masking in
  ``models/layers.py`` slot mode either way.
- ``engine``    — AOT-compiled chunked-prefill + decode + speculative-verify
  steps over the slot array, per-slot EOS/budget retirement, token
  streaming.  ``spec_k > 0`` enables speculative decoding: up to k
  prompt-lookup draft tokens verified per tick in one forward pass
  (greedy output token-exact vs the plain engine; rejected draft writes
  rolled back by length accounting + paged block freeing).
- ``draft``     — model-free draft sources: the per-slot prompt-lookup
  drafter and the shared cross-request n-gram index (the token-level
  analogue of the paged pool's prefix cache).
- ``scheduler`` — iteration-level continuous batching: admission into
  freed slots every tick (round-robin across tenants, FIFO within one),
  chunked prefill interleaved with decode, bounded-queue backpressure.
- ``disagg``    — disaggregated prefill/decode serving: role-split engine
  pools (prefill-role compiles only the chunked-prefill program,
  decode-role only decode+verify) with zero-copy KV handoff through the
  shared paged block pool — long-prompt bursts stop inflating decode
  TPOT, greedy output stays token-exact vs the interleaved engine.
- ``kv_store``  — the host-RAM KV tier: evicted refcount-0 prefix blocks
  spill there (instead of vanishing) and restore bit-identically on a
  hash-chain hit; ``sibling_fetch`` moves a hot prefix between replica
  pools so the router never recomputes what a sibling holds.
- ``router``    — the data-parallel tier above N engine replicas (each
  optionally TP-sharded over its own submesh via ``ServingEngine``'s
  ``tp_mesh``): one admission point, least-loaded dispatch with
  prefix-cache-affinity (a prompt whose hash-chained prefix is hot on
  replica k lands on replica k, falling back when k is saturated), a
  shared cross-replica ``NgramIndex``, and per-replica-attributed
  records/telemetry.
- ``failover``  — router-level replica failover: missed-tick/heartbeat
  death detection, straggler degradation, fence + drain + token-exact
  requeue of a dead replica's queued and in-flight requests onto
  survivors (re-prefill from prompt + streamed tokens), exactly-once
  retirement with a retry budget, brown-out shedding under capacity
  loss, and backoff-scheduled respawn — driven by the deterministic
  serving chaos plane (``resilience.ServeFaultInjector``).
- ``metrics``   — per-request SLO records (TTFT/TPOT), percentile summaries,
  goodput/queue-depth and speculation (acceptance rate, tokens-per-tick)
  accounting.
"""

from .autoscale import AutoscaleController
from .disagg import DisaggServingEngine
from .draft import NgramIndex, PromptLookupDrafter
from .engine import Event, Handoff, ServingEngine
from .failover import FailoverController, ReplicaHealth
from .policy import PriorityClass, ServePolicy, parse_priority_spec
from .kv_pool import (
    BlockPool, KVCachePool, PagedKVCachePool, SlotExport,
    hash_prompt_blocks,
)
from .kv_store import HostKVStore, sibling_fetch, sibling_fetch_striped
from .metrics import finalize_record, summarize_records
from .router import ReplicaRouter
from .scheduler import ContinuousScheduler, Request, VirtualClock

__all__ = [
    "AutoscaleController",
    "BlockPool",
    "ContinuousScheduler",
    "DisaggServingEngine",
    "Event",
    "FailoverController",
    "Handoff",
    "HostKVStore",
    "KVCachePool",
    "NgramIndex",
    "PagedKVCachePool",
    "PriorityClass",
    "PromptLookupDrafter",
    "ReplicaHealth",
    "ReplicaRouter",
    "Request",
    "ServePolicy",
    "ServingEngine",
    "SlotExport",
    "VirtualClock",
    "finalize_record",
    "hash_prompt_blocks",
    "parse_priority_spec",
    "sibling_fetch",
    "sibling_fetch_striped",
    "summarize_records",
]

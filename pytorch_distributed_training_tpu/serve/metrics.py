"""Serving SLO metrics: TTFT / TPOT percentiles, goodput, queue depth.

The two latencies that define an interactive serving SLO:

- **TTFT** (time to first token): arrival → first sampled token.  Under
  continuous batching this is queue wait + prefill; under static batching
  it also eats batch assembly AND the whole batch's decode (tokens only
  materialize when the batch completes).
- **TPOT** (time per output token): mean inter-token latency after the
  first token, ``(finish - first_token) / (generated - 1)``.

**Goodput** counts only tokens of COMPLETED requests per second — work a
user actually received, so over-admission that thrashes without finishing
shows up as a goodput loss even when raw tok/s looks fine.
"""

from __future__ import annotations

import numpy as np

from ..obs import percentiles


def percentile(xs, q: float) -> float | None:
    """Linear-interpolated percentile; None for an empty sample.  Thin
    front over the shared ``obs.percentiles`` reduction (one percentile
    implementation for serve SLOs and train-side histograms alike)."""
    (value,) = percentiles(xs, (q,)).values()
    return value


def finalize_record(rec: dict) -> dict:
    """Derive ttft/tpot in place from a completed request's raw
    timestamps (scheduler record or a re-read JSONL line — the derivation
    is the same either way, so percentiles are recomputable from the raw
    per-request logs)."""
    if rec.get("first_token") is not None:
        rec["ttft"] = rec["first_token"] - rec["arrival"]
    else:
        rec["ttft"] = None
    if (
        rec.get("finish") is not None
        and rec.get("first_token") is not None
        and rec.get("generated", 0) > 1
    ):
        rec["tpot"] = (rec["finish"] - rec["first_token"]) / (
            rec["generated"] - 1
        )
    else:
        rec["tpot"] = None
    return rec


def summarize_records(
    records: list[dict],
    *,
    elapsed: float | None = None,
    queue_depth_samples: list[int] | None = None,
    rejected: int = 0,
    active_slot_samples: list[int] | None = None,
    engine_stats: dict | None = None,
    failover_stats: dict | None = None,
) -> dict:
    """Aggregate completed per-request records into the SLO summary the
    bench emits per offered-load point.

    Deadline-shed requests (finish reason ``"shed"``) are finished-but-
    never-served: they count in ``shed`` and ``finish_reasons`` but are
    excluded from ``completed`` and every latency/goodput figure — a
    shed request has no TTFT and produced nothing a user received.
    Mid-decode cancellations (finish reason ``"cancelled"`` — the
    --serve-ttl in-flight half) are excluded the same way: whatever they
    generated before the deadline, nobody was waiting for it; so are
    failover retirements (finish reason ``"failed"`` — the retry budget
    died before the request did, serve/failover.py).

    Exactly-once: should two records ever share a request id (a replica
    death racing retirement — the failover controller suppresses these
    at the source, but a merged multi-run log can still carry them),
    only the FIRST is counted; later duplicates are excluded from every
    figure exactly once and reported under ``failover``."""
    duplicates = 0
    seen_ids: set = set()
    deduped = []
    for r in records:
        rid = r.get("id")
        if rid is not None and rid in seen_ids:
            duplicates += 1
            continue
        if rid is not None:
            seen_ids.add(rid)
        deduped.append(r)
    records = deduped
    finished = [r for r in records if r.get("finish") is not None]
    completed = [
        r for r in finished
        if r.get("finish_reason") not in ("shed", "cancelled", "failed")
    ]
    shed = sum(1 for r in finished if r.get("finish_reason") == "shed")
    cancelled = sum(
        1 for r in finished if r.get("finish_reason") == "cancelled"
    )
    failed = sum(
        1 for r in finished if r.get("finish_reason") == "failed"
    )
    tokens = sum(r.get("generated", 0) for r in completed)
    if elapsed is None and completed:
        t0 = min(r["arrival"] for r in completed)
        t1 = max(r["finish"] for r in completed)
        elapsed = max(t1 - t0, 1e-9)
    out = {
        "completed": len(completed),
        "rejected": int(rejected),
        "shed": shed,
        "cancelled": cancelled,
        "failed": failed,
        "generated_tokens": int(tokens),
        "elapsed_s": round(elapsed, 4) if elapsed else None,
        "goodput_tok_per_s": (
            round(tokens / elapsed, 2) if elapsed else None
        ),
        "ttft_p50_s": percentile([r["ttft"] for r in completed], 50),
        "ttft_p99_s": percentile([r["ttft"] for r in completed], 99),
        "tpot_p50_s": percentile([r["tpot"] for r in completed], 50),
        "tpot_p99_s": percentile([r["tpot"] for r in completed], 99),
        "finish_reasons": {
            reason: sum(
                1 for r in finished if r.get("finish_reason") == reason
            )
            for reason in sorted(
                {r.get("finish_reason") for r in finished} - {None}
            )
        },
    }
    replicas = sorted(
        {r.get("replica") for r in finished} - {None}, key=str
    )
    if replicas:
        # Data-parallel serving tier (serve/router.py): per-replica
        # attribution of the merged records — which replica served what,
        # with the same shed/cancel exclusions as the global figures.
        out["replicas"] = {}
        for rid in replicas:
            mine = [r for r in completed if r.get("replica") == rid]
            ttft50 = percentile([r["ttft"] for r in mine], 50)
            out["replicas"][str(rid)] = {
                "completed": len(mine),
                "generated_tokens": int(
                    sum(r.get("generated", 0) for r in mine)
                ),
                "shed": sum(
                    1 for r in finished
                    if r.get("replica") == rid
                    and r.get("finish_reason") == "shed"
                ),
                "cancelled": sum(
                    1 for r in finished
                    if r.get("replica") == rid
                    and r.get("finish_reason") == "cancelled"
                ),
                "failed": sum(
                    1 for r in finished
                    if r.get("replica") == rid
                    and r.get("finish_reason") == "failed"
                ),
                "ttft_p50_s": (
                    round(ttft50, 6) if ttft50 is not None else None
                ),
            }
    if queue_depth_samples:
        out["queue_depth_mean"] = round(
            float(np.mean(queue_depth_samples)), 2
        )
        out["queue_depth_max"] = int(np.max(queue_depth_samples))
    if active_slot_samples:
        # Concurrency actually sustained — the paged-vs-contiguous bench's
        # slots-per-byte comparison at a fixed cache budget.
        out["live_slots_max"] = int(np.max(active_slot_samples))
        out["live_slots_mean"] = round(
            float(np.mean(active_slot_samples)), 2
        )
    if engine_stats:
        # Prefill work + prefix-cache/block-pool accounting
        # (ServingEngine.stats()), carried verbatim into the bench rows.
        out["engine"] = dict(engine_stats)
        if engine_stats.get("spec_drafted_tokens") is not None:
            # Speculative-decoding headline stats: acceptance rate over
            # drafted tokens and effective tokens per decode tick (> 1.0
            # is the whole point — accepted tokens amortize the per-tick
            # param/KV read).
            drafted = engine_stats["spec_drafted_tokens"]
            ticks = engine_stats.get("decode_ticks", 0)
            slot_ticks = engine_stats.get("decode_slot_ticks", 0)
            out["spec"] = {
                "drafted_tokens": int(drafted),
                "accepted_tokens": int(
                    engine_stats["spec_accepted_tokens"]
                ),
                "rejected_tokens": int(
                    drafted - engine_stats["spec_accepted_tokens"]
                ),
                "acceptance_rate": (
                    round(
                        engine_stats["spec_accepted_tokens"] / drafted, 4
                    ) if drafted else None
                ),
                # Batch-level emission rate (conflates live-slot count
                # with speculation)…
                "tokens_per_decode_tick": (
                    round(engine_stats["decode_tokens"] / ticks, 3)
                    if ticks else None
                ),
                # …vs the per-slot amortization factor: 1.0 is the plain
                # one-token-per-tick floor; every point above it is
                # param/KV reads the accepted drafts saved.
                "tokens_per_slot_tick": (
                    round(engine_stats["decode_tokens"] / slot_ticks, 3)
                    if slot_ticks else None
                ),
            }
    retried_completed = sum(1 for r in completed if r.get("retries"))
    if failover_stats or duplicates or retried_completed or failed:
        # Failover accounting (serve/failover.py): the record-derived
        # figures (retried requests that still completed, duplicates
        # excluded above, budget-exhausted failures) plus the
        # controller's own counters and per-replica death ticks when a
        # live run hands them over.
        fo = {
            "duplicate_records_excluded": duplicates,
            "retried_completed": retried_completed,
            "failed": failed,
        }
        if failover_stats:
            for key in (
                "requeued", "retried", "duplicates_suppressed",
                "respawns", "replica_deaths", "deaths",
            ):
                if key in failover_stats:
                    fo[key] = failover_stats[key]
        out["failover"] = fo
    for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s"):
        if out[k] is not None:
            out[k] = round(out[k], 6)
    return out

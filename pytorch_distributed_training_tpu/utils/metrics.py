"""Human-facing metrics logging (stdout + per-epoch / per-request JSONL).

The reference computes the loss every batch but never surfaces it
(src/main.py:76; SURVEY.md §5 "metrics" row).  This logger prints
human-readable lines and optionally appends machine-readable JSONL — enough
for the BASELINE throughput comparisons without a TensorBoard dependency.
Only process 0 emits, so multi-host runs don't interleave output.

This module is the HUMAN surface; the machine surface — per-step
structured events, counters, histograms, flight-recorder anomalies,
per-rank logs — is ``obs.MetricsEmitter`` (``--metrics-dir``), which all
subsystems report through.  Percentile math lives there too
(``obs.percentiles``); nothing here re-rolls it.
"""

from __future__ import annotations

import json
import os
from typing import Any


class _JsonlEmitter:
    """Shared multi-host emit rule + JSONL path setup: only process 0
    writes (unless ``only_rank0=False``), so multi-host runs don't
    interleave output or double-append records."""

    def __init__(self, jsonl_path: str | None, only_rank0: bool):
        self.jsonl_path = jsonl_path
        self.only_rank0 = only_rank0
        if jsonl_path:
            os.makedirs(os.path.dirname(jsonl_path) or ".", exist_ok=True)

    def _is_emitter(self) -> bool:
        if not self.only_rank0:
            return True
        import jax

        return jax.process_index() == 0

    def _append(self, record: dict[str, Any]) -> None:
        with open(self.jsonl_path, "a") as f:
            f.write(json.dumps(record) + "\n")


class MetricsLogger(_JsonlEmitter):
    def __init__(self, jsonl_path: str | None = None, only_rank0: bool = True):
        super().__init__(jsonl_path, only_rank0)

    def log(self, record: dict[str, Any]) -> None:
        if not self._is_emitter():
            return
        parts = []
        for k, v in record.items():
            parts.append(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}")
        print(" | ".join(parts))
        if self.jsonl_path:
            self._append(record)


class RequestLogger(_JsonlEmitter):
    """Per-request serving records, one JSONL line per finished request.

    Serving summaries report TTFT/TPOT *percentiles*;
    this logger persists the raw material those numbers reduce —
    request id, prompt length, TTFT, TPOT, finish reason, generated count,
    timestamps — so any percentile (or a different SLO cut entirely) is
    recomputable from the logs without re-running the trace.  Unlike
    :class:`MetricsLogger` it never prints: per-request volume belongs on
    disk, not stdout.
    """

    _FIELDS = (
        "id", "prompt_len", "max_new_tokens", "arrival", "deadline",
        "tenant", "replica",
        "admitted", "first_token", "finish", "finish_reason", "generated",
        "ttft", "tpot",
        # Failover provenance (serve/failover.py): re-placement count and
        # the ordered replicas that held the request — additive, absent
        # from records written before the failover plane existed.
        "retries", "replica_history",
    )

    def __init__(self, jsonl_path: str, only_rank0: bool = True):
        super().__init__(jsonl_path, only_rank0)

    def log(self, record: dict[str, Any]) -> None:
        if not self._is_emitter():
            return
        self._append({k: record[k] for k in self._FIELDS if k in record})

    def read(self) -> list[dict[str, Any]]:
        """Load the records back (the recompute path)."""
        with open(self.jsonl_path) as f:
            return [json.loads(line) for line in f if line.strip()]

"""Unified telemetry: the one spine train and serve report through.

Four pieces, one vocabulary (ISSUE 3):

- ``emitter``  — :class:`MetricsEmitter`: counters/gauges/histograms plus
  the schema-versioned per-process JSONL event log (rank-tagged, one
  writer per process) and the shared :func:`percentiles` reduction.
- ``trace``    — canonical xprof phase names (:data:`PHASES`) and the
  compat-shimmed annotation entry points (host spans, step markers,
  trace-time named scopes) threaded through the trainer, grad-sync tiers,
  pipeline ticks, and the serve engine's programs.
- ``cost``     — compiled-cost accounting: FLOPs/bytes from
  ``cost_analysis()``, MFU, a collective census of the compiled HLO, and
  the analytic DCN byte model as per-step counters.
- ``flight``   — the multi-host flight recorder: anomaly detection on the
  write side, step-aligned rank merge + straggler flagging on the read
  side (``tools/telemetry_report.py``).

The live SLO plane (ISSUE 13) rides the same spine as extra SINKS:

- ``live``     — :class:`LiveAggregator`: the online reduction (rolling
  windows + mergeable fixed-log-bucket histograms) teed from the emitter
  via ``attach_sink``;
- ``slo``      — :class:`SLOPolicy`: declared objectives and
  Google-SRE-style multi-window burn-rate alerts, emitted back into the
  log as schema-v4 ``alert`` events;
- ``http``     — :class:`OpsServer`: the stdlib background thread serving
  ``/metrics`` (Prometheus text), ``/healthz``, ``/slo``.
"""

from .cost import (
    collective_census,
    compiled_cost,
    dcn_step_counters,
    grad_sync_wall_model,
    kv_pool_model_bytes,
    memory_stats,
    memory_totals,
    mfu,
    peak_flops_for,
    pp_step_counters,
    serve_activation_estimate,
    spec_shard_factor,
    step_cost_report,
    train_activation_estimate,
    tree_bytes_per_device,
)
from .emitter import (
    ALERT_STATES,
    EVENT_KINDS,
    SCHEMA_VERSION,
    SUPPORTED_SCHEMA_VERSIONS,
    MetricsEmitter,
    percentiles,
    read_events,
    validate_events,
)
from .http import OpsServer, render_prometheus
from .live import (
    FixedLogHistogram,
    LiveAggregator,
    bucket_counts_of,
    bucket_index,
    bucket_upper,
    labeled,
    parse_metric_name,
    quantile_from_buckets,
)
from .slo import (
    PROMOTED_ANOMALIES,
    Objective,
    SLOPolicy,
    parse_slo_spec,
    reduce_alerts,
)
from .spans import (
    SPAN_NAMES,
    Span,
    SpanRecorder,
    span_events,
    ttft_decomposition,
)
from .flight import (
    FlightRecorder,
    load_rank_logs,
    merge_timeline,
    straggler_report,
)
from .ledger import (
    BACKOFF_ENV,
    CATEGORIES as LEDGER_CATEGORIES,
    GoodputLedger,
    fleet_ledger,
)
from .schema import METRICS as METRIC_SCHEMA, check_metric_name
from .trace import PHASES, phase_span, scope, step_annotation

__all__ = [
    "ALERT_STATES",
    "BACKOFF_ENV",
    "EVENT_KINDS",
    "GoodputLedger",
    "LEDGER_CATEGORIES",
    "METRIC_SCHEMA",
    "check_metric_name",
    "fleet_ledger",
    "FixedLogHistogram",
    "FlightRecorder",
    "LiveAggregator",
    "MetricsEmitter",
    "Objective",
    "OpsServer",
    "PHASES",
    "PROMOTED_ANOMALIES",
    "SLOPolicy",
    "SCHEMA_VERSION",
    "SPAN_NAMES",
    "SUPPORTED_SCHEMA_VERSIONS",
    "Span",
    "SpanRecorder",
    "bucket_counts_of",
    "bucket_index",
    "bucket_upper",
    "collective_census",
    "compiled_cost",
    "dcn_step_counters",
    "grad_sync_wall_model",
    "kv_pool_model_bytes",
    "labeled",
    "load_rank_logs",
    "memory_stats",
    "memory_totals",
    "merge_timeline",
    "mfu",
    "parse_metric_name",
    "parse_slo_spec",
    "peak_flops_for",
    "percentiles",
    "phase_span",
    "pp_step_counters",
    "quantile_from_buckets",
    "read_events",
    "reduce_alerts",
    "render_prometheus",
    "scope",
    "span_events",
    "ttft_decomposition",
    "serve_activation_estimate",
    "spec_shard_factor",
    "step_annotation",
    "step_cost_report",
    "train_activation_estimate",
    "tree_bytes_per_device",
    "straggler_report",
    "validate_events",
]

"""Auxiliary subsystems (SURVEY.md §5): metrics, profiling, seeding, debug.

The reference's entire observability story is five ``print`` sites and a tqdm
bar (src/main.py:42, 59, 66, 82, 84, 68), with the loss computed but never
logged; its profiling is one ``perf_counter`` pair (src/main.py:65, 81).
These modules supply the structured equivalents plus the debug tooling JAX
affords (NaN checking in place of race sanitizers — the functional model has
no data races to detect).
"""

from .backoff import BackoffPolicy
from .metrics import MetricsLogger, RequestLogger
from .profiling import trace
from .seeding import seed_everything
from .supervisor import (
    BACKOFF_ENV, PREEMPTED_EXIT_CODE, Heartbeat, SupervisorResult, supervise,
)

__all__ = [
    "BackoffPolicy", "MetricsLogger", "RequestLogger", "trace",
    "seed_everything", "Heartbeat", "SupervisorResult", "supervise",
    "BACKOFF_ENV", "PREEMPTED_EXIT_CODE",
]

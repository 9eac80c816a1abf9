"""Profiling: trace capture.

Upgrades the reference's single ``perf_counter`` pair around the epoch
(src/main.py:65, 81, 84) to a ``jax.profiler`` trace capture — the XLA
timeline showing MXU occupancy and collective overlap, the tool for chasing
the BASELINE ≥90 % scaling bar.  Step timing is the trainer's: the step
time each loss fetch closes (train/trainer.py).
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace (view with TensorBoard/xprof)."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

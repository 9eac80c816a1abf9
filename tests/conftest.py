"""Test harness: run everything on a simulated 8-device CPU mesh.

The reference has no tests at all (SURVEY.md §4).  Our strategy, per the
survey: CPU-backend JAX with ``--xla_force_host_platform_device_count=8`` to
fake an 8-device mesh in one process, so DP/TP/SP numerics and sharding are
exercised without TPU hardware.  These env vars must be set before JAX
initializes its backends, hence at conftest import time.
"""

import os

# The suite runs on the CPU whatever the session's environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from pytorch_distributed_training_tpu.compat import set_cpu_device_count  # noqa: E402
from pytorch_distributed_training_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

set_cpu_device_count(8)
jax.config.update("jax_threefry_partitionable", True)
# Persistent compilation cache: the suite's cost is dominated by XLA
# compiles of near-static graphs (pipeline schedules, GPT-2 step fns).
# Every xdist worker and every CLI smoke test's subprocess reads and
# writes the one directory, so a program one process compiled for longer
# than the threshold below is loaded, not compiled, by the next that
# builds it, in this run and in warm reruns.  Each process still traces
# and lowers on its own, and its in-memory caches are its own.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def audit_programs(devices8):
    """The graftcheck lowering cache: every audited program (train step
    per --grad-sync mode + the zero1 leg + all serving programs at
    tp=1/tp=2) lowered and compiled once per test process.  Under xdist
    each worker is a process of its own and would build its own copy, so
    every reader lives in one file, tests/test_shardcheck.py (pass 2's
    audits and pass 3's census/memory pins), which ``--dist loadfile``
    hands to one worker: one build a tier-1 run, read by both passes as
    the runner's shared cache is."""
    from pytorch_distributed_training_tpu.analysis.hlo_audit import (
        build_audit_programs,
    )

    return build_audit_programs(tp=2)

"""The JAX surface the codebase is written against, in one place.

jax 0.9.0 is the one installation there is (``pyproject.toml`` pins it):
top-level ``jax.shard_map`` with ``check_vma``, varying-axis typing via
``jax.typeof``/``lax.pcast``, and the ``jax_num_cpu_devices`` config.  The
names below are what the call sites import; there is no branch for another
release.
"""

from __future__ import annotations

import contextlib

import jax
from jax import lax

__all__ = [
    "shard_map", "typeof", "pcast", "set_cpu_device_count", "ambient_mesh",
    "trace_annotation", "step_trace_annotation", "named_scope",
]

shard_map = jax.shard_map
typeof = jax.typeof
pcast = lax.pcast


def ambient_mesh():
    """``(mesh, manual)``: the mesh of the enclosing ``with mesh:`` block
    (None outside one) and whether the current trace is already inside a
    ``shard_map`` body.  Valid at trace time, which the public
    ``jax.sharding.get_mesh`` is not; the codebase enters meshes through
    the ``with mesh:`` form, whose state only this private module holds."""
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.thread_resources.env.physical_mesh
    manual = bool(jax.sharding.get_abstract_mesh().manual_axes)
    return (None if mesh.empty else mesh), manual


# ---- profiler / tracing shims (obs/) ----------------------------------
#
# The telemetry subsystem (obs/trace.py) threads semantic phase names into
# xprof timelines.  The profiler is optional in some builds (stripped
# profiler) — every entry point degrades to a no-op context rather than an
# ImportError, so annotation call sites never need their own guards.


def trace_annotation(name: str, **kwargs):
    """Host-side xprof annotation: brackets the wall-clock span of the
    enclosed host code (dispatch, compiled-call wait) in the trace viewer.
    No-op outside an active profiler capture, and on profiler-less builds."""
    try:
        return jax.profiler.TraceAnnotation(name, **kwargs)
    except Exception:
        return contextlib.nullcontext()


def step_trace_annotation(name: str, step_num: int):
    """Step marker: xprof groups device activity under per-step rows."""
    try:
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
    except Exception:
        return contextlib.nullcontext()


def named_scope(name: str):
    """Trace-time scope: ops traced under it carry ``name`` in their HLO
    metadata, so compiled-program timelines show semantic phases (grad-sync
    tiers, pipeline ticks) instead of raw fusion names."""
    try:
        return jax.named_scope(name)
    except Exception:
        return contextlib.nullcontext()


def set_cpu_device_count(n: int) -> None:
    """Simulate ``n`` CPU devices; must run before the backend initializes
    (JAX raises ``RuntimeError`` afterwards)."""
    jax.config.update("jax_num_cpu_devices", int(n))

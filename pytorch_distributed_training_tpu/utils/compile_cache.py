"""Where JAX's persistent compilation cache lives, and what it was asked.

Every process that compiles — the CLI, the benches, the dry-run legs, the
test session and the children they launch — calls
:func:`enable_compile_cache` before its first compile, so a relaunched
child, a second run, or a serving process started after a training process
finds what the first one built.

``JAX_COMPILATION_CACHE_DIR`` moves the cache from outside: JAX reads that
variable itself, so when it is set this module sets nothing.  Otherwise the
cache is ``<checkout>/.jax_cache`` — one fixed path, because the path is
part of how a deployment finds its cache again and a name that moves
(temp dir, pid, time) never hits.

The same call registers, once per process, ``jax.monitoring`` listeners for
the events JAX reports where it traces, lowers, compiles and looks into that
cache, and keeps one record per event in memory (:func:`compile_events`):
what start-up spent on compiling, for which function, whether the cache
answered, and whether anything compiled after warm-up.  A listener runs
only when JAX compiles or reads its cache, so a steady-state step pays
nothing.  Each record carries the innermost program phase open when it
fired; the code that runs a phase opens it (:func:`compile_phase`).
"""

from __future__ import annotations

import contextlib
import os
import time

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# jax.monitoring event -> the record's ``what``.  JAX reports the three
# compile steps as time spans with ``fun_name``, the cache's retrieval as a
# duration, and a hit or a miss as a bare occurrence.
_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_OCCURRENCES = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}

# The phases a compile event can be put down to (tests pin membership).
COMPILE_PHASES = (
    "startup/state",     # CLI: building the model and the train state
    "startup/restore",   # CLI: restoring a checkpoint
    "startup/step",      # CLI: building and probing the train step
    "train/epoch",       # Trainer.run_epoch, with the epoch's number
    "trace/scopes",      # train/step.py::step_scopes: the traced step compiled for its text
)

_events: list[dict] = []
_phases: list[dict] = []      # the open phases, innermost last
_listening = False


def _record(what: str, seconds: float, t_end: float, kw: dict) -> None:
    _events.append({
        "what": what, "fun_name": kw.get("fun_name"), "seconds": float(seconds),
        "t_end": t_end, **(_phases[-1] if _phases else {"phase": None}),
    })


def _on_span(event: str, t_start: float, t_end: float, **kw) -> None:
    if event in _SPANS:
        _record(_SPANS[event], t_end - t_start, t_end, kw)


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event in _DURATIONS:
        _record(_DURATIONS[event], seconds, time.time(), kw)


def _on_occurrence(event: str, **kw) -> None:
    if event in _OCCURRENCES:
        _record(_OCCURRENCES[event], 0.0, time.time(), kw)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and start recording compile
    events; returns the cache's directory.  Touches ``jax.config`` and
    ``jax.monitoring`` only — no backend is initialized."""
    global _listening
    import jax
    from jax import monitoring

    if not _listening:
        monitoring.register_event_time_span_listener(_on_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_occurrence)
        _listening = True
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


@contextlib.contextmanager
def compile_phase(name: str, **attrs):
    """Open a program phase: compile events fired inside carry ``phase`` =
    ``name`` and ``attrs`` (``train/epoch`` carries ``epoch``)."""
    if name not in COMPILE_PHASES:
        raise ValueError(f"unknown compile phase {name!r} ({COMPILE_PHASES})")
    _phases.append({"phase": name, **attrs})
    try:
        yield
    finally:
        _phases.pop()


def compile_events(since: int = 0) -> list[dict]:
    """The records so far, oldest first (from index ``since``): ``what``,
    ``fun_name`` (None where JAX gives none), ``seconds``, ``t_end`` (host
    time the event ended), ``phase`` and the phase's attributes."""
    return _events[since:]


def compile_totals(events: list[dict]) -> dict:
    """``compiles`` (backend compiles, a cache hit's load included),
    ``compile_s``, ``cache_hits`` and ``cache_misses`` of ``events``.
    ``compile_s`` is the host time covered by a trace, a lowering or a
    backend compile: their union, because a function traced inside another
    reports a span inside the outer one's."""
    count = {w: sum(e["what"] == w for e in events)
             for w in ("backend_compile", "cache_hit", "cache_miss")}
    covered, edge = 0.0, float("-inf")
    for t0, t1 in sorted((e["t_end"] - e["seconds"], e["t_end"])
                         for e in events if e["what"] in _SPANS.values()):
        covered += max(t1 - max(t0, edge), 0.0)
        edge = max(edge, t1)
    return {
        "compiles": count["backend_compile"], "compile_s": covered,
        "cache_hits": count["cache_hit"], "cache_misses": count["cache_miss"],
    }

"""Loads a cell by name, runs its kind once, and shapes the result line.

Nothing here knows a model, a traffic mix or a metric: the cell's file names
its configuration and its kind, ``BENCHMARK.json`` names the metrics the cell
reports, and each per-layer metric's file names its reader.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    kinds = load_json("peaks.json")["kinds"]
    if device_kind not in kinds:
        raise SystemExit(
            f"benchmark: device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(kinds)}); add it with its source before measuring on it"
        )
    return kinds[device_kind]


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def model_overrides(config: dict) -> dict:
    """The program's constructor arguments from the configuration file's own
    numbers (``system.map``: source key -> program key), so that the file of
    sizes is what runs."""
    system = config["system"]
    out = {dst: config[src] for src, dst in system.get("map", {}).items()}
    out.update(system.get("overrides", {}))
    return out


@dataclasses.dataclass
class Context:
    cell_name: str
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict | None       # None only in the CPU rehearsal
    t_start: float           # time.time() at process start
    setup_s: float | None = None
    trace_events: dict | None = None
    memory_seen: int = 0     # largest footprint ``sample_memory`` has read
    _trace_dir: str | None = None

    @property
    def measuring(self) -> bool:
        """False only in the CPU rehearsal, which prints no time and no rate."""
        return self.peaks is not None

    @property
    def seed32(self) -> int:
        """``--seed`` folded into what a 32-bit PRNG key takes."""
        return int(self.seed) % 2147483629

    def mark(self, what: str) -> None:
        """An earlier line saying where set-up's time goes (measuring runs only)."""
        if self.measuring:
            print(f"set-up: {time.time() - self.t_start:7.2f} s  {what}", flush=True)

    def sample_memory(self) -> None:
        """Read the fullest chip's footprint now; the kinds call this while
        their programs run (each batch pull, each tick)."""
        self.memory_seen = max(self.memory_seen, memory_footprint_bytes(self.devices))

    def open_window(self) -> None:
        """Set-up ends here: imports, state or engine build, warm-up and the
        correctness check are behind, the measured window starts."""
        self.setup_s = time.time() - self.t_start
        self.mark("window opens")

    def prime_profiler(self) -> None:
        """One throwaway capture during set-up, so that the traced window's
        own start does not pay the profiler's first-time initialisation."""
        import jax

        tmp = tempfile.mkdtemp(prefix="bench_prime_")
        try:
            jax.profiler.start_trace(tmp)
            jax.profiler.stop_trace()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def trace_dir(self) -> str:
        """A temporary directory for the profiler (under TMPDIR, never in the
        checkout); ``collect_trace`` reduces what lands there and deletes it."""
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        return self._trace_dir

    def collect_trace(self) -> None:
        from . import tracered

        try:
            self.trace_events = tracered.load_events(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    @contextlib.contextmanager
    def capture(self):
        """Trace what runs inside (host annotations on, per-call Python
        events off) and collect it."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir(), profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            self.collect_trace()


def memory_footprint_bytes(devices: list) -> int:
    """Bytes held on the fullest chip at this instant.  The TPU runtime keeps
    live arrays (``bytes_in_use``) and the scratch it reserves for running
    programs (``bytes_reserved``: a step's activations and temporaries) in
    two disjoint regions of one chip's memory (PERF.md section 6, PR 23), so
    the footprint is their sum, both read in one ``memory_stats()`` call."""
    footprint = 0
    for d in devices:
        stats = d.memory_stats() or {}
        footprint = max(footprint, int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0)))
    return footprint


def peak_memory_bytes(ctx: Context) -> int:
    """The peak on the fullest chip: the largest footprint that was read at
    one instant while the programs ran (``Context.sample_memory``), or either
    region's own peak counter where that is larger.  Each of the three was
    held at a real instant, so the reading can fall short of the true peak
    and never passes it."""
    peak = ctx.memory_seen
    for d in ctx.devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)), int(stats.get("peak_bytes_reserved", 0)))
    return peak


def device_block(ctx: Context, peak: int, reduced: dict | None) -> dict:
    """The device as JAX reports it, and under ``--trace 1`` the busy time
    and the length of the traced window."""
    devices = ctx.devices
    if ctx.measuring:
        for d in devices:
            print(f"memory {d}: " + " ".join(
                f"{k}={v}" for k, v in sorted((d.memory_stats() or {}).items())), flush=True)
        print(f"memory: largest footprint read at one instant {ctx.memory_seen}, reported peak {peak}",
              flush=True)
    out = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    if reduced is not None:
        out["busy_s"] = reduced["busy_s"]
        out["window_s"] = reduced["window_s"]
    return out


def run_cell(ctx: Context, manifest: dict) -> dict:
    """Run the cell's kind and return the result line as a dict."""
    from . import tracered

    kind = importlib.import_module(f"benchmark.kinds.{ctx.cell['kind']}")
    outcome = kind.run(ctx)
    facts = outcome["facts"]
    reduced = None
    if ctx.trace:
        if ctx.trace_events is None:
            raise SystemExit("benchmark: --trace 1 but the kind captured no trace")
        reduced = tracered.reduce(ctx.trace_events, ctx.cell.get("trace", {}))
        facts["trace"] = reduced
    facts["memory_peak_bytes"] = peak_memory_bytes(ctx)
    facts["peaks"] = ctx.peaks
    facts["config"] = ctx.config

    metrics: dict = {}
    if ctx.trace:
        for m in manifest["per_layer"]:
            if not applies(m, ctx.cell_name):
                continue
            spec = load_json("layers", m["name"] + ".json")
            reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
            value = reader.read(facts, **spec.get("args", {}))
            if value is not None:     # a reader that finds nothing returns nothing
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
        for m in manifest["end_to_end"]:
            if applies(m, ctx.cell_name):
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    line = {
        "correct": bool(outcome["correct"]),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": metrics,
        "device": device_block(ctx, facts["memory_peak_bytes"], reduced),
    }
    if reduced is not None:
        line["breakdown"] = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["top_gaps"][:10],
        }
    return line

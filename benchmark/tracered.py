"""The reduction from a profiler trace to numbers, in two steps.

``load_events`` reads the ``.xplane.pb`` the JAX profiler wrote (with nothing
but ``jax.profiler.ProfileData``) into a plain event table; ``reduce`` turns
an event table into device busy time, idle share, per-operation sums and the
longest idle gaps, each attributed to the host annotation that covers it.  The
recorded trace under ``benchmark/fixtures/`` is such a table, cut from a real
run on the chip, and the benchmark's tests check ``reduce`` against it.

What a TPU trace holds (read by hand, PR 23): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed HLO
operation (a ``while`` or ``conditional`` spans its body's events on the same
line, so sums are taken over SELF time; its name is the whole HLO instruction
text, and a Pallas kernel is a ``custom-call`` whose target is
``tpu_custom_call``) and whose line ``XLA Modules`` has one event per executed
program, named ``jit_<function>(<id>)``; and the plane ``/host:CPU`` with one line per
thread, where ``jax.profiler.TraceAnnotation`` and ``StepTraceAnnotation``
land under their own names.  All planes share one clock (nanoseconds).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def load_events(trace_dir: str) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]}`` of every ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for path in paths:
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            lines = []
            for line in plane.lines:
                events = []
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    events.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
                if events:
                    lines.append({"name": line.name, "events": events})
            if lines:
                planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def read_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


_LAYOUT = re.compile(r"\{[^}]*\}")
_INSTR = re.compile(r"(%[\w.\-]+) = (.*?) ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep
    ``%name = <result shape> <opcode>`` without layouts and operands, and for
    a custom call its target (``tpu_custom_call`` is a Mosaic kernel)."""
    m = _INSTR.match(_LAYOUT.sub("", text))
    if not m:
        return text[:160]
    out = f"{m.group(1)} = {m.group(2)} {m.group(3)}"
    if m.group(3) == "custom-call":
        target = _TARGET.search(text)
        out += " " + (target.group(1) if target else "?")
    return out[:200]


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events) -> list:
    """``[(name, self_ns)]`` for events of ONE line: an event's own duration
    less what the events nested inside it cover."""
    out, stack = [], []          # stack of [name, end, self_ns]
    for name, start, dur, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    out.extend((name, self_ns) for name, _, self_ns in stack)
    return out


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(events: dict) -> list:
    return [p for p in events["planes"]
            if p["name"].startswith(DEVICE_PREFIX) and _line(p, OPS_LINE)]


def host_events(events: dict, names=None) -> list:
    """Host annotation events ``[name, start, dur]``, optionally only
    those whose name is in ``names``."""
    out = []
    for p in events["planes"]:
        if p["name"] != HOST_PLANE:
            continue
        for line in p["lines"]:
            out.extend(e for e in line["events"] if names is None or e[0] in names)
    return out


def reduce(events: dict, trace_cfg: dict | None = None) -> dict:
    """Busy union, idle share, per-name self-time sums and gap attribution.

    ``trace_cfg["annotations"]`` lists the host annotations idle gaps are
    attributed to, innermost last; a gap under none of them is "none".
    Times are seconds; ``busy_s`` is averaged over the chips in the trace.
    The window runs from the first to the last device operation or named host
    annotation, whichever is wider.
    """
    trace_cfg = trace_cfg or {}
    names = list(trace_cfg.get("annotations", []))
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    hosts = host_events(events, set(names))
    starts = [e[1] for p in planes for e in _line(p, OPS_LINE)] + [e[1] for e in hosts]
    ends = [e[1] + e[2] for p in planes for e in _line(p, OPS_LINE)] + [e[1] + e[2] for e in hosts]
    w0, w1 = min(starts), max(ends)

    busy_ns, per_name, gaps, custom_calls = 0.0, {}, [], []
    for p in planes:
        ops = _line(p, OPS_LINE)
        merged = union([e[1], e[1] + e[2]] for e in ops)
        busy_ns += sum(e - s for s, e in merged)
        for name, self_ns in self_times(ops):
            name = short_name(name)
            per_name[name] = per_name.get(name, 0.0) + self_ns
            if " custom-call " in name:
                custom_calls.append([name, self_ns / 1e9])
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = len(planes)

    def cover(start, end):
        """The innermost named annotation covering most of the gap."""
        best, best_ns = "none", 0.0
        for name, s, d, *_ in hosts:
            overlap = min(end, s + d) - max(start, s)
            if overlap > best_ns or (overlap == best_ns and overlap > 0
                                     and names.index(name) > names.index(best)):
                best, best_ns = name, overlap
        return best

    by_cover: dict = {}
    for s, e in gaps:
        who = cover(s, e)
        by_cover[who] = by_cover.get(who, 0.0) + (e - s)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    modules = [[e[0], e[1], e[2]] for p in planes for e in _line(p, MODULES_LINE)]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "idle_share": 1.0 - busy_ns / n / (w1 - w0),
        "chips": n,
        "op_self_s": {k: v / n / 1e9 for k, v in per_name.items()},
        "top_ops": [[k, v / n / 1e9] for k, v in
                    sorted(per_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_by_annotation_s": {k: v / n / 1e9 for k, v in by_cover.items()},
        "top_gaps": [[cover(s, e), (e - s) / 1e9] for s, e in longest],
        "custom_calls": custom_calls,
        "modules": modules,
        "host": [[e[0], e[1], e[2]] for e in hosts],
    }

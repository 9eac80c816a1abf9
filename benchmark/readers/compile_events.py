"""What the program's own compile log says (``utils/compile_cache.py``: one
record per ``jax.monitoring`` compile or cache event, with the program phase
that was open when it fired).  The log lives in the measuring process, so the
reader asks the program for it; a program that keeps none (the parent of the
PR that added it) gives nothing to read.

The window is every ``train/epoch`` phase whose epoch is at least
``from_epoch``: the train kind runs its warm-up as epoch 0, its calibration
as epoch 1 and the measured window as epochs 2 and, traced, 3.  Everything
else — imports, state, the reference check, warm-up — is start-up.
"""


def in_window(event, from_epoch):
    return event.get("phase") == "train/epoch" and event.get("epoch", -1) >= from_epoch


def covered_s(events):
    """Host seconds covered by the events' spans: their union, because a
    function traced inside another reports a span inside the outer one's."""
    covered, edge = 0.0, float("-inf")
    for t0, t1 in sorted((e["t_end"] - e["seconds"], e["t_end"]) for e in events):
        covered += max(t1 - max(t0, edge), 0.0)
        edge = max(edge, t1)
    return covered


def read(facts, whats, window, from_epoch, reduce, events=None):
    """``reduce`` = "seconds" (host time covered) or "count" of the events
    whose ``what`` is in ``whats``, inside the window or before it."""
    if events is None:
        from pytorch_distributed_training_tpu.utils import compile_cache

        log = getattr(compile_cache, "compile_events", None)
        if log is None:
            return None
        events = log()
    chosen = [e for e in events
              if e["what"] in whats and in_window(e, from_epoch) == bool(window)]
    return covered_s(chosen) if reduce == "seconds" else float(len(chosen))

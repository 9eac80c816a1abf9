"""Device time by the program's own scopes.  The program keeps a table of the
train step that ran inside the capture — instruction name -> the innermost
``obs.trace.PHASES`` scope in its ``op_name``, a fusion by its product
(``train/step.py::step_scopes``, ``obs/cost.py::scope_table``) — and the
device trace names every event by its instruction, so the two join on the
``%name`` that starts each key of ``facts["trace"]["op_self_s"]``.  The table
lives in the measuring process, so the reader asks the program for it; a
program that keeps none (the parent of the PR that added it) gives nothing to
read.
"""

# Scopes that only wrap others (a microbatch; its forward and loss): an
# operation whose innermost scope is one of them is in no layer.
WRAPPERS = ("grad_accum/microbatch", "train/loss")
_told = False


def by_scope(op_self_s, table):
    """``({scope or None: seconds}, seconds that joined)``: self time by the
    scope the table gives the operation's ``%name``; None holds what has no
    scope and what the table does not know."""
    seconds, joined = {}, 0.0
    for key, s in op_self_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        joined += s if name in table else 0.0
        scope = table.get(name)
        seconds[scope] = seconds.get(scope, 0.0) + s
    return seconds, joined


def read(facts, scopes, table=None):
    """100 x the self time of the operations whose scope is in ``scopes`` /
    device busy time.  ``scopes`` None is what is left: 100 less every scope
    but the two wrappers — operations with no scope, with only a wrapper, or
    not found in the table all land there, so a stale table cannot hide."""
    global _told
    if table is None:
        from pytorch_distributed_training_tpu.train import step

        ask = getattr(step, "step_scopes", None)
        table = ask() if ask else None
    trace = facts["trace"]
    if table is None or trace["busy_s"] <= 0:
        return None
    seconds, joined = by_scope(trace["op_self_s"], table)
    share = {k: 100.0 * v / trace["busy_s"] for k, v in seconds.items()}
    if not _told:
        _told = True
        print(f"scopes: joined {100.0 * joined / trace['busy_s']:.2f} " + " ".join(
            f"{k or 'none'} {v:.2f}" for k, v in sorted(share.items(), key=lambda kv: -kv[1])), flush=True)
    if scopes is None:
        return 100.0 - sum(v for k, v in share.items() if k is not None and k not in WRAPPERS)
    return sum(share.get(k, 0.0) for k in scopes)

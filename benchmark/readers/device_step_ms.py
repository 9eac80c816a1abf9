from ..stats import median


def read(facts, module_prefix):
    """Device time of one optimizer step: the median duration of the step
    program's executions in the traced window (the trace's program line; the
    window opens mid-step, so its first execution is cut short and the median,
    not the mean, is the step)."""
    runs = [dur for name, _, dur in facts["trace"]["modules"] if name.startswith(module_prefix)]
    return median(runs) / 1e6 if runs else None

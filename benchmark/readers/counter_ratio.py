def read(facts, numerator, denominator, scale=1.0, per=None):
    """``scale * numerator / denominator`` of the window's step counters
    (``facts["counters"]``: what the program's step returned beside its loss,
    summed over the window's steps by the kind); with ``per``, the
    denominator is divided by that counter first (a mean per item).  A kind
    or a program that keeps no such counters gives nothing to read."""
    counters = facts.get("counters") or {}
    if numerator not in counters or (denominator is not None and denominator not in counters):
        return None
    if denominator is None:
        return scale * counters[numerator]
    below = counters[denominator] / (counters[per] if per else 1.0)
    return scale * counters[numerator] / below if below > 0 else None

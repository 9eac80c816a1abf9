def read(facts, annotation):
    """Host time inside one of the program's own annotations (the durations
    of its events in the capture's host plane, on the clock the device planes
    use), % of the traced window.  The annotation has to be one of the cell's
    ``trace.annotations``: the reduction keeps only those."""
    trace = facts["trace"]
    inside = [dur for name, _, dur in trace["host"] if name == annotation]
    if not inside or trace["window_s"] <= 0:
        return None
    return 100.0 * sum(inside) / 1e9 / trace["window_s"]

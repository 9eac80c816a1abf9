import re


def read(facts, pattern):
    """Share of device busy time inside operations whose trace name matches
    ``pattern`` (self time, so a loop is not counted with its body), %."""
    trace = facts["trace"]
    rx = re.compile(pattern)
    inside = sum(s for name, s in trace["op_self_s"].items() if rx.search(name))
    return 100.0 * inside / trace["busy_s"] if trace["busy_s"] > 0 else None

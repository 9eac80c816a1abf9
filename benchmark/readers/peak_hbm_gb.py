def read(facts):
    """Peak device memory on the fullest chip, as the result line's
    ``memory_peak_bytes`` takes it (``harness.peak_memory_bytes``)."""
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None

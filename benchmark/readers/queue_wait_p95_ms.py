from ..stats import percentile


def read(facts):
    """95th percentile of admitted - due arrival over the window's requests."""
    waits = [r["admitted"] - r["arrival"] for r in facts.get("records", [])
             if r.get("admitted") is not None]
    return 1e3 * percentile(waits, 95.0) if waits else None

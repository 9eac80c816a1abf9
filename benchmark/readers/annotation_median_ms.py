from ..stats import median


def read(facts, annotation):
    """Median host duration of one annotation (dispatch + the token fetch
    that closes it) in the traced window."""
    durs = [dur for name, _, dur in facts["trace"]["host"] if name == annotation]
    return median(durs) / 1e6 if durs else None

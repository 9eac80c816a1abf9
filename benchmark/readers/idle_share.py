def read(facts):
    """1 - union of device-operation intervals / traced window, %."""
    return 100.0 * facts["trace"]["idle_share"]

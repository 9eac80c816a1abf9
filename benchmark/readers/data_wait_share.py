def read(facts):
    """Host time the train loop waited on its batch iterator, % of the window."""
    if "data_wait_s" not in facts:
        return None
    return 100.0 * facts["data_wait_s"] / facts["window_s"]

def read(facts):
    """Live decoding slots per decode tick over the slots the engine has, %."""
    stats = facts.get("engine_stats")
    if not stats or not stats.get("decode_ticks"):
        return None
    return 100.0 * stats["decode_slot_ticks"] / (stats["decode_ticks"] * facts["num_slots"])

"""group_ticks_per_s: groups x fleet rounds completed in the window, over
the window's wall seconds (the window ends on block_until_ready)."""


def reduce(rec, trace):
    rounds = rec.values.get("rounds")
    if not rounds or "groups" not in rec.values:
        return None
    return rec.values["groups"] * rounds / rec.window_s

"""serve.dispatch_ms_per_round: host milliseconds per served round in the
program's `dispatch` phase (ServeLoop.stats): one enqueue per resident
block, and resolving the previous round's egress bundles into the
router."""


def reduce(rec, trace):
    rounds = rec.values.get("rounds")
    if "dispatch_s" not in rec.values or not rounds:
        return None
    return rec.values["dispatch_s"] * 1e3 / rounds

"""serve.host_ms_per_round: host milliseconds per served round in the
serving front end, from the program's ServeLoop.stats: the admission,
coalesce, drain_reads, resync and tier phases over the window, over the
rounds served in it."""


def reduce(rec, trace):
    rounds = rec.values.get("rounds")
    if "host_s" not in rec.values or not rounds:
        return None
    return rec.values["host_s"] * 1e3 / rounds

"""mesh.collective_ms_per_round: device milliseconds of the cross-chip
collectives (HLO `all-reduce*` and `collective-permute*` operations) per
fleet round, on the chip where they take longest. A collective holds its
chip until every chip has reached it, so a chip that waits at the
rendezvous shows the wait here.

On one chip the psum is no operation at all and this reads 0. On several,
a trace in which no operation carries those names is read as missing, not
as 0: the names would have changed, and a 0 would measure nothing."""

import trace_reduce

KINDS = ("all-reduce", "collective-permute")


def reduce(rec, trace):
    rounds = rec.values.get("rounds")
    if trace is None or not rounds:
        return None
    lo, hi = trace.window
    found = [[e for e in ops if e[0].startswith(KINDS)] for ops in trace.ops]
    if rec.values.get("chips", 1) > 1 and not any(found):
        return None
    per = [trace_reduce.union_ns(ops, lo, hi) for ops in found]
    return max(per) / 1e6 / rounds

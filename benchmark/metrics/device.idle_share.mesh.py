"""device.idle_share.mesh: percent of the traced window in which no
operation ran on the least busy chip (1 - union of its op intervals /
window, per chip, and the largest of those). The chips meet at every
dispatch's psum, so the one that idles most shows what the others wait
for."""

import trace_reduce


def reduce(rec, trace):
    if trace is None:
        return None
    lo, hi = trace.window
    busy = min(trace_reduce.union_ns(ops, lo, hi) for ops in trace.ops)
    return 100.0 * (1.0 - busy / (hi - lo))

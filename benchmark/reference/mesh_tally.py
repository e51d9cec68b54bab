"""Plain reference for the metrics plane's commit tally: what the host's
committed columns say the `commits` counter has to read, written with numpy
alone.

The counter (the program's metrics plane) sums, every round, the advance
of each replica's committed index over the lanes of a block; on several
chips each chip sums its own lanes and a cross-chip psum adds the chips'
sums. Over a window, then, the counter's change equals the sum over every
lane of (committed after the window - committed before it), whatever the
chips and however the lanes are split among them.
"""

from __future__ import annotations

import numpy as np


def commit_advance(committed_start, committed_end) -> int:
    """Committed-index advance summed over lanes, between two readings of
    the same lanes' committed column."""
    start = np.asarray(committed_start).astype(np.int64)
    end = np.asarray(committed_end).astype(np.int64)
    return int((end - start).sum())


def tally_off(counter_deltas, committed_starts, committed_ends) -> int:
    """How far the counter's window change is from the columns', summed
    over blocks as absolute differences, so that errors of two blocks
    cannot cancel."""
    return sum(
        abs(int(d) - commit_advance(s, e))
        for d, s, e in zip(counter_deltas, committed_starts, committed_ends)
    )


def lanes_differ(a: dict, b: dict, fields) -> int:
    """Lanes whose columns differ in any of `fields` between two readings
    of the same lanes ([lane] or [lane, ...] columns)."""
    n = len(np.asarray(a[fields[0]]))
    bad = np.zeros(n, bool)
    for f in fields:
        x, y = np.asarray(a[f]), np.asarray(b[f])
        d = x.astype(np.int64) != y.astype(np.int64)
        bad |= d.reshape(n, -1).any(axis=1)
    return int(bad.sum())

"""The trace reduction: the union of intervals against hand counts, and a
recorded CPU trace (`record_trace.py`) reduced end to end."""

from __future__ import annotations

import os

import numpy as np
import pytest

import tiny
import trace_reduce as tr
from record_trace import DISPATCHES, OUT


def test_union_and_gaps_by_hand():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 24),
           ("e", 40, 40)]
    assert tr.union_ns(evs, 0, 50) == 25
    assert tr.union_ns(evs, 8, 25) == 7 + 5
    assert tr.idle_gaps(evs, 0, 50) == [(15, 20), (30, 50)]
    assert tr.idle_gaps(evs, 8, 25) == [(15, 20)]
    assert tr.union_ns([], 0, 10) == 0


def sweep_union(events, lo, hi) -> int:
    """A second count of the union: walk the sorted interval edges."""
    edges = []
    for _, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort(key=lambda x: (x[0], -x[1]))
    total, depth, start = 0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            start = t
        depth += d
        if depth == 0:
            total += t - start
    return total


@pytest.fixture(scope="module")
def reduced():
    assert os.path.exists(OUT)
    with tiny.cpu_trace_names():
        return tr.reduce_profile(tr.load(OUT))


def test_recorded_trace_busy_is_the_union_of_ops(reduced):
    lo, hi = reduced.window
    ops = reduced.ops[0]
    busy = tr.union_ns(ops, lo, hi)
    assert busy == sweep_union(ops, lo, hi)
    durations = [min(e, hi) - max(s, lo) for _, s, e in ops if e > lo and s < hi]
    assert max(durations) <= busy <= sum(durations)
    assert 0 < reduced.busy_s <= reduced.window_s
    assert np.isclose(reduced.idle_share, 1 - reduced.busy_s / reduced.window_s)


def test_recorded_trace_finds_the_round_program_by_name(reduced):
    # on the CPU each dispatch shows as two nested PjitFunction events
    assert reduced.module_count("fused_rounds") == 2 * DISPATCHES
    assert reduced.module_seconds("fused_rounds") > 0
    assert reduced.module_seconds("no_such_program") is None


def test_breakdown_shape(reduced):
    b = reduced.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= tr.TOP
        for name, secs in b[key]:
            assert isinstance(name, str) and secs > 0
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    labels = {n for n, _ in b["idle_gaps"]}
    assert labels <= {"bench.dispatch", "bench.sync", "host:unlabelled"}

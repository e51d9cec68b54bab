"""The log window's range primitives against plain numpy loops.

`onehot.scatter_range_set_multi` and `onehot.gather_range_multi` address a
contiguous circular range of a `[.., W]` column by each slot's offset from
the run's start, and `log.find_conflict` reads the offered range's terms
through them. Each case batches every start (the wrapping ones, W-K+1 ..
W-1, and 0), every prefix count, arbitrary masks and five column dtypes
into one program, and checks it slot by slot against the reference loop.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import Shape
from raft_tpu.ops import log as lg
from raft_tpu.ops import onehot as oh
from raft_tpu.state import init_state

DTYPES = (np.int32, np.uint16, np.int16, np.int8, np.bool_)


def _starts(w: int, k: int) -> np.ndarray:
    return np.unique(np.r_[0, np.arange(max(w - k + 1, 1), w), 1])


def _cols(rng, n: int, w: int):
    return [rng.integers(0, 2 if dt is np.bool_ else 100, (n, w)).astype(dt)
            for dt in DTYPES]


def _ranges(cols, start, vals, mask, count, peer_starts, fc):
    """Both scatter forms, both gather forms and the conflict scan, in one
    program."""
    k = vals[0].shape[-1]
    pre = count.shape[0]
    return (
        oh.scatter_range_set_multi(cols, start, vals, mask),
        oh.scatter_range_set_multi([c[:pre] for c in cols], start[:pre],
                                   [v[:pre] for v in vals], count),
        oh.gather_range_multi(cols, start, k),
        oh.gather_range_multi([cols[0], cols[-1]], peer_starts, k),
        lg.find_conflict(*fc),
    )


@pytest.mark.parametrize("w,k", [(16, 1), (16, 2), (16, 8), (128, 1),
                                 (128, 2), (128, 8), (128, 5)])
def test_range_primitives_match_the_loop(w, k):
    rng = np.random.default_rng(w * 100 + k)
    starts = _starts(w, k)
    # per start: every prefix count 0..k, then two arbitrary masks
    counts = np.arange(k + 1)
    n_pre = len(starts) * len(counts)
    start = np.r_[np.repeat(starts, len(counts)), np.repeat(starts, 2)]
    count = np.r_[np.tile(counts, len(starts)), np.zeros(2 * len(starts), int)]
    n = len(start)
    mask = np.arange(k)[None, :] < count[:, None]
    mask[n_pre:] = rng.integers(0, 2, (n - n_pre, k)).astype(bool)
    cols = _cols(rng, n, w)
    vals = [rng.integers(0, 2 if dt is np.bool_ else 100, (n, k)).astype(dt)
            for dt in DTYPES]
    peer_starts = rng.integers(0, w, (n, 3))
    fc, fc_want = _conflict_cases(rng, w, k)

    pre = slice(0, n_pre)
    by_mask, by_count, lane_g, peer_g, fc_got = jax.jit(_ranges)(
        [jnp.asarray(c) for c in cols], jnp.asarray(start, jnp.int32),
        [jnp.asarray(v) for v in vals], jnp.asarray(mask),
        jnp.asarray(count[pre], jnp.int32),
        jnp.asarray(peer_starts, jnp.int32), fc)

    want = [c.copy() for c in cols]
    for i in range(n):
        for j in range(k):
            if mask[i, j]:
                for c, v in zip(want, vals):
                    c[i, (start[i] + j) % w] = v[i, j]
    for got_m, got_c, ref in zip(by_mask, by_count, want):
        assert got_m.dtype == ref.dtype and got_c.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got_m), ref)
        np.testing.assert_array_equal(np.asarray(got_c), ref[pre])

    # gather at one start a lane, and at one start per [lane, peer] cell
    for st, got, src in ((start, lane_g, cols),
                         (peer_starts, peer_g, [cols[0], cols[-1]])):
        rows = np.arange(n).reshape((n,) + (1,) * (st.ndim - 1) + (1,))
        at = (st[..., None] + np.arange(k)) % w
        for g, c in zip(got, src):
            np.testing.assert_array_equal(np.asarray(g), c[rows, at])

    np.testing.assert_array_equal(np.asarray(fc_got), fc_want)


def _find_conflict_ref(log_term, snap_i, snap_t, last, pend_i, pend_t,
                       prev, ents, n_ents):
    w = log_term.shape[-1]
    out = np.zeros(len(prev), np.int64)
    for i in range(len(prev)):
        for j in range(n_ents[i]):
            idx = prev[i] + 1 + j
            ours = log_term[i, idx % w] if snap_i[i] < idx <= last[i] else 0
            if idx == snap_i[i]:
                ours = snap_t[i]
            if pend_i[i] > 0 and idx == pend_i[i]:
                ours = pend_t[i]
            if ours != ents[i, j]:
                out[i] = idx
                break
    return out


def _conflict_cases(rng, w: int, e: int):
    """find_conflict's arguments and the loop's answers. Per lane our log
    holds terms 1.. from snap_index + 1 to last, the window wrapped; the
    offered run starts below, at and inside it, and holds no conflict, one
    at its first or last entry, or a pending snapshot's index."""
    cases = []  # (snap, last, prev, n_ents, entry terms, pending index)
    for snap in (0, w - 3, 3 * w - 2):
        last = snap + min(w, 10)
        ours = {i: 1 + (i - snap) // 3 for i in range(snap + 1, last + 1)}
        ours[snap] = 7 if snap else 0
        for prev in (snap - 1, snap, last - e, last - 1, last):
            base = [ours.get(prev + 1 + j, 9) for j in range(e)]
            first, tail = list(base), list(base)
            first[0] += 1
            tail[-1] += 1
            cases += [(snap, last, prev, 0, base, 0),
                      (snap, last, prev, e, base, 0),
                      (snap, last, prev, e, first, 0),
                      (snap, last, prev, e, tail, 0),
                      (snap, last, prev, e, base, prev + 1)]
    n = len(cases)
    shape = Shape(n_lanes=n, max_peers=1, log_window=w, max_msg_entries=e)
    st = init_state(shape, np.ones(n, np.int32), np.ones((n, 1), np.int32))
    log_term = rng.integers(0, 50, (n, w)).astype(np.int32)
    col = {f: np.zeros(n, np.int32) for f in
           ("snap_index", "snap_term", "last", "pending_snap_index",
            "pending_snap_term")}
    prev = np.zeros(n, np.int32)
    n_ents = np.zeros(n, np.int32)
    ents = np.zeros((n, e), np.int32)
    for i, (snap, last, p, ne, terms, pend) in enumerate(cases):
        for idx in range(snap + 1, last + 1):
            log_term[i, idx % w] = 1 + (idx - snap) // 3
        col["snap_index"][i], col["snap_term"][i] = snap, 7 if snap else 0
        col["last"][i] = last
        col["pending_snap_index"][i] = pend
        col["pending_snap_term"][i] = 5 if pend else 0
        prev[i], n_ents[i], ents[i] = p, ne, terms
    st = dataclasses.replace(
        st, log_term=jnp.asarray(log_term),
        **{f: jnp.asarray(v) for f, v in col.items()})
    want = _find_conflict_ref(
        log_term, col["snap_index"], col["snap_term"], col["last"],
        col["pending_snap_index"], col["pending_snap_term"], prev, ents,
        n_ents)
    # the cases reach each outcome: none, a conflict at the first offered
    # entry and one at the last
    assert (want == 0).any()
    assert (want == prev + 1).any() and (want == prev + e).any()
    return (st, jnp.asarray(prev), jnp.asarray(ents),
            jnp.asarray(n_ents)), want

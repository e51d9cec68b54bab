"""Batched log-window ops.

The reference keeps three cooperating structures — `raftLog` cursor logic
(log.go:24-63), the `unstable` in-memory tail (log_unstable.go:33-50) and a
pluggable stable `Storage` (storage.go:46-90). On device they collapse into
one circular columnar window per lane:

    entry index i lives at slot i & (W-1), valid when snap_index < i <= last

with cursors  snap_index <= applied <= applying <= committed <= last  and a
`stabled` cursor marking the durably-persisted prefix (everything above it is
the reference's "unstable" tail). The stable/unstable split is therefore a
*cursor*, not a copy — there is no stitching step (reference log.go:491-540's
`slice`) because there is only one buffer.

All ops are masked elementwise updates over the `[N]`/`[N, W]` arrays; where
the reference panics, we set a bit in `state.error_bits` and clamp (see
state.py). Entry *indexes* are implicit (slot position); only term/type/size
columns exist on device — every decision in the reference log layer reads
exactly those (log.go:109-456).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from raft_tpu.ops import onehot as oh
from raft_tpu.state import RaftState

I32 = jnp.int32

# error_bits flags (see RaftState.error_bits)
ERR_COMMIT_OUT_OF_RANGE = 1  # reference log.go:319-324 panic
ERR_CONFLICT_BELOW_COMMIT = 2  # reference log.go:118-120 panic
ERR_APPEND_BELOW_COMMIT = 4  # reference log.go:135-137 panic
ERR_WINDOW_OVERFLOW = 8  # no reference analog: device window capacity
ERR_APPLIED_OUT_OF_RANGE = 16  # reference log.go:328-331 panic
# int32 device indexes (vs the reference's uint64): flag the approach to
# the representable bound LOUDLY instead of silently wrapping. 2^30 leaves
# a billion-entry margin to react (snapshot + re-key the group host-side).
ERR_INDEX_NEAR_OVERFLOW = 32
INDEX_OVERFLOW_MARGIN = 1 << 30
# diet-v2 pack boundary clamp (defined beside pack_state; re-exported here
# so the error_bits flag family reads as one table)
from raft_tpu.state import ERR_DIET_OVERFLOW  # noqa: E402,F401

# paged entry log pool exhaustion (ops/paged.py page_out clamp)
from raft_tpu.state import ERR_PAGE_EXHAUSTED  # noqa: E402,F401


def scrub_stale_slots(state: RaftState) -> RaftState:
    """Zero every log slot outside the live window (idx <= snap_index).

    The circular window leaves compacted/overwritten entries as garbage in
    their slots; nothing device-side reads them, but the paged entry log
    needs a canonical zeros-outside-window layout so that a paged round
    trip (page_out -> page_in, which reconstructs absent slots as zeros)
    is bit-identical to never having paged at all. Both engines run this
    on the UNPAGED exit path too, so raw carries, WAL deltas and digests
    match across paged on/off. Works on slim and diet-packed columns alike
    (mask math is done in int32; the column dtypes are preserved).
    """
    n, w = state.log_term.shape
    s = jnp.arange(w, dtype=I32)[None, :]
    last = state.last.astype(I32)[:, None]
    idx = last - ((last - s) & (w - 1))
    stale = idx <= state.snap_index.astype(I32)[:, None]

    def z(col):
        return jnp.where(stale, jnp.zeros((), col.dtype), col)

    return dataclasses.replace(
        state,
        log_term=z(state.log_term),
        log_type=z(state.log_type),
        log_bytes=z(state.log_bytes),
    )


def _err(state: RaftState, cond, bit: int) -> RaftState:
    return dataclasses.replace(
        state, error_bits=state.error_bits | jnp.where(cond, bit, 0).astype(I32)
    )


def slot_of(state: RaftState, idx):
    w = state.log_term.shape[-1]
    return idx & (w - 1)


def window_indexes(state: RaftState):
    """Per-slot entry index and validity: [N, W] each.

    Slot s holds index first + ((s - first) mod W); valid when <= last.
    """
    n, w = state.log_term.shape
    s = jnp.arange(w, dtype=I32)[None, :]
    first = state.first_index[:, None]
    idx = first + ((s - first) & (w - 1))
    valid = idx <= state.last[:, None]
    return idx, valid


def _mask_terms(state: RaftState, idx, raw):
    """Shared boundary rules for term lookups (reference log.go:380-404
    folded into the zeroTermOnOutOfBounds convention): 0 outside the window,
    the compaction point's own term is known (log.go:387-389), and a pending
    snapshot answers its index (log_unstable.go maybeTerm). idx/raw share a
    shape whose leading dim is the lane axis."""
    extra = idx.ndim - 1
    ex = (slice(None),) + (None,) * extra

    def b(x):
        return x[ex]

    in_window = (idx > b(state.snap_index)) & (idx <= b(state.last))
    t = jnp.where(in_window, raw, 0)
    t = jnp.where(idx == b(state.snap_index), b(state.snap_term), t)
    has_pending = b(state.pending_snap_index) > 0
    t = jnp.where(
        has_pending & (idx == b(state.pending_snap_index)),
        b(state.pending_snap_term),
        t,
    )
    return t


def term_at(state: RaftState, idx):
    """Term of entry `idx` per lane; 0 when unknown (compacted/unavailable).

    idx: [N] or [N, K] — trailing dims broadcast against per-lane cursors.
    """
    raw = oh.gather(state.log_term, slot_of(state, idx))
    return _mask_terms(state, idx, raw)


def last_term(state: RaftState):
    return term_at(state, state.last)


def terms_range(state: RaftState, start, e: int):
    """term_at for the contiguous indexes start..start+e-1 ([N] -> [N, e]),
    read by slot offset from `start` instead of an [N, e, W] gather."""
    idx = start[:, None] + jnp.arange(e, dtype=I32)[None, :]
    (raw,) = oh.gather_range_multi([state.log_term], slot_of(state, start), e)
    return _mask_terms(state, idx, raw)


def match_term(state: RaftState, idx, term):
    """reference log.go:435-441 — with the wrinkle that a real entry's term is
    never 0, so a 0 == 0 match only happens at (0, 0), the empty-log base case,
    which must match. Unknown indexes (term_at == 0) vs term > 0 correctly
    mismatch."""
    return term_at(state, idx) == term


def is_up_to_date(state: RaftState, lasti, term):
    """reference log.go:428-433."""
    lt = last_term(state)
    return (term > lt) | ((term == lt) & (lasti >= state.last))


def commit_to(state: RaftState, tocommit) -> RaftState:
    """reference log.go:317-325: never decrease; past last is corruption."""
    bad = tocommit > state.last
    state = _err(state, (tocommit > state.committed) & bad, ERR_COMMIT_OUT_OF_RANGE)
    new_commit = jnp.maximum(state.committed, jnp.minimum(tocommit, state.last))
    return dataclasses.replace(state, committed=new_commit)


def maybe_commit(state: RaftState, max_index, term) -> tuple[RaftState, jnp.ndarray]:
    """reference log.go:447-456: only commit entries of the given (current)
    term — the §5.4.2 safety rule."""
    ok = (max_index > state.committed) & (term != 0) & (term_at(state, max_index) == term)
    state = commit_to(state, jnp.where(ok, max_index, 0))
    return state, ok


def applied_to(state: RaftState, idx) -> RaftState:
    """reference log.go:327-341 (size accounting lives host-side)."""
    bad = (state.committed < idx) | (idx < state.applied)
    idx = jnp.clip(idx, state.applied, state.committed)
    state = _err(state, bad, ERR_APPLIED_OUT_OF_RANGE)
    return dataclasses.replace(
        state, applied=idx, applying=jnp.maximum(state.applying, idx)
    )


def stable_to(state: RaftState, idx, term) -> RaftState:
    """Advance the durable cursor, guarding against the ABA problem where the
    unstable tail was truncated+rewritten while the write was in flight: only
    entries whose term still matches are acknowledged (reference:
    log_unstable.go:134-160)."""
    ok = (term_at(state, idx) == term) & (idx > state.stabled) & (term != 0)
    return dataclasses.replace(
        state, stabled=jnp.where(ok, jnp.minimum(idx, state.last), state.stabled)
    )


def append(
    state: RaftState, prev_index, ent_term, ent_type, ent_bytes, n_ents
) -> RaftState:
    """Truncate-at-prev_index-and-append (reference log.go:131-141 append +
    log_unstable.go:196-218 truncateAndAppend, collapsed: with a single
    circular buffer all three reference cases are one masked column write).

    prev_index: [N] — entries cover (prev_index, prev_index + n_ents].
    ent_*: [N, E] columns; n_ents: [N] (0 = lane no-op).

    The durable cursor rolls back to prev_index when truncating below it
    (reference log_unstable.go:204-216 shifts unstable.offset instead).
    Capacity: if the result would exceed the window, the lane is clamped to a
    no-op and ERR_WINDOW_OVERFLOW set — callers gate on `has_capacity`.
    """
    n, w = state.log_term.shape
    e = ent_term.shape[-1]
    act = n_ents > 0

    state = _err(state, act & (prev_index < state.committed), ERR_APPEND_BELOW_COMMIT)
    overflow = act & (prev_index + n_ents - state.snap_index > w)
    state = _err(state, overflow, ERR_WINDOW_OVERFLOW)
    ok = act & (prev_index >= state.committed) & ~overflow

    # contiguous circular scatter of the [N, E] prefix into [N, W], the
    # three columns at one set of slot offsets
    new_term, new_type, new_bytes = oh.scatter_range_set_multi(
        [state.log_term, state.log_type, state.log_bytes],
        slot_of(state, prev_index + 1),
        [ent_term, ent_type, ent_bytes],
        jnp.where(ok, n_ents, 0),
    )

    new_last = jnp.where(ok, prev_index + n_ents, state.last)
    state = _err(
        state, ok & (new_last >= INDEX_OVERFLOW_MARGIN), ERR_INDEX_NEAR_OVERFLOW
    )
    return dataclasses.replace(
        state,
        log_term=new_term,
        log_type=new_type,
        log_bytes=new_bytes,
        last=new_last,
        stabled=jnp.where(ok, jnp.minimum(state.stabled, prev_index), state.stabled),
        applying=jnp.minimum(state.applying, new_last),
    )


def find_conflict(state: RaftState, prev_index, ent_term, n_ents):
    """First index among the offered entries whose term mismatches ours, or 0
    when we already contain them all (reference log.go:143-165). Indexes past
    our last are mismatches by construction (term_at == 0 != real term)."""
    e = ent_term.shape[-1]
    idx = prev_index[:, None] + 1 + jnp.arange(e, dtype=I32)[None, :]
    valid = jnp.arange(e, dtype=I32)[None, :] < n_ents[:, None]
    mism = valid & (terms_range(state, prev_index + 1, e) != ent_term)
    big = jnp.int32(2**31 - 1)
    ci = jnp.min(jnp.where(mism, idx, big), axis=-1)
    return jnp.where(ci == big, 0, ci)


def maybe_append(
    state: RaftState, index, log_term, committed, ent_term, ent_type, ent_bytes, n_ents
) -> tuple[RaftState, jnp.ndarray, jnp.ndarray]:
    """The follower append path (reference log.go:107-129): match the
    predecessor, locate the conflict point, truncate+append the novel suffix,
    then advance commit to min(leaderCommit, lastnewi).

    Returns (state', lastnewi [N], ok [N]). Lanes with n_ents < 0 are no-ops
    (mask convention for the batched caller).
    """
    ok = match_term(state, index, log_term)
    lastnewi = index + n_ents
    ci = find_conflict(state, index, ent_term, n_ents)
    state = _err(state, ok & (ci != 0) & (ci <= state.committed), ERR_CONFLICT_BELOW_COMMIT)

    # Append the suffix starting at the conflict point: shift the entry
    # columns left by (ci - index - 1) so entry ci lands first.
    shift = jnp.where(ci > 0, ci - index - 1, 0)  # [N]
    e = ent_term.shape[-1]

    # contiguous in the source; wrapped reads land only in slots the
    # n_keep write mask excludes. One set of slot offsets for the triple.
    sh_term, sh_type, sh_bytes = oh.gather_range_multi(
        [ent_term, ent_type, ent_bytes], shift, e
    )

    n_keep = jnp.where(ok & (ci > 0), n_ents - shift, 0)
    state = append(
        state,
        jnp.where(ci > 0, ci - 1, 0),
        sh_term,
        sh_type,
        sh_bytes,
        n_keep,
    )
    state = commit_to(state, jnp.where(ok, jnp.minimum(committed, lastnewi), 0))
    return state, jnp.where(ok, lastnewi, 0), ok


def find_conflict_by_term(state: RaftState, index, term):
    """Best-guess rollback point for rejected appends (reference
    log.go:166-194): the max i <= index whose term is <= `term` or unknown.
    Returns (idx, term-or-0).  Vectorized: a masked max over the window plus
    the two boundary cases (above last / below the compaction point)."""
    idx_w, valid_w = window_indexes(state)
    t_w = state.log_term
    cand = valid_w & (idx_w <= index[:, None]) & (t_w <= term[:, None])
    best_w = jnp.max(jnp.where(cand, idx_w, -1), axis=-1)
    # The compaction point (term known, = snap_term):
    snap_ok = (state.snap_index <= index) & (state.snap_term <= term)
    best = jnp.maximum(best_w, jnp.where(snap_ok, state.snap_index, -1))
    # Anything unknown stops the scan immediately: above last...
    above = index > state.last
    best = jnp.where(above, index, best)
    # ...or below the compaction point (term unknown -> possible match).
    below = jnp.minimum(index, state.snap_index - 1)
    best = jnp.where(best < 0, jnp.maximum(below, 0), best)
    best = jnp.maximum(best, 0)
    t = jnp.where(above, 0, term_at(state, best))
    return best, t


def rebase_indexes(state: RaftState, mask, delta) -> RaftState:
    """Host-driven index re-keying — the recovery path for the i32 device
    index space (the reference's indexes are uint64, raftpb/raft.proto:21-26;
    here ERR_INDEX_NEAR_OVERFLOW fires at 2^30 and the host rebases).

    Subtracts `delta` [N] from every index-valued field of masked lanes.
    delta MUST be a multiple of the window size so circular slot positions
    (idx & (W-1)) are invariant — no log data moves. Sentinel-zero fields
    (pending/avail snapshot, pending conf index, live read slots) shift only
    where set; pr_match/pr_next clamp at their floors. Clears the overflow
    flag. The caller owns shifting its host-side mirrors by the same delta
    (payload store keys, HardState history — see RawNodeBatch.rebase_group).
    """
    w = state.log_term.shape[-1]
    d = jnp.where(mask, delta, 0)
    dv = d[:, None]

    def sub(x, floor=0):
        return jnp.maximum(x - d, floor)

    def sub_nv(x, floor=0):
        return jnp.maximum(x - dv, floor)

    def sub_if(x, live, dd):
        return jnp.where(live, jnp.maximum(x - dd, 0), x)

    state = dataclasses.replace(
        state,
        last=sub(state.last),
        stabled=sub(state.stabled),
        committed=sub(state.committed),
        applying=sub(state.applying),
        applied=sub(state.applied),
        snap_index=sub(state.snap_index),
        pending_snap_index=sub_if(
            state.pending_snap_index, state.pending_snap_index > 0, d
        ),
        avail_snap_index=sub_if(
            state.avail_snap_index, state.avail_snap_index > 0, d
        ),
        pending_conf_index=sub_if(
            state.pending_conf_index, state.pending_conf_index > 0, d
        ),
        pr_match=sub_nv(state.pr_match),
        pr_next=sub_nv(state.pr_next, 1),
        pr_pending_snapshot=sub_if(
            state.pr_pending_snapshot, state.pr_pending_snapshot > 0, dv
        ),
        infl_index=sub_if(state.infl_index, state.infl_index > 0, dv[..., None]),
        ro_index=sub_if(state.ro_index, state.ro_ctx != 0, dv),
        rs_index=sub_if(state.rs_index, state.rs_ctx != 0, dv),
        error_bits=jnp.where(
            mask,
            state.error_bits & ~jnp.int32(ERR_INDEX_NEAR_OVERFLOW),
            state.error_bits,
        ),
    )
    # delta must have been a multiple of W; flag misuse loudly
    state = _err(state, mask & ((delta & (w - 1)) != 0), ERR_COMMIT_OUT_OF_RANGE)
    return state


def compact(state: RaftState, to_index, to_term) -> RaftState:
    """Host-driven compaction: move the snapshot point forward, freeing window
    slots (reference storage.go:251-272 Compact + CreateSnapshot). Caller must
    pass to_index <= applied and the matching term."""
    ok = (to_index > state.snap_index) & (to_index <= state.applied)
    return dataclasses.replace(
        state,
        snap_index=jnp.where(ok, to_index, state.snap_index),
        snap_term=jnp.where(ok, to_term, state.snap_term),
    )


def restore_snapshot(state: RaftState, idx, term, mask) -> RaftState:
    """Follower adopting a leader snapshot (reference log.go:458-462 restore +
    log_unstable.go:188-194): wipe the log view, set commit, and stage the
    snapshot as pending until the host acks it applied."""
    w = state.log_term.shape[-1]
    m1 = mask[:, None]

    return dataclasses.replace(
        state,
        log_term=jnp.where(m1, 0, state.log_term),
        log_type=jnp.where(m1, 0, state.log_type),
        log_bytes=jnp.where(m1, 0, state.log_bytes),
        last=jnp.where(mask, idx, state.last),
        stabled=jnp.where(mask, idx, state.stabled),
        committed=jnp.where(mask, idx, state.committed),
        snap_index=jnp.where(mask, idx, state.snap_index),
        snap_term=jnp.where(mask, term, state.snap_term),
        pending_snap_index=jnp.where(mask, idx, state.pending_snap_index),
        pending_snap_term=jnp.where(mask, term, state.pending_snap_term),
        applying=jnp.where(mask, jnp.minimum(state.applying, idx), state.applying),
        applied=jnp.where(mask, jnp.minimum(state.applied, idx), state.applied),
    )


def gather_entries(state: RaftState, lo, count, e: int):
    """Read entry columns [lo, lo+count) into [N, e] SoA (for building MsgApp
    payloads on device — reference log.go:406-412 entries()). count must be
    <= e; invalid positions zeroed."""
    idx = lo[:, None] + jnp.arange(e, dtype=I32)[None, :]
    valid = (jnp.arange(e, dtype=I32)[None, :] < count[:, None]) & (
        idx <= state.last[:, None]
    ) & (idx > state.snap_index[:, None])
    slot0 = slot_of(state, lo)

    t, ty, by = (
        jnp.where(valid, x, 0)
        for x in oh.gather_range_multi(
            [state.log_term, state.log_type, state.log_bytes], slot0, e
        )
    )
    return t, ty, by, valid

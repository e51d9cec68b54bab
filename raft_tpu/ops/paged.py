"""Paged entry log (RAFT_TPU_PAGED): ragged log depth without max-W lanes.

The uniform `[N, W]` log window makes every lane pay `W x entry` resident
bytes whether it holds one entry or a full pipeline — one deep-log group
taxes all N lanes (ROADMAP item 3). This module ports the Ragged Paged
Attention idiom to the log window: each lane keeps only a small resident
tail of W_res entries in the carry, and the colder `(snap, last - W_res]`
middle lives in a shared page pool addressed through a per-lane page table.

Layout (all device arrays; one PagedLog pytree rides beside the carry):

    pt         [N, M]   uint16 page ids; 0 = unmapped, page 0 is a
                        reserved trash row so id 0 stays "absent"
    pool_term  [P, PE]  same dtype as the carry's log_term (packed uint16
                        under RAFT_TPU_DIET, int32 slim otherwise)
    pool_type  [P, PE]  carry log_type dtype
    pool_bytes [P, PE]  carry log_bytes dtype
    faults     [N]      int32, cumulative pages gathered at page_in
    exhausted  [N]      int32, cumulative page_out clamp events

Addressing: entry index i belongs to page key `k = i >> log2(PE)`; key k
maps to page-table slot `k & (M - 1)`. The paged key range of one lane is
contiguous (`(snap+1)>>lpe .. lo_res>>lpe`, at most kmax keys — see
resolve_page_plan), so with M = next_pow2(kmax) the mod-M slots are
distinct and the mapping is exact.

Paging is DISPATCH-granular by default: `page_in` reconstructs the full
`[N, W]` window at the top of a fused/pallas dispatch (inside the jit),
the round scan runs on the full window exactly as before — the Pallas
megakernel is untouched, so K>1 bit-identity is structural — and
`page_out` re-splits the result before the dispatch returns. What the
pool buys is the *between-dispatch* resident footprint (the carry XLA
keeps live across round calls and streams over WAL/egress fences), not
in-kernel VMEM.

RAFT_TPU_PAGED_INKERNEL=1 moves the paging passes INTO the round
program (ROADMAP item 3's stretch goal): each Pallas grid step pages in
its own tile's slice of the pool/page-table, runs the K rounds on the
reconstructed window in VMEM, and re-splits before writing back — the
two whole-fleet `[N, W]` gather/scatter passes and the full-window HBM
temporary disappear from the dispatch. The XLA engine gets a tile-free
jnp twin inside its scan body. Because page_out . page_in is
value-identity on scrubbed windows (dead slots never influence round
output), paging at any granularity yields bit-identical trajectories;
only the bookkeeping counters (faults/dirty/skipped cadence) differ
across modes. The allocator additionally becomes conditional in this
mode — `page_out_cond` skips the realloc pass when no lane's log moved
past its resident window since the matching page_in.

`page_out` is a realloc-from-scratch allocator: every dispatch recomputes
`need` pages per lane, assigns page ids by exclusive cumsum (the same
cumsum-scatter idiom as the trace ring), and rebuilds pool + tables with
one scatter. There is no persistent free list to corrupt, page ids never
influence reconstructed values, and mono/sharded/mesh runs stay
digest-identical (ids are shard-local under shard_map, invisibly so).

Pool exhaustion CLAMPS AND FLAGS, mirroring ERR_DIET_OVERFLOW: lanes
whose pages do not fit keep their resident tail, drop the overflow pages
(absent entries read back as zeros at the next page_in), set
ERR_PAGE_EXHAUSTED in error_bits and bump `exhausted` — never a silent
wrap. The default pool size fully provisions every lane so exhaustion
only happens with an explicitly pinned small pool.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from raft_tpu import config
from raft_tpu.config import Shape
from raft_tpu.state import ERR_PAGE_EXHAUSTED, RaftState  # noqa: F401
from raft_tpu.testing.counters import CallCounter

I32 = jnp.int32

# trace-time counter: bumps once per page_in() traced into a program; flat
# while RAFT_TPU_PAGED=0 (the elision claim, checked by the static
# auditor's plane-elision pass)
_CALLS = CallCounter("paged")


def paged_enabled() -> bool:
    """Read RAFT_TPU_PAGED lazily (default OFF); like diet_enabled, the
    value is baked into each cluster at construction — the carry split
    never flips mid-run."""
    return config.env_flag("RAFT_TPU_PAGED", default=False)


def paged_inkernel_enabled() -> bool:
    """Read RAFT_TPU_PAGED_INKERNEL lazily (default OFF): fuse the
    page_in/page_out passes into the round program itself instead of
    running them as whole-fleet passes at the dispatch boundary. Baked
    at cluster construction alongside the engine choice; a no-op unless
    RAFT_TPU_PAGED=1."""
    return config.env_flag("RAFT_TPU_PAGED_INKERNEL", default=False)


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class PagePlan:
    """Resolved paged-log geometry (host-side, static)."""

    w: int  # full log window (Shape.log_window)
    w_res: int  # resident entries per lane
    pe: int  # entries per page
    m: int  # page-table slots per lane (next_pow2(kmax))
    pool_pages: int  # total pool rows incl. the reserved trash page 0

    @property
    def kmax(self) -> int:
        """Max pages one lane can need: the paged range `(snap, lo_res]`
        spans at most `w - w_res` consecutive indexes, which touch at most
        `ceil((w - w_res) / pe) + 1` page keys (the +1 covers straddling
        both ends)."""
        return -((self.w - self.w_res) // -self.pe) + 1


def validate_page_plan(shape: Shape, n_lanes: int) -> PagePlan:
    """Resolve Shape fields / env knobs into a PagePlan, raising
    config-time ValueError on bad geometry (raise, never fall back —
    same contract as validate_round_plan). Zero Shape fields fall back to
    RAFT_TPU_PAGE_WINDOW / RAFT_TPU_PAGE_ENTRIES / RAFT_TPU_POOL_PAGES,
    then to safe defaults (W_res = min(8, W/2), PE = min(4, W_res),
    pool = full provisioning so the default geometry never exhausts)."""
    w = shape.log_window
    if w < 4:
        raise ValueError("paged entry log needs log_window >= 4 "
                         "(page_window must be a strict subset of it)")
    w_res = shape.page_window or config.env_int("RAFT_TPU_PAGE_WINDOW") or min(8, w // 2)
    if w_res & (w_res - 1) or not 2 <= w_res < w:
        raise ValueError(
            f"page_window={w_res} must be a power of two in 2..log_window/2 "
            f"(log_window={w})"
        )
    pe = shape.page_entries or config.env_int("RAFT_TPU_PAGE_ENTRIES") or min(4, w_res)
    if pe & (pe - 1) or not 1 <= pe <= w:
        raise ValueError(
            f"page_entries={pe} must be a power of two in 1..log_window "
            f"(log_window={w})"
        )
    plan = PagePlan(w=w, w_res=w_res, pe=pe, m=0, pool_pages=0)
    kmax = plan.kmax
    m = _next_pow2(kmax)
    pool = shape.pool_pages or config.env_int("RAFT_TPU_POOL_PAGES")
    if pool == 0:
        # Full provisioning: every lane can hold its kmax pages at once,
        # +8 keeps the total divisible by any mesh shard count <= 8 while
        # leaving each shard its own trash page. Pin Shape.pool_pages /
        # RAFT_TPU_POOL_PAGES for the actual savings.
        pool = n_lanes * kmax + 8
    if pool < kmax + 1:
        raise ValueError(
            f"pool_pages={pool} too small: must hold at least one lane's "
            f"full page set plus the trash row (kmax+1 = {kmax + 1} for "
            f"log_window={w}, page_window={w_res}, page_entries={pe})"
        )
    if pool > 1 << 16:
        raise ValueError(
            f"pool_pages={pool} must be <= 65536 (page ids are uint16 with "
            "page 0 reserved as the trash row)"
        )
    return PagePlan(w=w, w_res=w_res, pe=pe, m=m, pool_pages=pool)


@dataclasses.dataclass(frozen=True)
class PagedLog:
    pt: Any
    pool_term: Any
    pool_type: Any
    pool_bytes: Any
    faults: Any
    exhausted: Any
    dirty: Any  # [N] i32, cumulative pages (re)written by the allocator
    skipped: Any  # [N] i32, allocator passes elided by page_out_cond
    # static geometry rides in the treedef (meta fields), so jit twins and
    # shard_map see it for free and shard-local pool shapes come from the
    # leaves themselves
    w: int
    w_res: int


jax.tree_util.register_dataclass(
    PagedLog,
    data_fields=[
        "pt", "pool_term", "pool_type", "pool_bytes",
        "faults", "exhausted", "dirty", "skipped",
    ],
    meta_fields=["w", "w_res"],
)


def init_paged(plan: PagePlan, state: RaftState) -> PagedLog:
    """Fresh empty PagedLog with pool columns in `state`'s carry dtypes
    (packed uint16/int8/int16 under diet, int32/int8 slim otherwise)."""
    n = state.last.shape[0]

    def pool(col):
        return jnp.zeros((plan.pool_pages, plan.pe), col.dtype)

    return PagedLog(
        pt=jnp.zeros((n, plan.m), jnp.uint16),
        pool_term=pool(state.log_term),
        pool_type=pool(state.log_type),
        pool_bytes=pool(state.log_bytes),
        faults=jnp.zeros((n,), I32),
        exhausted=jnp.zeros((n,), I32),
        dirty=jnp.zeros((n,), I32),
        skipped=jnp.zeros((n,), I32),
        w=plan.w,
        w_res=plan.w_res,
    )


def page_in(state: RaftState, paged: PagedLog):
    """Reconstruct the full `[N, W]` log window from the resident tail +
    pool. Returns (full_state, paged') where paged' only has `faults`
    bumped. Slots outside `(snap, last]` come back as zeros — i.e. the
    canonical scrubbed layout (ops/log.py scrub_stale_slots). Index math
    runs in int32 regardless of the (possibly uint16-packed) carry dtypes."""
    _CALLS.bump()
    w, w_res = paged.w, paged.w_res
    p, pe = paged.pool_term.shape
    m = paged.pt.shape[1]
    lpe = pe.bit_length() - 1
    s = jnp.arange(w, dtype=I32)[None, :]
    last = state.last.astype(I32)[:, None]
    snap = state.snap_index.astype(I32)[:, None]
    idx = last - ((last - s) & (w - 1))
    valid = idx > snap
    lo_res = jnp.maximum(snap, last - w_res)
    from_res = valid & (idx > lo_res)
    r_slot = idx & (w_res - 1)
    page = jnp.take_along_axis(paged.pt.astype(I32), (idx >> lpe) & (m - 1), axis=1)
    mapped = valid & ~from_res & (page > 0)
    ent = jnp.where(mapped, page, 0) * pe + (idx & (pe - 1))

    def col(res_col, pool_col):
        rv = jnp.take_along_axis(res_col, r_slot, axis=1)
        pv = pool_col.reshape(p * pe)[ent]
        z = jnp.zeros((), res_col.dtype)
        return jnp.where(from_res, rv, jnp.where(mapped, pv, z))

    full = dataclasses.replace(
        state,
        log_term=col(state.log_term, paged.pool_term),
        log_type=col(state.log_type, paged.pool_type),
        log_bytes=col(state.log_bytes, paged.pool_bytes),
    )
    faults = paged.faults + jnp.sum((paged.pt > 0).astype(I32), axis=1)
    return full, dataclasses.replace(paged, faults=faults)


def _resident_tail(state: RaftState, paged: PagedLog) -> RaftState:
    """The allocator-free half of page_out: mask a full `[N, W]` state
    down to its resident `[N, W_res]` tail (entry i at slot
    i & (W_res - 1), canonical zeros elsewhere). Shared by page_out and
    page_out_cond's skip branch."""
    w, w_res = paged.w, paged.w_res
    last = state.last.astype(I32)
    snap = state.snap_index.astype(I32)
    lo_res = jnp.maximum(snap, last - w_res)
    r = jnp.arange(w_res, dtype=I32)[None, :]
    i_r = last[:, None] - ((last[:, None] - r) & (w_res - 1))
    rvalid = i_r > lo_res[:, None]
    rsl = i_r & (w - 1)

    def res_col(full_col):
        z = jnp.zeros((), full_col.dtype)
        return jnp.where(rvalid, jnp.take_along_axis(full_col, rsl, axis=1), z)

    return dataclasses.replace(
        state,
        log_term=res_col(state.log_term),
        log_type=res_col(state.log_type),
        log_bytes=res_col(state.log_bytes),
    )


def page_out(state: RaftState, paged: PagedLog):
    """Split a full `[N, W]` state into the resident `[N, W_res]` tail +
    a freshly rebuilt pool/page-table. Lanes whose pages do not fit the
    pool clamp: overflow pages are dropped (read back as zeros), the lane
    gets ERR_PAGE_EXHAUSTED in error_bits and `exhausted` increments."""
    w, w_res = paged.w, paged.w_res
    p, pe = paged.pool_term.shape
    m = paged.pt.shape[1]
    n = state.last.shape[0]
    lpe = pe.bit_length() - 1
    last = state.last.astype(I32)
    snap = state.snap_index.astype(I32)
    lo_res = jnp.maximum(snap, last - w_res)

    # allocate: contiguous page-id ranges by exclusive cumsum over per-lane
    # need, ids starting at 1 (page 0 = trash row)
    k_lo = (snap + 1) >> lpe
    k_hi = lo_res >> lpe
    need = jnp.where(lo_res > snap, k_hi - k_lo + 1, 0)
    page0 = 1 + jnp.cumsum(need) - need
    n_alloc = jnp.clip(p - page0, 0, need)
    exh = n_alloc < need

    # page-table fill: slot mm holds key k_m == mm (mod M); keys in
    # [k_lo, k_lo + M) cover the whole live range since need <= kmax <= M
    mm = jnp.arange(m, dtype=I32)[None, :]
    k_m = k_lo[:, None] + ((mm - k_lo[:, None]) & (m - 1))
    j = k_m - k_lo[:, None]
    live = j < n_alloc[:, None]
    pid = jnp.where(live, page0[:, None] + j, 0)

    # pool scatter: row pid(k) column c holds entry k*PE + c; positions
    # outside (snap, lo_res] and dead pages write zeros into the trash row
    ent_idx = k_m[:, :, None] * pe + jnp.arange(pe, dtype=I32)[None, None, :]
    pvalid = (
        live[:, :, None]
        & (ent_idx > snap[:, None, None])
        & (ent_idx <= lo_res[:, None, None])
    )
    esl = (ent_idx & (w - 1)).reshape(n, m * pe)
    tid = pid.reshape(n * m)

    def pool_col(full_col):
        z = jnp.zeros((), full_col.dtype)
        d = jnp.where(
            pvalid,
            jnp.take_along_axis(full_col, esl, axis=1).reshape(n, m, pe),
            z,
        )
        return jnp.zeros((p, pe), full_col.dtype).at[tid].set(d.reshape(n * m, pe))

    err = state.error_bits | jnp.where(exh, ERR_PAGE_EXHAUSTED, 0).astype(I32)
    resident = dataclasses.replace(
        _resident_tail(state, paged), error_bits=err
    )
    new_paged = PagedLog(
        pt=pid.astype(paged.pt.dtype),
        pool_term=pool_col(state.log_term),
        pool_type=pool_col(state.log_type),
        pool_bytes=pool_col(state.log_bytes),
        faults=paged.faults,
        exhausted=paged.exhausted + exh.astype(I32),
        dirty=paged.dirty + n_alloc.astype(I32),
        skipped=paged.skipped,
        w=w,
        w_res=w_res,
    )
    return resident, new_paged


def page_out_cond(state: RaftState, paged: PagedLog, last_pre, snap_pre,
                  *, can_skip: bool):
    """Conditional page_out for the in-kernel path: elide the
    realloc-from-scratch allocator pass when no lane's `last` or
    `snap_index` moved since the matching page_in (`last_pre`/`snap_pre`
    are int32 snapshots captured right after it). Static `can_skip` must
    only be True when every in-dispatch log write lands inside the
    resident window (append fan-in E <= w_res): then unmoved last/snap
    means the paged region `(snap, lo_res]` is untouched and the
    deterministic allocator would rebuild the exact same pt/pool, so the
    skip branch's resident-tail-only split is value-identical (only the
    dirty/exhausted accumulators would differ — bookkeeping, never
    compared across modes). Snapshots, compaction, and truncation all
    move last or snap, so they always take the full branch."""
    if not can_skip:
        return page_out(state, paged)

    def full_branch(st):
        return page_out(st, paged)

    def skip_branch(st):
        bump = dataclasses.replace(paged, skipped=paged.skipped + 1)
        return _resident_tail(st, paged), bump

    moved = jnp.any(state.last.astype(I32) != last_pre) | jnp.any(
        state.snap_index.astype(I32) != snap_pre
    )
    return jax.lax.cond(moved, full_branch, skip_branch, state)


# --------------------------------------------------------------------------
# host-boundary twins (view / adopt / restore / rebase)
#
# Page ids are LOCAL to the pool array the allocator saw. Inside a
# shard_map dispatch that is the shard's sub-pool, so a sharded cluster's
# [P, PE] global pool is really S independent sub-pools of P/S rows whose
# tables must never be interpreted against the full pool. The host-side
# twins therefore take a static `segs` (1 for monolithic/blocked clusters,
# n_shards for sharded/mesh — FusedCluster._paged_segs) and vmap the local
# ops over a [S, N/S, ...] / [S, P/S, PE] view, which reproduces the
# in-dispatch shard-local semantics exactly (per-segment cumsum, local
# ids, per-segment trash page).


def _seg_tree(tree, segs: int):
    return jax.tree.map(
        lambda x: x.reshape((segs, x.shape[0] // segs) + x.shape[1:]), tree
    )


def _unseg_tree(tree):
    return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), tree)


@functools.partial(jax.jit, static_argnums=(2,))
def page_in_host(state: RaftState, paged: PagedLog, segs: int = 1):
    """page_in with segment-aware addressing; returns (full_state, paged')."""
    if segs == 1:
        return page_in(state, paged)
    full, pg = jax.vmap(page_in)(_seg_tree(state, segs), _seg_tree(paged, segs))
    return _unseg_tree(full), _unseg_tree(pg)


@functools.partial(jax.jit, static_argnums=(2,))
def page_in_view(state: RaftState, paged: PagedLog, segs: int = 1):
    """Read-only full-window view (the faults bump is discarded)."""
    if segs == 1:
        return page_in(state, paged)[0]
    full = jax.vmap(lambda s, p: page_in(s, p)[0])(
        _seg_tree(state, segs), _seg_tree(paged, segs)
    )
    return _unseg_tree(full)


@functools.partial(jax.jit, static_argnums=(2,))
def page_out_host(state: RaftState, paged: PagedLog, segs: int = 1):
    """page_out with segment-aware addressing; returns (resident, paged')."""
    if segs == 1:
        return page_out(state, paged)
    res, pg = jax.vmap(page_out)(_seg_tree(state, segs), _seg_tree(paged, segs))
    return _unseg_tree(res), _unseg_tree(pg)


def split_state(state: RaftState, plan: PagePlan, segs: int = 1):
    """Ctor/adopt/restore helper: split a full-window state into
    (resident_state, paged). The input must already be in its final
    storage form (slim or diet-packed) so the pool dtypes match."""
    return page_out_host(state, init_paged(plan, state), segs)


def resegment(state: RaftState, paged: PagedLog, old_segs: int,
              new_segs: int):
    """Re-key the pool/page-table from one allocation segmentation to
    another (sharded adoption of a mono carry): read
    the full window under the old segmentation, re-split under the new.
    Page ids are local to the sub-pool the allocator saw, so tables
    written under one segmentation must never be read under another —
    this is the only legal conversion. Value-identity on the logical
    log is structural (page_out . page_in roundtrip)."""
    if old_segs == new_segs:
        return state, paged
    full = page_in_view(state, paged, old_segs)
    return page_out_host(full, paged, new_segs)


def check_pool_segments(plan: PagePlan, segs: int) -> None:
    """Config-time geometry gate for segment-local allocation (raise,
    never fall back): the pool must split evenly into `segs` sub-pools
    (one per kernel tile per shard under RAFT_TPU_PAGED_INKERNEL) and
    each sub-pool must still hold one lane's full page set plus its own
    trash row."""
    if segs <= 1:
        return
    if plan.pool_pages % segs:
        raise ValueError(
            f"pool_pages={plan.pool_pages} must divide evenly into "
            f"{segs} allocation segments (one sub-pool per kernel tile "
            "per shard); pin Shape.pool_pages / RAFT_TPU_POOL_PAGES to "
            "a multiple"
        )
    if plan.pool_pages // segs < plan.kmax + 1:
        raise ValueError(
            f"pool_pages={plan.pool_pages} over {segs} allocation "
            f"segments leaves {plan.pool_pages // segs} pages per "
            f"segment; each needs at least kmax+1 = {plan.kmax + 1} "
            "(one lane's page set plus the segment's trash row)"
        )


def audit_records(resident_state: RaftState, paged: PagedLog,
                  full_state: RaftState, paged0: PagedLog) -> list:
    """Audit records for the two host-boundary programs (raft_tpu/
    analysis): page_in against the live (resident, paged) pair and
    page_out against a full-window carry with a fresh all-resident
    sidecar. The mutual ``roundtrip`` keys declare the aval-inverse
    pairing the auditor proves (page_out's outputs == page_in's inputs
    and vice versa), and the carry metadata budgets the TOTAL paged
    residency per lane — resident columns plus sidecar — in the ledger.
    Nothing here dispatches: records are traced and lowered only."""
    n = resident_state.term.shape[0]
    common = dict(
        kwargs={}, static={}, donate=False,
        donate_argnums=(), donate_argnames=(),
        checks=("capture", "hygiene", "donation"),
        lanes=n, rounds=1,
        carry_argnums=(0, 1), carry_argnames=(),
    )
    return [
        dict(common, name="paged.page_in", fn=page_in,
             jit=page_in_host, args=(resident_state, paged),
             roundtrip="paged.page_out"),
        dict(common, name="paged.page_out", fn=page_out,
             jit=page_out_host, args=(full_state, paged0),
             roundtrip="paged.page_in"),
    ]


def paged_stats(paged: PagedLog) -> dict:
    """Host occupancy snapshot (forces a device sync — call lazily from
    metrics_snapshot / benches, never per dispatch)."""
    import numpy as np

    return {
        "paged_pool_in_use": int(np.asarray((paged.pt > 0).sum())),
        "paged_pool_pages": int(paged.pool_term.shape[0]),
        "paged_page_faults": int(np.asarray(paged.faults.sum())),
        "paged_exhausted": int(np.asarray(paged.exhausted.sum())),
        "paged_pages_dirty": int(np.asarray(paged.dirty.sum())),
        "paged_alloc_skipped": int(np.asarray(paged.skipped.sum())),
    }


def mapped_pages_per_lane(paged: PagedLog):
    """Host-side per-lane mapped-page counts (numpy [N] int64) — the
    tier scorer's pool-pressure signal. One device sync; call at
    dispatch boundaries only."""
    import numpy as np

    return np.asarray((paged.pt > 0).sum(axis=1)).astype(np.int64)


def paged_bytes_per_lane(paged: PagedLog) -> float:
    """Bytes/lane of the paged sidecar (page table + counters + this
    lane's share of the pool); the bench adds the resident log columns."""
    n = paged.pt.shape[0]
    leaves = (paged.pt, paged.pool_term, paged.pool_type, paged.pool_bytes,
              paged.faults, paged.exhausted, paged.dirty, paged.skipped)
    return sum(x.size * x.dtype.itemsize for x in leaves) / n

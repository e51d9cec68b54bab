"""Eviction/re-admission engine: device rows <-> host cold records.

Eviction is suspend-to-RAM, not crash-restart: the engine gathers the
group's FULL slim-canonical state rows AND its fabric rows off the
carry into a host cold record, so a later admission scatters the exact
bytes back and the group resumes mid-election, mid-confchange,
mid-replication — the chaos soak in tests/test_tier.py proves digest
parity against a never-evicted twin. (A WAL-replay restore would reset
volatile state and cost extra rounds of re-election; suspend-to-RAM is
what keeps re-admission p99 under 4 rounds.)

Batching rides the existing dispatch-boundary discipline (the
_apply_rebase pattern in ops/fused.py): flush the D2H stream fences,
page in / unpack to the slim-canonical full-window carry, run ONE
gather jit + ONE scatter jit for the whole evict/admit batch, re-pack /
page out. Batch lane counts are padded to the next power of two
(duplicate-pad with the first lane; duplicate scatter of identical rows
is idempotent) so XLA sees a handful of program shapes, not one per
batch size.

Parked slots (evicted, not yet recycled) hold genesis-template rows
with two anti-campaign edits — election_elapsed = PARKED_ELAPSED and
randomized_election_timeout = PARKED_TIMEOUT — because mute only cuts
message send/receive (route_fabric + snap_fail): muted lanes STILL
TICK, and an untreated parked follower would campaign within ~20
rounds and pollute term counters. The sentinel values buy ~46k quiet
rounds per parking, far beyond any dispatch block between recycles.

Cold records store the slim-canonical rows diet-compacted (bool masks
bit-packed host-side) plus the group's WAL watermark (min stabled) and
eviction round. The ColdStore keeps records in host RAM up to
RAFT_TPU_TIER_RAM_MB, then spills whole records to
RAFT_TPU_TIER_SPILL_DIR (npz files) — the optional WAL-spill tier.
"""

from __future__ import annotations

import os

import numpy as np

from raft_tpu import tier as tier_cfg
from raft_tpu.testing.counters import CallCounter
from raft_tpu.tier.lanes import GroupRef, LaneAllocator
from raft_tpu.tier.scorer import ActivityScorer

# anti-campaign sentinels for parked lanes (int16 slim dtypes): a parked
# follower reaches election_elapsed >= randomized_election_timeout after
# PARKED_TIMEOUT - PARKED_ELAPSED ~= 46k ticks
PARKED_ELAPSED = -30000
PARKED_TIMEOUT = 16383

# trace-time elision counter: bumps inside the gather/scatter jit bodies,
# so a flat counter proves no tier primitive ever entered a program
# (RAFT_TPU_TIER=0 elision, asserted by analysis check_elision)
_CALLS = CallCounter("tier")
kernel_calls = _CALLS.calls


def _tier_gather(state, fab, lanes):
    """Batched row gather: the evict-snapshot jit. Returns fresh row
    buffers (never aliases the carry), so the carry stays valid for the
    scatter that follows in the same apply()."""
    import jax
    import jax.numpy as jnp

    _CALLS.bump()
    take = lambda x: jnp.take(x, lanes, axis=0)
    return jax.tree.map(take, state), jax.tree.map(take, fab)


def _tier_scatter(state, fab, lanes, st_rows, fb_rows):
    """Batched row scatter: the admit-restore jit. Donatable variant
    below consumes the carry in place (the dominant tier-on path)."""
    import jax
    import jax.numpy as jnp

    _CALLS.bump()
    put = lambda x, r: x.at[lanes].set(r)
    return (
        jax.tree.map(put, state, st_rows),
        jax.tree.map(put, fab, fb_rows),
    )


_gather_jit = None
_scatter_jit = None
_scatter_donate_jit = None


def _jits():
    """Lazy jit wrappers (keeps `import raft_tpu.tier.engine` jax-free
    until a tier actually runs)."""
    global _gather_jit, _scatter_jit, _scatter_donate_jit
    if _gather_jit is None:
        import jax

        _gather_jit = jax.jit(_tier_gather)
        _scatter_jit = jax.jit(_tier_scatter)
        _scatter_donate_jit = jax.jit(_tier_scatter, donate_argnums=(0, 1))
    return _gather_jit, _scatter_jit, _scatter_donate_jit


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _pad_rows(lanes: np.ndarray, leaves: list[np.ndarray] | None):
    """Duplicate-pad a lane batch (and optionally its row leaves) to the
    next power of two so batch sizes map to O(log) program shapes."""
    m = len(lanes)
    p = _pow2(m) - m
    if p == 0:
        return lanes, leaves
    lanes = np.concatenate([lanes, np.repeat(lanes[:1], p)])
    if leaves is not None:
        leaves = [
            np.concatenate([x, np.repeat(x[:1], p, axis=0)]) for x in leaves
        ]
    return lanes, leaves


# -- cold records --------------------------------------------------------


def _compact_leaf(x: np.ndarray):
    """Diet-compact one cold-record leaf: bool masks bit-pack 8:1; every
    other dtype is already its slim storage width."""
    if x.dtype == np.bool_:
        return ("b", x.shape, np.packbits(x))
    return x


def _expand_leaf(x):
    if isinstance(x, tuple):
        _, shape, packed = x
        n = int(np.prod(shape))
        return np.unpackbits(packed, count=n).reshape(shape).astype(bool)
    return x


def _leaf_bytes(x) -> int:
    return int(x[2].nbytes if isinstance(x, tuple) else x.nbytes)


class ColdRecord:
    """One hibernated group: its slim-canonical state + fabric rows
    (diet-compacted), the WAL watermark at eviction, the evict round."""

    __slots__ = ("lgid", "st", "fb", "watermark", "evict_round", "nbytes")

    def __init__(self, lgid, st_leaves, fb_leaves, watermark, evict_round):
        self.lgid = int(lgid)
        self.st = [_compact_leaf(x) for x in st_leaves]
        self.fb = [_compact_leaf(x) for x in fb_leaves]
        self.watermark = int(watermark)
        self.evict_round = int(evict_round)
        self.nbytes = sum(_leaf_bytes(x) for x in self.st) + sum(
            _leaf_bytes(x) for x in self.fb
        )

    def rows(self):
        return (
            [_expand_leaf(x) for x in self.st],
            [_expand_leaf(x) for x in self.fb],
        )


class ColdStore:
    """Host-RAM cold-record map with optional disk spill. Insertion-FIFO
    spill order: the oldest hibernators go to disk first."""

    def __init__(self, spill_dir=None, ram_budget_mb=None):
        self.spill_dir = (
            tier_cfg.spill_dir() if spill_dir is None else spill_dir
        )
        budget = (
            tier_cfg.ram_budget_mb() if ram_budget_mb is None
            else ram_budget_mb
        )
        self.ram_budget = int(budget) * (1 << 20)
        self.recs: dict[int, ColdRecord] = {}
        self.spilled: dict[int, tuple[str, int, int, int]] = {}
        self.ram_bytes = 0
        self.spill_bytes = 0

    def __len__(self) -> int:
        return len(self.recs) + len(self.spilled)

    def __contains__(self, lgid) -> bool:
        return int(lgid) in self.recs or int(lgid) in self.spilled

    def bytes(self) -> int:
        return self.ram_bytes + self.spill_bytes

    def put(self, rec: ColdRecord) -> None:
        self.recs[rec.lgid] = rec
        self.ram_bytes += rec.nbytes
        self._maybe_spill()

    def pop(self, lgid: int) -> ColdRecord:
        lgid = int(lgid)
        rec = self.recs.pop(lgid, None)
        if rec is not None:
            self.ram_bytes -= rec.nbytes
            return rec
        return self._load(lgid)

    def _maybe_spill(self) -> None:
        if not self.spill_dir or self.ram_budget <= 0:
            return
        while self.ram_bytes > self.ram_budget and self.recs:
            lgid = next(iter(self.recs))  # oldest insertion
            self._spill(self.recs.pop(lgid))

    def _spill(self, rec: ColdRecord) -> None:
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(self.spill_dir, f"cold_{rec.lgid}.npz")
        blob = {}
        for pfx, leaves in (("s", rec.st), ("f", rec.fb)):
            for i, x in enumerate(leaves):
                if isinstance(x, tuple):
                    blob[f"{pfx}{i}__b"] = x[2]
                    blob[f"{pfx}{i}__shape"] = np.asarray(x[1])
                else:
                    blob[f"{pfx}{i}"] = x
        np.savez(path, n_st=np.asarray(len(rec.st)), **blob)
        self.ram_bytes -= rec.nbytes
        self.spill_bytes += rec.nbytes
        self.spilled[rec.lgid] = (
            path, rec.watermark, rec.evict_round, rec.nbytes
        )

    def _load(self, lgid: int) -> ColdRecord:
        path, watermark, evict_round, nbytes = self.spilled.pop(lgid)
        with np.load(path) as z:
            n_st = int(z["n_st"])

            def leaf(pfx, i):
                if f"{pfx}{i}" in z:
                    return z[f"{pfx}{i}"]
                shape = tuple(int(d) for d in z[f"{pfx}{i}__shape"])
                n = int(np.prod(shape))
                return (
                    np.unpackbits(z[f"{pfx}{i}__b"], count=n)
                    .reshape(shape)
                    .astype(bool)
                )

            st = [leaf("s", i) for i in range(n_st)]
            i, fb = 0, []
            while f"f{i}" in z or f"f{i}__b" in z:
                fb.append(leaf("f", i))
                i += 1
        os.remove(path)
        self.spill_bytes -= nbytes
        rec = ColdRecord(lgid, st, fb, watermark, evict_round)
        return rec


# -- the engine ----------------------------------------------------------


class TierEngine:
    """Hot/cold tiering for ONE FusedCluster carry (the blocked/mesh
    drivers coordinate one engine per block through ClusterTier).

    `initial` is the genesis cohort: the logical ids bound to slots
    0..G-1 at construction, in slot order — defaults to range(G), which
    makes a tier-on cluster with n_logical == n_groups lane-identical
    to a tier-off one (the A/B identity arm of benches/tier_ab.py).
    """

    def __init__(
        self,
        cluster,
        *,
        seed: int = 1,
        n_logical: int | None = None,
        initial=None,
        lane_base: int = 0,
        scorer: ActivityScorer | None = None,
        spans=None,
    ):
        self.cl = cluster
        self.g, self.v = cluster.g, cluster.v
        self.seed = int(seed)
        self.n_logical = int(n_logical) if n_logical is not None else None
        self.lane_base = int(lane_base)
        self.alloc = LaneAllocator(self.g, self.v)
        for lgid in (range(self.g) if initial is None else initial):
            self.alloc.bind_initial(lgid)
        if len(self.alloc.slot_of) != self.g:
            raise ValueError(
                "initial cohort must fill every resident slot "
                f"({len(self.alloc.slot_of)} != {self.g})"
            )
        self.scorer = scorer if scorer is not None else ActivityScorer()
        self.cold = ColdStore()
        self.spans = spans
        # serve-plane shield: callable returning lgids with in-flight
        # work that must not be evicted mid-proposal
        self.pinned = None
        # post-commit hook (ShardedFusedCluster re-shards the carry here)
        self.post_commit = None
        # keep this many slots free by proactively evicting eligible
        # residents (0 = pure demand-driven eviction)
        self.reserve_slots = 0
        self._admit_q: dict[int, None] = {}
        self._evict_q: dict[int, None] = {}
        self._st_def = None
        self._fb_def = None
        self.evictions = 0
        self.admissions = 0
        self.births = 0
        # victims that were holding pool pages when picked under paged
        # pool pressure (the scorer's page_weight signal doing work)
        self.paged_pressure_evictions = 0

    # -- indirection (GroupRef contract) --------------------------------

    def resident(self, lgid: int) -> bool:
        return self.alloc.resident(lgid)

    def slot(self, lgid: int) -> int | None:
        return self.alloc.slot(lgid)

    def residents(self):
        """Resident logical ids (the serve loop's bootstrap set)."""
        return self.alloc.residents()

    def lane_of_group(self, lgid: int) -> int | None:
        """Global base carry lane of a resident group, or None."""
        s = self.alloc.slot(lgid)
        return None if s is None else self.lane_base + s * self.v

    def group_of_lane(self, lane: int) -> int | None:
        return self.alloc.group_of_lane(int(lane) - self.lane_base)

    def ref(self, lgid: int) -> GroupRef:
        return self.alloc.ref(lgid)

    # -- signals ---------------------------------------------------------

    def touch(self, lgid: int, round_id: int, weight: float = 1.0) -> None:
        self.scorer.touch(lgid, round_id, weight)

    def request_admit(self, lgid: int, round_id: int) -> bool:
        """Queue a cold group for admission (returns True when already
        resident). Each request is an activity touch, so repeated misses
        push the score over the admit threshold."""
        lgid = int(lgid)
        if self.alloc.resident(lgid):
            self.scorer.touch(lgid, round_id)
            return True
        self.scorer.touch(lgid, round_id)
        self._admit_q.setdefault(lgid, None)
        return False

    def request_evict(self, lgid: int) -> None:
        """Queue an explicit eviction (tests, migration drains). Applied
        at the next apply() regardless of score, but still refused for
        pinned groups."""
        lgid = int(lgid)
        if self.alloc.resident(lgid):
            self._evict_q.setdefault(lgid, None)

    def pending(self) -> bool:
        return bool(self._admit_q or self._evict_q) or (
            self.reserve_slots > self.alloc.free_slots()
        )

    def tick(self, round_id: int) -> None:
        """Cheap per-round bookkeeping (scorer compaction every 1k)."""
        if round_id and round_id % 1024 == 0:
            self.scorer.compact()

    # -- the dispatch-boundary batch -------------------------------------

    def apply(self, round_id: int):
        """Drain the queues at a dispatch boundary: grant ready
        admissions (evicting quiet victims when the free list runs dry),
        apply explicit evictions, and commit the whole batch as one
        gather + one scatter. Returns (evicted_lgids, admitted_lgids)."""
        pinned = set(self.pinned()) if self.pinned is not None else set()

        grant = [
            g for g in self._admit_q
            if not self.alloc.resident(g)
            and self.scorer.admit_ready(g, round_id)
        ]
        evict = [
            g for g in self._evict_q
            if self.alloc.resident(g) and g not in pinned
        ]
        self._evict_q.clear()

        protect = pinned | set(grant)
        shortfall = (
            len(grant) - (self.alloc.free_slots() + len(evict))
            + self.reserve_slots
        )
        if shortfall > 0:
            page_weight = self._page_weights()
            victims = self.scorer.pick_victims(
                [g for g in self.alloc.residents() if g not in set(evict)],
                shortfall, round_id, protect=protect,
                page_weight=page_weight,
            )
            if page_weight is not None:
                self.paged_pressure_evictions += sum(
                    1 for g in victims if page_weight.get(int(g), 0) > 0
                )
            evict += victims
        room = self.alloc.free_slots() + len(evict)
        grant = grant[:room]  # the rest stays queued for the next apply
        for g in grant:
            self._admit_q.pop(g, None)
        if not grant and not evict:
            return [], []
        self._commit(evict, grant, round_id)
        return evict, grant

    # paged pool occupancy fraction at or above which victim picking
    # becomes page-aware (scorer prefers page-heavy among equally-quiet)
    POOL_PRESSURE = 0.75

    def _page_weights(self) -> dict[int, int] | None:
        """Mapped-page counts per resident logical group from the HOST
        side of the page table, or None when paging is off or the pool is
        below the pressure threshold (no reason to bias victim picking
        while pages are plentiful)."""
        pg = getattr(self.cl, "paged", None)
        if pg is None:
            return None
        from raft_tpu.ops import paged as pgmod

        per_lane = pgmod.mapped_pages_per_lane(pg)
        pool = int(pg.pool_term.shape[0])
        if pool <= 0 or int(per_lane.sum()) < self.POOL_PRESSURE * pool:
            return None
        weights: dict[int, int] = {}
        for g in self.alloc.residents():
            # cluster-local lanes, matching _commit's gather indexing
            # (lane_base only globalizes names for the mesh drivers)
            lo = self.alloc.slot(g) * self.v
            weights[int(g)] = int(per_lane[lo : lo + self.v].sum())
        return weights

    def _commit(self, evict, admit, round_id):
        """The device phase: one gather for the evict batch, one scatter
        for the union of parked + admitted slots, bracketed by the same
        page/pack boundary _apply_rebase uses."""
        import jax
        import jax.numpy as jnp

        from raft_tpu.ops import paged as pgmod
        from raft_tpu.ops.fused import (
            pack_fabric,
            slim_fabric,
            unpack_fabric,
        )
        from raft_tpu.state import (
            is_packed,
            pack_state,
            slim_state,
            unpack_state,
        )

        cl = self.cl
        gather_jit, scatter_jit, scatter_donate_jit = _jits()
        cl._flush_stream_fences()
        packed = is_packed(cl.state)
        carry = cl.state
        if cl.paged is not None:
            carry, cl.paged = pgmod.page_in_host(
                carry, cl.paged, cl._paged_segs
            )
        st, fb = unpack_state(carry), unpack_fabric(cl.fab)

        # 1) snapshot the evict batch into cold records (fresh buffers)
        freed_slots: list[int] = []
        if evict:
            slots = [self.alloc.slot_of[g] for g in evict]
            lanes = np.concatenate(
                [np.arange(s * self.v, (s + 1) * self.v) for s in slots]
            ).astype(np.int32)
            plain, _ = _pad_rows(lanes, None)
            st_rows, fb_rows = gather_jit(st, fb, jnp.asarray(plain))
            st_rows = jax.tree.map(np.asarray, st_rows)
            fb_rows = jax.tree.map(np.asarray, fb_rows)
            wm = np.asarray(st_rows.stabled).astype(np.int64)
            st_leaves, self._st_def = jax.tree.flatten(st_rows)
            fb_leaves, self._fb_def = jax.tree.flatten(fb_rows)
            for i, g in enumerate(evict):
                sl = slice(i * self.v, (i + 1) * self.v)
                self.cold.put(ColdRecord(
                    g,
                    [x[sl].copy() for x in st_leaves],
                    [x[sl].copy() for x in fb_leaves],
                    int(wm[sl].min()),
                    round_id,
                ))
                freed_slots.append(self.alloc.release(g))
                self.scorer.note_evicted(g)
                self.evictions += 1
                self._span("tier_evict", g, round_id)

        # 2) bind admits (recycling just-freed slots first)
        admitted_slots = []
        rows = []
        for g in admit:
            s = self.alloc.alloc(g)
            admitted_slots.append(s)
            if g in self.cold:
                rec = self.cold.pop(g)
                rows.append(rec.rows())
                self.admissions += 1
                self._span(
                    "tier_admit", g, round_id, watermark=rec.watermark
                )
            else:
                rows.append(self._genesis_rows(g))
                self.births += 1
                self._span("tier_admit", g, round_id, genesis=1)
            self.scorer.note_admitted(g, round_id)

        # 3) slots freed THIS batch and not immediately recycled park
        # with anti-campaign rows (slots freed earlier were parked then)
        parked = [s for s in freed_slots if s not in set(admitted_slots)]

        scatter_slots = admitted_slots + parked
        if scatter_slots:
            if parked:
                prow = self._parked_rows()
                rows = rows + [prow] * len(parked)
            lanes = np.concatenate([
                np.arange(s * self.v, (s + 1) * self.v)
                for s in scatter_slots
            ]).astype(np.int32)
            st_cat = [
                np.concatenate([r[0][i] for r in rows])
                for i in range(len(rows[0][0]))
            ]
            fb_cat = [
                np.concatenate([r[1][i] for r in rows])
                for i in range(len(rows[0][1]))
            ]
            lanes, all_cat = _pad_rows(lanes, st_cat + fb_cat)
            st_cat = all_cat[: len(st_cat)]
            fb_cat = all_cat[len(st_cat):]
            st_rows = jax.tree.unflatten(
                self._template_defs()[0], [jnp.asarray(x) for x in st_cat]
            )
            fb_rows = jax.tree.unflatten(
                self._template_defs()[1], [jnp.asarray(x) for x in fb_cat]
            )
            lanes_j = jnp.asarray(lanes)
            if cl._donate:
                st, fb = scatter_donate_jit(st, fb, lanes_j, st_rows, fb_rows)
            else:
                st, fb = scatter_jit(st, fb, lanes_j, st_rows, fb_rows)

        st, fb = slim_state(st), slim_fabric(fb)
        if packed:
            st, fb = pack_state(st), pack_fabric(fb)
        if cl.paged is not None:
            st, cl.paged = pgmod.page_out_host(st, cl.paged, cl._paged_segs)
        cl.state, cl.fab = st, fb
        # the scatter may have raised max(last) past the headroom budget
        # (an admitted group's log indexes) — force a re-sync like rebase
        cl._diet_budget = 0

        # 4) mute parked lanes on / active lanes off (numpy round-trip —
        # the set_mute discipline, preserving externally-set mutes on
        # untouched lanes)
        m = np.asarray(cl.mute).copy()
        for s in parked:
            m[s * self.v:(s + 1) * self.v] = True
        for s in admitted_slots:
            m[s * self.v:(s + 1) * self.v] = False
        cl.mute = self._put_mute(m)
        if self.post_commit is not None:
            self.post_commit()

    def _put_mute(self, m):
        import jax.numpy as jnp

        return jnp.asarray(m)

    # -- row synthesis ----------------------------------------------------

    def _template(self):
        tpl = getattr(self.cl, "_tier_template", None)
        if tpl is None:
            raise RuntimeError(
                "cluster has no tier template (constructed with "
                "RAFT_TPU_TIER=0?)"
            )
        return tpl

    def _template_defs(self):
        import jax

        if self._st_def is None:
            st_t, fb_t = self._template()
            _, self._st_def = jax.tree.flatten(st_t)
            _, self._fb_def = jax.tree.flatten(fb_t)
        return self._st_def, self._fb_def

    def _genesis_rows(self, lgid: int):
        """Fresh-group rows from the construction-time template, with the
        per-lane PRNG re-seeded by the LOGICAL lane index (matching
        state.init_state's formula) so late-born groups draw decorrelated
        election timeouts exactly like genesis-cohort ones."""
        import dataclasses
        import jax

        st_t, fb_t = self._template()
        lanes = (
            np.uint64(lgid) * np.uint64(self.v)
            + np.arange(self.v, dtype=np.uint64)
        )
        rng = np.asarray(
            (
                (
                    np.uint64(self.seed) * np.uint64(2654435761)
                    + lanes * np.uint64(0x9E3779B9)
                )
                & np.uint64(0xFFFFFFFF)
            )
            | np.uint64(1),
            np.uint32,
        )
        et = np.asarray(st_t.cfg.election_tick).astype(np.uint32)
        rand_to = (et + (rng >> np.uint32(16)) % et).astype(
            np.asarray(st_t.randomized_election_timeout).dtype
        )
        st = dataclasses.replace(
            st_t,
            rng=rng,
            randomized_election_timeout=rand_to,
            election_elapsed=np.zeros_like(st_t.election_elapsed),
        )
        st_leaves, _ = jax.tree.flatten(st)
        fb_leaves, _ = jax.tree.flatten(fb_t)
        return [x.copy() for x in st_leaves], [x.copy() for x in fb_leaves]

    def _parked_rows(self):
        """Anti-campaign filler for evicted-and-idle slots (see module
        docstring): a valid muted follower that won't reach its election
        timeout for ~46k rounds."""
        import dataclasses
        import jax

        st_t, fb_t = self._template()
        ee = np.full_like(st_t.election_elapsed, PARKED_ELAPSED)
        rt = np.full_like(st_t.randomized_election_timeout, PARKED_TIMEOUT)
        st = dataclasses.replace(
            st_t, election_elapsed=ee, randomized_election_timeout=rt
        )
        st_leaves, _ = jax.tree.flatten(st)
        fb_leaves, _ = jax.tree.flatten(fb_t)
        return [x.copy() for x in st_leaves], [x.copy() for x in fb_leaves]

    # -- spans / stats ----------------------------------------------------

    def set_pinned(self, fn) -> None:
        """Uniform wiring surface with ClusterTier."""
        self.pinned = fn

    def set_spans(self, spans) -> None:
        self.spans = spans

    def _span(self, name, lgid, round_id, **extra):
        if self.spans is None:
            return
        import time

        labels = {"group": int(lgid), "round": int(round_id)}
        labels.update(extra)
        self.spans.spans.append((name, time.perf_counter(), 0.0, labels))

    def stats(self, mirror: bool = False) -> dict:
        """TIER_COUNTERS snapshot. The accounting identity
        `tier_evictions - tier_admissions == tier_cold` holds exactly:
        genesis admissions count as tier_births, never tier_admissions."""
        s = {
            "tier_evictions": self.evictions,
            "tier_admissions": self.admissions,
            "tier_births": self.births,
            "tier_resident": len(self.alloc.slot_of),
            "tier_cold": len(self.cold),
            "tier_cold_bytes": self.cold.bytes(),
            "tier_thrash_suppressed": self.scorer.thrash_suppressed,
            "paged_pressure_evictions": self.paged_pressure_evictions,
        }
        if mirror:
            from raft_tpu.metrics.host import record_tier_stats

            record_tier_stats(s)
        return s


class ClusterTier:
    """Tier coordinator for the multi-block drivers: one TierEngine per
    block (per-block allocators under the shared BlockPlan), logical ids
    partitioned contiguously so an L == G binding is lane-identical to
    the tier-off blocked layout."""

    def __init__(self, engines: list[TierEngine], n_logical: int):
        self.engines = engines
        self.k = len(engines)
        self.n_logical = int(n_logical)
        if self.n_logical < sum(e.g for e in engines):
            raise ValueError(
                "logical_groups must be >= total resident slots"
            )

    def home(self, lgid: int) -> int:
        """Owning block of a logical id: contiguous equal partition of
        the logical space (block i owns [i*L/k, (i+1)*L/k))."""
        return min(int(lgid) * self.k // self.n_logical, self.k - 1)

    @staticmethod
    def initial_cohort(n_logical: int, k: int, block: int, g: int):
        """Genesis lgids of one block: the first `g` ids of its range."""
        lo = block * n_logical // k
        hi = (block + 1) * n_logical // k
        if hi - lo < g:
            raise ValueError(
                f"block {block} logical range [{lo},{hi}) smaller than "
                f"its {g} resident slots"
            )
        return range(lo, lo + g)

    def _eng(self, lgid: int) -> TierEngine:
        return self.engines[self.home(lgid)]

    def resident(self, lgid: int) -> bool:
        return self._eng(lgid).resident(lgid)

    def residents(self):
        out = []
        for e in self.engines:
            out.extend(e.residents())
        return out

    def lane_of_group(self, lgid: int) -> int | None:
        return self._eng(lgid).lane_of_group(lgid)

    def group_of_lane(self, lane: int) -> int | None:
        for e in self.engines:
            lo = e.lane_base
            if lo <= lane < lo + e.g * e.v:
                return e.group_of_lane(lane)
        return None

    def ref(self, lgid: int) -> GroupRef:
        return self._eng(lgid).ref(lgid)

    def touch(self, lgid: int, round_id: int, weight: float = 1.0) -> None:
        self._eng(lgid).touch(lgid, round_id, weight)

    def request_admit(self, lgid: int, round_id: int) -> bool:
        return self._eng(lgid).request_admit(lgid, round_id)

    def request_evict(self, lgid: int) -> None:
        self._eng(lgid).request_evict(lgid)

    def pending(self) -> bool:
        return any(e.pending() for e in self.engines)

    def tick(self, round_id: int) -> None:
        for e in self.engines:
            e.tick(round_id)

    def apply(self, round_id: int):
        evicted, admitted = [], []
        for e in self.engines:
            ev, ad = e.apply(round_id)
            evicted += ev
            admitted += ad
        return evicted, admitted

    def set_pinned(self, fn) -> None:
        for e in self.engines:
            e.pinned = fn

    def set_spans(self, spans) -> None:
        for e in self.engines:
            e.spans = spans

    def stats(self, mirror: bool = False) -> dict:
        out: dict[str, int] = {}
        for e in self.engines:
            for key, val in e.stats(mirror=False).items():
                out[key] = out.get(key, 0) + val
        if mirror:
            from raft_tpu.metrics.host import record_tier_stats

            record_tier_stats(out)
        return out

"""Completion router: egress bundles in, resolved client futures out.

Registered as the EgressStream sink, the router is the only consumer of
the device's outbound plane on the serving path. Each resolved
DeltaBundle gives it, for every ACTIVE lane, the current term / lead /
state / committed cursors; from the leader lane of each group it learns
how far that group's log has committed and resolves, in log order, every
attributed proposal at or below the watermark:

  propose -> commit -> notify
  (coalescer assigns the log index at injection; commit is observed via
  the bundle's committed column; notify completes the ProposeTicket and
  applies the command to the host KV materialization, dedup included.)

Index attribution is exact under a stable leader: the fused round appends
injected entries at last+1.. for the leader lane, and the router
initializes next_index from the leader's `last` at attach. When the
bundle shows the leader lane's term moved or its state left LEADER, the
attribution is void — the router flags the group for EPOCH RESYNC: the
serving loop re-pulls that group's columns synchronously, re-attaches to
the new leader, and RE-PROPOSES every in-flight ticket (front of queue,
original order). Commands may then commit twice in the log; the
(session, seq) dedup cursor collapses the second apply, so the client
contract stays exactly-once. Unreleased read batches of the group are
cancelled back to the wait queue the same way (a ReadIndex from a
deposed leader must not serve).

Exactly-once notification is AUDITED, not assumed: completing a ticket
that is already done increments the notify_violations counter instead of
silently double-firing — the bench's acceptance gate asserts it stays 0.
"""

from __future__ import annotations

import time

import numpy as np

from raft_tpu.metrics.host import LEASE_EVENTS
from raft_tpu.serve.coalescer import ReadBatch
from raft_tpu.serve.kv import KVStore
from raft_tpu.types import StateType

_LEADER = int(StateType.LEADER)


class GroupView:
    """The router's model of one raft group: who leads, at what term, how
    far its log has committed, and where the next injected entry lands."""

    __slots__ = (
        "gid", "leader_lane", "term", "watermark", "next_index",
        "attached", "epoch",
    )

    def __init__(self, gid: int):
        self.gid = gid
        self.leader_lane = -1  # global lane index, -1 = not attached
        self.term = 0
        self.watermark = 0  # highest committed index applied to the KV
        self.next_index = 1  # next log slot an injection takes
        self.attached = False
        self.epoch = 0  # bumps on every resync

    def floor(self) -> int:
        """Estimated device compaction point (snap_index) for the window
        budget: auto-compaction keeps `lag` entries below applied, and
        applied == committed at the end of every fused round."""
        return self.watermark

    def attach(self, leader_lane: int, term: int, committed: int, last: int):
        self.leader_lane = leader_lane
        self.term = term
        self.watermark = max(self.watermark, committed)
        self.next_index = last + 1
        self.attached = True
        self.epoch += 1

    def detach(self):
        self.leader_lane = -1
        self.attached = False


class ViewTable:
    """Lazy GroupView map: views materialize on first touch. Under
    RAFT_TPU_TIER the group key space is LOGICAL (millions of ids, few
    ever served); dense preallocation would defeat the tier's O(active)
    host-memory claim. Indexing is list-compatible (`views[g]`), and a
    view survives its group's eviction — watermark/epoch continuity
    across hibernation cycles rides on that."""

    def __init__(self):
        self._views: dict[int, GroupView] = {}

    def __getitem__(self, gid: int) -> GroupView:
        v = self._views.get(gid)
        if v is None:
            v = self._views[gid] = GroupView(gid)
        return v

    def __iter__(self):
        return iter(self._views.values())

    def __len__(self) -> int:
        return len(self._views)


class CompletionRouter:
    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        lanes_per_block: int,
        kv: KVStore,
        metrics,
        admission,
        coalescer,
        *,
        compact_lag: int = 0,
    ):
        self.g, self.v = n_groups, n_voters
        self.lanes_per_block = lanes_per_block
        self.kv = kv
        self.metrics = metrics
        self.admission = admission
        self.coalescer = coalescer
        self.compact_lag = compact_lag
        self.views = ViewTable()
        # per group: log index -> ProposeTicket (ours), in ascending order
        # (lazy like the views — keyed by logical group id under the tier)
        self.cmd_log: dict[int, dict] = {}
        # lane <-> group indirection, rebindable by the tier (ServeLoop
        # wires TierEngine.group_of_lane / lane_of_group here): defaults
        # are the static identity layout. lane_to_group may return None
        # (parked lane — no logical group resides there); base_lane may
        # return None (the group is cold, no resident lanes).
        self.lane_to_group = lambda lane: lane // self.v
        self.base_lane = lambda gid: gid * self.v
        # activity hook (lgid, round) — the tier's scorer feed, called
        # once per active-lane bundle row
        self.on_group_activity = None
        self.needs_resync: set[int] = set()
        self.round = 0  # the serving loop's clock, stamped before each run
        # -- leader-lease read fast path (RAFT_TPU_LEASE) ---------------
        # latest bundle's lease columns per scheduler block: full [N]
        # (state, term, committed, lease_ok, lease_epoch) numpy views —
        # populated only when the bundles carry lease columns, i.e. the
        # device lease plane is compiled in. No extra host sync: these are
        # the same resolved arrays on_bundle already holds.
        self._lease_cols: dict[int, tuple] = {}
        # gid -> [(tickets, term, epoch), ...]: read batches the coalescer
        # routed past ReadIndex on a lease-valid snapshot; served (or
        # bounced back) against the NEXT bundle's columns
        self.lease_pending: dict[int, list] = {}
        # narration feed for trace/assemble.py explain(): (round, gid,
        # event, n) tuples, kept only under the flight recorder
        from raft_tpu.trace.device import tracelog_enabled as _tl

        self.lease_log: list | None = [] if _tl() else None
        # apply-ordered (group, Command, tick) log for the scalar twin
        self.applied_log: list = []
        self._served_batches: list = []  # released batches awaiting watermark
        # proposal-lifecycle log for the trace assembler: one
        # (group, submit, inject, commit, notify) round tuple per notified
        # proposal. Only kept while the flight recorder is on — it grows
        # with every proposal, and untraced loops must not accumulate it.
        from raft_tpu.trace.device import tracelog_enabled

        self.lifecycle: list | None = [] if tracelog_enabled() else None

    # -- injection bookkeeping -------------------------------------------

    def record_injections(self, injections) -> None:
        """Called right after coalescer.build: indexes were assigned, make
        them resolvable before the round's commits arrive."""
        for view, batch in injections:
            log = self.cmd_log.setdefault(view.gid, {})
            for t in batch:
                log[t.index] = t

    @property
    def inflight_cmds(self) -> int:
        return sum(len(d) for d in self.cmd_log.values())

    def groups_with_inflight(self) -> set:
        """Groups holding attributed-but-unresolved proposals or released
        read batches — the tier's eviction shield (evicting one of these
        mid-flight would orphan its attribution)."""
        out = {g for g, d in self.cmd_log.items() if d}
        out.update(b.group for b, _ in self._served_batches)
        out.update(g for g, pend in self.lease_pending.items() if pend)
        return out

    # -- the egress sink --------------------------------------------------

    def on_bundle(self, block_id: int, seq: int, bundle) -> None:
        """EgressStream sink for scheduler block `block_id` (the stream's
        own push counter `seq` is not lane-addressing — each resident
        block owns its own stream)."""
        lo = block_id * self.lanes_per_block
        count = int(bundle.count)
        active = np.asarray(bundle.active)
        state = np.asarray(bundle.state)
        term = np.asarray(bundle.term)
        committed = np.asarray(bundle.committed)
        if getattr(bundle, "lease_ok", None) is not None:
            # refresh the block's lease snapshot (full columns — the fast
            # path must see lease state even for lanes that went quiet)
            self._lease_cols[block_id] = (
                state, term, committed,
                np.asarray(bundle.lease_ok), np.asarray(bundle.lease_epoch),
            )
        for j in range(count):
            lane_local = int(active[j])
            glane = lo + lane_local
            gid = self.lane_to_group(glane)
            if gid is None:
                continue  # parked lane (tier): no logical group here
            if self.on_group_activity is not None:
                # the tier scorer's egress feed: this lane changed state
                # this dispatch — exactly the activity signal, for free
                self.on_group_activity(gid, self.round)
            view = self.views[gid]
            if glane != view.leader_lane:
                continue
            if (
                int(state[lane_local]) != _LEADER
                or int(term[lane_local]) != view.term
            ):
                # deposed / re-elected: attribution void, resync the group
                view.detach()
                self.needs_resync.add(view.gid)
                continue
            c = int(committed[lane_local])
            if c > view.watermark:
                self._advance(view, c)
        if self.lease_pending:
            # AFTER the active sweep: deposed leaders already detached and
            # watermarks already cover this bundle's committed cursors, so
            # a lease-served batch resolves in this very call
            self._serve_lease_pending(block_id, lo)
        if self._served_batches:
            self._serve_ready_batches()

    def _advance(self, view: GroupView, committed: int) -> None:
        """Resolve every attributed index in (watermark, committed]."""
        log = self.cmd_log.get(view.gid)
        for idx in range(view.watermark + 1, committed + 1):
            t = log.pop(idx, None) if log else None
            if t is None:
                continue  # not ours (election empty entry, pre-attach)
            t.commit_round = self.round
            t.release_t = time.perf_counter()
            applied = self.kv.apply(view.gid, t.cmd, self.round)
            self.applied_log.append((view.gid, t.cmd, self.round))
            self._complete(t, applied)
        view.watermark = committed

    def _complete(self, t, applied: bool) -> None:
        if t.done:
            self.metrics.counters.inc("notify_violations")
            return
        t.applied = applied
        t.notify_round = self.round
        t.notify_t = time.perf_counter()
        t.done = True
        self.admission.release()
        self.metrics.counters.inc("proposals_notified")
        self.metrics.hist.observe(self.round - t.submit_round)
        self.metrics.observe_stages("put", t)
        if self.lifecycle is not None:
            self.lifecycle.append((
                t.group, t.submit_round, t.inject_round,
                t.commit_round, t.notify_round,
            ))

    # -- the lease read fast path (RAFT_TPU_LEASE) ------------------------

    def route_lease_reads(self, view, tickets) -> bool:
        """Coalescer hook, called at build time for a group with NEW
        waiting reads: when the latest bundle shows the group's leader
        holding a live lease at the view's attached term, take the
        tickets onto the lease fast path — no read_ctx injection, no
        quorum touch — snapshotting (term, epoch). The snapshot is
        re-validated against the NEXT bundle before anything serves, so
        a revocation (or a revoke+regrant, which moves the epoch) in the
        gap bounces the batch to the ReadIndex path instead of serving
        stale. Returns False to leave the tickets on the ReadIndex path."""
        glane = view.leader_lane
        if glane < 0:
            return False
        cols = self._lease_cols.get(glane // self.lanes_per_block)
        if cols is None:
            return False
        state, term, _committed, ok, epoch = cols
        local = glane % self.lanes_per_block
        if (
            int(state[local]) != _LEADER
            or int(term[local]) != view.term
            or not bool(ok[local])
        ):
            return False
        now = time.perf_counter()
        for t in tickets:
            t.inject_t = now
        self.lease_pending.setdefault(view.gid, []).append(
            (list(tickets), view.term, int(epoch[local]))
        )
        return True

    def _serve_lease_pending(self, block_id: int, lo: int) -> None:
        """Resolve lease-routed batches against this block's fresh
        columns: the leader must still be THE leader at the snapshotted
        term with a live lease of the SAME epoch — then the leader's
        commit index IS a linearizable read index (every write notified
        before the read was routed is <= it), and the batch rides the
        ordinary watermark machinery. Any mismatch falls back to
        ReadIndex; reads are idempotent, so the fallback only costs the
        round-trip the fast path tried to skip."""
        cols = self._lease_cols.get(block_id)
        hi = lo + self.lanes_per_block
        for gid in list(self.lease_pending.keys()):
            view = self.views[gid]
            glane = view.leader_lane
            if glane >= 0 and not (lo <= glane < hi):
                continue  # another block's bundle owns this leader lane
            entries = self.lease_pending.pop(gid, None) or ()
            for tickets, term0, epoch0 in entries:
                index = None
                if glane >= 0 and cols is not None:
                    state, term, committed, ok, epoch = cols
                    local = glane - lo
                    if (
                        int(state[local]) == _LEADER
                        and view.term == term0 == int(term[local])
                        and bool(ok[local])
                        and int(epoch[local]) == epoch0
                    ):
                        index = int(committed[local])
                if index is None:
                    # lease lapsed / epoch moved / leadership changed in
                    # the snapshot->serve gap: back to the wait queue (the
                    # next build re-batches through ReadIndex or a fresh
                    # lease snapshot)
                    self.coalescer._read_wait(gid).extend(tickets)
                    self._count_lease("lease_reads_fallback", gid, len(tickets))
                    continue
                self._released(tickets)
                self._served_batches.append(
                    (ReadBatch(0, gid, tickets, self.round), index)
                )
                self._count_lease("lease_reads_served", gid, len(tickets))

    def _count_lease(self, name: str, gid: int, n: int) -> None:
        self.metrics.counters.inc(name, n)
        LEASE_EVENTS.inc(name, n)  # the process-wide Prometheus mirror
        if self.lease_log is not None:
            self.lease_log.append((self.round, gid, name, n))

    # -- the linearizable read path --------------------------------------

    def on_read_release(self, glane: int, ctx: int, index: int) -> None:
        """One drained ReadState: the device released ctx at ReadIndex
        `index` (quorum-confirmed leadership, or lease/single-voter fast
        path). Stale releases (retried ctx already taken) are ignored —
        reads are idempotent."""
        batch = self.coalescer.take_batch(ctx)
        if batch is None:
            return
        self.metrics.counters.inc("read_batches_released")
        view = self.views[batch.group]
        if glane != view.leader_lane:
            # released by a lane we no longer trust; re-batch the tickets
            self.coalescer._read_wait(batch.group).extend(batch.tickets)
            return
        self._released(batch.tickets)
        self._served_batches.append((batch, index))
        self._serve_ready_batches()

    @staticmethod
    def _released(tickets) -> None:
        now = time.perf_counter()
        for rt in tickets:
            rt.release_t = now

    def _serve_ready_batches(self) -> None:
        still = []
        for batch, index in self._served_batches:
            view = self.views[batch.group]
            if view.watermark >= index:
                for rt in batch.tickets:
                    self._finish_read(rt, index)
            else:
                still.append((batch, index))  # wait for the apply wavefront
        self._served_batches = still

    def _finish_read(self, rt, index: int) -> None:
        if rt.done:
            self.metrics.counters.inc("notify_violations")
            return
        rt.value = self.kv.get(rt.group, rt.key, self.round)
        rt.index = index
        rt.notify_round = self.round
        rt.notify_t = time.perf_counter()
        rt.done = True
        self.admission.release()
        self.metrics.counters.inc("reads_served")
        self.metrics.read_hist.observe(self.round - rt.submit_round)
        self.metrics.observe_stages("get", rt)

    @property
    def reads_waiting_apply(self) -> int:
        return len(self._served_batches)

    # -- epoch resync -----------------------------------------------------

    def resync(self, columns: dict) -> int:
        """Re-attach every flagged group from a fresh synchronous column
        pull ({state, lead, term, committed, last} as [N] numpy). In-flight
        tickets re-propose at the queue head; unreleased read batches
        cancel back to the wait queue. Returns how many groups reattached
        (a group still electing stays detached and is retried next call)."""
        state, term = columns["state"], columns["term"]
        committed, last = columns["committed"], columns["last"]
        reattached = 0
        for gid in sorted(self.needs_resync):
            view = self.views[gid]
            base = self.base_lane(gid)
            if base is None:
                # the group went cold while flagged (tier eviction):
                # nothing to attach to; the admit path re-flags it
                self.needs_resync.discard(gid)
                continue
            lanes = range(base, base + self.v)
            leaders = [l for l in lanes if int(state[l]) == _LEADER]
            if len(leaders) != 1:
                continue  # mid-election; keep the flag, retry next round
            lead = leaders[0]
            was_attached = view.epoch > 0
            view.attach(
                lead, int(term[lead]), int(committed[lead]), int(last[lead])
            )
            # Indexes committed while detached are NOT resolved from the old
            # attribution — a leader change may have replaced the entry at
            # an attributed index. Every in-flight ticket re-proposes; a
            # command whose first copy did commit commits twice in the log
            # and the (session, seq) cursor collapses the second apply.
            log = self.cmd_log.get(gid) or {}
            survivors = [log.pop(i) for i in sorted(log)]
            for t in survivors:
                t.index = None
                t.inject_round = None
                t.inject_t = None
            self.coalescer.requeue_front(gid, survivors)
            for rt in self.coalescer.drop_group_reads(gid):
                self.coalescer._read_wait(gid).append(rt)
            # lease-routed batches of a resynced group cancel the same
            # way: their (term, epoch) snapshot is void by definition
            for tickets, _t, _e in self.lease_pending.pop(gid, ()):
                self.coalescer._read_wait(gid).extend(tickets)
                self._count_lease("lease_reads_fallback", gid, len(tickets))
            if was_attached:  # the initial bootstrap attach is not a resync
                self.metrics.counters.inc("epoch_resyncs")
            self.needs_resync.discard(gid)
            reattached += 1
        return reattached

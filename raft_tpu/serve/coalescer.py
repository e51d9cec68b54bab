"""Proposal coalescer: everything clients submitted between rounds becomes
ONE batched op injection per round: a RoundOps record of the lanes it
touched, which serve/inject.py expands into the per-block LocalOps on
the device in one dispatch.

The Podracer shape (PAPERS.md, arxiv 2104.06272): the device runs rounds
back-to-back; the host's only hot-path job is to fold the client queues
into the next round's ops, in O(active) work: the [N] op columns are
never built on the host. Per group, per round, the coalescer injects at
most

  min(queue depth, Shape.max_msg_entries, window budget)

entries at the group's leader lane. max_msg_entries is a KERNEL cap — the
fused round clamps prop_n to E (ops/fused.py `pn = min(prop_n, e)`), so
injecting more would silently truncate; the window budget keeps the
device log window from refusing the append (append_entry's fits gate,
ops/step.py) by accounting resident entries host-side against
log_window - auto_compact_lag. Neither limit ever drops work: commands
past the per-round cap simply wait in the (bounded) queue, and the bound
surfaces as a typed Rejected(queue_full) at admission.

Linearizable GETs batch harder: all reads for a group waiting at round r
share ONE ReadIndex ticket (the read_ctx column carries one ctx per lane
per round — the etcd read-batching shape). A batch whose release
never arrives (dropped beat under chaos, ro-ring overflow, leader not yet
committed in its term) is re-injected with the SAME ctx after
read_retry_rounds; reads are idempotent, so a double release is ignored
by the router.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, NamedTuple

import numpy as np

from raft_tpu.serve.admission import (
    REJECT_QUEUE_FULL,
    REJECT_READ_BATCH_FULL,
    Rejected,
)
from raft_tpu.serve.kv import Command


class RoundOps(NamedTuple):
    """One round's ops, one entry per touched lane (global lane order of
    the cluster): a lane that gets both a proposal and a read ctx in the
    round is one entry. Four [n] int32 columns; every other LocalOps
    column is zero on a served round."""

    lane: np.ndarray  # global lane (the group's leader lane)
    prop_n: np.ndarray  # entries proposed this round (0 = none)
    prop_bytes: np.ndarray  # payload size per entry (0 with prop_n 0)
    read_ctx: np.ndarray  # ReadIndex ctx ticket (0 = none)


class ProposeTicket:
    """One admitted mutation's future: propose -> commit -> notify.

    Each round stamp has a `time.perf_counter()` twin: submit_t, inject_t
    (the last injection: a re-proposal after an epoch resync re-stamps
    it), release_t (the commit seen) and notify_t."""

    __slots__ = (
        "cmd", "group", "index", "submit_round", "inject_round",
        "commit_round", "notify_round", "done", "applied",
        "submit_t", "inject_t", "release_t", "notify_t",
    )

    def __init__(self, cmd: Command, group: int, submit_round: int):
        self.cmd = cmd
        self.group = group
        self.index = None  # log index, assigned at injection
        self.submit_round = submit_round
        self.inject_round = None
        self.commit_round = None
        self.notify_round = None
        self.done = False
        self.applied = None  # True = mutated KV, False = dedup collapsed
        self.submit_t = time.perf_counter()
        self.inject_t = None
        self.release_t = None
        self.notify_t = None

    @property
    def latency_rounds(self) -> int | None:
        if self.notify_round is None:
            return None
        return self.notify_round - self.submit_round


class ReadTicket:
    """One admitted linearizable GET's future. Wall-clock stamps: submit_t,
    inject_t (its batch's ReadIndex ctx injected, or the lease route
    taken; re-stamped when a batch is re-opened), release_t (the ReadIndex
    released, or the lease validated) and notify_t."""

    __slots__ = (
        "session", "group", "key", "submit_round", "notify_round",
        "done", "value", "index",
        "submit_t", "inject_t", "release_t", "notify_t",
    )

    def __init__(self, session: int, group: int, key: str, submit_round: int):
        self.session = session
        self.group = group
        self.key = key
        self.submit_round = submit_round
        self.notify_round = None
        self.done = False
        self.value = None
        self.index = None  # the ReadIndex the answer reflects
        self.submit_t = time.perf_counter()
        self.inject_t = None
        self.release_t = None
        self.notify_t = None


class ReadBatch:
    """All GETs of one group sharing one ReadIndex ctx ticket."""

    __slots__ = ("ctx", "group", "tickets", "inject_round", "retries")

    def __init__(self, ctx: int, group: int, tickets: list, round_id: int):
        self.ctx = ctx
        self.group = group
        self.tickets = tickets
        self.inject_round = round_id
        self.retries = 0


class ProposalCoalescer:
    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        *,
        max_entries_per_round: int,
        log_window: int,
        compact_lag: int,
        max_read_batches: int,
        queue_cap: int = 1024,
        cmd_bytes: int = 64,
        read_retry_rounds: int = 8,
    ):
        self.g, self.v = n_groups, n_voters
        self.max_per_round = max_entries_per_round
        # resident-entry budget: the device window holds W entries above
        # the compaction point (snap_index ~ applied - lag once
        # auto_compact_lag engages); 2 slots of margin absorb election
        # empty entries so append_entry's fits gate never refuses us
        self.window_budget = max(1, log_window - compact_lag - 2)
        self.max_read_batches = max_read_batches
        self.queue_cap = queue_cap
        self.cmd_bytes = cmd_bytes
        self.read_retry_rounds = read_retry_rounds
        # per-group queues materialize lazily (dict, not dense list): the
        # group key space is LOGICAL under RAFT_TPU_TIER — millions of
        # ids, of which only the actively-served ones may hold queues
        self.pending: dict[int, deque] = {}
        self.read_wait: dict[int, list] = {}
        self.read_batches: dict[int, ReadBatch] = {}  # ctx -> batch
        self._batches_of: dict[int, set] = {}
        self._next_ctx = 1
        self.on_read_retry = None  # optional hook (ServeLoop -> metrics)
        # exact injection counts (ServeLoop.metrics_snapshot folds them)
        self.entries_injected = 0
        self.read_batches_injected = 0
        # lease fast-path hook (ServeLoop -> router.route_lease_reads,
        # wired only when the device lease plane is on): offered a group's
        # NEW waiting reads at build time; True = the router took them
        # (no read_ctx injection), False = ReadIndex path as always
        self.lease_route = None

    def _pending(self, group: int) -> deque:
        q = self.pending.get(group)
        if q is None:
            q = self.pending[group] = deque()
        return q

    def _read_wait(self, group: int) -> list:
        q = self.read_wait.get(group)
        if q is None:
            q = self.read_wait[group] = []
        return q

    def _batches(self, group: int) -> set:
        s = self._batches_of.get(group)
        if s is None:
            s = self._batches_of[group] = set()
        return s

    def active_groups(self) -> set:
        """Groups with any queued/in-flight coalescer work — the serve
        loop's iteration set for build() and the tier's eviction shield."""
        return (
            {g for g, q in self.pending.items() if q}
            | {g for g, q in self.read_wait.items() if q}
            | {g for g, s in self._batches_of.items() if s}
        )

    # -- intake -----------------------------------------------------------

    def queue_depth(self, group: int) -> int:
        return len(self.pending.get(group) or ()) + len(
            self.read_wait.get(group) or ()
        )

    def enqueue(self, ticket: ProposeTicket) -> Rejected | None:
        g = ticket.group
        if self.queue_depth(g) >= self.queue_cap:
            return Rejected(REJECT_QUEUE_FULL, f"group={g}")
        self._pending(g).append(ticket)
        return None

    def requeue_front(self, group: int, tickets: list) -> None:
        """Epoch resync: put re-proposed tickets back at the queue head in
        original order (dedup makes the re-commit exactly-once)."""
        self._pending(group).extendleft(reversed(tickets))

    def enqueue_read(self, ticket: ReadTicket) -> Rejected | None:
        g = ticket.group
        # the more specific reason first: the ReadIndex batch window is
        # saturated AND the wait queue is at capacity behind it
        if (
            len(self._batches_of.get(g) or ()) >= self.max_read_batches
            and len(self.read_wait.get(g) or ()) >= self.queue_cap
        ):
            return Rejected(REJECT_READ_BATCH_FULL, f"group={g}")
        if self.queue_depth(g) >= self.queue_cap:
            return Rejected(REJECT_QUEUE_FULL, f"group={g}")
        self._read_wait(g).append(ticket)
        return None

    def take_batch(self, ctx: int) -> ReadBatch | None:
        b = self.read_batches.pop(ctx, None)
        if b is not None:
            self._batches(b.group).discard(ctx)
        return b

    @property
    def outstanding_reads(self) -> int:
        return len(self.read_batches)

    def drop_group_reads(self, group: int) -> list:
        """Epoch resync: cancel the group's unreleased batches and return
        every waiting ticket for re-admission-free re-batching."""
        tickets = []
        for ctx in sorted(self._batches_of.get(group) or ()):
            b = self.read_batches.pop(ctx)
            tickets.extend(b.tickets)
        self._batches_of.pop(group, None)
        tickets.extend(self.read_wait.get(group) or ())
        self.read_wait.pop(group, None)
        return tickets

    # -- the per-round batched injection ----------------------------------

    def build(self, views, round_id: int):
        """Fold the queues into one round's ops, one entry per lane.

        views: router.GroupView list (leader lane + next_index + commit
        watermark per group); next_index advances here, at assignment.
        Returns (RoundOps | None, injections) where injections is the
        [(view, [ProposeTicket, ...]), ...] the router must record before
        the round's commits can resolve. None means a zero-op round (the
        engine's cached no_ops fast path).
        """
        lanes, prop_n, read_ctx = [], [], []
        injections = []
        now = time.perf_counter()
        # iterate only the groups with queued/in-flight work — O(active),
        # never O(logical groups); sorted for deterministic injection order
        for g in sorted(self.active_groups()):
            view = views[g]
            if view.leader_lane < 0:
                continue
            room = self.window_budget - (view.next_index - 1 - view.floor())
            q = self.pending.get(g) or ()
            m = min(len(q), self.max_per_round, max(0, room))
            if m > 0:
                batch = [self.pending[g].popleft() for _ in range(m)]
                for t in batch:
                    t.index = view.next_index
                    t.inject_round = round_id
                    t.inject_t = now
                    view.next_index += 1
                self.entries_injected += m
                injections.append((view, batch))
            if (
                self.lease_route is not None
                and self.read_wait.get(g)
                and self.lease_route(view, self.read_wait[g])
            ):
                # the router took this group's new reads onto the lease
                # fast path — already-open ReadIndex batches still retry
                # through _pick_read_ctx below
                self.read_wait.pop(g)
            ctx = self._pick_read_ctx(g, view, round_id, now)
            if m > 0 or ctx:
                lanes.append(view.leader_lane)
                prop_n.append(m)
                read_ctx.append(ctx)
        if not lanes:
            return None, injections
        prop_n = np.array(prop_n, np.int32)
        ops = RoundOps(
            lane=np.array(lanes, np.int32),
            prop_n=prop_n,
            prop_bytes=np.where(prop_n > 0, self.cmd_bytes, 0).astype(np.int32),
            read_ctx=np.array(read_ctx, np.int32),
        )
        return ops, injections

    def _pick_read_ctx(self, g: int, view, round_id: int, now: float) -> int:
        """One read_ctx slot per lane per round: a due retry of the oldest
        unreleased batch wins over opening a new batch."""
        due = [
            self.read_batches[c]
            for c in self._batches_of.get(g) or ()
            if round_id - self.read_batches[c].inject_round
            >= self.read_retry_rounds * (self.read_batches[c].retries + 1)
        ]
        if due:
            b = min(due, key=lambda b: b.inject_round)
            b.retries += 1
            if self.on_read_retry is not None:
                self.on_read_retry()
            return b.ctx
        if (
            self.read_wait.get(g)
            and len(self._batches_of.get(g) or ()) < self.max_read_batches
        ):
            ctx = self._next_ctx
            # i32, nonzero, wraps long before the ro ring could still hold
            # a colliding live ticket
            self._next_ctx = 1 if self._next_ctx >= (1 << 30) else ctx + 1
            b = ReadBatch(ctx, g, self.read_wait.pop(g), round_id)
            for t in b.tickets:
                t.inject_t = now
            self.read_batches[ctx] = b
            self._batches(g).add(ctx)
            self.read_batches_injected += 1
            return ctx
        return 0

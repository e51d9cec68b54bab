"""ServeLoop: the multi-tenant serving frontend wired onto the fused
fabric — ROADMAP item 3's production loop.

One ServeLoop owns one FusedCluster or BlockedFusedCluster and runs the
whole propose -> commit -> notify pipeline per device round:

    round += 1; admission buckets refill
    coalescer folds the client queues into ONE LocalOps injection
      (per block, through the scheduler's prepare_ops path)
    cluster.run(1, ops, egress=streams, auto_compact_lag=lag)
      - the push resolves the PREVIOUS round's egress bundle while this
        round computes; the CompletionRouter (the sink) advances commit
        watermarks, applies committed commands to the host KV, and
        resolves client futures
    if linearizable reads are outstanding: drain the rs_* ring
    if a leader/term change voided attribution: synchronous epoch resync

The host never scans all N lanes and never issues per-lane scalar reads
on the hot path: commit discovery rides the O(active) egress bundles, and
the only synchronous pulls are the read drain (gated on outstanding
reads) and epoch resyncs (gated on observed leader changes).

Measurement: each phase above is a `StepStats.span` (utils/profiling.py)
in the loop's one bag, shared with the cluster's per-block dispatch and
the egress streams — `serve.admission`, `serve.coalesce`,
`serve.dispatch` (around `sched.dispatch` > `sched.enqueue`,
`egress.resolve.wait`, `egress.sink`), `serve.drain_reads` (its
`.wait` / `.decode` / `.reset.wait` per block), `serve.resync` (its
`.wait`) — each also a jax.profiler annotation on the device trace's
clock. Tickets carry perf_counter stamps beside their round stamps, and
`metrics_snapshot()` adds the flow counts and the wall-clock stage
histograms.

Clock: rounds ARE ticks (do_tick=True — the engine's 1-round = 1-tick
contract), so `self.round` is simultaneously the latency clock, the lease
clock, and the device election clock. Bootstrap rounds count.

Admission rejections come back as typed `Rejected(reason)` values, falsy
and never raised — callers route on them; every one is counted under
`rejected_<reason>` plus the aggregate `proposals_rejected`.
"""

from __future__ import annotations

from raft_tpu.metrics.host import (
    HostCounters,
    HostHistogram,
    MetricsRegistry,
    prometheus_text,
)
from raft_tpu.ops import ready_mask
from raft_tpu.runtime.egress import EgressStream
from raft_tpu.serve.admission import (
    REJECT_COLD_GROUP,
    REJECT_NO_LEADER,
    REJECT_SESSION_CLOSED,
    AdmissionController,
    Rejected,
)
from raft_tpu.serve.coalescer import (
    ProposalCoalescer,
    ProposeTicket,
    ReadTicket,
)
from raft_tpu.serve.kv import (
    OP_DELETE,
    OP_LEASE,
    OP_PUT,
    Command,
    KVStore,
)
from raft_tpu.serve.router import CompletionRouter
from raft_tpu.serve.session import Session, SessionManager
from raft_tpu.runtime.trace import TraceStream
from raft_tpu.trace import device as trdev
from raft_tpu.utils.profiling import SpanRecorder, StepStats


# log-spaced (1-2-5) edges of the wall-clock stage histograms, in ms
STAGE_EDGES_MS = (
    1, 2, 5, 10, 20, 50, 100, 200, 500,
    1000, 2000, 5000, 10000, 20000, 50000, 100000,
)
# a request's wall-clock stages, between its tickets' perf_counter stamps
# submit_t -> inject_t -> release_t -> notify_t; release is the ReadIndex
# release (or lease validation) for a get and the commit seen for a put
STAGES = {
    "get": ("get_queue_ms", "get_readindex_ms", "get_apply_ms"),
    "put": ("put_queue_ms", "put_commit_ms", "put_apply_ms"),
}


class ServeMetrics:
    """The serving plane's own registry: host counters + the notify
    latency histogram (device-round edges). Deliberately NOT merged into
    the engine snapshot — merge_snapshots sums histograms blindly, and
    notify latency must never fold into device commit latency. The HTTP
    endpoint (serve/http.py) renders both planes under distinct prefixes."""

    def __init__(self):
        self.counters = HostCounters()
        self.hist = HostHistogram()
        # read-notify latency, split from write-notify: one histogram hid
        # the lease plane's read win behind the proposal pipeline's 2-3
        # round floor (benches/serve_bench.py reports both percentiles)
        self.read_hist = HostHistogram()
        # the same latencies in wall time, one histogram per stage
        self.stage_hists = {
            name: HostHistogram(STAGE_EDGES_MS)
            for names in STAGES.values() for name in names
        }
        self.rounds = 0

    def observe_stages(self, kind: str, t) -> None:
        """File a notified ticket's queue / readindex-or-commit / apply
        wall times (ms) under `kind` ("get" or "put")."""
        queue, middle, apply = (self.stage_hists[n] for n in STAGES[kind])
        queue.observe(1e3 * (t.inject_t - t.submit_t))
        middle.observe(1e3 * (t.release_t - t.inject_t))
        apply.observe(1e3 * (t.notify_t - t.release_t))

    def snapshot(self) -> dict:
        # the stamped hist_name lets merge_snapshots namespace this family
        # away from the device plane's commit-latency histogram, so the
        # registry below can merge serve + step-stats sources safely; the
        # named "hists" map carries the write/read split (merge_snapshots
        # setdefault keeps the legacy "hist" from double counting)
        return {
            "counters": dict(self.counters.counts),
            "hist": self.hist.snapshot(),
            "hist_name": "notify_latency_rounds",
            "hists": {
                "notify_latency_rounds": self.hist.snapshot(),
                "read_notify_latency_rounds": self.read_hist.snapshot(),
                **{n: h.snapshot() for n, h in self.stage_hists.items()},
            },
            "rounds": int(self.rounds),
        }

    def prometheus(self) -> str:
        return prometheus_text(
            self.snapshot(),
            prefix="raft_tpu_serve",
            hist_name="notify_latency_rounds",
        )


class ServeLoop:
    def __init__(
        self,
        cluster,
        *,
        tenant_rate: float = 64.0,
        tenant_burst: float = 256.0,
        inflight_cap: int = 1 << 16,
        queue_cap: int = 1024,
        cmd_bytes: int = 64,
        auto_compact_lag: int | None = None,
        read_retry_rounds: int = 8,
        expire_every: int = 16,
    ):
        if not ready_mask.egress_enabled():
            raise RuntimeError(
                "serving frontend needs the egress plane: commit discovery "
                "rides the DeltaBundle sink (unset RAFT_TPU_EGRESS=0)"
            )
        self.cluster = cluster
        # cluster-protocol duck test, not an isinstance/attr-name check on
        # one concrete class: anything exposing the blocked driving surface
        # (global-lane prepare_ops + per-block geometry) is driven
        # block-wise — BlockedFusedCluster and the mesh driver
        # (parallel/mesh.py MeshBlockedCluster) both qualify; a bare
        # FusedCluster (no prepare_ops) is driven whole.
        self.blocked = callable(getattr(cluster, "prepare_ops", None))
        base = cluster.blocks[0] if self.blocked else cluster
        self.g, self.v = cluster.g, cluster.v
        self.n = self.g * self.v
        self.shape = base.shape
        self.k = cluster.k if self.blocked else 1
        self.lanes_per_block = (
            cluster.lanes_per_block if self.blocked else self.n
        )
        self.compact_lag = (
            self.shape.log_window // 4
            if auto_compact_lag is None
            else auto_compact_lag
        )
        self.expire_every = expire_every
        self.round = 0
        # hot/cold tier (RAFT_TPU_TIER): when the cluster carries one, the
        # serve plane speaks LOGICAL group ids everywhere — sessions, KV,
        # coalescer queues, router views — and the tier maps the resident
        # subset onto carry slots. None on tier-off clusters, and every
        # tier branch below is skipped then.
        self.tier = getattr(cluster, "tier", None)
        self.logical_groups = (
            self.g if self.tier is None
            else (self.tier.n_logical or self.g)
        )

        self.metrics = ServeMetrics()
        # host span log for the trace assembler; gated on the flight
        # recorder so the span list does not grow on untraced loops
        self.spans = SpanRecorder() if trdev.tracelog_enabled() else None
        # one span bag for the round loop (serve.*), the cluster's per-block
        # dispatch (sched.*) and the egress streams (egress.*): exported as
        # step_* counters through the registry so one Prometheus scrape
        # covers the serving counters AND where the host spends its wall
        # time; every span is also a jax.profiler annotation
        self.stats = StepStats("serve", spans=self.spans)
        cluster.use_stats(self.stats)
        self.registry = MetricsRegistry()
        self.registry.register("serve", self.metrics.snapshot)
        self.registry.register("steps", self.stats.snapshot)
        self.registry.register("flow", self._flow_snapshot)
        self.sessions = SessionManager(self.logical_groups)
        self.kv = KVStore(self.logical_groups)
        self.admission = AdmissionController(
            tenant_rate=tenant_rate,
            tenant_burst=tenant_burst,
            inflight_cap=inflight_cap,
        )
        self.coalescer = ProposalCoalescer(
            self.g,
            self.v,
            max_entries_per_round=self.shape.max_msg_entries,
            log_window=self.shape.log_window,
            compact_lag=self.compact_lag,
            # leave one ro-ring slot of headroom per lane so a retried ctx
            # plus the live window never overflow max_read_index
            max_read_batches=max(1, self.shape.max_read_index - 1),
            queue_cap=queue_cap,
            cmd_bytes=cmd_bytes,
            read_retry_rounds=read_retry_rounds,
        )
        self.coalescer.on_read_retry = lambda: self.metrics.counters.inc(
            "reads_retried"
        )
        self.router = CompletionRouter(
            self.g,
            self.v,
            self.lanes_per_block,
            self.kv,
            self.metrics,
            self.admission,
            self.coalescer,
            compact_lag=self.compact_lag,
        )
        # leader-lease read fast path (RAFT_TPU_LEASE): wired only when
        # the cluster's carry actually holds the lease columns — the
        # coalescer then offers each group's new waiting reads to the
        # router's lease router before opening a ReadIndex batch
        if getattr(base.state, "lease_left", None) is not None:
            self.coalescer.lease_route = self.router.route_lease_reads
        # one egress stream per resident block; the sink closure pins the
        # SCHEDULER block index (the stream's own push counter is a
        # sequence number, not lane addressing)
        self.streams = [
            EgressStream(
                sink=lambda seq, bundle, bi=i: self.router.on_bundle(
                    bi, seq, bundle
                ),
                stats=self.stats,
            )
            for i in range(self.k)
        ]
        self._egress_arg = self.streams if self.blocked else self.streams[0]
        # flight-recorder drains ride the same per-block stream layout;
        # built only when the device plane is compiled in (the cluster was
        # constructed under the same RAFT_TPU_TRACELOG, so enabled here
        # implies the rings exist there). Drained event counters land in
        # the serve counter bag (trace_events / trace_events_dropped).
        self.traces = None
        self._trace_arg = None
        if trdev.tracelog_enabled():
            self.traces = [
                TraceStream(counters=self.metrics.counters)
                for _ in range(self.k)
            ]
            self._trace_arg = (
                self.traces if self.blocked else self.traces[0]
            )
        if self.tier is not None:
            # the router resolves lanes <-> logical ids through the tier's
            # allocator, feeds the activity scorer straight from the egress
            # bundles (one touch per active-lane row), and its in-flight
            # attribution pins groups against mid-proposal eviction
            self.router.lane_to_group = self.tier.group_of_lane
            self.router.base_lane = self.tier.lane_of_group
            self.router.on_group_activity = self.tier.touch
            self.tier.set_pinned(
                lambda: self.router.groups_with_inflight()
                | self.coalescer.active_groups()
            )
            if self.spans is not None:
                self.tier.set_spans(self.spans)

    def audit_programs(self, rounds: int = 1):
        """Audit records for the serving frontend (raft_tpu/analysis).
        The loop's device-side program IS the cluster round program it
        drives — `_step_one` dispatches `cluster.run(1, ops, egress=...,
        trace=...)` and the egress/trace streams are host-side consumers,
        not program inputs — so the record is the cluster's own, renamed
        and pinned to the loop's one-round cadence. Blocked drivers that
        don't export audit records themselves (BlockedFusedCluster)
        delegate to their first block: every block runs the identical
        program."""
        target = self.cluster
        if not callable(getattr(target, "audit_programs", None)):
            target = target.blocks[0]
        recs = target.audit_programs(rounds)
        for r in recs:
            r["name"] = "serve.round"
            r["rounds"] = rounds
        return recs

    # -- bootstrap ---------------------------------------------------------

    def bootstrap(self, max_rounds: int = 512) -> None:
        """Run election rounds until every group has exactly one leader,
        then attach the router's group views from one synchronous column
        pull (initial attach rides the epoch-resync machinery on empty
        queues)."""
        if self.tier is not None:
            # attach only the resident (genesis) cohort; cold logical ids
            # attach when a miss admits them
            self.router.needs_resync.update(self.tier.residents())
        else:
            self.router.needs_resync.update(range(self.g))
        spent = 0
        while self.router.needs_resync and spent < max_rounds:
            self.cluster.run(
                8, auto_compact_lag=self.compact_lag,
                trace=self._trace_arg,
            )
            self.round += 8
            spent += 8
            self.router.round = self.round
            self.router.resync(self._columns())
        if self.router.needs_resync:
            raise RuntimeError(
                f"bootstrap: {len(self.router.needs_resync)} group(s) still "
                f"electing after {spent} rounds"
            )

    def _columns(self) -> dict:
        return self.cluster.state_columns(
            "state", "term", "committed", "last"
        )

    # -- client surface ----------------------------------------------------

    def open_session(self, tenant: str) -> Session:
        s = self.sessions.open(tenant)
        return s

    def close_session(self, session: Session) -> None:
        self.sessions.close(session)

    def put(self, session, key, value, nbytes: int = 0):
        return self._submit(session, OP_PUT, key, value, 0, nbytes)

    def delete(self, session, key):
        return self._submit(session, OP_DELETE, key, None, 0, 0)

    def lease(self, session, key, value, ttl: int):
        """Put with a lifetime: the entry expires `ttl` device ticks after
        it APPLIES (the tick plane is the lease clock)."""
        return self._submit(session, OP_LEASE, key, value, ttl, 0)

    def _submit(self, session, op, key, value, ttl, nbytes):
        gate = self._gate(session)
        if gate is not None:
            return gate
        cmd = Command(
            op, session.tenant, session.id, session.next_seq(),
            key, value, ttl, nbytes,
        )
        return self._enqueue_cmd(session, cmd)

    def resubmit(self, session, ticket: ProposeTicket):
        """Client retry of a timed-out proposal: SAME command, SAME seq —
        the (session, seq) dedup cursor collapses a double commit into one
        apply, turning at-least-once delivery into exactly-once apply."""
        gate = self._gate(session)
        if gate is not None:
            return gate
        return self._enqueue_cmd(session, ticket.cmd)

    def _enqueue_cmd(self, session, cmd: Command):
        rej = self.admission.admit(session.tenant)
        if rej is not None:
            return self._rejected(rej)
        t = ProposeTicket(cmd, session.group, self.round)
        rej = self.coalescer.enqueue(t)
        if rej is not None:
            self.admission.release()
            return self._rejected(rej)
        self.metrics.counters.inc("proposals_admitted")
        return t

    def get(self, session, key):
        """Linearizable GET: batches through the ReadIndex plane (all of a
        group's waiting reads share one ctx ticket per round) and answers
        from the applied KV once the group's watermark covers the released
        ReadIndex."""
        gate = self._gate(session)
        if gate is not None:
            return gate
        rej = self.admission.admit(session.tenant)
        if rej is not None:
            return self._rejected(rej, read=True)
        rt = ReadTicket(session.id, session.group, key, self.round)
        rej = self.coalescer.enqueue_read(rt)
        if rej is not None:
            self.admission.release()
            return self._rejected(rej, read=True)
        self.metrics.counters.inc("reads_admitted")
        return rt

    def _gate(self, session) -> Rejected | None:
        if not session.open:
            return self._rejected(Rejected(REJECT_SESSION_CLOSED))
        if self.tier is not None and not self.tier.resident(session.group):
            # hibernated group: the miss queues its re-admission (the
            # request is itself a scorer touch) and the client gets a
            # typed retry-later — never a drop
            self.tier.request_admit(session.group, self.round)
            return self._rejected(
                Rejected(REJECT_COLD_GROUP, f"group={session.group}")
            )
        if not self.router.views[session.group].attached:
            return self._rejected(
                Rejected(REJECT_NO_LEADER, f"group={session.group}")
            )
        return None

    def _rejected(self, rej: Rejected, read: bool = False) -> Rejected:
        self.metrics.counters.inc("proposals_rejected")
        self.metrics.counters.inc(f"rejected_{rej.reason}")
        return rej

    # -- the round loop ----------------------------------------------------

    def step(self, rounds: int = 1) -> None:
        for _ in range(rounds):
            self._step_one()

    def _step_one(self) -> None:
        self.round += 1
        self.metrics.rounds = self.round
        self.router.round = self.round
        if self.tier is not None:
            self.tier.tick(self.round)
            if self.tier.pending():
                # dispatch-boundary batch: evictions detach their views
                # (attribution parks with the cold record's exact rows);
                # admissions re-attach through the resync machinery below
                # — the restored leader re-attaches the same round
                with self.stats.span("serve.tier"):
                    evicted, admitted = self.tier.apply(self.round)
                for g in evicted:
                    self.router.views[g].detach()
                    self.router.needs_resync.discard(g)
                for g in admitted:
                    self.router.needs_resync.add(g)
        with self.stats.span("serve.admission"):
            self.admission.tick()
        with self.stats.span("serve.coalesce", round=self.round):
            ops, injections = self.coalescer.build(
                self.router.views, self.round
            )
            self.router.record_injections(injections)
            if ops is not None and self.blocked:
                # slice once, explicitly — the scheduler's identity LRU
                # cannot hit on a fresh per-round ops object
                ops = self.cluster.prepare_ops(ops)
        with self.stats.span("serve.dispatch", round=self.round):
            self.cluster.run(
                1,
                ops=ops,
                egress=self._egress_arg,
                trace=self._trace_arg,
                auto_compact_lag=self.compact_lag,
            )
        if self.coalescer.outstanding_reads:
            with self.stats.span("serve.drain_reads"):
                drained = self.cluster.drain_read_states()
                for glane, rss in drained.items():
                    for ctx, index in rss:
                        self.router.on_read_release(glane, ctx, index)
        if self.router.needs_resync:
            with self.stats.span("serve.resync"):
                with self.stats.span("serve.resync.wait"):
                    columns = self._columns()
                self.router.resync(columns)
        if self.expire_every and self.round % self.expire_every == 0:
            self.kv.expire(self.round)
        self.metrics.counters.set("sessions_active", self.sessions.active)

    def flush(self) -> None:
        """Resolve the in-flight egress tail: the double-buffered push
        resolves bundles one round behind, so the final round's commits
        only notify after a flush. The flight-recorder streams drain on
        the same fence so `traces[i].events` is complete afterwards."""
        with self.stats.span("serve.host_drain", round=self.round):
            for s in self.streams:
                s.flush()
            if self.traces is not None:
                for t in self.traces:
                    t.flush()
        self.router.round = self.round

    @property
    def outstanding(self) -> int:
        """Admitted-but-unnotified work (proposals + reads)."""
        return self.admission.inflight

    def drain(self, max_rounds: int = 256) -> bool:
        """Step (with per-round flushes, killing the one-round notify lag)
        until every admitted future resolved; False if max_rounds elapsed
        with work still outstanding."""
        spent = 0
        self.flush()
        while self.outstanding and spent < max_rounds:
            self._step_one()
            self.flush()
            spent += 1
        return self.outstanding == 0

    # -- oracles / export --------------------------------------------------

    def digest(self) -> str:
        """sha256 of the full applied KV materialization at `round`."""
        return self.kv.digest(self.round)

    def twin_digest(self) -> str:
        """Replay the router's apply-ordered command log through a fresh
        scalar KVStore — the acceptance oracle the digests must match."""
        from raft_tpu.serve.kv import replay

        return replay(
            self.logical_groups, self.router.applied_log, self.round
        )

    def _flow_snapshot(self) -> dict:
        """Exact counts at the serving path's boundaries: points where the
        host blocks on the device (`syncs`, one per `.wait` span),
        round-program executions enqueued (`dispatches`, one per
        `sched.enqueue` span), the egress bundles' bytes and active lanes,
        and the coalescer's injections (`read_batches_released` is the
        router's counter)."""
        counts = self.stats.counts
        return {
            "counters": {
                "syncs": sum(
                    n for k, n in counts.items() if k.endswith(".wait")
                ),
                "dispatches": counts.get("sched.enqueue", 0),
                "egress_bytes": sum(s.bytes for s in self.streams),
                "egress_lanes_active": sum(
                    s.lanes_active for s in self.streams
                ),
                "entries_injected": self.coalescer.entries_injected,
                "read_batches_injected": self.coalescer.read_batches_injected,
            },
            "rounds": 0,
        }

    def metrics_snapshot(self) -> dict:
        """Merged host-plane snapshot: serving counters + notify-latency
        histograms in rounds and stage histograms in wall ms (namespaced
        by name) + step_* span timings + the flow counts."""
        return self.registry.snapshot()

    def engine_snapshot(self) -> dict | None:
        return self.cluster.metrics_snapshot()

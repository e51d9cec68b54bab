"""Serving frontend (raft_tpu/serve/): sessions, admission, coalescing,
the linearizable read path, completion routing, and the exactly-once /
digest-twin acceptance oracles.

Device-backed tests share module-scoped ServeLoops (one FusedCluster, one
BlockedFusedCluster) so the XLA:CPU compile count stays low — tests
namespace their keys/sessions instead of rebuilding clusters. The pure
host layers (kv, session, admission, coalescer, http) test without any
device dispatch."""

import json
import urllib.request

import numpy as np
import pytest

from raft_tpu.serve import (
    OP_PUT,
    REJECT_INFLIGHT_CAP,
    REJECT_NO_LEADER,
    REJECT_QUEUE_FULL,
    REJECT_SESSION_CLOSED,
    REJECT_TENANT_RATE,
    AdmissionController,
    Command,
    GroupStore,
    KVStore,
    MetricsHTTPServer,
    ProposalCoalescer,
    ProposeTicket,
    Rejected,
    ServeLoop,
    TokenBucket,
    place,
    replay,
)
from raft_tpu.serve.coalescer import ReadTicket


# -- host-side layers (no device) -------------------------------------------


def test_placement_static_and_stable():
    # crc32-based: stable across processes/PYTHONHASHSEED, full coverage
    assert place("tenant-a", 16) == place("tenant-a", 16)
    hits = {place(f"t{i}", 8) for i in range(256)}
    assert hits == set(range(8))


def test_rejected_is_falsy_and_typed():
    r = Rejected(REJECT_TENANT_RATE, "t0")
    assert not r
    assert r.reason == REJECT_TENANT_RATE
    assert isinstance(r, tuple)  # NamedTuple: structured, matchable


def test_token_bucket_and_admission_reasons():
    a = AdmissionController(tenant_rate=1.0, tenant_burst=2.0, inflight_cap=3)
    assert a.admit("t") is None and a.admit("t") is None
    r = a.admit("t")
    assert r is not None and r.reason == REJECT_TENANT_RATE
    a.tick()  # one round refills one token
    assert a.admit("t") is None
    r = a.admit("u")  # fresh tenant, fresh bucket — but the GLOBAL cap hit
    assert r is not None and r.reason == REJECT_INFLIGHT_CAP
    a.release(1)
    assert a.admit("u") is None


def test_groupstore_dedup_and_lease_expiry():
    g = GroupStore()
    c1 = Command(OP_PUT, "t", 1, 1, "k", "v1")
    assert g.apply(c1, now=10) is True
    assert g.apply(c1, now=11) is False  # retried duplicate collapses
    assert g.deduped_cmds == 1
    assert g.get("k", now=12) == "v1"
    from raft_tpu.serve import OP_LEASE

    g.apply(Command(OP_LEASE, "t", 1, 2, "lk", "lv", ttl=5), now=20)
    assert g.get("lk", now=24) == "lv"
    assert g.get("lk", now=25) is None  # expired lazily
    assert g.expire(now=25) == 1  # and swept


def test_replay_twin_digest_matches_direct_apply():
    log = [
        (0, Command(OP_PUT, "t", 1, 1, "a", 1), 5),
        (1, Command(OP_PUT, "u", 2, 1, "b", 2), 6),
        (0, Command(OP_PUT, "t", 1, 1, "a", 99), 7),  # dup: must not apply
        (0, Command(OP_PUT, "t", 1, 2, "c", 3), 8),
    ]
    kv = KVStore(2)
    for g, cmd, tick in log:
        kv.apply(g, cmd, tick)
    assert kv.digest(10) == replay(2, log, 10)
    assert kv.get(0, "a", 10) == 1  # the duplicate did not clobber


class _View:
    """Minimal GroupView stand-in for coalescer unit tests."""

    def __init__(self, leader_lane, next_index=1, watermark=0):
        self.leader_lane = leader_lane
        self.next_index = next_index
        self.watermark = watermark

    def floor(self):
        return self.watermark


def _cmd(seq, key="k"):
    return Command(OP_PUT, "t", 1, seq, key, seq)


def test_coalescer_caps_per_round_batch_at_max_msg_entries():
    co = ProposalCoalescer(
        1, 3, max_entries_per_round=4, log_window=64, compact_lag=16,
        max_read_batches=3,
    )
    for i in range(10):
        assert co.enqueue(ProposeTicket(_cmd(i + 1), 0, 0)) is None
    views = [_View(leader_lane=0)]
    ops, inj = co.build(views, round_id=1)
    assert ops is not None
    # the kernel clamps prop_n at E — the host must never exceed it
    assert int(np.asarray(ops.prop_n)[0]) == 4
    (view, batch), = inj
    assert [t.index for t in batch] == [1, 2, 3, 4]
    assert views[0].next_index == 5
    ops, _ = co.build(views, round_id=2)
    assert int(np.asarray(ops.prop_n)[0]) == 4
    ops, _ = co.build(views, round_id=3)
    assert int(np.asarray(ops.prop_n)[0]) == 2  # tail
    ops, inj = co.build(views, round_id=4)
    assert ops is None and inj == []  # idle round builds nothing


def test_coalescer_window_budget_backpressure():
    co = ProposalCoalescer(
        1, 3, max_entries_per_round=8, log_window=16, compact_lag=4,
        max_read_batches=3,
    )
    # budget = 16 - 4 - 2 = 10 resident entries
    for i in range(20):
        co.enqueue(ProposeTicket(_cmd(i + 1), 0, 0))
    views = [_View(leader_lane=0)]
    n1 = int(np.asarray(co.build(views, 1)[0].prop_n)[0])
    n2 = int(np.asarray(co.build(views, 2)[0].prop_n)[0])
    assert n1 + n2 == 10  # stalls at the budget while watermark is stuck
    assert co.build(views, 3)[0] is None
    views[0].watermark = 10  # commits applied -> window drains
    n3 = int(np.asarray(co.build(views, 4)[0].prop_n)[0])
    assert n3 == 8  # E-capped resumption


def test_coalescer_queue_cap_rejects_typed():
    co = ProposalCoalescer(
        1, 3, max_entries_per_round=8, log_window=64, compact_lag=16,
        max_read_batches=3, queue_cap=2,
    )
    assert co.enqueue(ProposeTicket(_cmd(1), 0, 0)) is None
    assert co.enqueue(ProposeTicket(_cmd(2), 0, 0)) is None
    r = co.enqueue(ProposeTicket(_cmd(3), 0, 0))
    assert r is not None and r.reason == REJECT_QUEUE_FULL


def test_coalescer_reads_share_one_ctx_per_group_round():
    co = ProposalCoalescer(
        1, 3, max_entries_per_round=8, log_window=64, compact_lag=16,
        max_read_batches=2, read_retry_rounds=4,
    )
    for i in range(5):
        co.enqueue_read(ReadTicket(1, 0, f"k{i}", 0))
    ops, _ = co.build([_View(leader_lane=0)], 1)
    ctx = int(np.asarray(ops.read_ctx)[0])
    assert ctx > 0
    assert co.outstanding_reads == 1  # ONE batch carries all five
    assert len(co.read_batches[ctx].tickets) == 5
    # a due retry re-injects the SAME ctx (idempotent release contract)
    retried = []
    co.on_read_retry = lambda: retried.append(1)
    ops, _ = co.build([_View(leader_lane=0)], 5)
    assert int(np.asarray(ops.read_ctx)[0]) == ctx
    assert retried == [1]
    assert co.take_batch(ctx) is not None and co.take_batch(ctx) is None


def test_delta_bundle_rs_count_keeps_lane_active():
    """A lane holding undrained ReadIndex results stays in the egress
    active set even with zero cursor movement — the serving wake-up."""
    from types import SimpleNamespace

    import jax.numpy as jnp

    from raft_tpu.ops.ready_mask import PrevCursors, delta_bundle

    z = jnp.zeros((4,), jnp.int32)
    st = SimpleNamespace(
        term=z, lead=z, state=z, committed=z, applied=z, last=z,
        rs_count=jnp.asarray([0, 2, 0, 0], jnp.int32),
    )
    prev = PrevCursors(z, z, z, z, z, z)
    b = delta_bundle(st, prev)
    assert int(b.count) == 1 and int(b.active[0]) == 1
    assert int(b.rs_count[1]) == 2


def test_http_endpoint_renders_both_planes():
    snap = {
        "counters": {"proposals_admitted": 3},
        "hist": {"edges": [1, 2], "buckets": [1, 0, 2], "sum": 9, "count": 3},
        "rounds": 7,
    }
    srv = MetricsHTTPServer()
    srv.add_source("raft_tpu_serve", "notify_latency_rounds", lambda: snap)
    srv.add_source("raft_tpu", "commit_latency_rounds", lambda: None)  # off
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "raft_tpu_serve_proposals_admitted_total 3" in body
        assert 'raft_tpu_serve_notify_latency_rounds_bucket{le="+Inf"} 3' in body
        assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")
    finally:
        srv.stop()


# -- device-backed: FusedCluster serving loop -------------------------------


@pytest.fixture(scope="module")
def loop():
    from raft_tpu.ops.fused import FusedCluster

    sl = ServeLoop(FusedCluster(2, 3, seed=3), read_retry_rounds=6)
    sl.bootstrap()
    return sl


def test_put_commit_notify_exactly_once(loop):
    s = loop.open_session("acct-x")
    ts = [loop.put(s, f"x/{i}", i) for i in range(20)]
    assert all(not isinstance(t, Rejected) for t in ts)
    assert loop.drain(200)
    for t in ts:
        assert t.done and t.applied and t.notify_round is not None
        assert t.latency_rounds >= 1
    m = loop.metrics_snapshot()["counters"]
    assert m.get("notify_violations", 0) == 0
    assert loop.kv.get(s.group, "x/7", loop.round) == 7


def test_digest_matches_scalar_twin(loop):
    s = loop.open_session("acct-twin")
    for i in range(12):
        loop.put(s, f"tw/{i}", f"v{i}")
    loop.delete(s, "tw/3")
    assert loop.drain(200)
    assert loop.digest() == loop.twin_digest()


def test_dedup_of_retried_proposals(loop):
    """At-least-once submission -> exactly-once apply: a client retry
    (same session seq) commits twice in the log but applies once."""
    s = loop.open_session("acct-retry")
    t1 = loop.put(s, "r/k", "first")
    t2 = loop.resubmit(s, t1)  # same Command, same seq
    assert not isinstance(t2, Rejected)
    assert loop.drain(200)
    assert t1.done and t2.done
    assert (t1.applied, t2.applied) == (True, False)
    assert loop.kv.get(s.group, "r/k", loop.round) == "first"
    g = loop.kv.groups[s.group]
    assert g.deduped_cmds >= 1
    assert loop.digest() == loop.twin_digest()
    assert loop.metrics_snapshot()["counters"].get("notify_violations", 0) == 0


def test_linearizable_read_observes_prior_write(loop):
    s = loop.open_session("acct-read")
    t = loop.put(s, "lr/k", "seen")
    assert loop.drain(200) and t.done
    rt = loop.get(s, "lr/k")
    assert not isinstance(rt, Rejected)
    assert loop.drain(200)
    assert rt.done and rt.value == "seen"
    assert rt.index is not None and rt.index > 0
    # the ReadIndex the answer reflects covers the write's log index
    assert rt.index >= t.index


def test_read_batching_one_ticket_many_gets(loop):
    s = loop.open_session("acct-batch")
    for i in range(6):
        loop.put(s, f"b/{i}", i)
    assert loop.drain(200)
    served_before = loop.metrics_snapshot()["counters"].get("reads_served", 0)
    rts = [loop.get(s, f"b/{i}") for i in range(6)]
    assert loop.coalescer.queue_depth(s.group) == 6  # all waiting, 0 batches
    assert loop.drain(200)
    assert [rt.value for rt in rts] == list(range(6))
    # all six shared ONE ReadIndex: identical released index
    assert len({rt.index for rt in rts}) == 1
    served = loop.metrics_snapshot()["counters"]["reads_served"]
    assert served - served_before == 6


def test_lease_expiry_across_ticks(loop):
    s = loop.open_session("acct-lease")
    lt = loop.lease(s, "ls/k", "alive", ttl=8)
    assert loop.drain(200) and lt.done
    applied_at = lt.commit_round
    assert loop.kv.get(s.group, "ls/k", loop.round) == "alive"
    while loop.round < applied_at + 8:
        loop.step()
    loop.flush()
    # rounds ARE ticks: the lease dies at apply_tick + ttl exactly
    assert loop.kv.get(s.group, "ls/k", loop.round) is None
    rt = loop.get(s, "ls/k")
    assert loop.drain(200)
    assert rt.done and rt.value is None
    assert loop.digest() == loop.twin_digest()  # expiry is digest-neutral


def test_session_gates(loop):
    s = loop.open_session("acct-closed")
    loop.close_session(s)
    r = loop.put(s, "c/k", 1)
    assert isinstance(r, Rejected) and r.reason == REJECT_SESSION_CLOSED
    r = loop.get(s, "c/k")
    assert isinstance(r, Rejected) and r.reason == REJECT_SESSION_CLOSED


def test_tenant_isolation_under_full_bucket():
    """One tenant saturating its token bucket must not affect another
    tenant's admission or latency — isolation is per-bucket, and the
    rejection is typed, not silent."""
    from raft_tpu.ops.fused import FusedCluster

    sl = ServeLoop(
        FusedCluster(2, 3, seed=11), tenant_rate=1.0, tenant_burst=4.0
    )
    sl.bootstrap()
    hog = sl.open_session("hog")
    quiet = sl.open_session("quiet")
    hog_rej = 0
    for i in range(12):
        if isinstance(sl.put(hog, f"h/{i}", i), Rejected):
            hog_rej += 1
    assert hog_rej == 8  # burst 4 + 0 refills at submit time
    qt = [sl.put(quiet, f"q/{i}", i) for i in range(4)]
    assert all(not isinstance(t, Rejected) for t in qt)  # untouched bucket
    assert sl.drain(200)
    assert all(t.done for t in qt)
    m = sl.metrics_snapshot()["counters"]
    assert m["rejected_tenant_rate"] == hog_rej
    assert m["proposals_rejected"] == hog_rej
    assert m.get("notify_violations", 0) == 0
    assert sl.digest() == sl.twin_digest()


# -- device-backed: blocked scheduler path ----------------------------------


@pytest.fixture(scope="module")
def blocked_loop():
    from raft_tpu.scheduler import BlockedFusedCluster

    sl = ServeLoop(BlockedFusedCluster(4, 3, block_groups=2, seed=5))
    sl.bootstrap()
    return sl


def test_blocked_serving_round_trip(blocked_loop):
    """K resident blocks: per-block egress sinks route lanes back to the
    right global groups, prepare_ops slices the one global injection."""
    sl = blocked_loop
    assert sl.k == 2
    ss = [sl.open_session(f"bt{i}") for i in range(6)]
    assert len({s.group for s in ss}) >= 2  # spans blocks
    ts = []
    for i in range(8):
        for s in ss:
            t = sl.put(s, f"{s.tenant}/{i}", f"{s.tenant}-{i}")
            assert not isinstance(t, Rejected)
            ts.append(t)
    assert sl.drain(300)
    assert all(t.done for t in ts)
    rts = [sl.get(s, f"{s.tenant}/5") for s in ss]
    assert sl.drain(300)
    for s, rt in zip(ss, rts):
        assert rt.done and rt.value == f"{s.tenant}-5"
    m = sl.metrics_snapshot()["counters"]
    assert m.get("notify_violations", 0) == 0
    assert sl.digest() == sl.twin_digest()


def test_blocked_round_spans_counters_and_stage_stamps(blocked_loop):
    """Served rounds up to the first ReadIndex release, then a flush, with
    a SpanRecorder attached: span names and nesting, dotted StepStats
    children within their parent, exact flow counts, and monotone ticket
    stamps whose wall-clock stage histograms count every answered
    request."""
    from raft_tpu.metrics.host import prometheus_text
    from raft_tpu.utils.profiling import SpanRecorder

    sl = blocked_loop
    k = sl.k
    ss = [sl.open_session(f"sp{i}") for i in range(4)]
    c0 = sl.metrics_snapshot()["counters"]
    puts = [sl.put(s, f"{s.tenant}/k", s.tenant) for s in ss]
    assert sl.drain(300)
    c1 = sl.metrics_snapshot()["counters"]
    assert c1["entries_injected"] - c0["entries_injected"] == len(puts)
    gets = [sl.get(s, f"{s.tenant}/k") for s in ss]
    rec = sl.stats.spans = SpanRecorder()
    rounds = 0
    try:
        while rounds < 2 or not any(
            sp[0] == "serve.drain_reads.reset.wait" for sp in rec.spans
        ):
            assert rounds < 16, "no ReadIndex release drained"
            sl.step()
            rounds += 1
        sl.flush()
    finally:
        sl.stats.spans = None
    spans = rec.spans
    names = [sp[0] for sp in spans]
    assert {
        "serve.admission", "serve.coalesce", "serve.dispatch",
        "sched.dispatch", "sched.enqueue", "egress.resolve.wait",
        "egress.sink", "serve.drain_reads", "serve.drain_reads.wait",
        "serve.drain_reads.decode", "serve.drain_reads.reset.wait",
        "serve.host_drain",
    } <= set(names)

    def inside(child, parents):
        _, t0, dt, _ = child
        return any(
            p[1] <= t0 and t0 + dt <= p[1] + p[2] + 1e-9
            for p in spans if p[0] in parents
        )

    nest = {
        "sched.dispatch": {"serve.dispatch"},
        "sched.enqueue": {"sched.dispatch"},
        "egress.resolve.wait": {"sched.dispatch", "serve.host_drain"},
        "egress.sink": {"sched.dispatch", "serve.host_drain"},
        "serve.drain_reads.wait": {"serve.drain_reads"},
        "serve.drain_reads.decode": {"serve.drain_reads"},
        "serve.drain_reads.reset.wait": {"serve.drain_reads"},
    }
    for sp in spans:
        if sp[0] in nest:
            assert inside(sp, nest[sp[0]]), sp
    assert names.count("sched.enqueue") == rounds * k
    assert names.count("serve.drain_reads.wait") == k * names.count(
        "serve.drain_reads"
    )
    st = sl.stats
    children = ("wait", "decode", "reset.wait")
    assert sum(st.seconds[f"drain_reads.{c}"] for c in children) <= (
        st.seconds["drain_reads"]
    )
    c2 = sl.metrics_snapshot()["counters"]
    assert c2["dispatches"] - c1["dispatches"] == rounds * k
    # each round's egress bundle resolves in the next round's push, the
    # last one in the flush: one pull per block per round; per drain one
    # pull per block, and one reset per block that held releases; one
    # pull per resync
    assert names.count("egress.resolve.wait") == rounds * k
    assert c2["syncs"] - c1["syncs"] == (
        rounds * k + k * names.count("serve.drain_reads")
        + names.count("serve.drain_reads.reset.wait")
        + names.count("serve.resync")
    )
    assert names.count("serve.drain_reads.reset.wait") == names.count(
        "serve.drain_reads.decode"
    )
    assert c2["egress_bytes"] > c1["egress_bytes"]
    assert sl.drain(300)
    c3 = sl.metrics_snapshot()
    cnt, hists = c3["counters"], c3["hists"]
    groups = len({s.group for s in ss})
    for name in ("read_batches_injected", "read_batches_released"):
        assert cnt[name] - c1.get(name, 0) == groups, name
    assert cnt["entries_injected"] == c1["entries_injected"]
    for t in puts + gets:
        assert t.done
        assert t.submit_t <= t.inject_t <= t.release_t <= t.notify_t
    for name in ("get_queue_ms", "get_readindex_ms", "get_apply_ms"):
        assert hists[name]["count"] == cnt["reads_served"]
    for name in ("put_queue_ms", "put_commit_ms", "put_apply_ms"):
        assert hists[name]["count"] == cnt["proposals_notified"]
    # the scrape the HTTP endpoint serves (serve/http.py) carries both
    text = prometheus_text(c3, prefix="raft_tpu_serve")
    assert f"raft_tpu_serve_syncs_total {cnt['syncs']}" in text
    assert 'raft_tpu_serve_get_readindex_ms_bucket{le="+Inf"}' in text
    assert "raft_tpu_serve_put_commit_ms_count" in text


def test_blocked_no_leader_gate_before_bootstrap():
    from raft_tpu.scheduler import BlockedFusedCluster

    sl = ServeLoop(BlockedFusedCluster(2, 3, block_groups=2, seed=7))
    s = sl.open_session("early")
    r = sl.put(s, "k", 1)
    assert isinstance(r, Rejected) and r.reason == REJECT_NO_LEADER

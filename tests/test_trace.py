"""Flight recorder + trace plane (raft_tpu/trace/, runtime/trace.py).

Layers covered, cheapest first: pure-device detector/ring units (synthetic
states, no cluster), the TraceStream host drain (drop accounting, sharded
merge), the compile-time elision gate (jaxpr-asserted, the metrics-plane
idiom), engine parity (2-tile Pallas vs XLA bit-identity; transitions
vs a scalar state_columns oracle), the donation x cache fence, block-local
lane stamps under the scheduler, sharded parity, and the serve-loop
integration (lifecycle log, spans, Perfetto assembly, explain)."""

import contextlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.metrics.host import HostCounters
from raft_tpu.runtime.trace import EVENT_COLUMNS, TraceStream
from raft_tpu.trace import assemble as tasm
from raft_tpu.trace import device as trdev


# -- device detector units (no cluster, no scan) ---------------------------


def _st(n=2, **over):
    """Synthetic fat-state view with only the fields the detector reads."""
    base = dict(
        state=jnp.zeros((n,), jnp.int32),
        term=jnp.zeros((n,), jnp.int32),
        vote=jnp.zeros((n,), jnp.int32),
        snap_index=jnp.zeros((n,), jnp.int32),
        last=jnp.zeros((n,), jnp.int32),
        committed=jnp.zeros((n,), jnp.int32),
        applied=jnp.zeros((n,), jnp.int32),
        pending_conf_index=jnp.zeros((n,), jnp.int32),
    )
    for k, v in over.items():
        base[k] = jnp.asarray(v, jnp.int32)
    return types.SimpleNamespace(**base)


def _events(tr):
    """Decode a (non-wrapped) ring into [(round, lane, kind, arg), ...]."""
    w = int(tr.wr)
    r = tr.ring_round.shape[0]
    kept = min(w, r)
    slots = np.arange(w - kept, w) % r
    return [
        (
            int(np.asarray(tr.ring_round)[s]),
            int(np.asarray(tr.ring_lane)[s]),
            int(np.asarray(tr.ring_kind)[s]),
            int(np.asarray(tr.ring_arg)[s]),
        )
        for s in slots
    ]


_LEADER = trdev._LEADER


def test_detector_election_transitions():
    tr = trdev.init_trace(3, ring=16)
    st0 = _st(3)
    st1 = _st(
        3,
        state=[_LEADER, 0, 0],
        term=[2, 2, 1],
        vote=[1, 1, 0],
        last=[1, 1, 0],
    )
    tr = trdev.record_round(tr, st0, st1)
    ev = _events(tr)
    # lane-major, kind-minor order within the round
    assert ev == [
        (1, 0, trdev.LEADER_ELECTED, 2),
        (1, 0, trdev.TERM_BUMP, 2),
        (1, 0, trdev.VOTE_GRANTED, 1),
        (1, 1, trdev.TERM_BUMP, 2),
        (1, 1, trdev.VOTE_GRANTED, 1),
        (1, 2, trdev.TERM_BUMP, 1),
    ]
    assert int(tr.round) == 1 and int(tr.wr) == 6


def test_detector_loss_snapshot_confchange_and_lane_offset():
    tr = trdev.init_trace(2, ring=16)
    st0 = _st(
        2,
        state=[_LEADER, 0],
        term=[3, 3],
        snap_index=[0, 4],
        last=[6, 4],
        applied=[2, 4],
        committed=[2, 4],
        pending_conf_index=[5, 0],
    )
    st1 = _st(
        2,
        state=[0, 0],
        term=[3, 3],
        # lane 1: installed a snapshot PAST its old last (receive-install);
        # lane 0: applied catches up past pending_conf_index
        snap_index=[0, 9],
        last=[6, 9],
        applied=[6, 9],
        committed=[6, 9],
        pending_conf_index=[0, 0],
    )
    tr = trdev.record_round(tr, st0, st1, lane_offset=jnp.int32(10))
    assert _events(tr) == [
        (1, 10, trdev.LEADERSHIP_LOST, 3),
        (1, 10, trdev.CONFCHANGE_APPLY, 5),
        (1, 11, trdev.SNAPSHOT_INSTALL, 9),
    ]


def test_detector_local_compaction_is_not_snapshot_install():
    tr = trdev.init_trace(1, ring=8)
    st0 = _st(1, snap_index=[2], last=[10], applied=[10], committed=[10])
    st1 = _st(1, snap_index=[8], last=[10], applied=[10], committed=[10])
    tr = trdev.record_round(tr, st0, st1)
    assert int(tr.wr) == 0  # snap_index moved below last: auto-compaction


def test_detector_commit_stall_onset_fires_once():
    tr = trdev.init_trace(1, ring=32)
    stuck0 = _st(1, state=[_LEADER], last=[5], committed=[1])
    for i in range(trdev.STALL_AFTER + 3):
        tr = trdev.record_round(tr, stuck0, stuck0)
    ev = [e for e in _events(tr) if e[2] == trdev.COMMIT_STALL]
    # onset at round STALL_AFTER, once per episode, arg = stuck committed
    assert ev == [(trdev.STALL_AFTER, 0, trdev.COMMIT_STALL, 1)]
    # progress resets the counter; a new stall episode fires again
    moved = _st(1, state=[_LEADER], last=[5], committed=[2])
    tr = trdev.record_round(tr, stuck0, moved)
    for _ in range(trdev.STALL_AFTER):
        tr = trdev.record_round(tr, moved, moved)
    ev = [e for e in _events(tr) if e[2] == trdev.COMMIT_STALL]
    assert len(ev) == 2 and ev[1][3] == 2


def test_detector_chaos_fault_edges():
    tr = trdev.init_trace(2, ring=8)
    st = _st(2)
    chaos = types.SimpleNamespace(
        round=jnp.int32(7),
        crash_at=jnp.asarray([7, -1], jnp.int32),
        restart_at=jnp.asarray([7, 9], jnp.int32),
    )
    tr = trdev.record_round(tr, st, st, chaos=chaos)
    assert _events(tr) == [(1, 0, trdev.CHAOS_FAULT, 3)]


def test_ring_overflow_drops_oldest_and_wr_is_monotone():
    tr = trdev.init_trace(4, ring=4)
    # one round, 8 events (4 lanes x term_bump+vote_granted): only the
    # LAST ring-size survive, in order, and wr counts all 8
    st1 = _st(4, term=[1] * 4, vote=[2] * 4)
    tr = trdev.record_round(tr, _st(4), st1)
    assert int(tr.wr) == 8
    assert _events(tr) == [
        (1, 2, trdev.TERM_BUMP, 1),
        (1, 2, trdev.VOTE_GRANTED, 2),
        (1, 3, trdev.TERM_BUMP, 1),
        (1, 3, trdev.VOTE_GRANTED, 2),
    ]


def test_rebase_shifts_only_index_args():
    tr = trdev.init_trace(2, ring=8)
    st0 = _st(2, state=[_LEADER, 0], snap_index=[0, 3], last=[9, 3],
              committed=[1, 3], applied=[1, 3])
    st1 = _st(2, state=[_LEADER, 0], snap_index=[0, 8], last=[9, 8],
              committed=[1, 8], applied=[1, 8])
    for _ in range(trdev.STALL_AFTER):
        tr = trdev.record_round(tr, st0, st1)
        st0 = st1
    kinds = {e[2] for e in _events(tr)}
    assert trdev.SNAPSHOT_INSTALL in kinds and trdev.COMMIT_STALL in kinds
    before = _events(tr)
    tr2 = trdev.rebase(tr, jnp.asarray([True, True]), jnp.int32(-2))
    after = _events(tr2)
    for b, a in zip(before, after):
        if b[2] in (trdev.SNAPSHOT_INSTALL, trdev.COMMIT_STALL):
            assert a[3] == b[3] - 2
        else:
            assert a == b


# -- TraceStream host drain -------------------------------------------------


def _stream_trace(ring_vals, wr, n=1):
    """Build a TraceState whose ring columns all hold ring_vals (so the
    drained rows are easy to predict)."""
    r = np.asarray(ring_vals, np.int32)
    col = jnp.asarray(r)
    return trdev.TraceState(
        ring_round=col, ring_lane=col, ring_kind=col, ring_arg=col,
        wr=jnp.asarray(wr, jnp.int32), round=jnp.int32(0),
        stall=jnp.zeros((n,), jnp.int32),
    )


def test_stream_exact_drop_accounting(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    ctr = HostCounters()
    ts = TraceStream(counters=ctr)
    # ring of 4, wr=10: 6 oldest overwritten, slots [6..9] % 4 live
    ts.push(_stream_trace(np.arange(4) + 100, wr=10))
    ts.flush()
    assert ts.dropped == 6 and ts.events_total == 10
    assert ts.events[:, 0].tolist() == [102, 103, 100, 101]
    assert ctr.get("trace_events") == 4
    assert ctr.get("trace_events_dropped") == 6
    # second drain: 2 new events, none dropped, counter deltas exact
    ts.push(_stream_trace(np.arange(4) + 200, wr=12))
    ts.flush()
    assert ts.dropped == 6
    assert ctr.get("trace_events") == 6
    assert ctr.get("trace_events_dropped") == 6


def test_stream_sharded_merge_is_round_sorted_stable(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    ts = TraceStream()
    # two shards, stacked [2, 4] rings; rounds interleave across shards
    rr = jnp.asarray([[1, 3, 5, 7], [2, 3, 6, 0]], jnp.int32)
    lane = jnp.asarray([[0, 0, 0, 0], [9, 9, 9, 9]], jnp.int32)
    z = jnp.zeros((2, 4), jnp.int32)
    tr = trdev.TraceState(
        ring_round=rr, ring_lane=lane, ring_kind=z, ring_arg=z,
        wr=jnp.asarray([4, 3], jnp.int32), round=jnp.int32(0),
        stall=jnp.zeros((2,), jnp.int32),
    )
    ts.push(tr)
    ts.flush()
    ev = ts.events
    assert ev[:, 0].tolist() == [1, 2, 3, 3, 5, 6, 7]
    # stable: shard 0's round-3 event precedes shard 1's
    assert ev[ev[:, 0] == 3][:, 1].tolist() == [0, 9]


def test_stream_disabled_is_noop():
    assert "round" == EVENT_COLUMNS[0]
    ts = TraceStream()  # RAFT_TPU_TRACELOG unset -> default off
    assert not ts.enabled
    ts.push(None)
    ts.flush()
    assert ts.events.shape == (0, 4)


# -- compile-time elision gate ---------------------------------------------


def test_trace_off_elides_from_jaxpr_and_dispatches_nothing(monkeypatch):
    from raft_tpu.analysis import jaxpr_audit
    from raft_tpu.ops.fused import FusedCluster

    monkeypatch.delenv("RAFT_TPU_TRACELOG", raising=False)
    calls0 = trdev.kernel_calls()
    c = FusedCluster(1, 3, seed=2)
    assert c.trace is None
    rec = c.audit_programs()[0]
    off, deltas = jaxpr_audit.traced_counter_deltas(rec)
    assert not jaxpr_audit.check_elision(rec["name"], deltas,
                                         {"trace": False})
    # ring-shaped values must not ride the scan carry / kernel operands
    assert not any(
        shape == (trdev.ring_capacity(),)
        for shape, _ in jaxpr_audit.storage_avals(off)
    )
    c.run(2, trace=TraceStream())
    assert trdev.kernel_calls() == calls0
    assert c.metrics_snapshot() is not None  # metrics plane untouched


def test_trace_on_carries_ring_through_scan(monkeypatch):
    from raft_tpu.analysis import jaxpr_audit
    from raft_tpu.ops.fused import FusedCluster

    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    monkeypatch.setenv("RAFT_TPU_TRACE_RING", "257")  # collision-proof shape
    calls0 = trdev.kernel_calls()
    c = FusedCluster(1, 3, seed=2)
    assert c.trace is not None and c.trace.ring_round.shape == (257,)
    rec = c.audit_programs()[0]
    on, deltas = jaxpr_audit.traced_counter_deltas(rec)
    assert not jaxpr_audit.check_elision(rec["name"], deltas,
                                         {"trace": True})
    assert (257,) in {shape for shape, _ in jaxpr_audit.storage_avals(on)}
    assert trdev.kernel_calls() > calls0


# -- engine parity ----------------------------------------------------------


def _drain_run(c, rounds=20, chunk=5):
    ts = TraceStream()
    for _ in range(rounds // chunk):
        c.run(chunk, trace=ts)
    ts.flush()
    return ts


def test_xla_events_match_scalar_column_oracle(monkeypatch):
    """Round-by-round single dispatches vs a state_columns poll: every
    drained transition must match the diff of the polled columns — the
    scalar-twin oracle (same derivation trace_ab.py uses)."""
    from raft_tpu.ops.fused import FusedCluster

    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    c = FusedCluster(1, 3, seed=2)
    ts = TraceStream()
    cols = ("state", "term", "vote")
    prev = c.state_columns(*cols)
    expect = []
    for rnd in range(1, 13):
        c.run(1, ops=c.ops(hup={0: True}) if rnd == 1 else None,
              do_tick=False, trace=ts)
        cur = c.state_columns(*cols)
        for lane in range(3):
            l0 = int(prev["state"][lane]) == _LEADER
            l1 = int(cur["state"][lane]) == _LEADER
            if l1 and not l0:
                expect.append(
                    (rnd, lane, trdev.LEADER_ELECTED, int(cur["term"][lane]))
                )
            if l0 and not l1:
                expect.append(
                    (rnd, lane, trdev.LEADERSHIP_LOST, int(cur["term"][lane]))
                )
            if int(cur["term"][lane]) > int(prev["term"][lane]):
                expect.append(
                    (rnd, lane, trdev.TERM_BUMP, int(cur["term"][lane]))
                )
            if int(cur["vote"][lane]) != int(prev["vote"][lane]) and (
                int(cur["vote"][lane]) > 0
            ):
                expect.append(
                    (rnd, lane, trdev.VOTE_GRANTED, int(cur["vote"][lane]))
                )
        prev = cur
    ts.flush()
    got = [tuple(e) for e in ts.events.tolist()]
    assert got == expect
    assert any(k == trdev.LEADER_ELECTED for _, _, k, _ in got)


def test_pallas_two_tiles_bit_identical_to_xla(monkeypatch):
    from raft_tpu.ops.fused import FusedCluster

    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    cx = FusedCluster(8, 3, seed=0, engine="xla")
    ex = _drain_run(cx).events
    cp = FusedCluster(8, 3, seed=0, engine="pallas", tile_lanes=12)
    ep = _drain_run(cp).events
    assert cp.engine == "pallas", "pallas engine fell back"
    assert ex.shape[0] > 0
    np.testing.assert_array_equal(ex, ep)


def test_donation_off_on_same_events(monkeypatch):
    """RAFT_TPU_DONATE=0 vs =1 (same seed, same rounds, warm jit cache in
    one process) must drain identical event streams: the push fence
    (_trace_pending flush before the next donating dispatch) is what makes
    the =1 side safe."""
    from raft_tpu.ops.fused import FusedCluster

    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    runs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("RAFT_TPU_DONATE", flag)
        c = FusedCluster(4, 3, seed=7)
        runs[flag] = _drain_run(c, rounds=30, chunk=5)
    np.testing.assert_array_equal(runs["0"].events, runs["1"].events)
    assert runs["1"].events.shape[0] > 0
    assert runs["0"].dropped == runs["1"].dropped == 0


# -- scheduler / sharded ----------------------------------------------------


def test_blocked_lanes_are_block_local_and_globalize(monkeypatch):
    from raft_tpu.scheduler import BlockedFusedCluster

    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    bc = BlockedFusedCluster(4, 3, block_groups=2, seed=3)
    streams = [TraceStream() for _ in range(bc.k)]
    for _ in range(4):
        bc.run(5, trace=streams)
    for s in streams:
        s.flush()
    per_block = [s.events for s in streams]
    assert all(ev.shape[0] > 0 for ev in per_block)
    lpb = bc.lanes_per_block
    for ev in per_block:
        assert ev[:, 1].max() < lpb  # block-LOCAL lane stamps
    merged = tasm.merge_block_events(per_block, lpb)
    assert merged[:, 1].max() >= lpb  # block 1's lanes globalized
    assert np.all(np.diff(merged[:, 0]) >= 0)
    # every group elects: a LEADER_ELECTED event per group, globally unique
    # lanes
    el = merged[merged[:, 2] == trdev.LEADER_ELECTED]
    assert len({int(lane) // 3 for lane in el[:, 1]}) == 4


def test_sharded_trace_matches_monolithic(monkeypatch):
    from raft_tpu.ops.fused import FusedCluster
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    mono = _drain_run(FusedCluster(8, 3, seed=0)).events
    sts = _drain_run(ShardedFusedCluster(8, 3, seed=0))
    sh = sts.events
    assert sh.shape == mono.shape and sh.shape[0] > 0
    # per-round multisets identical (within-round shard order may differ
    # from the monolithic lane order)
    for rnd in np.unique(mono[:, 0]):
        a = sorted(map(tuple, mono[mono[:, 0] == rnd].tolist()))
        b = sorted(map(tuple, sh[sh[:, 0] == rnd].tolist()))
        assert a == b, f"round {rnd} events diverge"


# -- serve loop + assembler -------------------------------------------------


def test_serve_loop_traces_lifecycle_and_assembles(monkeypatch):
    from raft_tpu.ops.fused import FusedCluster
    from raft_tpu.serve import ServeLoop

    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    loop = ServeLoop(FusedCluster(2, 3, seed=3))
    loop.bootstrap()
    s = loop.open_session("tenant-tr")
    tickets = [loop.put(s, f"k{i}", f"v{i}") for i in range(4)]
    assert loop.drain()
    assert all(t.done and t.applied for t in tickets)
    assert loop.digest() == loop.twin_digest()

    # lifecycle: one tuple per notified proposal, rounds totally ordered
    lc = [t for t in loop.router.lifecycle if t[1] > 0]
    assert len(lc) >= 4
    for g, submit, inject, commit, notify in lc:
        assert submit <= inject <= commit <= notify

    # device events drained through the loop's own streams
    ev = tasm.merge_block_events(
        [t.events for t in loop.traces], loop.lanes_per_block
    )
    assert (ev[:, 2] == trdev.LEADER_ELECTED).sum() >= 2

    # host plane: phase timings + trace counters through the registry
    snap = loop.metrics_snapshot()
    assert snap["counters"]["step_dispatch_count"] > 0
    assert snap["counters"]["trace_events"] == ev.shape[0]
    assert snap["hists"]["notify_latency_rounds"]["count"] >= 4
    assert snap["counters"]["proposals_notified"] >= 4

    # spans recorded (gated on the recorder being enabled by TRACELOG):
    # the loop's phases, the cluster's jit call and the egress stream's
    # wait and sink, all into the loop's one StepStats bag
    names = {s0 for s0, _, _, _ in loop.spans.spans}
    assert {
        "serve.coalesce", "serve.dispatch", "serve.host_drain",
        "sched.enqueue", "egress.resolve.wait", "egress.sink",
    } <= names
    assert loop.stats.counts["sched.enqueue"] == snap["counters"]["dispatches"]

    # one Perfetto document from all three planes; it must round-trip
    # json and contain all three process tracks
    doc = tasm.from_serve(loop)
    doc = json.loads(json.dumps(doc))
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "i", "X"} <= phases
    pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert {tasm.PID_DEVICE, tasm.PID_SERVE, tasm.PID_HOST} <= pids

    # explain: a per-group round timeline that mentions the election and
    # at least one proposal lifecycle (on the session's own group)
    lines = tasm.explain(
        s.group, events=ev, lifecycle=loop.router.lifecycle, v=loop.v
    )
    assert any("leader_elected" in ln for ln in lines)
    assert any("proposal" in ln for ln in lines)
    rounds = [int(ln[1:6]) for ln in lines]
    assert rounds == sorted(rounds)


def test_serve_loop_untraced_has_no_trace_surface():
    from raft_tpu.ops.fused import FusedCluster
    from raft_tpu.serve import ServeLoop

    loop = ServeLoop(FusedCluster(2, 3, seed=3))
    assert loop.traces is None and loop.spans is None
    assert loop.router.lifecycle is None
    snap = loop.metrics_snapshot()
    assert "trace_events" not in snap["counters"]


# -- satellite units --------------------------------------------------------


def test_step_stats_snapshot_schema():
    from raft_tpu.utils.profiling import StepStats

    st = StepStats()
    with st.span("tick"):
        pass
    snap = st.snapshot()
    assert snap["counters"]["step_tick_count"] == 1
    assert "step_tick_micros" in snap["counters"]
    assert "hist" not in snap  # must not pollute merged histograms
    # a layer's bag keys its own spans without the prefix and the shared
    # ones in full; dots become underscores in the counter names
    st = StepStats("serve")
    with st.span("serve.drain_reads.wait"), st.span("egress.sink"):
        pass
    assert set(st.counts) == {"drain_reads.wait", "egress.sink"}
    assert {"step_drain_reads_wait_count", "step_egress_sink_micros"} <= set(
        st.snapshot()["counters"]
    )


def test_node_host_stats_time_loop_ops():
    from raft_tpu.api.node import NodeHost
    from raft_tpu.api.rawnode import RawNodeBatch
    from raft_tpu.config import Shape

    v = 3
    shape = Shape(n_lanes=v, max_peers=4)
    ids = list(np.arange(1, v + 1, dtype=np.int32))
    peers = np.zeros((v, shape.v), np.int32)
    peers[:, :v] = np.arange(1, v + 1)
    host = NodeHost(RawNodeBatch(shape, ids, peers, seed=1))
    try:
        host.node(0).campaign()
        host.node(0).status()
        ct = host.metrics_snapshot()["counters"]
        assert ct["step_campaign_count"] == 1
        assert ct["step_status_count"] == 1
        assert ct["step_campaign_micros"] >= 0
    finally:
        host.stop()


def test_warn_rate_limited(caplog):
    import logging as pylogging

    from raft_tpu.logging import (
        reset_warn_rate_limits,
        warn_rate_limited,
    )

    reset_warn_rate_limits()
    with caplog.at_level(pylogging.WARNING, logger="raft_tpu"):
        warn_rate_limited("k1", 60.0, "truncated at %s", 5)
        warn_rate_limited("k1", 60.0, "truncated at %s", 6)  # suppressed
        warn_rate_limited("k2", 60.0, "other %s", 1)  # distinct key passes
    msgs = [r.getMessage() for r in caplog.records]
    assert msgs == ["truncated at 5", "other 1"]
    reset_warn_rate_limits()
    with caplog.at_level(pylogging.WARNING, logger="raft_tpu"):
        warn_rate_limited("k1", 60.0, "truncated at %s", 7)  # reset passes
    assert caplog.records[-1].getMessage() == "truncated at 7"

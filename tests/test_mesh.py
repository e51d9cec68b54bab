"""Mesh-blocked multi-chip driver tests on the virtual 8-device CPU mesh:
the sharded x blocked composition (parallel/mesh.py MeshBlockedCluster)
must be bit-invisible against the single-chip blocked scheduler, with the
per-(shard, block) stream payloads byte-identical after host-side merge."""

import hashlib

import jax
import numpy as np
import pytest

from raft_tpu.config import Shape
from raft_tpu.parallel.mesh import MeshBlockedCluster
from raft_tpu.scheduler import BlockedFusedCluster, BlockPlan

DIGEST_FIELDS = (
    "term", "vote", "lead", "state", "committed", "last",
    "log_term", "error_bits",
)


# the largest shard_map programs abort XLA's CPU executable serializer
pytestmark = pytest.mark.usefixtures("no_persistent_cache")


@pytest.fixture(scope="module")
def devices():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs 8 virtual devices")
    return d[:8]


def _set_env(monkeypatch, **kw):
    """Pin the full knob surface (test_diet.py idiom): unset keys are
    DELETED so a test never inherits a stray RAFT_TPU_* from the shell."""
    knobs = (
        "DIET", "ENGINE", "PALLAS_ROUNDS", "DONATE",
        "TRACELOG", "METRICS", "CHAOS",
    )
    for k in knobs:
        v = kw.pop(k.lower(), None)
        if v is None:
            monkeypatch.delenv(f"RAFT_TPU_{k}", raising=False)
        else:
            monkeypatch.setenv(f"RAFT_TPU_{k}", str(v))
    assert not kw, kw


def _block_shape(bg, v):
    """Per-BLOCK shape: every resident block (and its sharded twin) runs
    the same bg*v-lane program."""
    return Shape(
        n_lanes=bg * v, max_peers=v, log_window=16, max_msg_entries=2,
        max_inflight=2, max_read_index=2,
    )


def _digest(c) -> str:
    cols = c.state_columns(*DIGEST_FIELDS)
    h = hashlib.sha256()
    for name in DIGEST_FIELDS:
        h.update(np.ascontiguousarray(cols[name]).tobytes())
    return h.hexdigest()


def _drive(c, g, v):
    """Shared workload: elections, steady-state commits, then one ops
    injection (a leadership transfer in the LAST group, so at K=2 the
    global-lane prepare_ops slice lands in block 1)."""
    c.run(40)
    c.run(10, auto_propose=True, auto_compact_lag=8)
    c.run(1, ops=c.ops(transfer_to={(g - 1) * v: 2}), do_tick=False)
    c.run(10, auto_propose=True, auto_compact_lag=8)
    c.check_no_errors()
    return c


# -- satellite: stream-list uniqueness (host-only, no dispatch) ------------


def test_stream_list_uniqueness_rejected(devices):
    from raft_tpu.runtime.wal import WalStream

    plan = BlockPlan(16, 3, 8)
    w = WalStream()
    with pytest.raises(ValueError, match="same"):
        plan.check_streams([w, w], "wal", "WalStream")
    # distinct objects (and a single-block list) still pass
    assert len(plan.check_streams([WalStream(), WalStream()], "wal", "W")) == 2

    # the mesh driver rejects the same aliasing before any dispatch
    c = MeshBlockedCluster(
        16, 3, block_groups=8, devices=devices, seed=3,
        shape=_block_shape(8, 3),
    )
    with pytest.raises(ValueError, match="same"):
        c.run(1, wal=[w, w])


# -- bit-identity against the single-chip blocked scheduler ----------------


def test_mesh_matches_blocked_bitwise(monkeypatch, devices):
    """K=2 blocks of 8 groups sharded over 8 devices vs the monolithic
    BlockedFusedCluster: same seeds, same sweep, bit-identical columns."""
    _set_env(monkeypatch)
    g, v, bg = 16, 3, 8
    mono = _drive(
        BlockedFusedCluster(g, v, block_groups=bg, seed=7,
                            shape=_block_shape(bg, v)),
        g, v,
    )
    mesh = _drive(
        MeshBlockedCluster(g, v, block_groups=bg, devices=devices, seed=7,
                           shape=_block_shape(bg, v)),
        g, v,
    )
    assert mesh.k == 2 and mesh.n_shards == 8
    mc, bc = mesh.state_columns(*DIGEST_FIELDS), mono.state_columns(*DIGEST_FIELDS)
    for f in DIGEST_FIELDS:
        np.testing.assert_array_equal(mc[f], bc[f], err_msg=f)
    assert mesh.leader_count() == g
    np.testing.assert_array_equal(mesh.leader_lanes(), mono.leader_lanes())
    assert mesh.total_committed() == mono.total_committed()


def test_mesh_k1_matches_blocked_bitwise(monkeypatch, devices):
    """The K=1 fast path (one sharded block) against its monolithic twin."""
    _set_env(monkeypatch)
    g, v = 8, 3
    mono = _drive(
        BlockedFusedCluster(g, v, block_groups=g, seed=5,
                            shape=_block_shape(g, v)),
        g, v,
    )
    mesh = _drive(
        MeshBlockedCluster(g, v, block_groups=g, devices=devices, seed=5,
                           shape=_block_shape(g, v)),
        g, v,
    )
    assert mesh.k == 1
    assert _digest(mesh) == _digest(mono)


def test_mesh_stream_payloads_match_blocked(monkeypatch, devices):
    """WAL deltas and egress bundles addressed per (shard, block) must
    reassemble byte-identically to the monolithic per-block payloads, and
    the stacked trace-ring drain must keep per-shard batches."""
    from raft_tpu.runtime.egress import EgressStream, merge_delta_bundles
    from raft_tpu.runtime.trace import TraceStream
    from raft_tpu.runtime.wal import WalStream, merge_shard_deltas

    _set_env(monkeypatch, tracelog="1")
    g, v, bg = 16, 3, 8

    def settle(c):
        c.run(40)
        c.run(10, auto_propose=True, auto_compact_lag=8)
        return c

    mono = settle(BlockedFusedCluster(g, v, block_groups=bg, seed=17,
                                      shape=_block_shape(bg, v)))
    mesh = settle(MeshBlockedCluster(g, v, block_groups=bg, devices=devices,
                                     seed=17, shape=_block_shape(bg, v)))

    # one streamed sweep on each arm
    m_wal, m_eg = {}, {}
    wal = mesh.wal_streams(
        sink=lambda b, s, seq, d: m_wal.setdefault(b, {}).__setitem__(s, d)
    )
    egress = mesh.egress_streams(
        sink=lambda b, s, seq, bn: m_eg.setdefault(b, {}).__setitem__(s, bn)
    )
    traces = mesh.trace_streams()
    mesh.run(1, auto_propose=True, auto_compact_lag=8, wal=wal,
             egress=egress, trace=traces)

    b_wal, b_eg = {}, {}
    mwal = [
        WalStream(sink=lambda seq, d, b=i: b_wal.__setitem__(b, d))
        for i in range(mono.k)
    ]
    megress = [
        EgressStream(sink=lambda seq, bn, b=i: b_eg.__setitem__(b, bn))
        for i in range(mono.k)
    ]
    mtraces = [TraceStream() for _ in range(mono.k)]
    mono.run(1, auto_propose=True, auto_compact_lag=8, wal=mwal,
             egress=megress, trace=mtraces)
    for st in wal + egress + traces + mwal + megress + mtraces:
        st.flush()

    S = mesh.n_shards
    for b in range(mesh.k):
        merged = merge_shard_deltas([m_wal[b][s] for s in range(S)])
        for f in WalStream.FIELDS:
            assert (
                np.ascontiguousarray(merged[f]).tobytes()
                == np.ascontiguousarray(b_wal[b][f]).tobytes()
            ), (b, f)
        mb = merge_delta_bundles([m_eg[b][s] for s in range(S)])
        for f in ("changed", "active", "term", "lead", "state", "committed",
                  "applied", "last", "rs_count"):
            assert (
                np.ascontiguousarray(getattr(mb, f)).tobytes()
                == np.ascontiguousarray(getattr(b_eg[b], f)).tobytes()
            ), (b, f)

    # per-shard trace batches: every resolved event lives in exactly one
    # shard batch, and the union equals the merged stream
    for ts in traces:
        parts = [ts.shard_events(s) for s in range(S)]
        assert sum(p.shape[0] for p in parts) == ts.events.shape[0]
        if ts.events.shape[0]:
            assert any(p.shape[0] for p in parts)
    # event streams match when neither arm dropped (full row sort: the
    # cross-shard merge interleaves same-round events by shard index)
    if all(t.dropped == 0 for t in traces + mtraces):
        def tdig(tss):
            h = hashlib.sha256()
            for ts in tss:
                ev = ts.events
                ev = ev[np.lexsort(ev.T[::-1])]
                h.update(np.ascontiguousarray(ev).tobytes())
            return h.hexdigest()

        assert tdig(traces) == tdig(mtraces)


# -- satellite: sharded diet auto-rebase -----------------------------------


def test_sharded_diet_auto_rebase_crosses_threshold(monkeypatch, devices):
    """The packed-carry overflow guard must fire from the SHARDED dispatch
    path (PR 9 wired it only into FusedCluster.run): fast-forward the
    batch into the uint16 danger zone, keep dispatching under shard_map,
    and the automatic pre-overflow rebase lands the indexes back down —
    never ERR_DIET_OVERFLOW's clamp-and-flag."""
    from raft_tpu.ops.fused import FusedCluster
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    _set_env(monkeypatch, diet="1")
    g, v = 8, 3
    sh = ShardedFusedCluster(g, v, devices=devices, seed=7,
                             shape=_block_shape(g, v))
    sh.run(40)
    sh.run(16, auto_propose=True, auto_compact_lag=8)
    # negative delta = the live-rebase jit fast-forwarding the whole batch
    # toward the 2^16 guard (test_diet.py _overflow_twin recipe)
    sh.rebase_groups(range(g), delta=-(48 * 1024))
    pre = int(np.asarray(sh.host_state().last).max())
    assert pre >= 48 * 1024
    sh.run(16, auto_propose=True, auto_compact_lag=8)
    post = int(np.asarray(sh.host_state().last).max())
    assert post < FusedCluster.DIET_REBASE_AT  # auto-rebase fired
    sh.check_no_errors()  # ERR_DIET_OVERFLOW never set


# -- serving frontend rides the mesh unchanged -----------------------------


def test_serve_loop_on_mesh_round_trip(monkeypatch, devices):
    """ServeLoop's cluster-protocol duck test: the mesh driver exposes the
    blocked driving surface, so puts/gets route through per-block egress
    sinks back to the right global groups."""
    from raft_tpu.serve.loop import Rejected, ServeLoop

    _set_env(monkeypatch)
    sl = ServeLoop(
        MeshBlockedCluster(4, 3, block_groups=2, devices=devices[:2], seed=5)
    )
    assert sl.blocked and sl.k == 2
    sl.bootstrap()
    ss = [sl.open_session(f"mt{i}") for i in range(4)]
    assert len({s.group for s in ss}) >= 2  # spans blocks
    ts = []
    for i in range(4):
        for s in ss:
            t = sl.put(s, f"{s.tenant}/{i}", f"{s.tenant}-{i}")
            assert not isinstance(t, Rejected)
            ts.append(t)
    assert sl.drain(300)
    assert all(t.done for t in ts)
    rts = [sl.get(s, f"{s.tenant}/3") for s in ss]
    assert sl.drain(300)
    for s, rt in zip(ss, rts):
        assert rt.done and rt.value == f"{s.tenant}-3"

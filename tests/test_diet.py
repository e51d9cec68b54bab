"""Diet-v2 packed carry (ISSUE 9): pack_state/pack_fabric narrow the
resident scan carry below the slim layout — bool masks become bitset
words, rebased index/term columns become uint16, canonical-id columns
int8 — behind the RAFT_TPU_DIET knob (default OFF, read at cluster
construction).

The contract under test is the same one test_slim.py pins for the slim
layer, one level down: packing is STORAGE-ONLY. Every trajectory digest
must be bit-identical diet on/off across engines (XLA scan, pallas K=1,
pallas K>1 in-kernel replay), under donation on/off, and every
host-facing byte stream (WAL, egress, trace) must be byte-identical —
the packed carry may never leak through a read path. Overflow is never
silent: out-of-range values clamp AND flag ERR_DIET_OVERFLOW, and the
automatic pre-overflow rebase (FusedCluster._diet_headroom) re-keys the
index space before a packed uint16 column can reach its edge.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import Shape
from raft_tpu.ops.fused import (
    FusedCluster,
    empty_fabric,
    fabric_diet_overflow,
    is_packed_fabric,
    pack_fabric,
    slim_fabric,
    unpack_fabric,
)
from raft_tpu.state import (
    ERR_DIET_OVERFLOW,
    PACK_BITSET,
    PACK_I8,
    PACK_I16,
    PACK_U16,
    bitset_dtype,
    is_packed,
    make_lane_config,
    pack_state,
    slim_state,
    unpack_state,
)

G, V = 8, 3

DIGEST_FIELDS = (
    "term", "vote", "lead", "state", "committed", "last",
    "log_term", "error_bits",
)


def _digest(st) -> str:
    h = hashlib.sha256()
    for name in DIGEST_FIELDS:
        h.update(np.ascontiguousarray(np.asarray(getattr(st, name))).tobytes())
    return h.hexdigest()


def _assert_trees_equal(a, b, msg=""):
    """Bit-exact leaf equality INCLUDING dtypes (a uint16 column that
    merely compares equal to an int32 one is still a layout leak)."""
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, _ = jax.tree_util.tree_flatten_with_path(b)
    assert len(la) == len(lb), msg
    for (path, x), (_, y) in zip(la, lb):
        where = f"{msg}{jax.tree_util.keystr(path)}"
        assert x.dtype == y.dtype, (where, x.dtype, y.dtype)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=where)


def _set_env(monkeypatch, **kw):
    """Pin the full knob surface: unset keys are DELETED so a test never
    inherits a stray RAFT_TPU_* from the invoking shell."""
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


def _drive(c):
    """One shared workload recipe so every twin in this module reuses the
    same jit cache entries (per dtype-signature) — elections, proposals,
    compaction."""
    c.run(40)
    c.run(24, auto_propose=True, auto_compact_lag=8)
    c.check_no_errors()
    return c


def _carry_bytes(c) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(c.state)) + sum(
        x.nbytes for x in jax.tree.leaves(c.fab)
    )


def _small_shape(g=G, v=V):
    return Shape(
        n_lanes=g * v, max_peers=v, log_window=16, max_msg_entries=2,
        max_inflight=3, max_read_index=2,
    )


def _random_slim_state(seed=0, g=3, v=3):
    """A slim-canonical state with every PACKABLE field randomized across
    its full in-range span (joint-config corners, negative i8 ids,
    ro_acks at every [N, R, V] cell) — values a live trajectory would
    rarely visit all at once."""
    c = FusedCluster(g, v, seed=seed, shape=_small_shape(g, v))
    st = slim_state(c.state)
    rng = np.random.default_rng(seed)
    upd = {}
    for f in PACK_U16:
        x = np.asarray(getattr(st, f))
        upd[f] = jnp.asarray(rng.integers(0, 1 << 16, x.shape).astype(x.dtype))
    for f in PACK_I8:
        x = np.asarray(getattr(st, f))
        upd[f] = jnp.asarray(rng.integers(-128, 128, x.shape).astype(x.dtype))
    for f in PACK_I16:
        x = np.asarray(getattr(st, f))
        upd[f] = jnp.asarray(rng.integers(0, 1 << 15, x.shape).astype(x.dtype))
    for f in PACK_BITSET:
        x = np.asarray(getattr(st, f))
        upd[f] = jnp.asarray(rng.integers(0, 2, x.shape).astype(bool))
    return dataclasses.replace(st, **upd)


# -- pack/unpack round trips ----------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pack_unpack_round_trip_randomized(seed):
    st = _random_slim_state(seed)
    _assert_trees_equal(unpack_state(pack_state(st)), st, "roundtrip")


def test_pack_is_idempotent_and_detected():
    st = _random_slim_state(3)
    p = pack_state(st)
    assert not is_packed(st) and is_packed(p)
    _assert_trees_equal(pack_state(p), p, "pack∘pack")
    u = unpack_state(p)
    assert not is_packed(u)
    _assert_trees_equal(unpack_state(u), u, "unpack∘unpack")


def test_packed_layout_is_actually_narrow():
    st = _random_slim_state(4)
    p = pack_state(st)
    n, v = np.asarray(st.prs_id).shape
    r = np.asarray(st.ro_acks).shape[1]
    for f in PACK_U16:
        assert getattr(p, f).dtype == jnp.uint16, f
    for f in PACK_I8:
        assert getattr(p, f).dtype == jnp.int8, f
    for f in PACK_I16:
        assert getattr(p, f).dtype == jnp.int16, f
    w = bitset_dtype(v)
    for f in PACK_BITSET:
        col = getattr(p, f)
        assert col.dtype == w, f
        assert col.shape == ((n, r) if f == "ro_acks" else (n,)), f
    slim_bytes = sum(x.nbytes for x in jax.tree.leaves(st))
    packed_bytes = sum(x.nbytes for x in jax.tree.leaves(p))
    assert packed_bytes < 0.7 * slim_bytes, (packed_bytes, slim_bytes)


def test_bitset_dtype_steps():
    assert bitset_dtype(1) == jnp.uint8 and bitset_dtype(8) == jnp.uint8
    assert bitset_dtype(9) == jnp.uint16 and bitset_dtype(16) == jnp.uint16
    assert bitset_dtype(17) == jnp.uint32 and bitset_dtype(32) == jnp.uint32


def test_pack_overflow_clamps_and_flags():
    """Out-of-range values must clamp AND raise ERR_DIET_OVERFLOW on the
    offending lane only — never wrap silently."""
    st = _random_slim_state(5)
    last = np.asarray(st.last).copy()
    last[:] = 100  # in-range baseline everywhere
    last[0] = 70000  # above uint16
    last[1] = -7  # below uint16
    st = dataclasses.replace(st, last=jnp.asarray(last),
                             error_bits=jnp.zeros_like(st.error_bits))
    p = pack_state(st)
    eb = np.asarray(p.error_bits)
    assert eb[0] & ERR_DIET_OVERFLOW and eb[1] & ERR_DIET_OVERFLOW
    assert (eb[2:] == 0).all()
    u = np.asarray(unpack_state(p).last)
    assert u[0] == 65535 and u[1] == 0 and (u[2:] == 100).all()


def test_fabric_pack_round_trip_and_overflow():
    c = _drive(FusedCluster(G, V, seed=11, shape=_small_shape()))
    fab = slim_fabric(c.fab)
    assert not is_packed_fabric(fab)
    p = pack_fabric(fab)
    assert is_packed_fabric(p)
    assert not np.asarray(fabric_diet_overflow(fab)).any()
    _assert_trees_equal(unpack_fabric(p), fab, "fabric")
    _assert_trees_equal(pack_fabric(p), p, "fabric pack∘pack")
    # packed fabric reports no overflow by construction (already clamped)
    assert not np.asarray(fabric_diet_overflow(p)).any()
    # an out-of-range replication index flags its lane
    n = G * V
    bad = empty_fabric(n, V, c.shape.max_msg_entries)
    idx = np.zeros(np.asarray(bad.rep.index).shape, np.int32)
    idx[0] = 70000
    bad = dataclasses.replace(
        bad, rep=dataclasses.replace(bad.rep, index=jnp.asarray(idx))
    )
    ovf = np.asarray(fabric_diet_overflow(bad))
    assert ovf[0] and not ovf[1:].any()


# -- config-time bound enforcement (satellite 2) --------------------------


@pytest.mark.parametrize(
    "kw",
    [
        {"max_peers": 0},
        {"max_peers": 33},
        {"log_window": 1 << 15},
        {"max_entry_bytes": 0},
        {"max_entry_bytes": 40000},
        {"max_inflight": 0},
        {"max_inflight": 128},
        {"max_read_index": 0},
        {"max_read_index": 128},
        {"max_msg_entries": 0},
        {"max_msg_entries": 128},
    ],
)
def test_shape_rejects_unpackable_bounds(kw):
    base = dict(n_lanes=12, max_peers=3, log_window=16, max_msg_entries=2,
                max_inflight=2, max_read_index=2)
    base.update(kw)
    with pytest.raises(ValueError):
        Shape(**base)


def test_lane_config_rejects_unpackable_overrides():
    shape = _small_shape(2, 3)
    with pytest.raises(ValueError):
        make_lane_config(shape, max_inflight=[1, 2, 3, 4, 5, 128])
    with pytest.raises(ValueError):
        make_lane_config(shape, max_inflight=0)
    with pytest.raises(ValueError):
        make_lane_config(shape, election_tick=1 << 15)
    with pytest.raises(ValueError):
        make_lane_config(shape, heartbeat_tick=0)


# -- trajectory digests: diet must be invisible ---------------------------


def _twin(monkeypatch, diet, **env):
    _set_env(monkeypatch, diet=diet, **env)
    return _drive(FusedCluster(G, V, seed=11, shape=_small_shape()))


def test_xla_digest_identity_and_shrink(monkeypatch):
    off = _twin(monkeypatch, "0")
    on = _twin(monkeypatch, "1")
    assert not is_packed(off.state) and is_packed(on.state)
    assert is_packed_fabric(on.fab)
    assert (np.asarray(on.host_state().committed) > 0).any()
    assert _digest(on.host_state()) == _digest(off.host_state())
    # the ISSUE-9 acceptance floor on the resident carry
    assert _carry_bytes(on) <= 0.7 * _carry_bytes(off)
    # host_state() is the slim-canonical view: same leaves either way
    _assert_trees_equal(on.host_state(), off.host_state(), "host_state")


def test_pallas_packed_replay_bit_identity(monkeypatch):
    """The pallas kernel must cross the SAME packed storage boundary as
    the XLA scan: load_carry on entry, the in-kernel store/load replay
    between fused rounds at K>1, store_carry on writeback — every leaf
    bit-identical to XLA on a PACKED carry. Kernel-level like
    test_pallas_round's megakernel tests (a cluster-scale K>1 program is
    a multi-minute interpret compile on 1-core CI), 9 rounds at K=4 so
    both the full-K megakernel and the remainder-tail program run. Trace
    stays OFF — RAFT_TPU_TRACELOG forces K=1, so this is the only
    coverage of the K>1 in-kernel packed replay."""
    from raft_tpu.ops import fused as fmod
    from raft_tpu.ops import pallas_round as plr

    _set_env(monkeypatch, diet="1")
    g, v = 4, 3
    shape = Shape(n_lanes=g * v, max_peers=v, log_window=8,
                  max_msg_entries=2, max_inflight=2, max_read_index=2)
    c = FusedCluster(g, v, seed=7, shape=shape)
    assert is_packed(c.state) and is_packed_fabric(c.fab)
    kw = dict(
        v=v, n_rounds=9, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=None, chaos=None,
    )
    ref = fmod._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None, **kw
    )
    k1 = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=2 * v, interpret=True, **kw
    )
    k4 = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=2 * v, interpret=True, rounds_per_call=4, **kw
    )
    # the outputs are still PACKED (store_carry ran at the boundary):
    # compare the raw packed leaves, dtypes included
    assert is_packed(ref[0]) and is_packed(k1[0]) and is_packed(k4[0])
    _assert_trees_equal(k1[0], ref[0], "state K=1")
    _assert_trees_equal(k4[0], ref[0], "state K=4")
    _assert_trees_equal(k1[1], ref[1], "fabric K=1")
    _assert_trees_equal(k4[1], ref[1], "fabric K=4")


def test_donation_cache_fence_digest_identity(monkeypatch):
    """Donated packed carries under the warm compile-cache fence: both
    donation modes land on the diet-off trajectory bit-for-bit."""
    base = _twin(monkeypatch, "0")
    for donate in ("0", "1"):
        c = _twin(monkeypatch, "1", donate=donate)
        assert _digest(c.host_state()) == _digest(base.host_state()), donate


def test_planes_on_digest_identity(monkeypatch):
    """Metrics + chaos + trace all live: every plane reads the carry
    through the boundary, none may perturb the trajectory."""
    base = _twin(monkeypatch, "0")
    on = _twin(monkeypatch, "1", metrics="1", chaos="1", tracelog="1")
    assert on.metrics is not None and on.chaos is not None
    assert on.trace is not None
    assert _digest(on.host_state()) == _digest(base.host_state())


# -- automatic pre-overflow rebase ----------------------------------------


def _overflow_twin(monkeypatch, diet):
    _set_env(monkeypatch, diet=diet)
    c = FusedCluster(4, 3, seed=7, shape=_small_shape(4, 3))
    c.run(40)
    c.run(16, auto_propose=True, auto_compact_lag=8)
    # fast-forward the whole batch to the uint16 danger zone (negative
    # delta = the same live-rebase jit the i32 overflow recovery uses)
    c.rebase_groups(range(4), delta=-(48 * 1024))
    c.run(16, auto_propose=True, auto_compact_lag=8)
    mid_max = int(np.asarray(c.host_state().last).max())
    # normalize both twins into the canonical index space: the diet twin's
    # automatic rebase was window-aligned, so one min-snap rebase lands
    # both on identical absolute indexes
    c.rebase_groups(range(4))
    c.check_no_errors()
    return c, mid_max


def test_auto_rebase_triggers_before_uint16_overflow(monkeypatch):
    off, off_max = _overflow_twin(monkeypatch, "0")
    on, on_max = _overflow_twin(monkeypatch, "1")
    # the slim twin kept running in the danger zone; the packed twin
    # rebased down before dispatching (and never wrapped: error_bits == 0
    # was asserted inside the twin)
    assert off_max >= 48 * 1024
    assert on_max < FusedCluster.DIET_REBASE_AT
    assert _digest(on.host_state()) == _digest(off.host_state())


# -- host-facing byte streams (satellite 6) -------------------------------


def _stream_run(monkeypatch, diet, tracelog=None):
    from raft_tpu.runtime.egress import EgressStream
    from raft_tpu.runtime.trace import TraceStream
    from raft_tpu.runtime.wal import WalStream

    _set_env(monkeypatch, diet=diet, tracelog=tracelog)
    wal_out, egr_out = [], []
    wal = WalStream(sink=lambda bid, d: wal_out.append((bid, d)))
    egr = EgressStream(sink=lambda bid, d: egr_out.append((bid, d)))
    trc = TraceStream()
    c = FusedCluster(G, V, seed=5, shape=_small_shape())
    for _ in range(4):
        c.run(10, auto_propose=True, auto_compact_lag=8,
              wal=wal, egress=egr, trace=trc)
    wal.flush()
    egr.flush()
    trc.flush()
    c.check_no_errors()
    return wal_out, egr_out, trc


def test_wal_and_egress_streams_byte_identical(monkeypatch):
    """The WAL streams _wal_view() (slim-canonical) and the egress bundle
    i32-casts every cursor read: both planes must emit the EXACT bytes —
    values and dtypes — diet on or off."""
    wal_off, egr_off, _ = _stream_run(monkeypatch, "0")
    wal_on, egr_on, _ = _stream_run(monkeypatch, "1")
    assert len(wal_off) == len(wal_on) == 4
    for (b0, d0), (b1, d1) in zip(wal_off, wal_on):
        assert b0 == b1 and d0.keys() == d1.keys()
        for f in d0:
            assert d0[f].dtype == d1[f].dtype, f
            np.testing.assert_array_equal(d0[f], d1[f], err_msg=f)
    assert len(egr_off) == len(egr_on) > 0
    for (b0, d0), (b1, d1) in zip(egr_off, egr_on):
        assert b0 == b1
        for f, x, y in zip(type(d0)._fields, d0, d1):
            assert x.dtype == y.dtype, f
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=f
            )


def test_trace_stream_byte_identical(monkeypatch):
    _, _, t_off = _stream_run(monkeypatch, "0", tracelog="1")
    _, _, t_on = _stream_run(monkeypatch, "1", tracelog="1")
    ev_off, ev_on = t_off.events, t_on.events
    assert ev_off.shape[0] > 0
    assert ev_off.dtype == ev_on.dtype
    np.testing.assert_array_equal(ev_off, ev_on)


# -- WAL restore and membership changes under diet ------------------------


def test_restore_from_wal_under_diet(monkeypatch):
    """A WAL delta (slim-canonical bytes) restores into a PACKED carry
    when the restoring process runs diet-on — and the restored block's
    persistent image matches the delta exactly through host_state()."""
    from raft_tpu.runtime.wal import WalStream

    _set_env(monkeypatch, diet="1")
    sink = {}
    wal = WalStream(sink=lambda bid, d: sink.__setitem__(bid, d))
    c = FusedCluster(G, V, seed=5, shape=_small_shape())
    for _ in range(4):
        c.run(10, auto_propose=True, auto_compact_lag=8, wal=wal)
    wal.flush()
    last = sink[max(sink)]
    for f in WalStream.FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(c.host_state(), f)), last[f], err_msg=f
        )
    b = FusedCluster.restore_from_wal(G, V, last, seed=99,
                                      shape=_small_shape())
    assert is_packed(b.state)
    for f in WalStream.FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(b.host_state(), f)), last[f], err_msg=f
        )
    # the restored packed block keeps running
    b.run(20, auto_propose=True, auto_compact_lag=8)
    b.check_no_errors()


def _confchange_twin(monkeypatch, diet):
    from raft_tpu import confchange as ccm

    _set_env(monkeypatch, diet=diet)
    g, v = 4, 4
    shape = Shape(n_lanes=g * v, max_peers=v, log_window=32,
                  max_msg_entries=2, max_inflight=2)
    c = FusedCluster(g, v, seed=7, shape=shape, learner_ids=(4,))
    hups = {lane: True for lane in range(0, g * v, v)}
    c.run(1, ops=c.ops(hup=hups), do_tick=False)
    c.run(3, auto_propose=True)
    assert len(c.leader_lanes()) == g
    ch = c.conf_changer()
    cc = ccm.ConfChange(type=int(ccm.ConfChangeType.ADD_NODE), node_id=4)
    assert len(ch.propose(cc)) == g
    ch.settle(auto_propose=True)
    c.run(6, auto_propose=True)
    c.check_no_errors()
    return c


def test_confchange_digest_identity(monkeypatch):
    """The membership driver reads/writes the carry via host_state() /
    adopt_state(): a learner promotion lands bit-identically packed or
    slim, and the promoted config is visible through the boundary."""
    off = _confchange_twin(monkeypatch, "0")
    on = _confchange_twin(monkeypatch, "1")
    assert is_packed(on.state)
    assert _digest(on.host_state()) == _digest(off.host_state())
    hs = on.host_state()
    vin = np.asarray(hs.voters_in[0])
    ids = np.asarray(hs.prs_id[0])
    assert {int(i) for i in ids[vin] if i} == {1, 2, 3, 4}


# -- multi-block / multi-shard composition --------------------------------


def _blocked_twin(monkeypatch, diet):
    from raft_tpu.scheduler import BlockedFusedCluster

    _set_env(monkeypatch, diet=diet)
    c = BlockedFusedCluster(4, 3, block_groups=2, seed=3,
                            shape=_small_shape(2, 3))
    for _ in range(3):
        c.run(8, auto_propose=True, auto_compact_lag=8)
    c.check_no_errors()
    return c


def test_blocked_scheduler_digest_identity(monkeypatch):
    off = _blocked_twin(monkeypatch, "0")
    on = _blocked_twin(monkeypatch, "1")
    assert all(is_packed(b.state) for b in on.blocks)
    cols_off = off.state_columns(*DIGEST_FIELDS)
    cols_on = on.state_columns(*DIGEST_FIELDS)
    for f in DIGEST_FIELDS:
        assert cols_off[f].dtype == cols_on[f].dtype, f
        np.testing.assert_array_equal(cols_off[f], cols_on[f], err_msg=f)
    assert on.total_committed() == off.total_committed() > 0


def _sharded_twin(monkeypatch, diet):
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    _set_env(monkeypatch, diet=diet)
    sh = ShardedFusedCluster(n_groups=8, n_voters=3, seed=13)
    sh.run(40)
    sh.run(16, auto_propose=True, auto_compact_lag=8)
    sh.check_no_errors()
    return sh


def test_sharded_digest_identity(monkeypatch, no_persistent_cache):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    off = _sharded_twin(monkeypatch, "0")
    on = _sharded_twin(monkeypatch, "1")
    assert is_packed(on.inner.state)
    assert _digest(on.host_state()) == _digest(off.host_state())

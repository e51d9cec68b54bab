"""Paged entry log (ISSUE 11): ops/paged.py splits the `[N, W]` log
window into a small resident tail per lane plus a shared HBM page pool
addressed through per-lane page tables — behind the RAFT_TPU_PAGED knob
(default OFF, read at cluster construction).

The contract under test mirrors test_diet.py one layer down the storage
stack: paging is STORAGE-ONLY and DISPATCH-granular. Every trajectory
digest must be bit-identical paged on/off across engines (XLA scan,
pallas K=1, pallas K>1 in-kernel replay), stacked with diet on/off, and
every host-facing byte stream (WAL, egress, trace) must stay
byte-identical — page ids may never leak into values. Geometry errors
are config-time ValueErrors from every cluster constructor (raise, never
fall back), and pool exhaustion is never silent: overflow pages drop
(clamp), ERR_PAGE_EXHAUSTED flags the lane, and the host metrics plane
sees the exhaustion counter plus a rate-limited warning.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import Shape
from raft_tpu.ops import log as lg
from raft_tpu.ops import paged as pgmod
from raft_tpu.ops.fused import FusedCluster
from raft_tpu.state import ERR_PAGE_EXHAUSTED, is_packed, slim_state

G, V = 8, 3

DIGEST_FIELDS = (
    "term", "vote", "lead", "state", "committed", "last",
    "log_term", "log_type", "log_bytes", "error_bits",
)


def _digest(st) -> str:
    h = hashlib.sha256()
    for name in DIGEST_FIELDS:
        h.update(np.ascontiguousarray(np.asarray(getattr(st, name))).tobytes())
    return h.hexdigest()


def _assert_trees_equal(a, b, msg=""):
    """Bit-exact leaf equality INCLUDING dtypes (test_diet.py idiom)."""
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
        "DIET", "ENGINE", "PALLAS_ROUNDS", "PALLAS_TILE", "DONATE",
        "TRACELOG", "METRICS", "CHAOS", "TIER",
        "PAGED", "PAGE_WINDOW", "PAGE_ENTRIES", "POOL_PAGES",
        "PAGED_INKERNEL",
    )
    for k in knobs:
        v = kw.pop(k.lower(), None)
        if v is None:
            monkeypatch.delenv(f"RAFT_TPU_{k}", raising=False)
        else:
            monkeypatch.setenv(f"RAFT_TPU_{k}", str(v))
    assert not kw, kw


def _drive(c):
    """The test_diet.py workload recipe (same jit cache entries per
    dtype signature): elections, proposals, compaction."""
    c.run(40)
    c.run(24, auto_propose=True, auto_compact_lag=8)
    c.check_no_errors()
    return c


def _small_shape(g=G, v=V, **page_kw):
    return Shape(
        n_lanes=g * v, max_peers=v, log_window=16, max_msg_entries=2,
        max_inflight=3, max_read_index=2, **page_kw,
    )


def _random_logged_state(seed=0, g=G, v=V):
    """A slim-canonical state with randomized ragged log depth: every
    (snap, last] span from empty to the full window, garbage values in
    the stale slots (scrub must hide them)."""
    c = FusedCluster(g, v, seed=seed, shape=_small_shape(g, v))
    st = slim_state(c.state)
    n, w = np.asarray(st.log_term).shape
    rng = np.random.default_rng(seed)
    last = rng.integers(0, 50, size=n).astype(np.int32)
    snap = np.maximum(0, last - rng.integers(0, w + 1, size=n)).astype(np.int32)
    return dataclasses.replace(
        st,
        last=jnp.asarray(last),
        snap_index=jnp.asarray(snap),
        log_term=jnp.asarray(rng.integers(1, 9, (n, w)).astype(np.int32)),
        log_type=jnp.asarray(rng.integers(0, 3, (n, w)).astype(np.int32)),
        log_bytes=jnp.asarray(rng.integers(0, 100, (n, w)).astype(np.int32)),
    )


# -- page_out / page_in round trips (host-boundary twins) ------------------


@pytest.mark.parametrize("segs", [1, 2, 4])
def test_page_round_trip_exact(segs):
    """page_out then page_in reproduces the scrubbed full window exactly,
    with page ids local to each segment's sub-pool (the shard_map
    semantics the segmented host twins must reproduce)."""
    st = _random_logged_state(0)
    plan = pgmod.validate_page_plan(_small_shape(), G * V)
    canon = lg.scrub_stale_slots(st)
    res, pgd = pgmod.page_out_host(canon, pgmod.init_paged(plan, st), segs)
    assert res.log_term.shape == (G * V, plan.w_res)
    sub = pgd.pool_term.shape[0] // segs
    assert int(np.asarray(pgd.pt).max()) < sub, "page id escaped its sub-pool"
    full, pgd2 = pgmod.page_in_host(res, pgd, segs)
    _assert_trees_equal(
        (full.log_term, full.log_type, full.log_bytes, full.last),
        (canon.log_term, canon.log_type, canon.log_bytes, canon.last),
        f"roundtrip segs={segs}",
    )
    assert not (np.asarray(full.error_bits) & ERR_PAGE_EXHAUSTED).any()
    # faults counted one per mapped page on the read back
    assert int(np.asarray(pgd2.faults).sum()) == int(np.asarray((pgd.pt > 0).sum()))
    # page_out is realloc-from-scratch: a second split of the same state
    # rebuilds identical tables and pool rows (deterministic ids)
    res2, pgd3 = pgmod.page_out_host(full, pgd2, segs)
    _assert_trees_equal(res2.log_term, res.log_term, "re-split resident")
    _assert_trees_equal(pgd3.pt, pgd.pt, "re-split page table")
    _assert_trees_equal(pgd3.pool_term, pgd.pool_term, "re-split pool")


def test_page_out_exhaustion_clamps_and_flags():
    """A pool too small for the batch drops overflow pages (they read
    back as zeros), sets ERR_PAGE_EXHAUSTED on the clamped lanes ONLY,
    and round-trips the surviving lanes exactly — never a silent wrap."""
    shape = _small_shape(page_window=4, page_entries=2, pool_pages=8)
    plan = pgmod.validate_page_plan(shape, G * V)
    assert plan.kmax == 7 and plan.pool_pages == 8
    st = _random_logged_state(1)
    canon = lg.scrub_stale_slots(st)
    res, pgd = pgmod.page_out_host(canon, pgmod.init_paged(plan, st), 1)
    eb = np.asarray(res.error_bits)
    exh = np.asarray(pgd.exhausted) > 0
    assert exh.any() and not exh.all()
    np.testing.assert_array_equal((eb & ERR_PAGE_EXHAUSTED) != 0, exh)
    full, _ = pgmod.page_in_host(res, pgd, 1)
    ok = ~exh
    np.testing.assert_array_equal(
        np.asarray(full.log_term)[ok], np.asarray(canon.log_term)[ok]
    )
    # clamped lanes keep their resident tail; only pooled slots zero out
    lt = np.asarray(full.log_term)
    ct = np.asarray(canon.log_term)
    assert ((lt == ct) | (lt == 0)).all()


# -- config-time geometry enforcement (satellite: raise, never fall back) --


@pytest.mark.parametrize(
    "kw",
    [
        {"page_window": 3},
        {"page_window": 16},  # not < log_window
        {"page_window": 1},
        {"page_entries": 3},
        {"page_entries": 32},  # > log_window
        {"pool_pages": 1},
        {"pool_pages": 70000},
    ],
)
def test_shape_rejects_bad_page_geometry(kw):
    with pytest.raises(ValueError):
        Shape(n_lanes=12, max_peers=3, log_window=16, max_msg_entries=2,
              max_inflight=2, max_read_index=2, **kw)


@pytest.mark.parametrize(
    "env",
    [
        {"page_entries": "3"},  # not a power of two
        {"pool_pages": "2"},  # < kmax + 1 for the default window split
    ],
)
def test_all_constructors_raise_on_env_geometry(monkeypatch, env):
    """Env-resolved geometry (which Shape.__post_init__ cannot see) must
    still fail at CONSTRUCTION time from every cluster entry point —
    config-time ValueError, never a silent fallback at first dispatch."""
    from raft_tpu.parallel.mesh import MeshBlockedCluster
    from raft_tpu.scheduler import BlockedFusedCluster

    _set_env(monkeypatch, paged="1", **env)
    shape = _small_shape(2, 3)
    with pytest.raises(ValueError):
        FusedCluster(2, 3, seed=1, shape=shape)
    with pytest.raises(ValueError):
        BlockedFusedCluster(4, 3, block_groups=2, seed=1, shape=shape)
    with pytest.raises(ValueError):
        MeshBlockedCluster(4, 3, block_groups=2, devices=jax.devices()[:1],
                           seed=1, shape=shape)


def test_sharded_rejects_indivisible_pool(monkeypatch):
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    # kmax = 3 for the W=16 default split -> pool must be >= 4 and is
    # pinned to 9, which does not divide over 8 shards
    _set_env(monkeypatch, paged="1", pool_pages="9")
    with pytest.raises(ValueError, match="divide evenly"):
        ShardedFusedCluster(n_groups=8, n_voters=3, seed=13,
                            shape=_small_shape())


# -- trajectory digests: paging must be invisible --------------------------


def _twin(monkeypatch, paged, **env):
    _set_env(monkeypatch, paged=paged, **env)
    return _drive(FusedCluster(G, V, seed=11, shape=_small_shape()))


@pytest.mark.parametrize("page_window", [None, "2"])
def test_xla_digest_identity_and_narrow_carry(monkeypatch, page_window):
    """Digest identity at the default split AND at a tiny resident window
    (page_window=2 forces real pool traffic every dispatch)."""
    off = _twin(monkeypatch, "0")
    on = _twin(monkeypatch, "1", page_window=page_window)
    w_res = int(page_window) if page_window else 8
    assert on.paged is not None and off.paged is None
    assert on.state.log_term.shape == (G * V, w_res)
    assert (np.asarray(on.host_state().committed) > 0).any()
    assert _digest(on.host_state()) == _digest(off.host_state())
    _assert_trees_equal(on.host_state(), off.host_state(), "host_state")
    # the resident carry sheds the cold window: strictly fewer log bytes
    on_log = sum(getattr(on.state, f).nbytes
                 for f in ("log_term", "log_type", "log_bytes"))
    off_log = sum(getattr(off.state, f).nbytes
                  for f in ("log_term", "log_type", "log_bytes"))
    assert on_log < off_log


def test_paged_elision_via_auditor(monkeypatch):
    """Paging is compile-time elided, not branch-skipped: with
    RAFT_TPU_PAGED=0 the page gather never traces into the round program
    (flat 'paged' counter via the shared auditor); with it on, the round
    program pages the window in at the dispatch boundary."""
    from raft_tpu.analysis import jaxpr_audit

    _set_env(monkeypatch, paged="0")
    rec = FusedCluster(G, V, seed=11, shape=_small_shape()).audit_programs()[0]
    _, deltas = jaxpr_audit.traced_counter_deltas(rec)
    assert not jaxpr_audit.check_elision(rec["name"], deltas,
                                         {"paged": False})

    _set_env(monkeypatch, paged="1")
    rec = FusedCluster(G, V, seed=11, shape=_small_shape()).audit_programs()[0]
    _, deltas = jaxpr_audit.traced_counter_deltas(rec)
    assert not jaxpr_audit.check_elision(rec["name"], deltas,
                                         {"paged": True})
    # detector sanity: claiming paged-off against the paged program fails
    assert jaxpr_audit.check_elision(rec["name"], deltas, {"paged": False})


def test_paged_stats_and_metrics_plane(monkeypatch):
    from raft_tpu.metrics.host import PAGED_COUNTERS, PAGED_EVENTS

    on = _twin(monkeypatch, "1", page_window="2", metrics="1")
    # one more dispatch so page_in reads the now-populated pool back
    # (faults only count pages GATHERED at a dispatch entry)
    on.run(8, auto_propose=True, auto_compact_lag=8)
    stats = on.paged_stats()
    assert stats["paged_pool_in_use"] > 0
    assert stats["paged_page_faults"] > 0  # pool read back across runs
    assert stats["paged_exhausted"] == 0
    for name in PAGED_COUNTERS:
        assert PAGED_EVENTS.counts[name] == stats[name]
    snap = on.metrics_snapshot()
    assert snap["counters"]["paged_pool_in_use"] == stats["paged_pool_in_use"]


def test_diet_paged_digest_identity(monkeypatch):
    """Stacked storage layers: diet packs the carry, paging splits the
    packed log columns (uint16 pool rows) — still bit-invisible."""
    base = _twin(monkeypatch, "0")
    on = _twin(monkeypatch, "1", diet="1", page_window="2")
    assert is_packed(on.state)
    assert on.paged.pool_term.dtype == jnp.uint16
    assert _digest(on.host_state()) == _digest(base.host_state())


def test_donation_cache_fence_digest_identity(monkeypatch):
    base = _twin(monkeypatch, "0")
    for donate in ("0", "1"):
        c = _twin(monkeypatch, "1", donate=donate)
        assert _digest(c.host_state()) == _digest(base.host_state()), donate


def test_planes_on_digest_identity(monkeypatch):
    base = _twin(monkeypatch, "0")
    on = _twin(monkeypatch, "1", metrics="1", chaos="1", tracelog="1")
    assert on.metrics is not None and on.chaos is not None
    assert on.trace is not None
    assert _digest(on.host_state()) == _digest(base.host_state())


def test_pallas_paged_replay_bit_identity(monkeypatch):
    """The pallas dispatch pages in BEFORE the specs are built and pages
    out after the scan — the megakernel itself never sees the pool, so
    K=1 and the K=4 in-kernel replay must both land bit-identical to the
    XLA scan on the same paged carry, and the reconstructed window must
    equal the never-paged run's."""
    from raft_tpu.ops import fused as fmod
    from raft_tpu.ops import pallas_round as plr

    g, v = 4, 3
    shape = Shape(n_lanes=g * v, max_peers=v, log_window=8,
                  max_msg_entries=2, max_inflight=2, max_read_index=2)
    kw = dict(
        v=v, n_rounds=9, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=None, chaos=None,
    )
    _set_env(monkeypatch)
    c0 = FusedCluster(g, v, seed=7, shape=shape)
    ref0 = fmod._fused_rounds_nodonate_jit(
        c0.state, c0.fab, c0._no_ops, c0.mute, straddle=None, **kw
    )
    _set_env(monkeypatch, paged="1", page_window="2")
    c1 = FusedCluster(g, v, seed=7, shape=shape)
    assert c1.paged is not None and c1.state.log_term.shape[1] == 2
    ref1 = fmod._fused_rounds_nodonate_jit(
        c1.state, c1.fab, c1._no_ops, c1.mute, straddle=None,
        paged=c1.paged, **kw
    )
    k1 = plr._pallas_rounds_nodonate_jit(
        c1.state, c1.fab, c1._no_ops, c1.mute,
        tile_lanes=2 * v, interpret=True, paged=c1.paged, **kw
    )
    k4 = plr._pallas_rounds_nodonate_jit(
        c1.state, c1.fab, c1._no_ops, c1.mute,
        tile_lanes=2 * v, interpret=True, rounds_per_call=4,
        paged=c1.paged, **kw
    )
    _assert_trees_equal(k1[0], ref1[0], "state K=1")
    _assert_trees_equal(k4[0], ref1[0], "state K=4")
    _assert_trees_equal(k1[1], ref1[1], "fabric K=1")
    _assert_trees_equal(k4[1], ref1[1], "fabric K=4")
    _assert_trees_equal(k1[-1], ref1[-1], "paged K=1")
    _assert_trees_equal(k4[-1], ref1[-1], "paged K=4")
    # reconstructing the paged result gives the never-paged carry exactly
    # (the unpaged exit path runs the same canonical scrub)
    full = pgmod.page_in_view(ref1[0], ref1[-1], 1)
    _assert_trees_equal(full, ref0[0], "paged vs never-paged state")


# -- exhaustion end to end (clamp + flag + counter + warning) --------------


def test_cluster_exhaustion_flags_and_counts(monkeypatch):
    """Driving deeper than a deliberately tiny pool clamps (the run keeps
    going), flags ERR_PAGE_EXHAUSTED, bumps the host counter and fires
    the rate-limited warning — never a silent drop."""
    import logging as pylog

    from raft_tpu.metrics.host import PAGED_EVENTS

    _set_env(monkeypatch, paged="1")
    shape = _small_shape(4, 3, page_window=4, page_entries=2, pool_pages=8)
    c = FusedCluster(4, 3, seed=11, shape=shape)
    c.run(40)
    c.run(24, auto_propose=True, auto_compact_lag=14)
    c.run(8, auto_propose=True, auto_compact_lag=14)
    bits = np.asarray(c.host_state().error_bits)
    assert (bits & ERR_PAGE_EXHAUSTED).any()
    with pytest.raises(AssertionError, match="error_bits"):
        c.check_no_errors()  # also mirrors stats onto the host plane
    stats = c.paged_stats()
    assert stats["paged_exhausted"] > 0
    assert stats["paged_page_faults"] > 0
    assert PAGED_EVENTS.counts["paged_exhausted"] == stats["paged_exhausted"]
    # the warning is rate-limited but never silent on first occurrence
    records = []
    h = pylog.Handler()
    h.emit = records.append
    logger = pylog.getLogger("raft_tpu")
    logger.addHandler(h)
    try:
        from raft_tpu.logging import _last_warn  # reset the limiter
        _last_warn.pop("paged_exhausted", None)
        c.paged_stats()
    finally:
        logger.removeHandler(h)
    assert any("exhausted" in r.getMessage() for r in records)


# -- host-facing byte streams ----------------------------------------------


def _stream_run(monkeypatch, paged, tracelog=None):
    from raft_tpu.runtime.egress import EgressStream
    from raft_tpu.runtime.trace import TraceStream
    from raft_tpu.runtime.wal import WalStream

    _set_env(monkeypatch, paged=paged, tracelog=tracelog,
             page_window="2" if paged == "1" else None)
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
    """The WAL streams _wal_view() — which reconstructs the full window
    from the pool — and egress reads no log columns: both planes must
    emit the EXACT bytes paged on or off."""
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


# -- WAL restore, rebase, membership changes under paging ------------------


def test_restore_from_wal_under_paging(monkeypatch):
    """A WAL delta (full-window canonical bytes) restores into a PAGED
    carry: the pool and page tables repopulate from the delta's log
    columns, the restored image round-trips through host_state(), and
    the block keeps running."""
    from raft_tpu.runtime.wal import WalStream

    _set_env(monkeypatch, paged="1", page_window="2")
    sink = {}
    wal = WalStream(sink=lambda bid, d: sink.__setitem__(bid, d))
    c = FusedCluster(G, V, seed=5, shape=_small_shape())
    for _ in range(4):
        c.run(10, auto_propose=True, auto_compact_lag=8, wal=wal)
    wal.flush()
    last = sink[max(sink)]
    b = FusedCluster.restore_from_wal(G, V, last, seed=99,
                                      shape=_small_shape())
    assert b.paged is not None
    assert int(np.asarray((b.paged.pt > 0).sum())) > 0, "pool not repopulated"
    for f in WalStream.FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(b.host_state(), f)), last[f], err_msg=f
        )
    b.run(20, auto_propose=True, auto_compact_lag=8)
    b.check_no_errors()


def _rebase_twin(monkeypatch, paged):
    _set_env(monkeypatch, paged=paged,
             page_window="2" if paged == "1" else None)
    c = FusedCluster(4, 3, seed=7, shape=_small_shape(4, 3))
    c.run(40)
    c.run(16, auto_propose=True, auto_compact_lag=8)
    # the live-rebase path pages the carry in and out around the rebase
    # jits (page ids are window keyed, a rebase re-keys every entry)
    c.rebase_groups(range(4))
    c.run(16, auto_propose=True, auto_compact_lag=8)
    c.check_no_errors()
    return c


def test_rebase_digest_identity(monkeypatch):
    off = _rebase_twin(monkeypatch, "0")
    on = _rebase_twin(monkeypatch, "1")
    assert _digest(on.host_state()) == _digest(off.host_state())


def _confchange_twin(monkeypatch, paged):
    from raft_tpu import confchange as ccm

    _set_env(monkeypatch, paged=paged,
             page_window="2" if paged == "1" else None)
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
    """The membership driver round-trips the carry through host_state()/
    adopt_state() — the paged split must survive the adopt re-split."""
    off = _confchange_twin(monkeypatch, "0")
    on = _confchange_twin(monkeypatch, "1")
    assert on.paged is not None
    assert _digest(on.host_state()) == _digest(off.host_state())


# -- multi-block / multi-shard composition ---------------------------------


def _blocked_twin(monkeypatch, paged):
    from raft_tpu.scheduler import BlockedFusedCluster

    _set_env(monkeypatch, paged=paged,
             page_window="2" if paged == "1" else None)
    c = BlockedFusedCluster(4, 3, block_groups=2, seed=3,
                            shape=_small_shape(2, 3))
    for _ in range(3):
        c.run(8, auto_propose=True, auto_compact_lag=8)
    c.check_no_errors()
    return c


def test_blocked_scheduler_digest_identity(monkeypatch):
    off = _blocked_twin(monkeypatch, "0")
    on = _blocked_twin(monkeypatch, "1")
    assert all(b.paged is not None for b in on.blocks)
    cols_off = off.state_columns(*DIGEST_FIELDS)
    cols_on = on.state_columns(*DIGEST_FIELDS)
    for f in DIGEST_FIELDS:
        assert cols_off[f].dtype == cols_on[f].dtype, f
        np.testing.assert_array_equal(cols_off[f], cols_on[f], err_msg=f)
    assert on.total_committed() == off.total_committed() > 0


def _sharded_twin(monkeypatch, paged):
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    _set_env(monkeypatch, paged=paged)
    sh = ShardedFusedCluster(n_groups=8, n_voters=3, seed=13,
                             shape=_small_shape())
    sh.run(40)
    sh.run(16, auto_propose=True, auto_compact_lag=8)
    sh.check_no_errors()
    return sh


def test_sharded_digest_identity(monkeypatch, no_persistent_cache):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    off = _sharded_twin(monkeypatch, "0")
    on = _sharded_twin(monkeypatch, "1")
    assert on.inner.paged is not None
    assert on.inner._paged_segs == 8
    # default pool (N*kmax + 8 = 80) divides over the 8 shards and
    # every page id stays inside its shard's 10-row sub-pool
    assert int(np.asarray(on.inner.paged.pt).max()) < 80 // 8
    assert _digest(on.host_state()) == _digest(off.host_state())


# -- in-kernel paging (RAFT_TPU_PAGED_INKERNEL, ISSUE 17) ------------------
# page_in/page_out move from the dispatch boundary into the round program
# itself: per-round in the XLA scan body, per grid step in the pallas
# megakernel (each lane tile owns its slice of the pool — allocation
# segment = tile). pg counters (faults/dirty/skipped/exhausted) are
# MODE-LOCAL bookkeeping and are never compared across paging modes; the
# bit-identity contract is on the reconstructed full window + fabric.


def test_inkernel_kernel_bit_identity_k1_k4(monkeypatch):
    """Kernel-level: in-kernel pallas at K=1 and K=4 (9 rounds = 4+4+1
    remainder tail) and the in-kernel XLA scan twin all reconstruct the
    exact window the host-boundary run produces, on the same operands."""
    from raft_tpu.ops import fused as fmod
    from raft_tpu.ops import pallas_round as plr

    g, v = 4, 3
    shape = Shape(n_lanes=g * v, max_peers=v, log_window=8,
                  max_msg_entries=2, max_inflight=2, max_read_index=2)
    kw = dict(
        v=v, n_rounds=9, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=None, chaos=None,
    )
    _set_env(monkeypatch, paged="1", page_window="2")
    c = FusedCluster(g, v, seed=7, shape=shape)
    assert c.paged is not None
    host = fmod._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None,
        paged=c.paged, **kw
    )
    ink_x = fmod._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None,
        paged=c.paged, paged_inkernel=True, **kw
    )
    k1 = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=2 * v, interpret=True, paged=c.paged,
        paged_inkernel=True, **kw
    )
    k4 = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=2 * v, interpret=True, rounds_per_call=4,
        paged=c.paged, paged_inkernel=True, **kw
    )
    ref_full = pgmod.page_in_view(host[0], host[-1], 1)
    for name, out, segs in (
        ("xla", ink_x, 1), ("pallas K=1", k1, 2), ("pallas K=4", k4, 2),
    ):
        full = pgmod.page_in_view(out[0], out[-1], segs)
        _assert_trees_equal(full, ref_full, f"{name} state")
        _assert_trees_equal(out[1], host[1], f"{name} fabric")
        assert int(np.asarray(out[-1].exhausted).sum()) == 0, name


@pytest.mark.parametrize("diet", ["0", "1"])
def test_inkernel_xla_digest_identity_and_alloc_skip(monkeypatch, diet):
    """Cluster-level XLA twin, diet stacked on/off: the in-kernel arm
    lands the host-boundary digest, and the conditional allocator
    actually elides rounds where no lane's log moved."""
    off = _twin(monkeypatch, "1", diet=diet)
    _set_env(monkeypatch, paged="1", paged_inkernel="1", diet=diet)
    on = _drive(FusedCluster(G, V, seed=11, shape=_small_shape()))
    assert on._paged_inkernel and on._paged_segs == 1
    assert _digest(on.host_state()) == _digest(off.host_state())
    stats = pgmod.paged_stats(on.paged)
    assert stats["paged_alloc_skipped"] > 0
    assert stats["paged_pages_dirty"] > 0


def test_inkernel_pallas_cluster_digest_identity(monkeypatch):
    """Cluster-level pallas engine: the in-kernel megakernel arm (two
    lane tiles -> two allocation segments) lands the host-boundary pallas
    digest; page ids stay inside each tile's sub-pool slice."""
    _set_env(monkeypatch, paged="1")
    ref = FusedCluster(G, V, seed=11, shape=_small_shape(),
                       engine="pallas", tile_lanes=2 * V)
    ref.run(16, auto_propose=True, auto_compact_lag=4)
    ref.check_no_errors()
    _set_env(monkeypatch, paged="1", paged_inkernel="1")
    on = FusedCluster(G, V, seed=11, shape=_small_shape(),
                      engine="pallas", tile_lanes=2 * V)
    on.run(16, auto_propose=True, auto_compact_lag=4)
    on.check_no_errors()
    assert on.engine == "pallas" and on._paged_inkernel
    assert on._paged_segs == (G * V) // (2 * V)
    sub = on.paged.pool_term.shape[0] // on._paged_segs
    assert int(np.asarray(on.paged.pt).max()) < sub
    assert _digest(on.host_state()) == _digest(ref.host_state())


def test_inkernel_exhaustion_mid_k_clamps_and_flags(monkeypatch):
    """A pool too small for the batch, paged in-kernel at K=4: the
    per-round page_out_cond clamps INSIDE the grid, flags
    ERR_PAGE_EXHAUSTED, and the run keeps going — never a crash, never a
    silent wrap."""
    from raft_tpu.ops import pallas_round as plr

    _set_env(monkeypatch, paged="1", page_window="4", page_entries="2",
             pool_pages="8")
    shape = _small_shape(4, 3, page_window=4, page_entries=2, pool_pages=8)
    c = FusedCluster(4, 3, seed=11, shape=shape)
    c.run(40)
    c.run(24, auto_propose=True, auto_compact_lag=14)  # overruns the pool
    ex0 = int(np.asarray(c.paged.exhausted).sum())
    assert ex0 > 0
    kw = dict(v=3, n_rounds=8, do_tick=True, auto_propose=True,
              auto_compact_lag=14, ops_first_round_only=True,
              metrics=None, chaos=None)
    out = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, tile_lanes=12, interpret=True,
        rounds_per_call=4, paged=c.paged, paged_inkernel=True, **kw
    )
    st, pg = out[0], out[-1]
    bits = np.asarray(st.error_bits)
    assert (bits & ERR_PAGE_EXHAUSTED).any()
    assert int(np.asarray(pg.exhausted).sum()) >= ex0


# -- segment-aware pool addressing (sharded / mesh) ------------------------


@pytest.mark.parametrize("segs", [2, 4])
def test_resegment_round_trip(segs):
    """resegment rewrites page ids between allocation segmentations (the
    sharded ctor / engine-fallback path) without touching values: the
    reconstructed window is identical before and after, and ids stay
    local to the new sub-pools."""
    st = _random_logged_state(3)
    plan = pgmod.validate_page_plan(_small_shape(), G * V)
    canon = lg.scrub_stale_slots(st)
    res, pgd = pgmod.page_out_host(canon, pgmod.init_paged(plan, st), 1)
    res2, pgd2 = pgmod.resegment(res, pgd, 1, segs)
    sub = pgd2.pool_term.shape[0] // segs
    assert int(np.asarray(pgd2.pt).max()) < sub
    full2 = pgmod.page_in_view(res2, pgd2, segs)
    full1 = pgmod.page_in_view(res, pgd, 1)
    _assert_trees_equal(
        (full2.log_term, full2.log_type, full2.log_bytes),
        (full1.log_term, full1.log_type, full1.log_bytes),
        f"resegment 1->{segs}",
    )
    res3, pgd3 = pgmod.resegment(res2, pgd2, segs, 1)
    _assert_trees_equal(pgd3.pt, pgd.pt, "resegment back: page table")
    _assert_trees_equal(pgd3.pool_term, pgd.pool_term,
                        "resegment back: pool")


def test_check_pool_segments_rejects_bad_geometry():
    plan = pgmod.validate_page_plan(
        _small_shape(4, 3, pool_pages=9), 12
    )
    with pytest.raises(ValueError, match="allocation segments"):
        pgmod.check_pool_segments(plan, 2)  # 9 % 2 != 0
    pgmod.check_pool_segments(plan, 1)  # mono is always fine
    plan8 = pgmod.validate_page_plan(
        _small_shape(4, 3, pool_pages=8), 12
    )
    with pytest.raises(ValueError, match="allocation segments"):
        # 8 // 4 = 2 < kmax + 1: a sub-pool couldn't hold one lane's
        # worst-case tail plus its trash row
        pgmod.check_pool_segments(plan8, 4)


def test_sharded_inkernel_xla_digest_identity(
    monkeypatch, no_persistent_cache
):
    """Sharded in-kernel XLA twin: paging runs per round inside
    shard_map (segment = shard), digest-identical to the host-boundary
    sharded run."""
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    off = _sharded_twin(monkeypatch, "0")
    _set_env(monkeypatch, paged="1", paged_inkernel="1")
    on = ShardedFusedCluster(n_groups=8, n_voters=3, seed=13,
                             shape=_small_shape())
    on.run(40)
    on.run(16, auto_propose=True, auto_compact_lag=8)
    on.check_no_errors()
    assert on.inner._paged_inkernel
    assert on.inner._paged_segs == 8  # xla engine: segment = shard
    assert _digest(on.host_state()) == _digest(off.host_state())


@pytest.mark.slow
def test_sharded_inkernel_pallas_segments_and_digest(
    monkeypatch, no_persistent_cache
):
    """Sharded in-kernel pallas: two shards x two tiles per shard ->
    four allocation segments; still digest-identical to the host-boundary
    sharded run. Interpret-mode pallas under shard_map is minutes-slow on
    CPU, hence the slow mark (the paged_ab bench smokes the same path)."""
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    dev = jax.devices()[:2]
    _set_env(monkeypatch, paged="1")
    off = ShardedFusedCluster(n_groups=8, n_voters=3, seed=13,
                              shape=_small_shape(), devices=dev)
    off.run(24, auto_propose=True, auto_compact_lag=8)
    off.check_no_errors()
    _set_env(monkeypatch, paged="1", paged_inkernel="1")
    on = ShardedFusedCluster(n_groups=8, n_voters=3, seed=13,
                             shape=_small_shape(), devices=dev,
                             engine="pallas", tile_lanes=6)
    on.run(24, auto_propose=True, auto_compact_lag=8)
    on.check_no_errors()
    assert on.inner.engine == "pallas"
    assert on.inner._paged_segs == 2 * (12 // 6)
    assert _digest(on.host_state()) == _digest(off.host_state())


# -- tier x paged (satellite: eviction must capture the deep paged tail) ---


def test_tier_paged_pool_conservation_and_deep_tail(monkeypatch):
    """Evicting a group whose log spills into the pool returns its pages
    exactly (paged_pool_in_use conserved across the evict/admit cycle),
    round-trips the deep tail bit-exactly, and the hiccuped cluster lands
    the identical trajectory as a never-evicted twin."""
    _set_env(monkeypatch, paged="1", page_window="2", tier="1")
    shape = _small_shape(4, 3, page_window=2)

    def mk():
        return FusedCluster(4, 3, seed=3, shape=shape, logical_groups=8)

    a, b = mk(), mk()
    assert a.tier is not None and a.paged is not None
    for c in (a, b):
        c.run(40)
        c.run(24, auto_propose=True, auto_compact_lag=8)
    per_lane = pgmod.mapped_pages_per_lane(a.paged)
    in_use0 = pgmod.paged_stats(a.paged)["paged_pool_in_use"]
    assert in_use0 > 0
    eng = a.tier
    # pick a victim that actually holds pool pages (deep tail)
    g = next(
        g for g in eng.residents()
        if per_lane[eng.lane_of_group(g):eng.lane_of_group(g) + a.v].sum()
    )
    lane0 = eng.lane_of_group(g)
    vp = int(per_lane[lane0:lane0 + a.v].sum())
    full0 = a.host_state()
    rows0 = {k: np.asarray(getattr(full0, k))[lane0:lane0 + a.v].copy()
             for k in DIGEST_FIELDS}
    assert (rows0["last"] - np.asarray(full0.snap_index)[
        lane0:lane0 + a.v]).max() > 2, "victim's tail must be paged-deep"

    eng.request_evict(g)
    ev, _ = eng.apply(1000)
    assert ev == [g]
    assert pgmod.paged_stats(a.paged)["paged_pool_in_use"] == in_use0 - vp

    eng.request_admit(g, 1000)
    _, ad = eng.apply(1000)
    assert ad == [g]
    assert pgmod.paged_stats(a.paged)["paged_pool_in_use"] == in_use0
    full1 = a.host_state()
    for k in DIGEST_FIELDS:
        np.testing.assert_array_equal(
            rows0[k], np.asarray(getattr(full1, k))[lane0:lane0 + a.v],
            err_msg=f"deep tail round-trip: {k}",
        )
    # chaos-soak digest twin: keep driving both, the hiccup is invisible
    for c in (a, b):
        c.run(16, auto_propose=True, auto_compact_lag=8)
        c.check_no_errors()
    assert _digest(a.host_state()) == _digest(b.host_state())

"""The VMEM-resident pallas round engine (raft_tpu/ops/pallas_round.py).

Interpret mode on the CPU test rig (the same kernel compiles for real via
Mosaic on TPU). The acceptance bar from the promotion PR:

1. Bit-identity: RAFT_TPU_ENGINE=pallas walks the exact slim_state
   trajectory of the XLA engine — every field, >= 32 rounds, and the
   metrics/chaos carries agree too (the per-tile partial reduction and
   the lane-offset chaos PRNG reconstruction are exact, not approximate).
2. Tile invariant: tile_lanes % v == 0 and tile_lanes | n, rejected with
   a clear TileError that is never swallowed by the fallback.
3. No silent degradation: a kernel failure raises out of the dispatch
   and the cluster stays on the pallas engine.
4. Donation composes: the donating pallas twin deletes the old carry and
   changes no value vs the copying twin.

Plus the satellites: BlockedFusedCluster ops-cache LRU regression, the
blocked/sharded engine passthrough, and the tile helper unit coverage.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

import jax

from raft_tpu.chaos.device import probability
from raft_tpu.config import Shape
from raft_tpu.ops import fused
from raft_tpu.ops import pallas_round as plr
from raft_tpu.ops.fused import FusedCluster
from raft_tpu.parallel.sharded import ShardedFusedCluster
from raft_tpu.scheduler import BlockedFusedCluster

V = 3
G = 4
N = G * V
TILE = 2 * V  # 2 tiles over 4 groups: exercises the program_id lane offset


def _shape(n_lanes=N):
    return Shape(
        n_lanes=n_lanes, max_peers=V, log_window=8, max_msg_entries=2,
        max_inflight=2, max_read_index=2,
    )


def _assert_trees_equal(a, b, what):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for (path, x), y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (what, path)


def _boom(*a, **k):
    raise RuntimeError("forced pallas kernel failure")


def _digest(*trees):
    """sha256 over every leaf's bytes — the acceptance-criteria digest."""
    h = hashlib.sha256()
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# -- 1. bit-identity -------------------------------------------------------


def test_trajectory_bit_identity_with_metrics_and_chaos(monkeypatch):
    """>= 32 rounds, 2 lane tiles, metrics AND chaos threaded through the
    kernel: every slim_state/fabric field plus both carries bit-identical
    to the XLA path (the chaos PRNG is a pure function of the GLOBAL lane
    index, so per-tile reconstruction must not shift it)."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "1")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    c = FusedCluster(G, V, seed=7, shape=_shape())
    c.set_chaos(
        drop_num=np.full((N, V), probability(0.2), np.int32),
        tick_skew_num=np.full(N, probability(0.1), np.int32),
        heal_round=7,
    )
    kw = dict(
        v=V, n_rounds=33, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=c.metrics, chaos=c.chaos,
    )
    ref = fused._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None, **kw
    )
    got = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=TILE, interpret=True, **kw
    )
    assert len(ref) == len(got) == 4
    for r, g, what in zip(ref, got, ("state", "fabric", "metrics", "chaos")):
        _assert_trees_equal(r, g, what)


def test_bit_identity_without_extras(monkeypatch):
    """Metrics/chaos elision holds on the kernel path: with both planes
    off, the pallas call takes no partials outputs and still matches."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    c = FusedCluster(G, V, seed=3, shape=_shape())
    assert c.metrics is None and c.chaos is None
    kw = dict(
        v=V, n_rounds=8, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=None, chaos=None,
    )
    ref = fused._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None, **kw
    )
    got = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=TILE, interpret=True, **kw
    )
    assert len(ref) == len(got) == 2
    _assert_trees_equal(ref[0], got[0], "state")
    _assert_trees_equal(ref[1], got[1], "fabric")


# -- 2. tile invariant -----------------------------------------------------


def test_tile_invariants_rejected():
    plr.check_tile(12, 3, 6)  # group-aligned divisor: fine
    with pytest.raises(plr.TileError, match="multiple of v"):
        plr.check_tile(12, 3, 4)
    with pytest.raises(plr.TileError, match="does not divide"):
        plr.check_tile(12, 3, 9)
    with pytest.raises(plr.TileError, match=">= 1"):
        plr.check_tile(12, 3, 0)
    # TileError is a config error: the cluster raises it and stays pallas
    c = FusedCluster(G, V, seed=1, shape=_shape(), engine="pallas",
                     tile_lanes=4)
    with pytest.raises(plr.TileError, match="multiple of v"):
        c.run(1)
    assert c.engine == "pallas"


def test_autotune_sweep_caches_winner():
    """The TPU first-dispatch sweep, exercised with a fake timer: fastest
    candidate wins, the winner lands in the (shape, backend) cache, and a
    second sweep under the same key never re-times."""
    n, v = 4096 * 3, 3
    cands = plr.tile_candidates(n, v)
    assert len(cands) > 1
    want = cands[len(cands) // 2]
    timed = []

    def fake_time(t):
        timed.append(t)
        return 0.5 if t == want else 1.0 + t * 1e-6

    key = ("test-autotune-sweep", "tpu")
    assert plr.autotune_tile(n, v, key=key, time_fn=fake_time) == want
    assert timed == cands
    assert plr.cached_tile(key) == want
    # warm cache: no timing at all on the second resolve
    assert plr.autotune_tile(n, v, key=key, time_fn=fake_time) == want
    assert timed == cands


def test_tile_helpers():
    assert plr.default_tile(N, V) == N  # tiny batch: whole-batch tile
    cands = plr.tile_candidates(4096 * 3, 3)
    assert cands and all(c % 3 == 0 and (4096 * 3) % c == 0 for c in cands)
    assert 4096 * 3 in cands
    key = ("test-tile-helpers", "cpu")
    assert plr.cached_tile(key) is None
    plr.remember_tile(key, 6)
    assert plr.cached_tile(key) == 6


# -- engine selection ------------------------------------------------------


def test_engine_selection(monkeypatch):
    monkeypatch.delenv("RAFT_TPU_ENGINE", raising=False)
    assert plr.resolve_engine() == "xla"
    assert plr.resolve_engine("pallas") == "pallas"
    monkeypatch.setenv("RAFT_TPU_ENGINE", "pallas")
    assert plr.resolve_engine() == "pallas"
    assert plr.resolve_engine("xla") == "xla"  # kwarg beats env
    assert FusedCluster(G, V, seed=1, shape=_shape()).engine == "pallas"
    with pytest.raises(ValueError, match="unknown engine"):
        plr.resolve_engine("bogus")
    monkeypatch.setenv("RAFT_TPU_ENGINE", "bogus")
    with pytest.raises(ValueError, match="unknown engine"):
        FusedCluster(G, V, seed=1, shape=_shape())


# -- 3. kernel failure -> raise, never a silent engine switch ----------------


def test_kernel_failure_raises(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    c = FusedCluster(G, V, seed=5, shape=_shape(), engine="pallas",
                     tile_lanes=TILE)
    monkeypatch.setattr(plr, "round_jit_twin", lambda donate: _boom)
    with pytest.raises(RuntimeError, match="forced pallas kernel failure"):
        c.run(4, auto_propose=True)
    assert c.engine == "pallas"


# -- 4. donation x pallas ---------------------------------------------------


def test_donation_composes_with_pallas(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    monkeypatch.setenv("RAFT_TPU_DONATE", "1")
    c = FusedCluster(G, V, seed=9, shape=_shape(), engine="pallas",
                     tile_lanes=TILE)
    assert c._donate
    st0, fab0 = c.state, c.fab
    c.run(4, auto_propose=True)
    assert c.engine == "pallas"  # really dispatched on the kernel path
    # the donated carry died in place
    assert st0.term.is_deleted()
    assert fab0.rep.kind.is_deleted()
    c.run(4, auto_propose=True)

    monkeypatch.setenv("RAFT_TPU_DONATE", "0")
    d = FusedCluster(G, V, seed=9, shape=_shape(), engine="pallas",
                     tile_lanes=TILE)
    dst0 = d.state
    d.run(4, auto_propose=True)
    d.run(4, auto_propose=True)
    assert not dst0.term.is_deleted()  # copying twin keeps inputs alive
    _assert_trees_equal(c.state, d.state, "donation changed a value")
    _assert_trees_equal(c.fab, d.fab, "donation changed the fabric")


# -- megakernel: K rounds per pallas_call ----------------------------------


def test_megakernel_bit_identity_with_metrics_and_chaos(monkeypatch):
    """33 rounds at K=4 leave a remainder tail (33 = 8*4+1), so the scan
    of full-K megakernels AND the remainder-sized second program are both
    exercised. Digest-identical (sha256 over every carry leaf) to the XLA
    fused_rounds and to K=1 pallas, with metrics AND chaos threading
    through the per-round [K, n_tiles, 128] partials. (One K only: each
    K variant is a fresh large interpreted program, ~1 min on 1-core CI;
    the divisible-K and cluster-level tests below cover other K values.)"""
    k = 4
    monkeypatch.setenv("RAFT_TPU_METRICS", "1")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    c = FusedCluster(G, V, seed=7, shape=_shape())
    c.set_chaos(
        drop_num=np.full((N, V), probability(0.2), np.int32),
        tick_skew_num=np.full(N, probability(0.1), np.int32),
        heal_round=7,
    )
    kw = dict(
        v=V, n_rounds=33, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=c.metrics, chaos=c.chaos,
    )
    ref = fused._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None, **kw
    )
    k1 = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=TILE, interpret=True, **kw
    )
    got = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=TILE, interpret=True, rounds_per_call=k, **kw
    )
    assert len(ref) == len(got) == 4
    for r, g, what in zip(ref, got, ("state", "fabric", "metrics", "chaos")):
        _assert_trees_equal(r, g, what)
    assert _digest(*got) == _digest(*ref) == _digest(*k1)


def test_megakernel_divisible_no_tail(monkeypatch):
    """K | n_rounds: pure scan of full-K calls, no remainder program.
    K=6 (vs K=4 above) also varies the in-kernel unroll depth."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    c = FusedCluster(G, V, seed=3, shape=_shape())
    kw = dict(
        v=V, n_rounds=12, do_tick=True, auto_propose=True,
        auto_compact_lag=4, ops_first_round_only=True,
        metrics=None, chaos=None,
    )
    ref = fused._fused_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute, straddle=None, **kw
    )
    got = plr._pallas_rounds_nodonate_jit(
        c.state, c.fab, c._no_ops, c.mute,
        tile_lanes=TILE, interpret=True, rounds_per_call=6, **kw
    )
    _assert_trees_equal(ref[0], got[0], "state")
    _assert_trees_equal(ref[1], got[1], "fabric")


def test_cluster_megakernel_run_parity(monkeypatch):
    """The FusedCluster wiring: ctor rounds_per_call flows through
    _run_pallas into the megakernel dispatch, bit-identical to XLA.
    (K=2 and few rounds: the kernel-level digest test above already
    covers K=4 at depth; this one only proves the cluster plumbing.)"""
    monkeypatch.setenv("RAFT_TPU_METRICS", "1")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    cx = FusedCluster(G, V, seed=2, shape=_shape())
    cp = FusedCluster(G, V, seed=2, shape=_shape(), engine="pallas",
                      tile_lanes=TILE, rounds_per_call=2)
    cx.run(5, auto_propose=True)
    cp.run(5, auto_propose=True)
    assert cp.engine == "pallas"
    assert cp._pallas_rounds == 2
    _assert_trees_equal(cx.state, cp.state, "cluster state")
    _assert_trees_equal(cx.metrics, cp.metrics, "cluster metrics")


def test_rounds_knob_parse_and_validation(monkeypatch):
    monkeypatch.delenv("RAFT_TPU_PALLAS_ROUNDS", raising=False)
    assert plr.env_rounds_per_call() is None
    monkeypatch.setenv("RAFT_TPU_PALLAS_ROUNDS", "4")
    assert plr.env_rounds_per_call() == 4
    for bad in ("abc", "0", "-2"):
        monkeypatch.setenv("RAFT_TPU_PALLAS_ROUNDS", bad)
        with pytest.raises(ValueError, match="RAFT_TPU_PALLAS_ROUNDS"):
            plr.env_rounds_per_call()
    monkeypatch.delenv("RAFT_TPU_PALLAS_ROUNDS", raising=False)
    plr.validate_round_plan(1)
    plr.validate_round_plan(plr.MAX_ROUNDS_PER_CALL)
    with pytest.raises(ValueError, match="MAX_ROUNDS_PER_CALL"):
        plr.validate_round_plan(plr.MAX_ROUNDS_PER_CALL + 1)
    with pytest.raises(ValueError, match="integer >= 1"):
        plr.validate_round_plan(0)
    with pytest.raises(ValueError, match="unrolled rounds"):
        plr.validate_round_plan(8, unroll=64)
    with pytest.raises(ValueError, match="round_chunk"):
        plr.validate_round_plan(3, round_chunk=4)
    plr.validate_round_plan(2, round_chunk=4, unroll=2)  # composes fine
    # the blocked ctor surfaces the composition error up front, for both a
    # ctor-pinned and an env-pinned K
    with pytest.raises(ValueError, match="round_chunk"):
        BlockedFusedCluster(4, 3, block_groups=2, seed=1, shape=_shape(6),
                            engine="pallas", rounds_per_call=3,
                            round_chunk=4)
    monkeypatch.setenv("RAFT_TPU_PALLAS_ROUNDS", "3")
    with pytest.raises(ValueError, match="round_chunk"):
        BlockedFusedCluster(4, 3, block_groups=2, seed=1, shape=_shape(6),
                            engine="pallas", round_chunk=4)
    # env pin resolves into the cluster's K
    monkeypatch.setenv("RAFT_TPU_PALLAS_ROUNDS", "2")
    c = FusedCluster(G, V, seed=1, shape=_shape(), engine="pallas",
                     tile_lanes=TILE)
    assert c._resolve_pallas_rounds() == 2


def test_trace_plane_routes_to_k1(monkeypatch):
    """The flight recorder's diff detection needs per-round boundary
    states outside the kernel, so a trace-enabled run routes to K=1: a
    rounds_per_call=4 cluster walks the identical state AND ring as K=1."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    monkeypatch.setenv("RAFT_TPU_TRACELOG", "1")
    c1 = FusedCluster(G, V, seed=11, shape=_shape(), engine="pallas",
                      tile_lanes=TILE, rounds_per_call=1)
    c4 = FusedCluster(G, V, seed=11, shape=_shape(), engine="pallas",
                      tile_lanes=TILE, rounds_per_call=4)
    assert c1.trace is not None and c4.trace is not None
    c1.run(9, auto_propose=True)
    c4.run(9, auto_propose=True)
    assert c4.engine == "pallas"
    _assert_trees_equal(c1.state, c4.state, "trace-routed state")
    _assert_trees_equal(c1.trace, c4.trace, "trace ring")


def test_donation_composes_with_megakernel(monkeypatch):
    """Donation x K>1: the donating twin deletes the old carry and
    changes no value. (K=2 keeps the interpreted program small.)"""
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    monkeypatch.setenv("RAFT_TPU_DONATE", "1")
    c = FusedCluster(G, V, seed=9, shape=_shape(), engine="pallas",
                     tile_lanes=TILE, rounds_per_call=2)
    assert c._donate
    st0 = c.state
    c.run(5, auto_propose=True)  # 2 full K=2 calls + a 1-round tail
    assert c.engine == "pallas"
    assert st0.term.is_deleted()
    c.run(5, auto_propose=True)

    monkeypatch.setenv("RAFT_TPU_DONATE", "0")
    d = FusedCluster(G, V, seed=9, shape=_shape(), engine="pallas",
                     tile_lanes=TILE, rounds_per_call=2)
    d.run(5, auto_propose=True)
    d.run(5, auto_propose=True)
    _assert_trees_equal(c.state, d.state, "megakernel donation changed a value")
    _assert_trees_equal(c.fab, d.fab, "megakernel donation changed the fabric")


def test_autotune_plan_joint_sweep():
    """The joint (tile, K) sweep with a fake timer: overall winner lands
    in the plan cache AND the plain tile cache, per-K tile winners land
    under (shape, backend, K), and a warm key never re-times."""
    n, v = 4096 * 3, 3
    cands = plr.tile_candidates(n, v)
    assert len(cands) > 1
    want_t, want_k = cands[len(cands) // 2], 4
    timed = []

    def fake_time(t, k):
        timed.append((t, k))
        return 0.5 if (t, k) == (want_t, want_k) else 1.0 + t * 1e-9 + k * 1e-3

    key = ("test-autotune-plan", "tpu")
    assert plr.autotune_plan(n, v, key=key, time_fn=fake_time) == (
        want_t, want_k,
    )
    assert len(timed) == len(cands) * len(plr.ROUND_CANDIDATES)
    assert plr.cached_plan(key) == (want_t, want_k)
    assert plr.cached_tile(key) == want_t
    for k in plr.ROUND_CANDIDATES:
        assert plr.cached_tile(key + (k,)) in cands
    n_before = len(timed)
    assert plr.autotune_plan(n, v, key=key, time_fn=fake_time) == (
        want_t, want_k,
    )
    assert len(timed) == n_before
    # a pinned tile restricts the tile axis but still sweeps K
    key2 = ("test-autotune-plan-pinned", "tpu")
    timed.clear()
    t_pin = cands[0]
    tile, k = plr.autotune_plan(
        n, v, key=key2, time_fn=fake_time, tiles=(t_pin,)
    )
    assert tile == t_pin
    assert len(timed) == len(plr.ROUND_CANDIDATES)


# -- satellite: BlockedFusedCluster ops-cache LRU --------------------------


def test_blocked_ops_cache_survives_alternation():
    """Regression: the old single-slot identity cache re-sliced K subtrees
    on EVERY call when a driver alternated two prepared ops objects."""
    c = BlockedFusedCluster(4, 3, block_groups=2, seed=4, shape=_shape(6))
    calls = []
    orig = c.prepare_ops
    c.prepare_ops = lambda ops: (calls.append(ops), orig(ops))[1]
    o1 = c.ops(hup={0: True})
    o2 = c.ops(hup={7: True})  # lane 7 lives in block 1
    p1, p2 = c._bind_ops(o1), c._bind_ops(o2)
    assert np.asarray(p1[0].hup)[0] and np.asarray(p2[1].hup)[1]
    for _ in range(3):  # the failing pattern: strict alternation
        assert c._bind_ops(o1) is p1
        assert c._bind_ops(o2) is p2
    assert len(calls) == 2, "alternating ops objects re-sliced the cache"
    # a third object evicts the least-recently-used (o1), keeps o2
    o3 = c.ops(hup={3: True})
    p3 = c._bind_ops(o3)
    assert c._bind_ops(o2) is p2 and c._bind_ops(o3) is p3
    assert len(calls) == 3
    assert c._bind_ops(o1) is not p1  # evicted: rebuilt fresh
    assert len(calls) == 4


# -- satellite: blocked + sharded engine passthrough -----------------------


def test_blocked_engine_passthrough_parity(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    bp = BlockedFusedCluster(4, 3, block_groups=2, seed=3, shape=_shape(6),
                             engine="pallas", tile_lanes=6)
    assert [b.engine for b in bp.blocks] == ["pallas", "pallas"]
    bx = BlockedFusedCluster(4, 3, block_groups=2, seed=3, shape=_shape(6))
    bp.run(4, auto_propose=True)
    bx.run(4, auto_propose=True)
    for p, x in zip(bp.blocks, bx.blocks):
        assert p.engine == "pallas"
        _assert_trees_equal(x.state, p.state, "blocked engine diverged")


def test_sharded_engine_parity(monkeypatch):
    # 2 shards x 6 lanes, tile 3: TWO pallas tiles inside EACH shard, so
    # the kernel's lane offsets nest under shard_map's lane slicing
    monkeypatch.setenv("RAFT_TPU_METRICS", "1")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    dev = jax.devices()[:2]
    sx = ShardedFusedCluster(G, V, seed=7, shape=_shape(), engine="xla",
                             devices=dev)
    sp = ShardedFusedCluster(G, V, seed=7, shape=_shape(), engine="pallas",
                             tile_lanes=V, devices=dev)
    sx.run(8, auto_propose=True)
    sp.run(8, auto_propose=True)
    assert sp.inner.engine == "pallas"
    _assert_trees_equal(sx.inner.state, sp.inner.state, "sharded state")
    _assert_trees_equal(sx.inner.metrics, sp.inner.metrics, "sharded metrics")


def test_blocked_megakernel_parity(monkeypatch):
    """RAFT_TPU_PALLAS_ROUNDS=2 on the blocked path: every block resolves
    K=2, round_chunk=2 dispatches one megakernel call per chunk, and the
    trajectory matches the XLA blocked run exactly."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    monkeypatch.setenv("RAFT_TPU_PALLAS_ROUNDS", "2")
    bx = BlockedFusedCluster(4, 3, block_groups=2, seed=3, shape=_shape(6))
    bp = BlockedFusedCluster(4, 3, block_groups=2, seed=3, shape=_shape(6),
                             engine="pallas", tile_lanes=6, round_chunk=2)
    bx.run(6, auto_propose=True)
    bp.run(6, auto_propose=True)
    for p, x in zip(bp.blocks, bx.blocks):
        assert p.engine == "pallas"
        assert p._pallas_rounds == 2
        _assert_trees_equal(x.state, p.state, "blocked megakernel diverged")


def test_sharded_megakernel_parity(monkeypatch):
    """Per-shard megakernel: K=2 inside shard_map, K in the stepper cache
    key, metrics psum-merged per dispatch — identical to the XLA mesh."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "1")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    monkeypatch.setenv("RAFT_TPU_PALLAS_ROUNDS", "2")
    dev = jax.devices()[:2]
    sx = ShardedFusedCluster(G, V, seed=7, shape=_shape(), engine="xla",
                             devices=dev)
    sp = ShardedFusedCluster(G, V, seed=7, shape=_shape(), engine="pallas",
                             tile_lanes=V, devices=dev)
    sx.run(7, auto_propose=True)  # 3 full K=2 calls + a 1-round tail
    sp.run(7, auto_propose=True)
    assert sp.inner.engine == "pallas"
    assert sp._shard_rounds == 2
    assert any(k[-1] == 2 for k in sp._cache)  # K rides the stepper key
    _assert_trees_equal(sx.inner.state, sp.inner.state, "sharded state")
    _assert_trees_equal(sx.inner.metrics, sp.inner.metrics,
                        "sharded metrics")


def test_sharded_straddle_vs_pallas(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    dev = jax.devices()[:2]
    # explicit request is a hard error (the in-kernel router is tile-local)
    with pytest.raises(ValueError, match="straddle"):
        ShardedFusedCluster(G, V, seed=1, shape=_shape(), engine="pallas",
                            straddle=True, devices=dev)
    # so is an env-selected pallas engine: no quiet switch to XLA
    monkeypatch.setenv("RAFT_TPU_ENGINE", "pallas")
    with pytest.raises(ValueError, match="straddle"):
        ShardedFusedCluster(G, V, seed=1, shape=_shape(), straddle=True,
                            devices=dev)


def test_sharded_kernel_failure_raises(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    dev = jax.devices()[:2]
    s = ShardedFusedCluster(G, V, seed=5, shape=_shape(), engine="pallas",
                            tile_lanes=V, devices=dev)
    monkeypatch.setattr(plr, "pallas_rounds", _boom)
    with pytest.raises(RuntimeError, match="forced pallas kernel failure"):
        s.run(4, auto_propose=True)
    assert s.inner.engine == "pallas"

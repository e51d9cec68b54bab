"""Sharded (multi-chip) cluster tests on the virtual 8-device CPU mesh:
the shard_map round must behave identically to the single-device round."""

import jax
import numpy as np
import pytest

from raft_tpu.cluster import Cluster
from raft_tpu.parallel.sharded import ShardedCluster


# the largest shard_map programs abort XLA's CPU executable serializer
pytestmark = pytest.mark.usefixtures("no_persistent_cache")


@pytest.fixture(scope="module")
def devices():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs 8 virtual devices")
    return d[:8]


def test_sharded_matches_single_device(devices):
    g, v = 16, 3
    ref = Cluster(g, v, seed=3)
    sh = ShardedCluster(g, v, devices=devices, seed=3)
    for _ in range(40):
        ref.tick(1)
        sh.tick(1)
        if len(sh.leader_lanes()) == g:
            break
    for name in ("term", "state", "lead", "committed", "last"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref.state, name)),
            np.asarray(getattr(sh.state, name)),
            err_msg=name,
        )
    assert len(sh.leader_lanes()) == g
    sh.check_no_errors()


def test_sharded_replication(devices):
    g, v = 8, 3
    sh = ShardedCluster(g, v, devices=devices, seed=5)
    for _ in range(40):
        sh.tick(1)
        if len(sh.leader_lanes()) == g:
            break
    assert len(sh.leader_lanes()) == g
    for lane in sh.leader_lanes():
        sh.propose(int(lane), 8)
    sh.settle()
    committed = np.asarray(sh.state.committed)
    for grp in range(g):
        lanes = sh.lanes_of_group(grp)
        assert (committed[lanes] == committed[lanes][0]).all()
        assert committed[lanes][0] >= 2
    sh.check_no_errors()


def test_device_resident_rounds(devices):
    sh = ShardedCluster(8, 3, devices=devices, seed=9)
    sh.run_device_rounds(40, do_tick=True)
    assert len(sh.leader_lanes()) == 8
    sh.check_no_errors()


def test_scanned_rounds_match_stepwise(devices):
    """cluster_rounds/run_scanned (one dispatch per block) must land in the
    same state as per-round dispatch."""
    g, v = 8, 3
    a = ShardedCluster(g, v, devices=devices, seed=11)
    b = ShardedCluster(g, v, devices=devices, seed=11)
    a.tick(24)
    b.run_scanned(24, do_tick=True)
    for name in ("term", "state", "lead", "committed", "last"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, name)),
            np.asarray(getattr(b.state, name)),
            err_msg=name,
        )
    a.check_no_errors()
    b.check_no_errors()


def test_scanned_rounds_single_device():
    from raft_tpu.cluster import Cluster

    a = Cluster(6, 3, seed=13)
    b = Cluster(6, 3, seed=13)
    a.tick(20)
    b.run_scanned(20, do_tick=True)
    for name in ("term", "state", "lead", "committed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, name)),
            np.asarray(getattr(b.state, name)),
            err_msg=name,
        )


def test_sharded_fused_cluster_elects_and_commits():
    """The fused round kernel under shard_map: elections + steady-state
    commits across an 8-device mesh, no collectives in the round body."""
    import numpy as np

    from raft_tpu.parallel.sharded import ShardedFusedCluster

    sh = ShardedFusedCluster(n_groups=16, n_voters=3)
    sh.run(60)
    sh.check_no_errors()
    assert len(sh.leader_lanes()) == 16
    com0 = np.asarray(sh.state.committed).copy()
    sh.run(20, auto_propose=True, auto_compact_lag=8)
    sh.check_no_errors()
    com1 = np.asarray(sh.state.committed)
    assert (com1 - com0 >= 10).all()


def test_straddling_groups_elect_and_commit(devices):
    """Cross-shard groups (SURVEY §5.8): 10 groups x 4 voters over 8 shards
    (5 lanes/shard) — several groups straddle shard boundaries, so votes,
    appends, and acks cross the mesh through route_cross_shard's
    all_to_all. Every group elects and commits."""
    import numpy as np

    from raft_tpu.parallel.sharded import ShardedCluster

    c = ShardedCluster(n_groups=10, n_voters=4, devices=devices, straddle=True)
    c.run_device_rounds(60, do_tick=True)
    c.check_no_errors()
    assert len(c.leader_lanes()) == 10

    # proposals on every leader lane commit group-wide, including across
    # the shard boundary
    for lane in c.leader_lanes():
        c.propose(int(lane), n_bytes=3)
    com0 = np.asarray(c.state.committed).reshape(10, 4).max(axis=1).copy()
    c.run_device_rounds(6, do_tick=False)
    c.check_no_errors()
    com1 = np.asarray(c.state.committed).reshape(10, 4)
    assert (com1.max(axis=1) == com0 + 1).all(), (com0, com1.max(axis=1))
    # followers across the boundary converge too
    assert (com1.min(axis=1) >= com0).all()


def test_fused_straddling_groups_elect_and_commit(devices):
    """Fused-path cross-shard groups (VERDICT r4 item 4): 10 groups x 4
    voters over 8 shards (5 lanes/shard) — several groups straddle shard
    boundaries, so the fabric's votes, appends, and acks cross the mesh
    through the halo router's ppermutes. Every group elects and commits."""
    import numpy as np

    from raft_tpu.parallel.sharded import ShardedFusedCluster

    sh = ShardedFusedCluster(
        n_groups=10, n_voters=4, devices=devices, straddle=True
    )
    sh.run(60)
    sh.check_no_errors()
    assert len(sh.leader_lanes()) == 10
    com0 = np.asarray(sh.state.committed).copy()
    sh.run(20, auto_propose=True, auto_compact_lag=8)
    sh.check_no_errors()
    com1 = np.asarray(sh.state.committed)
    assert (com1 - com0 >= 10).all()


def test_fused_straddle_matches_unsharded_bitwise(devices):
    """The halo router computes the same global delivery as the
    single-device fabric routing, so a straddling sharded run must land in
    the BIT-IDENTICAL state as an unsharded FusedCluster run — across
    elections, proposals, a transfer, and a partition (mute) phase."""
    import numpy as np

    from raft_tpu.ops.fused import FusedCluster
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    g, v = 10, 4
    ref = FusedCluster(g, v, seed=21)
    sh = ShardedFusedCluster(
        n_groups=g, n_voters=v, devices=devices, seed=21, straddle=True
    )

    def drive(c):
        c.run(40)
        c.run(10, auto_propose=True, auto_compact_lag=8)
        # leadership transfer in group 2 (lanes straddle shards 1|2)
        c.run(1, ops=c.ops(transfer_to={2 * v: 2}), do_tick=False)
        c.run(10)
        # partition group 5's member 0, then heal
        c.set_mute([5 * v], True)
        c.run(30, auto_propose=True)
        c.set_mute([5 * v], False)
        c.run(20, auto_propose=True)

    drive(ref)
    drive(sh)
    for f in (
        "term", "vote", "lead", "state", "committed", "last", "applied",
        "log_term", "snap_index", "error_bits",
    ):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref.state, f)),
            np.asarray(getattr(sh.state, f)),
            err_msg=f,
        )


def test_straddle_matches_aligned_results(devices):
    """With an aligned layout (no straddling), the cross-shard router must
    produce the same behavior as the shard-local router."""
    import numpy as np

    from raft_tpu.parallel.sharded import ShardedCluster

    a = ShardedCluster(n_groups=8, n_voters=3, devices=devices, straddle=False)
    b = ShardedCluster(n_groups=8, n_voters=3, devices=devices, straddle=True)
    a.run_device_rounds(40, do_tick=True)
    b.run_device_rounds(40, do_tick=True)
    for name in ("term", "state", "lead", "committed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, name)),
            np.asarray(getattr(b.state, name)),
            err_msg=name,
        )

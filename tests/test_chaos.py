"""Device-resident chaos plane (raft_tpu/chaos/).

Three contracts from the PR's acceptance bar:

1. RAFT_TPU_CHAOS=0 (the default) elides the plane from the traced
   program entirely — the scan carry holds no chaos-shaped values, and a
   chaos-on run with all-quiet fault columns is BITWISE identical to a
   chaos-off run (the masks gate at trace time, not with where()s that
   could perturb rounding or buffer layout).
2. Determinism: the counter-based fault PRNG makes same-seed runs
   bit-identical — in-process, across OS processes, and across the
   donation toggle (jax 0.4.37 donation workaround included).
3. Crash != amnesia: a crashed lane freezes, restarts as a follower, and
   keeps exactly the WalStream.FIELDS persisted set (term/vote/log/
   committed survive; leadership and timers do not).

Plus the engine integrations: BlockedFusedCluster global-lane column
slicing/aggregation, ShardedFusedCluster psum'd recovery tallies, the
ChaosRunner recovery-SLO probe, and the batched election-safety oracle.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from raft_tpu.chaos import ChaosRunner, ChaosSchedule, trajectory_digest
from raft_tpu.chaos.device import NEVER, init_chaos, probability
from raft_tpu.ops.fused import FusedCluster, fused_rounds, no_ops
from raft_tpu.scheduler import BlockedFusedCluster
from raft_tpu.types import StateType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _np_tree(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


def _assert_tree_equal(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), what


# -- compile-time gate -----------------------------------------------------


def _carry_avals(jaxpr):
    out = set()
    for eqn in jaxpr.jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.add((tuple(aval.shape), str(getattr(aval, "dtype", ""))))
    return out


def test_chaos_off_by_default(monkeypatch):
    monkeypatch.delenv("RAFT_TPU_CHAOS", raising=False)
    c = FusedCluster(1, 3, seed=2)
    assert c.chaos is None
    assert c.chaos_columns() == {}
    with pytest.raises(RuntimeError, match="chaos plane is off"):
        c.set_chaos(heal_round=0)
    c.run(2)


def test_chaos_off_elides_from_jaxpr(monkeypatch):
    """The chaos-off jaxpr must be today's fused round: no chaos-shaped
    values anywhere in the traced program. The plane's unique fingerprint
    is its scalar uint32 PRNG seed — no other carry leaf has that aval."""
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    c = FusedCluster(1, 3, seed=2)
    n = c.shape.n

    off = jax.make_jaxpr(
        lambda st, f: fused_rounds(st, f, no_ops(n), None, v=3, n_rounds=2)
    )(c.state, c.fab)
    assert ((), "uint32") not in _carry_avals(off)

    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    ch = init_chaos(n, 3, seed=2)
    on = jax.make_jaxpr(
        lambda st, f, chz: fused_rounds(
            st, f, no_ops(n), None, v=3, n_rounds=2, chaos=chz
        )
    )(c.state, c.fab, ch)
    # detector sanity: the same probe DOES see the seed when enabled
    assert ((), "uint32") in _carry_avals(on)


def test_quiet_chaos_bitwise_equals_chaos_off(monkeypatch):
    """Chaos enabled but all-quiet (no faults installed) must reproduce
    the chaos-off trajectory bit for bit: the fault masks default to
    pass-through, and the probe writes touch only chaos's own columns."""
    runs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("RAFT_TPU_CHAOS", flag)
        c = FusedCluster(4, 3, seed=11)
        assert (c.chaos is not None) == (flag == "1")
        c.run(16, auto_propose=True, auto_compact_lag=4)
        c.run(16, auto_propose=True, auto_compact_lag=4)
        runs[flag] = (_np_tree(c.state), _np_tree(c.fab))
    _assert_tree_equal(runs["0"][0], runs["1"][0], "state diverged")
    _assert_tree_equal(runs["0"][1], runs["1"][1], "fabric diverged")


# -- determinism -----------------------------------------------------------


def _faulted_run(seed: int):
    c = FusedCluster(4, 3, seed=seed)
    n = 12
    c.run(16, auto_propose=True, auto_compact_lag=4)
    c.set_chaos(
        drop_num=np.full((n, 3), probability(0.3), np.int32),
        dup_num=np.full((n, 3), probability(0.3), np.int32),
        tick_skew_num=np.full(n, probability(0.5), np.int32),
    )
    c.run(16, auto_propose=True, auto_compact_lag=4)
    c.set_chaos(
        drop_num=np.zeros((n, 3), np.int32),
        dup_num=np.zeros((n, 3), np.int32),
        tick_skew_num=np.zeros(n, np.int32),
    )
    c.run(16, auto_propose=True, auto_compact_lag=4)
    c.check_no_errors()
    return c


def test_same_seed_bit_identical_with_faults(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    a, b = _faulted_run(23), _faulted_run(23)
    assert trajectory_digest(a) == trajectory_digest(b)
    # and the faults actually bit: the noisy trajectory differs from a
    # quiet one with the same raft seed
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    q = FusedCluster(4, 3, seed=23)
    for _ in range(3):
        q.run(16, auto_propose=True, auto_compact_lag=4)
    assert trajectory_digest(a) != trajectory_digest(q)


_SUBPROC = """\
import os, sys
sys.path.insert(0, {repo!r})
os.environ["RAFT_TPU_CHAOS"] = "1"
import numpy as np
from raft_tpu.chaos import trajectory_digest
from raft_tpu.chaos.device import probability
from raft_tpu.ops.fused import FusedCluster

c = FusedCluster(4, 3, seed=31)
c.set_chaos(drop_num=np.full((12, 3), probability(0.25), np.int32))
c.run(24, auto_propose=True, auto_compact_lag=4)
print(trajectory_digest(c))
"""


def test_determinism_across_processes():
    """Same seed, two OS processes: bit-identical final state. This is
    the paper-grade reproducibility claim — nothing in the fault path
    reads wall clock, object ids, or hash randomization."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0")
    digests = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _SUBPROC.format(repo=REPO)],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        digests.append(out.stdout.strip().splitlines()[-1])
    assert digests[0] == digests[1]


def test_donation_parity_under_chaos(monkeypatch):
    """RAFT_TPU_DONATE=0 and =1 produce bit-identical chaos trajectories:
    every donated ChaosState field owns its buffer, so in-place execution
    never aliases a mask into a probe column (jax 0.4.37 workaround:
    the fused path's cache fence covers the chaos carry too)."""
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    digests = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("RAFT_TPU_DONATE", flag)
        c = _faulted_run(47)
        assert c._donate == (flag == "1")
        digests[flag] = trajectory_digest(c)
    assert digests["0"] == digests["1"]


# -- crash/restart semantics ----------------------------------------------


def test_crash_freezes_lane_and_preserves_hardstate(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    c = FusedCluster(2, 3, seed=5)
    c.run(32, auto_propose=True, auto_compact_lag=4)
    c.check_no_errors()
    leaders = c.leader_lanes()
    assert len(leaders) == 2
    victim = int(leaders[0])

    r = int(np.asarray(c.chaos.round))
    crash_at = np.full(6, NEVER, np.int32)
    restart_at = np.full(6, NEVER, np.int32)
    crash_at[victim] = r + 2
    restart_at[victim] = r + 10
    c.set_chaos(crash_at=crash_at, restart_at=restart_at)
    c.run(4, auto_propose=True, auto_compact_lag=4)  # into the window

    st = np.asarray(c.state.state)
    tm = np.asarray(c.state.term)
    com = np.asarray(c.state.committed)
    vt = np.asarray(c.state.vote)
    last = np.asarray(c.state.last)
    # crashed: volatile leadership gone, a follower with timers dark
    assert st[victim] == int(StateType.FOLLOWER)
    frozen = (tm[victim], com[victim], vt[victim], last[victim])

    c.run(4, auto_propose=True, auto_compact_lag=4)  # still down
    tm2 = np.asarray(c.state.term)
    com2 = np.asarray(c.state.committed)
    vt2 = np.asarray(c.state.vote)
    last2 = np.asarray(c.state.last)
    # the crashed window is a total freeze: no ticks, no inbound, no ops
    assert (tm2[victim], com2[victim], vt2[victim], last2[victim]) == frozen
    assert np.asarray(c.state.state)[victim] == int(StateType.FOLLOWER)

    c.run(40, auto_propose=True, auto_compact_lag=4)  # restart + settle
    c.check_no_errors()
    tm3 = np.asarray(c.state.term)
    com3 = np.asarray(c.state.committed)
    # HardState survived the restart: term never regressed, and the lane
    # rejoined — its committed cursor moved PAST the frozen value
    assert tm3[victim] >= frozen[0]
    assert com3[victim] > frozen[1]
    # the group as a whole recovered a leader
    g0 = victim // 3
    stf = np.asarray(c.state.state).reshape(2, 3)
    assert (stf[g0] == int(StateType.LEADER)).sum() == 1


# -- scenario runner + SLO -------------------------------------------------


def test_runner_partition_recovery_slo(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    sched = ChaosSchedule(4, 3).partition(groups=[1, 3], at=8, duration=8)
    c = FusedCluster(4, 3, seed=13)
    runner = ChaosRunner(c, sched, tick_budget=48, settle=40)
    snap = runner.run()
    assert snap["slo"]["ok"], snap
    assert snap["counters"]["chaos_groups_probed"] == 2
    assert snap["counters"]["chaos_unrecovered"] == 0
    assert len(snap["phases"]) == 1
    assert snap["phases"][0]["groups"] == [1, 3]
    assert all(t >= 1 for t in snap["phases"][0]["reelect_ticks"])
    assert snap["hist_reelect"]["count"] == 2
    assert snap["hist_recommit"]["count"] == 2


def test_runner_requires_chaos_plane(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    c = FusedCluster(4, 3, seed=13)
    sched = ChaosSchedule(4, 3).partition(groups=[0], at=4, duration=4)
    with pytest.raises(RuntimeError, match="no chaos plane"):
        ChaosRunner(c, sched)


def test_chaos_straddle_mutually_exclusive(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    c = FusedCluster(1, 3, seed=2)
    with pytest.raises(ValueError, match="straddl"):
        fused_rounds(
            c.state, c.fab, no_ops(3), None, v=3, n_rounds=1,
            chaos=c.chaos, straddle=object(),
        )


# -- schedule DSL ----------------------------------------------------------


def test_schedule_columns_and_segments():
    sched = (
        ChaosSchedule(4, 3)
        .partition(groups=[0], at=4, duration=6)
        .kill(lanes=[5], at=6, down=3)
        .drop(groups=[2], at=4, duration=8, prob=0.5)
    )
    # segment cuts at every event edge and heal
    segs = sched.segments(settle=10)
    cuts = [a for a, _ in segs] + [segs[-1][1]]
    for edge in (4, 6, 9, 10, 12):
        assert edge in cuts, (edge, cuts)
    cols = sched.columns(4)
    # partitioned minority (member 0 of group 0) vs majority masks
    assert cols["part_send"][0] == 2 and cols["part_recv"][0] == 2
    assert cols["part_send"][1] == 1 and cols["part_recv"][1] == 1
    # drop probability lands on group 2's inbound edges only
    p = probability(0.5)
    assert (cols["drop_num"][6:9] == p).all()
    assert (cols["drop_num"][:6] == 0).all()
    # the kill window is visible from a segment inside it
    cols6 = sched.columns(6)
    assert cols6["crash_at"][5] == 6 and cols6["restart_at"][5] == 9
    with pytest.raises(ValueError):
        ChaosSchedule(4, 3).partition(groups=[0], at=0, duration=1,
                                      members=(0, 1, 2))


# -- blocked + sharded engines ---------------------------------------------


def test_blocked_set_chaos_slices_global_columns(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    bc = BlockedFusedCluster(4, 3, block_groups=2, seed=3)
    assert bc.chaos_enabled
    n = 12
    crash = np.full(n, NEVER, np.int32)
    crash[1] = 100   # block 0, lane 1
    crash[7] = 200   # block 1, lane 1
    bc.set_chaos(crash_at=crash, heal_round=77)
    assert int(np.asarray(bc.blocks[0].chaos.crash_at)[1]) == 100
    assert int(np.asarray(bc.blocks[1].chaos.crash_at)[1]) == 200
    assert int(np.asarray(bc.blocks[0].chaos.heal_round)) == 77
    assert int(np.asarray(bc.blocks[1].chaos.heal_round)) == 77
    cols = bc.chaos_columns("crash_at", "heal_round", "n_reelected")
    assert cols["crash_at"].shape == (n,)
    assert cols["crash_at"][1] == 100 and cols["crash_at"][7] == 200
    assert cols["heal_round"] == 77
    assert cols["n_reelected"] == 0  # summed across blocks


def test_sharded_chaos_recovery_psum(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    devs = jax.devices()
    if 8 % len(devs):
        pytest.skip("needs a device count dividing 8 groups")
    sc = ShardedFusedCluster(8, 3, seed=9)
    assert sc.chaos is not None
    n = 24
    sc.run(24, auto_propose=True, auto_compact_lag=4)
    sc.check_no_errors()
    send = np.ones(n, np.int32)
    recv = np.ones(n, np.int32)
    send[[0, 21]] = 2
    recv[[0, 21]] = 2
    sc.set_chaos(part_send=send, part_recv=recv)
    sc.run(24, auto_propose=True, auto_compact_lag=4)
    r = int(np.asarray(sc.chaos.round))
    sc.set_chaos(
        part_send=np.ones(n, np.int32), part_recv=np.ones(n, np.int32),
        heal_round=r,
        reelect_round=np.full(n, NEVER, np.int32),
        recommit_round=np.full(n, NEVER, np.int32),
    )
    sc.run(24, auto_propose=True, auto_compact_lag=4)
    sc.check_no_errors()
    cols = sc.chaos_columns()
    # the recovery tallies are psum'd across shards: all 8 groups, once
    assert int(cols["n_reelected"]) == 8
    assert int(cols["n_recommitted"]) == 8
    assert cols["reelect_round"].shape == (n,)
    assert (cols["reelect_round"] != NEVER).all()


def test_sharded_chaos_rejects_straddle(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_CHAOS", "1")
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    with pytest.raises(ValueError, match="chaos \\+ straddle"):
        ShardedFusedCluster(8, 3, straddle=True)


# -- invariants ------------------------------------------------------------


def test_election_safety_batched_oracle(monkeypatch):
    from raft_tpu.testing.invariants import election_safety_batched

    monkeypatch.setenv("RAFT_TPU_CHAOS", "0")
    c = FusedCluster(4, 3, seed=2)
    c.run(24, auto_propose=True)
    election_safety_batched(c)  # healthy: passes

    # doctor a same-term double leader into group 1
    st = np.asarray(c.state.state).copy()
    tm = np.asarray(c.state.term).copy()
    st[:] = int(StateType.FOLLOWER)
    st[3] = st[4] = int(StateType.LEADER)
    tm[3] = tm[4] = 9
    bad = dataclasses.replace(
        c.state,
        state=jax.numpy.asarray(st, c.state.state.dtype),
        term=jax.numpy.asarray(tm, c.state.term.dtype),
    )

    class Doctored:
        v = 3
        g = 4
        state = bad

    with pytest.raises(AssertionError, match="group"):
        election_safety_batched(Doctored())
    # a stale leader in a DIFFERENT term is legal (partition aftermath)
    tm[3] = 8
    Doctored.state = dataclasses.replace(
        bad, term=jax.numpy.asarray(tm, c.state.term.dtype)
    )
    election_safety_batched(Doctored())

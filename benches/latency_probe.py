"""Commit-index latency probe — the second half of the BASELINE.json
metric ("groups x ticks/sec; commit-index latency @1M groups").

Measures, at a given resident group count:
  - in-fabric commit latency: rounds from proposal injection until every
    group's commit index covers it (the fused engine's propose->commit
    pipeline: append in round t, quorum-ack + commit in t+1), converted to
    wall time at the measured round rate;
  - client-visible latency: wall time of the same thing driven as one
    dispatch per round (what a host-side proposer would observe through
    the dispatch path, including per-dispatch latency).

Prints one JSON line per shape.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from raft_tpu.utils.compile_cache import enable_persistent_cache

if jax.default_backend() != "cpu":
    enable_persistent_cache()
import numpy as np


def measure(n_groups, n_voters, w=8, e=1):
    from raft_tpu.config import Shape
    from raft_tpu.ops.fused import FusedCluster

    shape = Shape(
        n_lanes=n_groups * n_voters,
        max_peers=n_voters,
        log_window=w,
        max_msg_entries=e,
        max_inflight=1,
        max_read_index=2,
    )
    c = FusedCluster(n_groups, n_voters, seed=13, shape=shape)
    lag = w // 2
    block = 16
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    jax.block_until_ready(c.state.term)
    warm = 0
    while len(c.leader_lanes()) < n_groups and warm < 40 * block:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm += block
    # warm every program variant the timed region uses (each distinct
    # (n_rounds, do_tick, auto_propose) tuple is its own XLA program)
    c.run(block, auto_compact_lag=lag)
    c.run(1, do_tick=False, auto_compact_lag=lag)
    jax.block_until_ready(c.state.term)

    # steady-state round rate (for the in-fabric conversion)
    t0 = time.perf_counter()
    c.run(block, auto_compact_lag=lag)
    jax.block_until_ready(c.state.term)
    round_s = (time.perf_counter() - t0) / block

    # inject ONE proposal at every leader; count rounds to full commit
    com0 = np.asarray(c.state.committed).copy()
    leaders = c.leader_lanes()
    prop = {int(l): 1 for l in leaders}
    t0 = time.perf_counter()
    c.run(1, ops=c.ops(prop_n=prop), do_tick=False, auto_compact_lag=lag)
    rounds = 1
    while True:
        com = np.asarray(c.state.committed)
        if (com[leaders] > com0[leaders]).all():
            break
        if rounds > 16:
            raise RuntimeError("proposal did not commit")
        c.run(1, do_tick=False, auto_compact_lag=lag)
        rounds += 1
    client_s = time.perf_counter() - t0
    c.check_no_errors()
    print(
        json.dumps(
            {
                "groups": n_groups,
                "voters": n_voters,
                "commit_rounds": rounds,
                "round_ms": round(1000 * round_s, 3),
                "in_fabric_commit_ms": round(1000 * round_s * rounds, 3),
                "client_visible_commit_ms": round(1000 * client_s, 3),
            }
        ),
        flush=True,
    )
    del c


def measure_blocked(n_groups, n_voters, block_groups, w=16, e=2):
    """Commit latency AT 1M resident groups (the literal BASELINE.json
    metric), via the blocked scheduler: the proposer's group lives in one
    64k-group block, so its commit needs 3 rounds of THAT block, not of a
    1M-lane kernel. Two figures:

      - quiet fabric: only the proposer's block is stepped (a priority
        scheduler's best case);
      - busy fabric: a full aggregate round over all K blocks is already
        enqueued when the proposal arrives (worst-case queueing behind one
        in-flight round of every other block on the single chip).
    """
    from raft_tpu.config import Shape
    from raft_tpu.scheduler import BlockedFusedCluster

    shape = Shape(
        n_lanes=block_groups * n_voters,
        max_peers=n_voters,
        log_window=w,
        max_msg_entries=e,
        max_inflight=min(8, e),
        max_read_index=2,
    )
    c = BlockedFusedCluster(
        n_groups, n_voters, block_groups=block_groups, seed=13, shape=shape
    )
    lag = min(8, w // 2)
    block = 16
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()
    warm = 0
    while c.leader_count() < n_groups and warm < 40 * block:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm += block
    b0 = c.blocks[0]
    # warm every program variant the timed region uses (shared by all
    # blocks: one compile serves the whole aggregate)
    b0.run(1, do_tick=False, auto_compact_lag=lag)
    c.run(1, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()

    # one block's steady round rate inside a scan (the in-fabric basis:
    # what a co-located host pays per round, without per-dispatch cost).
    # Two-point slope — time 1 dispatch and 1+K dispatches and divide the
    # difference — so the constant sync cost inside block_until_ready
    # cancels instead of biasing the per-round figure.
    def timed(n_disp):
        # min of 3 bounds the run-to-run spread of the sync cost so the
        # two-point subtraction really cancels the constant
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n_disp):
                b0.run(block, auto_propose=True, auto_compact_lag=lag)
            jax.block_until_ready(b0.state.term)
            best = min(best, time.perf_counter() - t0)
        return best

    timed(1)  # warm
    extra = 8
    block_round_ms = 1000 * (timed(1 + extra) - timed(1)) / (extra * block)
    assert block_round_ms > 0, "RTT variance swamped the slope window"

    def commit_block0(label, enqueue_aggregate):
        leaders = b0.leader_lanes()
        t0 = time.perf_counter()
        if enqueue_aggregate:  # one in-flight round of every block
            c.run(1, auto_propose=True, auto_compact_lag=lag)
        b0.run(
            1,
            ops=b0.ops(prop_n={int(l): 1 for l in leaders}),
            do_tick=False,
            auto_compact_lag=lag,
        )
        # the injected proposal's index: the leader's last entry after the
        # injection round (no later appends — subsequent rounds run without
        # tick or auto-propose), so commit >= this index is exactly "the
        # injected entry committed" even when the in-flight aggregate
        # round's auto-proposed entries commit in between
        target = np.asarray(b0.state.last)[leaders].copy()
        rounds = 1
        while True:
            com = np.asarray(b0.state.committed)
            if (com[leaders] >= target).all():
                break
            if rounds > 16:
                raise RuntimeError("proposal did not commit")
            b0.run(1, do_tick=False, auto_compact_lag=lag)
            rounds += 1
        dt = time.perf_counter() - t0
        c.check_no_errors()
        print(
            json.dumps(
                {
                    "resident_groups": n_groups,
                    "voters": n_voters,
                    "block_groups": block_groups,
                    "scenario": label,
                    "commit_rounds": rounds,
                    "block_round_ms": round(block_round_ms, 3),
                    "in_fabric_commit_ms": round(block_round_ms * rounds, 3),
                    "client_visible_commit_ms": round(1000 * dt, 3),
                }
            ),
            flush=True,
        )

    commit_block0("quiet_fabric", enqueue_aggregate=False)
    commit_block0("busy_fabric_1_aggregate_round_inflight", enqueue_aggregate=True)


if __name__ == "__main__":
    voters = int(os.environ.get("LAT_VOTERS", 3))
    if os.environ.get("LAT_BLOCKED", "0") not in ("", "0"):
        measure_blocked(
            int(os.environ.get("LAT_GROUPS", 1048576)),
            voters,
            int(os.environ.get("LAT_BLOCK_GROUPS", 65536)),
        )
    else:
        for g in [
            int(x)
            for x in os.environ.get("LAT_GROUPS", "16384,262144").split(",")
        ]:
            measure(g, voters)

"""The five BASELINE.json benchmark configs (see README.md, "North star").

Each config prints one JSON line; `python -m benches.baseline_configs [N...]`
runs the selected configs (default: all). The Go reference publishes no
numbers — these are the TPU engine's measurements of the same workload
shapes the reference's benchmark harnesses define:

1. 3-node single-group, 1k proposals            (rafttest/node_bench_test.go:25)
2. 1k x 3-voter groups, synchronized heartbeat  (quorum/bench_test.go via tick path)
3. 100k x 5 voters, steady MsgAppResp fan-in    (raft.go:1333-1526 hot loop)
4. 100k groups joint-consensus + replace-leader (quorum/joint.go + raft.go:1587)
5. max-resident x 7 voters, mixed election+replication, randomized timeouts

Configs 2-5 run on the fused engine (ops/fused.py) — the throughput path;
config 1 is a latency measurement of the single-group propose->commit loop.
"""

from __future__ import annotations

import json
import sys
import time

import jax

from raft_tpu.utils.compile_cache import enable_persistent_cache

if jax.default_backend() != "cpu":
    enable_persistent_cache()
import jax.numpy as jnp
import numpy as np


def _lean_shape(n_groups, v):
    """The lean resident window shared by the batch-scale configs
    (a pre-growth W/E A/B): steady state commits one entry per group per
    round under continuous compaction, so HBM traffic — the round's bound —
    scales with W and E, not with the workload."""
    from raft_tpu.config import Shape

    return Shape(
        n_lanes=n_groups * v, max_peers=v, log_window=16,
        max_msg_entries=2, max_inflight=2, max_read_index=2,
    )


def _emit(name, value, unit, extra):
    print(
        json.dumps(
            {"config": name, "value": round(value, 1), "unit": unit, "extra": extra}
        ),
        flush=True,
    )


def config1_single_group_proposals(n_proposals=1000):
    """Committed proposals/sec on ONE 3-voter group — the analog of
    BenchmarkProposal3Nodes (rafttest/node_bench_test.go:25).

    Two client models, both reported:
      - serial client: one outstanding proposal (1/round) — the pure
        propose->commit latency bound;
      - pipelined client: E outstanding proposals per round (the reference
        under load carries several entries per Ready/MsgApp, and its bench
        loop keeps proposals continuously queued) — the throughput figure.
    The whole run is device-resident via the multi-round scan
    (cluster-of-1 on the fused engine, blocks of 100 rounds/dispatch)."""
    import os

    from raft_tpu.config import Shape
    from raft_tpu.ops.fused import FusedCluster

    e = int(os.environ.get("BENCH1_ENTRIES", 8))
    shape = Shape(
        n_lanes=3, max_peers=3, log_window=64, max_msg_entries=e,
        max_inflight=2,
    )
    c = FusedCluster(1, 3, seed=2, shape=shape)
    c.run(40)
    leaders = c.leader_lanes()
    assert len(leaders) == 1
    lead = int(leaders[0])
    blocks, block = 10, 100
    res = {}
    for label, prop_n in (("serial", 1), ("pipelined", e)):
        ops = c.ops(prop_n={lead: prop_n})
        c.run(
            block, ops=ops, ops_first_round_only=False, auto_compact_lag=32
        )  # warm the exact program
        com0 = int(np.asarray(c.state.committed)[0])
        t0 = time.perf_counter()
        for _ in range(blocks):
            c.run(
                block, ops=ops, ops_first_round_only=False, auto_compact_lag=32
            )
        jax.block_until_ready(c.state.term)
        dt = time.perf_counter() - t0
        commits = int(np.asarray(c.state.committed)[0]) - com0
        res[label] = (commits / dt, 1e6 * dt / (blocks * block), commits)
    c.check_no_errors()
    _emit(
        "1_single_group_1k_proposals",
        res["pipelined"][0],
        "proposals_committed/s",
        {
            "serial_client_proposals_per_s": round(res["serial"][0], 1),
            "outstanding": e,
            "round_us": round(res["pipelined"][1], 1),
            "note": "one resident group, device-resident multi-round scan",
        },
    )


def config2_1k_groups_heartbeat(n_groups=1024):
    """1k independent 3-voter groups, synchronized tick/heartbeat — the
    batched-quorum steady state with no proposals.

    Small batches are bound by per-dispatch latency, so like config 1 the
    run rides long multi-round scans: one dispatch covers 512 rounds."""
    from raft_tpu.ops.fused import FusedCluster

    c = FusedCluster(n_groups, 3, seed=3, shape=_lean_shape(n_groups, 3))
    c.run(40)
    assert len(c.leader_lanes()) == n_groups
    iters, block = 4, 512
    c.run(block)  # compile + warm the timed program
    t0 = time.perf_counter()
    for _ in range(iters):
        c.run(block)
    jax.block_until_ready(c.state.term)
    dt = time.perf_counter() - t0
    c.check_no_errors()
    _emit(
        "2_1k_groups_sync_heartbeat",
        n_groups * iters * block / dt,
        "groups*ticks/s",
        {"groups": n_groups, "round_ms": round(1000 * dt / (iters * block), 3),
         "rounds_per_dispatch": block},
    )


def config3_fanin_100k_x5(n_groups=100_000):
    """100k groups x 5 voters, steady-state replication: every round the
    leader fans out MsgApp to 4 peers and fans in 4 MsgAppResp + self-ack,
    committing one entry — the raft.go:1333-1526 hot pair at scale."""
    from raft_tpu.ops.fused import FusedCluster

    v = 5
    c = FusedCluster(n_groups, v, seed=4, shape=_lean_shape(n_groups, v))
    iters, block = 5, 16
    for _ in range(4):  # elections + warm the exact timed program
        c.run(block, auto_propose=True, auto_compact_lag=8)
    n_lead = len(c.leader_lanes())
    com0 = int(jnp.sum(c.state.committed))
    t0 = time.perf_counter()
    for _ in range(iters):
        c.run(block, auto_propose=True, auto_compact_lag=8)
    jax.block_until_ready(c.state.term)
    dt = time.perf_counter() - t0
    commits = int(jnp.sum(c.state.committed)) - com0
    c.check_no_errors()
    _emit(
        "3_100k_x5_appresp_fanin",
        n_groups * iters * block / dt,
        "groups*rounds/s",
        {
            "groups": n_groups,
            "voters": v,
            "leaders": n_lead,
            "commits_per_group_round": round(
                commits / (n_groups * v * iters * block), 3
            ),
            "round_ms": round(1000 * dt / (iters * block), 3),
        },
    )


def config4_joint_consensus_replace_leader(n_groups=100_000):
    """100k groups in JOINT configuration (voters_in != voters_out) driving
    commit through the two-reduction quorum (quorum/joint.go:49-75), then a
    leadership transfer in every group (the replace-leader workload)."""
    import dataclasses

    from raft_tpu.ops.fused import FusedCluster

    v = 3
    c = FusedCluster(n_groups, v, seed=5, shape=_lean_shape(n_groups, v))
    iters, block = 5, 16
    for _ in range(3):  # elections + warm the exact timed program
        c.run(block, auto_propose=True, auto_compact_lag=8)
    assert len(c.leader_lanes()) == n_groups
    # enter a joint config on device: outgoing set = same voters (the
    # degenerate-but-real joint shape the quorum math must reduce over)
    c.state = dataclasses.replace(c.state, voters_out=c.state.voters_in)
    com0 = int(jnp.sum(c.state.committed))
    t0 = time.perf_counter()
    for _ in range(iters):
        c.run(block, auto_propose=True, auto_compact_lag=8)
    jax.block_until_ready(c.state.term)
    dt = time.perf_counter() - t0
    commits_joint = int(jnp.sum(c.state.committed)) - com0
    # leave joint, then replace every leader via transfer to member 2
    c.state = dataclasses.replace(
        c.state, voters_out=jnp.zeros_like(c.state.voters_out)
    )
    leaders0 = set(int(x) for x in c.leader_lanes())
    transfer = np.zeros((n_groups * v,), np.int32)
    ll = np.fromiter(leaders0, dtype=np.int64)
    transfer[ll] = ((ll % v + 1) % v + 1).astype(np.int32)  # next member's id
    t1 = time.perf_counter()
    c.run(1, ops=c.ops(transfer_to=transfer), do_tick=False)
    c.run(10, do_tick=False)
    jax.block_until_ready(c.state.term)
    dt_x = time.perf_counter() - t1
    leaders1 = set(int(x) for x in c.leader_lanes())
    moved = len(leaders1 - leaders0)
    c.check_no_errors()
    _emit(
        "4_100k_joint_replace_leader",
        n_groups * iters * block / dt,
        "groups*rounds/s (joint quorum)",
        {
            "groups": n_groups,
            "commits_per_group_round_joint": round(
                commits_joint / (n_groups * v * iters * block), 3
            ),
            "leaders_replaced": moved,
            "replace_all_leaders_ms_incl_compile": round(1000 * dt_x, 1),
        },
    )


def config5_mixed_1m_x7(n_groups=None):
    """Largest-resident x 7 voters: mixed election (randomized timeouts from
    cold start) + steady replication — BASELINE.json's headline shape, run
    at the LITERAL 1M x 7 = 7.34M-lane size on TPU via the blocked
    scheduler (scheduler.BlockedFusedCluster): the W=8/E=1 diet shape that
    fits the whole carry in HBM, stepped as 64k-group blocks by one
    compiled kernel."""
    from raft_tpu.config import Shape
    from raft_tpu.scheduler import BlockedFusedCluster

    v = 7
    platform = jax.devices()[0].platform
    if n_groups is None:
        n_groups = 1048576 if platform == "tpu" else 256
    # largest divisor of n_groups within the block cap, so any explicit
    # n_groups keeps working (BlockedFusedCluster requires an exact split)
    cap = 65536 if platform == "tpu" else 128
    block_groups = next(
        d for d in range(min(n_groups, cap), 0, -1) if n_groups % d == 0
    )
    shape = Shape(n_lanes=block_groups * v, max_peers=v, log_window=8,
                  max_msg_entries=1, max_inflight=1, max_read_index=2)
    c = BlockedFusedCluster(
        n_groups, v, block_groups=block_groups, seed=6, shape=shape
    )
    # election phase from cold start (the mixed-workload half)
    t0 = time.perf_counter()
    rounds_e = 0
    while c.leader_count() < n_groups and rounds_e < 40 * 16:
        c.run(16)
        rounds_e += 16
    dt_elect = time.perf_counter() - t0
    n_lead = c.leader_count()
    iters, block = 5, 16
    c.run(block, auto_propose=True, auto_compact_lag=4)  # warm exact program
    c.block_until_ready()
    com0 = c.total_committed()
    t0 = time.perf_counter()
    for _ in range(iters):
        c.run(block, auto_propose=True, auto_compact_lag=4)
    c.block_until_ready()
    dt = time.perf_counter() - t0
    commits = c.total_committed() - com0
    c.check_no_errors()
    _emit(
        "5_mixed_election_replication_x7",
        n_groups * iters * block / dt,
        "groups*ticks/s",
        {
            "groups": n_groups,
            "voters": v,
            "block_groups": block_groups,
            "leaders": n_lead,
            "election_rounds": rounds_e,
            "election_s": round(dt_elect, 1),
            "commits_per_group_round": round(
                commits / (n_groups * v * iters * block), 3
            ),
            "round_ms": round(1000 * dt / (iters * block), 3),
        },
    )


CONFIGS = {
    "1": config1_single_group_proposals,
    "2": config2_1k_groups_heartbeat,
    "3": config3_fanin_100k_x5,
    "4": config4_joint_consensus_replace_leader,
    "5": config5_mixed_1m_x7,
}


def main(argv):
    which = argv or list(CONFIGS)
    for k in which:
        CONFIGS[k]()


if __name__ == "__main__":
    main(sys.argv[1:])

"""North-star benchmark: raft groups x ticks per second on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload = BASELINE.json config 5 in spirit: many independent voter groups,
election + steady-state replication with randomized timeouts; every round is
one tick of every group plus full message delivery and handling, with one
committed entry per group per round (auto-propose) and continuous
snapshot+compaction of the device log window. Everything stays
device-resident; the host only sequences blocks of rounds.

Engines (BENCH_ENGINE): "fused" (default) = the one-invocation-per-round
kernel with transpose routing (ops/fused.py); "serial" = the per-message
step scan + grouped router (cluster.py), the conformance-exact path.

`vs_baseline` is measured against the BASELINE.json target of 1M
groups*ticks/s (the reference publishes no numbers; see README.md, "North
star"). The fleet is built as chip_smoke.py builds it; a failed run fails.
"""

from __future__ import annotations

import json
import os
import time

from raft_tpu import config

import jax

from raft_tpu.utils.compile_cache import enable_persistent_cache

import jax.numpy as jnp


def run_fused(n_groups, n_voters, n_iters, block, block_groups=None):
    from raft_tpu.config import Shape
    from raft_tpu.scheduler import BlockedFusedCluster

    # lean window: steady state commits 1 entry/group/round with continuous
    # compaction, so a small resident window maximizes throughput (HBM
    # traffic scales with W and E); raise via env for bursty workloads
    w = int(os.environ.get("BENCH_WINDOW", 16))
    e = int(os.environ.get("BENCH_ENTRIES", 2))
    block_groups = block_groups or n_groups
    shape = Shape(
        n_lanes=block_groups * n_voters,
        max_peers=n_voters,
        log_window=w,
        max_msg_entries=e,
        max_inflight=min(8, e),
        max_read_index=2,
    )
    # round-major dispatch knobs (scheduler.BlockedFusedCluster): chunk > 1
    # amortizes per-dispatch host overhead between interleave points;
    # BENCH_PIPELINE_DEPTH bounds enqueued-but-unfinished dispatches
    round_chunk = int(os.environ.get("BENCH_ROUND_CHUNK", 8))
    pd = os.environ.get("BENCH_PIPELINE_DEPTH")
    pipeline_depth = int(pd) if pd else None
    c = BlockedFusedCluster(
        n_groups, n_voters, block_groups=block_groups, seed=42, shape=shape,
        round_chunk=round_chunk, pipeline_depth=pipeline_depth,
    )
    lag = min(8, w // 2)  # must leave window headroom or appends stall

    t0 = time.perf_counter()
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()
    compile_s = time.perf_counter() - t0

    # warm through the election phase so the timed region is steady state
    # (bounded: persistent split votes should fail loudly, not hang)
    warm_rounds = 0
    while c.leader_count() < n_groups:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm_rounds += block
        if warm_rounds > 40 * 16:
            raise RuntimeError(
                f"warm-up stalled: {c.leader_count()}/{n_groups} "
                f"groups elected after {warm_rounds} rounds"
            )

    com0 = c.total_committed()
    t0 = time.perf_counter()
    for _ in range(n_iters):
        c.run(block, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()
    dt = time.perf_counter() - t0
    commits = c.total_committed() - com0
    c.check_no_errors()
    assert commits > 0, "benchmark workload stalled: no entries committed"
    # HBM-peak/live-buffer probe (outside the timed region): hold the
    # pre-dispatch carry references across one more round — with donation
    # on those buffers die in place, so live bytes read strictly lower
    # than the same dispatch under RAFT_TPU_DONATE=0
    from raft_tpu.ops.fused import donation_enabled
    from raft_tpu.utils.profiling import device_memory_stats, live_buffer_bytes

    keep = [(b.state, b.fab, b.metrics) for b in c.blocks]
    c.run(1, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()
    probe = {
        "donate": donation_enabled(),
        "round_chunk": round_chunk,
        "pipeline_depth": pipeline_depth,
        "live_buffer_bytes": live_buffer_bytes(),
    }
    del keep
    mem = device_memory_stats()
    if mem is not None:
        probe["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
        probe["bytes_in_use"] = mem.get("bytes_in_use")
    # device-plane observability pull AFTER the timed region (ONE batched
    # transfer for all K blocks; None when RAFT_TPU_METRICS=0)
    return dt, compile_s, c.leader_count(), commits, c.metrics_snapshot(), probe


def run_serial(n_groups, n_voters, n_iters, block):
    from functools import partial

    from raft_tpu.cluster import Cluster, cluster_rounds

    c = Cluster(n_groups, n_voters, seed=42)
    round_fn = partial(
        cluster_rounds, m_in=c.m_in, do_tick=True, n_rounds=block, v=c.v
    )
    state = c.state
    pending = jax.tree.map(jnp.asarray, c._pending)

    t0 = time.perf_counter()
    state, pending, dropped = round_fn(state, pending, c.group_of, c.lane_of)
    jax.block_until_ready(state.term)
    compile_s = time.perf_counter() - t0

    warm_blocks = max(0, -(-32 // block) - 1)
    for _ in range(warm_blocks):
        state, pending, dropped = round_fn(state, pending, c.group_of, c.lane_of)
    jax.block_until_ready(state.term)

    com0 = int(jnp.sum(state.committed))
    t0 = time.perf_counter()
    for _ in range(n_iters):
        state, pending, dropped = round_fn(state, pending, c.group_of, c.lane_of)
    jax.block_until_ready(state.term)
    dt = time.perf_counter() - t0
    commits = int(jnp.sum(state.committed)) - com0
    n_leaders = int(jnp.sum(state.state == 2))
    return dt, compile_s, n_leaders, commits, None, None


def main():
    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "cpu":
        enable_persistent_cache()
    engine = os.environ.get("BENCH_ENGINE", "fused")
    # The headline shape is BASELINE.json config 5's 1M groups, held
    # resident as 16 blocks of 64k groups (scheduler.BlockedFusedCluster):
    # one compiled 64k-group kernel serves all 16, XLA temporaries stay at
    # block size, and the slim carry keeps 3M lanes of state on one chip.
    n_groups = int(
        os.environ.get("BENCH_GROUPS", 1048576 if platform == "tpu" else 512)
    )
    block_groups = int(
        os.environ.get(
            "BENCH_BLOCK_GROUPS", min(n_groups, 65536 if platform == "tpu" else 256)
        )
    )
    n_iters = int(os.environ.get("BENCH_ITERS", 10))
    block = int(os.environ.get("BENCH_BLOCK", 32))
    n_voters = int(os.environ.get("BENCH_VOTERS", 3))

    if engine == "fused":
        dt, compile_s, n_leaders, commits, met, probe = run_fused(
            n_groups, n_voters, n_iters, block, block_groups
        )
    else:
        dt, compile_s, n_leaders, commits, met, probe = run_serial(
            n_groups, n_voters, n_iters, block
        )

    groups_ticks_per_sec = n_groups * n_iters * block / dt
    target = 1_000_000.0
    extra = {
        "engine": engine,
        "groups": n_groups,
        "block_groups": block_groups,
        "resident_blocks": -(-n_groups // block_groups),
        "voters": n_voters,
        "leaders_elected": n_leaders,
        "commits_per_group_round": round(
            commits / (n_groups * n_voters * n_iters * block), 3
        ),
        "round_ms": round(1000 * dt / (n_iters * block), 3),
        "block": block,
        "compile_s": round(compile_s, 1),
        "platform": platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    if probe is not None:
        extra.update(probe)
    if met is not None:
        # the device metrics plane's cumulative totals (raft_tpu/metrics/)
        extra["metrics"] = {k: v for k, v in met["counters"].items() if v}
        for k in ("elections_started", "elections_won", "leader_changes",
                  "commits"):
            extra["metrics"].setdefault(k, met["counters"].get(k, 0))
        # optional exporters, mirroring what a production driver would hang
        # off the registry
        from raft_tpu.metrics.host import JsonlWriter, prometheus_text

        jsonl = config.env_raw("RAFT_TPU_METRICS_JSONL")
        if jsonl:
            JsonlWriter(jsonl).write(met, source="bench", engine=engine)
        prom = config.env_raw("RAFT_TPU_METRICS_PROM")
        if prom:
            with open(prom, "w") as f:
                f.write(prometheus_text(met))
    print(
        json.dumps(
            {
                "metric": "raft_groups_ticks_per_sec",
                "value": round(groups_ticks_per_sec, 1),
                "unit": "groups*ticks/s",
                "vs_baseline": round(groups_ticks_per_sec / target, 4),
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    main()

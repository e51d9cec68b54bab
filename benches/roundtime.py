"""Quick steady-state ms/round probe at the north-star block shape.

Times `RT_REPS` x `RT_BLOCK`-round dispatches of one 64k x 3 FusedCluster
block after warmup (elections done, committing every round), printing
best/median ms/round — the fast inner loop for A/B-ing kernel changes
(full board re-measures stay in benches/tpu_session_r5.sh).

Env: RT_GROUPS, RT_VOTERS, BENCH_WINDOW, BENCH_ENTRIES, RT_BLOCK, RT_REPS,
plus the kernel knobs under test (RAFT_TPU_UNROLL, RAFT_TPU_ROUTE, ...).
"""

from __future__ import annotations

import json
import os

import time

from raft_tpu import config

import jax

from raft_tpu.utils.compile_cache import cache_dir_from_env, enable_persistent_cache

if cache_dir_from_env() or jax.default_backend() != "cpu":
    enable_persistent_cache()


def main():
    from raft_tpu.config import Shape
    from raft_tpu.ops.fused import FusedCluster

    groups = int(os.environ.get("RT_GROUPS", 65536))
    voters = int(os.environ.get("RT_VOTERS", 3))
    w = int(os.environ.get("BENCH_WINDOW", 16))
    e = int(os.environ.get("BENCH_ENTRIES", 2))
    block = int(os.environ.get("RT_BLOCK", 32))
    reps = int(os.environ.get("RT_REPS", 6))

    shape = Shape(
        n_lanes=groups * voters,
        max_peers=voters,
        log_window=w,
        max_msg_entries=e,
        max_inflight=min(8, e),
        max_read_index=2,
    )
    c = FusedCluster(groups, voters, seed=42, shape=shape)
    lag = min(8, w // 2)

    def sync():
        jax.block_until_ready(c.state.term)

    t0 = time.perf_counter()
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    sync()
    compile_s = time.perf_counter() - t0
    c.run(2 * block, auto_propose=True, auto_compact_lag=lag)
    sync()

    # two-point timing: time 1 dispatch vs 1+reps pipelined dispatches and
    # divide the delta — the constant per-sync cost cancels.
    t0 = time.perf_counter()
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    sync()
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(1 + reps):
        c.run(block, auto_propose=True, auto_compact_lag=lag)
    sync()
    t_many = time.perf_counter() - t0
    per_round = (t_many - t_one) / (reps * block) * 1e3
    times = [per_round]
    c.check_no_errors()
    leaders = len(c.leader_lanes())

    # live-buffer/HBM probe (outside the timed region): hold the old carry
    # across one dispatch — strictly lower with donation on
    from raft_tpu.ops.fused import donation_enabled
    from raft_tpu.utils.profiling import device_memory_stats, live_buffer_bytes

    keep = (c.state, c.fab, c.metrics)
    c.run(1, auto_propose=True, auto_compact_lag=lag)
    sync()
    live = live_buffer_bytes()
    del keep
    mem = device_memory_stats()
    print(json.dumps({
        "metric": "fused_round_ms",
        "per_round_ms": round(per_round, 3),
        "one_dispatch_ms": round(t_one * 1e3, 1),
        "pipelined_ms": round(t_many * 1e3, 1),
        "groups": groups, "voters": voters, "w": w, "e": e,
        "block": block, "compile_s": round(compile_s, 1),
        "leaders": leaders,
        "unroll": config.env_str("RAFT_TPU_UNROLL", default="1"),
        "route": config.env_str("RAFT_TPU_ROUTE", default="auto"),
        "donate": donation_enabled(),
        "live_buffer_bytes": live,
        "peak_bytes_in_use": None if mem is None else mem.get("peak_bytes_in_use"),
        "platform": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()

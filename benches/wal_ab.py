"""AsyncStorageWrites A/B on the fused engine (VERDICT r2 ask #10).

The reference's AsyncStorageWrites (doc.go:172-258) exists to keep the state
machine stepping while fsync is in flight. The fused engine's in-device
persist (stabled=last inside the round) has no host I/O to overlap — the
real-deployment analog is streaming a WAL of per-block append/commit deltas
to the host. This bench measures that pipeline at scale, three ways:

  none  — no host WAL: pure device throughput (upper bound).
  sync  — synchronous WAL: after every block, block the host on fetching
          the delta (committed cursors + appended window columns) before
          dispatching the next block — the AsyncStorageWrites=false shape.
  async — pipelined WAL: dispatch block k+1, then fetch block k's delta
          while the device runs — the AsyncStorageWrites=true shape (JAX
          async dispatch gives the overlap; the fetch of an already-
          computed array and the running block proceed concurrently).

Prints one JSON line per mode.
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


def fetch_delta(state):
    """The WAL payload: everything an external durability layer needs per
    block — hard-state cursors and the resident (term, type, size) columns
    (payload bytes live host-side already)."""
    return jax.device_get(
        (
            state.term,
            state.vote,
            state.committed,
            state.last,
            state.log_term,
            state.log_type,
            state.log_bytes,
        )
    )


def run(mode: str, n_groups: int, n_voters: int, iters: int, block: int):
    from raft_tpu.config import Shape
    from raft_tpu.ops.fused import FusedCluster

    w, e = 16, 2
    shape = Shape(
        n_lanes=n_groups * n_voters,
        max_peers=n_voters,
        log_window=w,
        max_msg_entries=e,
        max_inflight=2,
    )
    c = FusedCluster(n_groups, n_voters, seed=11, shape=shape)
    lag = w // 2
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    jax.block_until_ready(c.state.term)
    warm = 0
    while len(c.leader_lanes()) < n_groups and warm < 40 * block:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm += block

    wal_bytes = 0
    t0 = time.perf_counter()
    if mode == "none":
        for _ in range(iters):
            c.run(block, auto_propose=True, auto_compact_lag=lag)
        jax.block_until_ready(c.state.term)
    elif mode == "sync":
        for _ in range(iters):
            c.run(block, auto_propose=True, auto_compact_lag=lag)
            delta = fetch_delta(c.state)  # blocks until the round block done
            wal_bytes += sum(a.nbytes for a in delta)
    elif mode == "async":
        prev = None
        for _ in range(iters):
            c.run(block, auto_propose=True, auto_compact_lag=lag)
            if prev is not None:
                # fetch the ALREADY-COMPUTED previous block while the new
                # block executes on device
                delta = jax.device_get(prev)
                wal_bytes += sum(a.nbytes for a in delta)
            prev = (
                c.state.term, c.state.vote, c.state.committed, c.state.last,
                c.state.log_term, c.state.log_type, c.state.log_bytes,
            )
        delta = jax.device_get(prev)
        wal_bytes += sum(a.nbytes for a in delta)
        jax.block_until_ready(c.state.term)
    elif mode == "engine":
        # the built-in pipeline (FusedCluster.run(wal=...)): async D2H copy
        # started at push, resolved one block behind
        from raft_tpu.runtime.wal import WalStream

        wal = WalStream()
        for _ in range(iters):
            c.run(block, auto_propose=True, auto_compact_lag=lag, wal=wal)
        wal.flush()
        jax.block_until_ready(c.state.term)
        wal_bytes = wal.bytes
    else:
        raise ValueError(mode)
    dt = time.perf_counter() - t0
    c.check_no_errors()
    print(
        json.dumps(
            {
                "mode": mode,
                "groups": n_groups,
                "voters": n_voters,
                "groups_ticks_per_s": round(n_groups * iters * block / dt, 1),
                "round_ms": round(1000 * dt / (iters * block), 3),
                "wal_mb_per_block": round(wal_bytes / max(iters, 1) / 1e6, 2),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    g = int(os.environ.get("WAL_GROUPS", 131072))
    v = int(os.environ.get("WAL_VOTERS", 3))
    iters = int(os.environ.get("WAL_ITERS", 8))
    block = int(os.environ.get("WAL_BLOCK", 16))
    for mode in os.environ.get("WAL_MODES", "none,sync,async,engine").split(","):
        run(mode, g, v, iters, block)

"""Driver `mesh_tick`: the fleet's device path across the chips of one host,
no client traffic.

The fleet is the program's `MeshBlockedCluster` over
`jax.devices()[:chips]`: K resident blocks, each a `ShardedFusedCluster`
whose lanes split contiguously over the chips, so every chip holds a slice
of every block and each block's dispatch is one `shard_map` program on all
of them. The traffic and the timed loop are `fleet_tick`'s: every group
auto-proposes one entry per round, compaction trails the commit by the
configuration's lag, rounds go round-major over the blocks in chunks of
`round_chunk`, about `queue_seconds` of chunks stay enqueued, and the
window ends once every chip has run every chunk. The metric is groups x
rounds / wall seconds.

Correctness, each check with limit 0:

- `raft_fleet`'s five checks over every group, each block on its own
  columns, up to `CHECK_WORKERS` blocks at a time: at this fleet's 16
  blocks the host holds every block's columns at once (~7 GB, twice that
  with the control);
- `psum_tally_off`: the window's change of the metrics plane's `commits`
  counter (summed on each chip, then added over the chips by the
  dispatch's psum), read per block through `metrics_snapshot()`, against
  the committed advance the host columns give (`reference/mesh_tally.py`);
- `mesh_twin_lanes_differ`: once the mesh is gone, one block (chosen by
  the seed) is built again on the first chip alone as a one-block
  `BlockedFusedCluster` with the same seed, stepped through the same
  rounds, and its lanes are compared with the mesh's.
"""

from __future__ import annotations

import math
import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import fleet
from reference import mesh_tally, raft_fleet
from record import Record

# blocks checked at once: numpy releases the interpreter while it works,
# and one 262,144-group block's check alone takes seconds
CHECK_WORKERS = min(16, os.cpu_count() or 1)


def shape(config: dict):
    from raft_tpu.config import Shape

    v = config["voters"]
    return Shape(
        n_lanes=config["block_groups"] * v,
        max_peers=v,
        log_window=config["log_window"],
        max_msg_entries=config["max_msg_entries"],
        max_inflight=config["max_inflight"],
        max_read_index=config["max_read_index"],
    )


def build(config: dict, seed: int, round_chunk: int, devices):
    from raft_tpu.parallel.mesh import MeshBlockedCluster

    return MeshBlockedCluster(
        config["groups"], config["voters"],
        block_groups=config["block_groups"], devices=devices,
        seed=fleet.fleet_seed(seed), shape=shape(config),
        round_chunk=round_chunk, straddle=config["straddle"],
        heartbeat_tick=config["heartbeat_tick"],
        election_tick=config["election_tick"],
    )


def build_twin(config: dict, seed: int, block: int, round_chunk: int):
    """One block of the fleet alone on the default (first) device: the
    one-chip scheduler with the block's seed."""
    from raft_tpu.scheduler import BlockedFusedCluster

    bg = config["block_groups"]
    return BlockedFusedCluster(
        bg, config["voters"], block_groups=bg,
        seed=fleet.fleet_seed(seed) + 7919 * block, shape=shape(config),
        round_chunk=round_chunk,
        heartbeat_tick=config["heartbeat_tick"],
        election_tick=config["election_tick"],
    )


def marker(cluster):
    """Fresh one-element arrays, one on every chip, each sliced from that
    chip's shard of the last block after the last dispatch: all are ready
    once every chip has run every chunk enqueued before them (a slice of
    the sharded array itself may wait on the first chip alone)."""
    return [s.data[:1] for s in cluster.blocks[-1].state.term.addressable_shards]


def chip_bytes(devices, key: str) -> list:
    """One allocator reading per chip (empty where the backend keeps
    none)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if stats:
            out.append(int(stats.get(key, 0)))
    return out


def commits(cluster) -> list:
    """Each block's `commits` counter, pulled wraparound-aware."""
    if not cluster.metrics_enabled:
        raise RuntimeError("the metrics plane is off (RAFT_TPU_METRICS=0): "
                           "psum_tally_off has no counter to read")
    return [b.metrics_snapshot()["counters"]["commits"]
            for b in cluster.blocks]


def span_seconds(cluster, *names) -> dict:
    return {n: cluster.stats.seconds.get(n, 0.0) for n in names}


def run(ctx) -> Record:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    chunk, lag, v = tr["round_chunk"], cfg["compact_lag"], cfg["voters"]
    devices = jax.devices()[: ctx.cell.chips]
    marks = {"start": time.perf_counter() - ctx.t0}
    with ctx.annotate("bench.setup"):
        c = build(cfg, ctx.seed, chunk, devices)
        marks["built"] = time.perf_counter() - ctx.t0
        built_bytes = chip_bytes(devices, "bytes_in_use")
        build_s = c.stats.seconds.get("mesh.build", 0.0)
        elect_rounds = fleet.elect(c, chunk, tr["elect_bound_rounds"], lag)
        marks["elected"] = time.perf_counter() - ctx.t0
        # the pipeline fills (commit trails the append by its steady lag)
        t = time.perf_counter()
        c.run(tr["steady_rounds"], auto_propose=True, auto_compact_lag=lag)
        jax.block_until_ready(marker(c))
        c.block_until_ready()
        chunk_s = (time.perf_counter() - t) * chunk / tr["steady_rounds"]
        depth = max(2, math.ceil(tr["queue_seconds"] / chunk_s))
        start = [b["committed"] for b in fleet.block_columns(c, ("committed",))]
        counted = commits(c)
        spans_before = span_seconds(c, "sched.dispatch", "sched.enqueue")
        enqueued = c.stats.counts.get("sched.enqueue", 0)
        ctx.freeze_host()
    setup_s = time.perf_counter() - ctx.t0

    rounds, queued = 0, deque()
    with ctx.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            with ctx.annotate("bench.dispatch"):
                c.run(chunk, auto_propose=True, auto_compact_lag=lag)
                queued.append(marker(c))
            rounds += chunk
            if len(queued) > depth:
                with ctx.annotate("bench.sync"):
                    jax.block_until_ready(queued.popleft())
        with ctx.annotate("bench.sync"):
            c.block_until_ready()
        window_s = time.perf_counter() - t0
    spans = {k: s - spans_before[k] for k, s in
             span_seconds(c, "sched.dispatch", "sched.enqueue").items()}
    dispatches = c.stats.counts.get("sched.enqueue", 0) - enqueued
    peaks = chip_bytes(devices, "peak_bytes_in_use")
    total_rounds = elect_rounds + tr["steady_rounds"] + rounds
    twin_block = ctx.seed % c.k

    def check(i):
        """The reference's checks of block i, on its host columns (the
        control's too when asked for)."""
        cols = c.blocks[i].state_columns(*raft_fleet.FIELDS)
        keep = i == twin_block
        out = {"part": raft_fleet.check_block(cols, start[i], rounds, v),
               "end": cols["committed"], "cols": cols if keep else None}
        if ctx.control:
            cc = raft_fleet.control_columns(cols, v)
            out.update(
                control=raft_fleet.check_block(cc, start[i], rounds, v),
                control_end=cc["committed"],
                control_cols=cc if keep else None)
        return out

    with ctx.annotate("bench.check"):
        deltas = [a - b for a, b in zip(commits(c), counted)]
        t = time.perf_counter()
        with ThreadPoolExecutor(CHECK_WORKERS) as pool:
            checked = list(pool.map(check, range(c.k)))
        after_s = {"check": time.perf_counter() - t}
        t = time.perf_counter()
        del c, queued
        twin = build_twin(cfg, ctx.seed, twin_block, chunk)
        for _ in range(total_rounds // chunk):
            twin.run(chunk, auto_propose=True, auto_compact_lag=lag)
        twin_cols = twin.blocks[0].state_columns(*raft_fleet.FIELDS)
        del twin
        after_s["twin"] = time.perf_counter() - t
    res = raft_fleet.merge([x["part"] for x in checked])
    res["psum_tally_off"] = mesh_tally.tally_off(
        deltas, start, [x["end"] for x in checked])
    res["mesh_twin_lanes_differ"] = mesh_tally.lanes_differ(
        checked[twin_block]["cols"], twin_cols, raft_fleet.FIELDS)
    notes = {
        "setup_marks_s": marks,
        "elect_rounds": elect_rounds,
        "queue_chunks": depth,
        "build_s": build_s,
        "chip_bytes_after_build": built_bytes,
        "chip_peak_bytes": peaks,
        "fullest_chip": peaks.index(max(peaks)) if peaks else None,
        "window_span_s": spans,
        "window_dispatches": dispatches,
        "window_commits_counted": sum(deltas),
        "twin": {"block": twin_block, "rounds": total_rounds},
        "after_window_s": after_s,
    }
    if ctx.control:
        cres = raft_fleet.merge([x["control"] for x in checked])
        cres["psum_tally_off"] = mesh_tally.tally_off(
            deltas, start, [x["control_end"] for x in checked])
        cres["mesh_twin_lanes_differ"] = mesh_tally.lanes_differ(
            checked[twin_block]["control_cols"], twin_cols, raft_fleet.FIELDS)
        notes["control"] = cres
    checks = raft_fleet.CHECKS + ("psum_tally_off", "mesh_twin_lanes_differ")
    return Record(
        setup_s=setup_s,
        window_s=window_s,
        attempted=res["groups"],
        failed=res["bad_groups"],
        values={
            "groups": cfg["groups"],
            "rounds": rounds,
            "chips": len(devices),
            "config": cfg,
        },
        checks=[{"name": k, "value": res[k], "limit": 0} for k in checks],
        memory_peak_bytes=max(peaks, default=0),
        notes=notes,
    )

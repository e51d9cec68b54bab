"""Driver `fleet_tick`: the fleet's device path, no client traffic.

Every group auto-proposes one entry per round and compaction trails the
commit by the configuration's lag; rounds are dispatched round-major over
the resident blocks in chunks of `round_chunk`. The host keeps about
`queue_seconds` of chunks enqueued ahead of the device, so a pause of the
host shorter than that leaves the device busy, and the window ends on
`block_until_ready` of the last chunk. The window counts the fleet rounds
that completed in it; the metric is groups x rounds / wall seconds.

Correctness: the plain raft reference (`reference/raft_fleet.py`) checks
every group of the fleet after the window against the committed column
taken before it.
"""

from __future__ import annotations

import math
import time
from collections import deque

import fleet
from reference import raft_fleet
from record import Record, memory_peak


def marker(cluster):
    """A fresh one-element array computed after the last dispatch: it is
    ready once every chunk enqueued before it has run (the carry itself is
    donated to the next chunk and cannot be waited on later)."""
    return cluster.blocks[-1].state.term[:1]


def run(ctx) -> Record:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    chunk, lag = tr["round_chunk"], cfg["compact_lag"]
    marks = {"start": time.perf_counter() - ctx.t0}
    with ctx.annotate("bench.setup"):
        c = fleet.build(cfg, ctx.seed, chunk)
        marks["built"] = time.perf_counter() - ctx.t0
        elect_rounds = fleet.elect(c, chunk, tr["elect_bound_rounds"], lag)
        marks["elected"] = time.perf_counter() - ctx.t0
        # the pipeline fills (commit trails the append by its steady lag)
        t = time.perf_counter()
        c.run(tr["steady_rounds"], auto_propose=True, auto_compact_lag=lag)
        marker(c).block_until_ready()
        c.block_until_ready()
        chunk_s = (time.perf_counter() - t) * chunk / tr["steady_rounds"]
        depth = max(2, math.ceil(tr["queue_seconds"] / chunk_s))
        start = [b["committed"] for b in fleet.block_columns(c, ("committed",))]
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
                    queued.popleft().block_until_ready()
        with ctx.annotate("bench.sync"):
            c.block_until_ready()
        window_s = time.perf_counter() - t0
    mem = memory_peak(jax.devices()[: ctx.cell.chips])

    with ctx.annotate("bench.check"):
        ends = fleet.block_columns(c, raft_fleet.FIELDS)
        del c
        parts = [
            raft_fleet.check_block(e, s, rounds, cfg["voters"])
            for e, s in zip(ends, start)
        ]
        notes = {"setup_marks_s": marks, "elect_rounds": elect_rounds,
                 "queue_chunks": depth}
        if ctx.control:
            notes["control"] = raft_fleet.merge([
                raft_fleet.check_block(
                    raft_fleet.control_columns(e, cfg["voters"]), s, rounds,
                    cfg["voters"])
                for e, s in zip(ends, start)
            ])
    res = raft_fleet.merge(parts)
    return Record(
        setup_s=setup_s,
        window_s=window_s,
        attempted=res["groups"],
        failed=res["bad_groups"],
        values={
            "groups": cfg["groups"],
            "rounds": rounds,
            "config": cfg,
        },
        checks=[
            {"name": k, "value": res[k], "limit": 0}
            for k in raft_fleet.CHECKS
        ],
        memory_peak_bytes=mem,
        notes=notes,
    )


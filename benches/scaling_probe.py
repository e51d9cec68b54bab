"""Per-lane throughput scaling probe: where does the per-lane cost grow as
the resident group count rises? (A pre-growth record, since deleted, saw
~3x from 49k to 300k lanes.) Prints one JSON line per shape.

Two ladders:
  per-size programs (default): each rung compiles its own kernel.
  PROBE_BLOCKED=1: every rung = K resident blocks of PROBE_BLOCK_GROUPS
  groups stepped by ONE compiled kernel (scheduler.BlockedFusedCluster) —
  a fresh session pays one compile for the whole ladder and reaches its
  first north-star measurement in minutes (VERDICT r3 item 8).

PROBE_DIET=0/1 forces the diet-v2 packed carry (RAFT_TPU_DIET) off/on for
every rung, and each rung's JSON line carries live_bytes_per_lane (the
utils/profiling.py live-buffer probe over the resident carry) — run the
ladder twice with the knob flipped and the pair is the byte-diet
acceptance artifact (ISSUE 9: >= 30% lower bytes/lane with diet on).

PROBE_PAGED=0/1 does the same for the paged entry log (RAFT_TPU_PAGED,
ISSUE 11 / BENCH_r06): each rung's JSON line grows pool-occupancy
(paged_pool_in_use / paged_pool_pages / paged_page_faults /
paged_exhausted) and paged_bytes_per_lane columns, so a flipped pair of
ladders is the paged acceptance artifact. Pin RAFT_TPU_PAGE_WINDOW /
RAFT_TPU_POOL_PAGES to probe sub-full-provisioning pools.

PROBE_TIER=0/1 flips the hot/cold hibernation tier (RAFT_TPU_TIER,
ISSUE 16): each rung addresses PROBE_LOGICAL_RATIO x its resident group
count in logical groups (default 16x), the per-size rung hibernates half
its cohort to the host cold store before timing, and the JSON line grows
logical-vs-resident occupancy plus cold_host_bytes_per_logical columns —
the O(resident) HBM / O(total) logical-groups artifact: live bytes track
the RESIDENT column while the logical column scales away.

PROBE_LEASE=0/1 flips the leader-lease plane (RAFT_TPU_LEASE, ISSUE 20).
The lease arm constructs every rung with check_quorum=True (the grant
predicate requires it) and the JSON line grows the lease counters plus
`reads_per_round`: lease-covered group-rounds per device round over the
timed window ((grants + renewals) / rounds) — each one is a group that
could have answered a coalesced batch of linearizable GETs that round
with ZERO quorum traffic, the capacity the serve plane's fast path
draws on."""

from __future__ import annotations

import json
import os
import sys
import time


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tpu import config

import jax

from raft_tpu.utils.compile_cache import enable_persistent_cache

if jax.default_backend() != "cpu":
    enable_persistent_cache()
import jax.numpy as jnp


def paged_columns(c) -> dict:
    """Pool-occupancy / sidecar bytes-per-lane columns for the
    PROBE_PAGED=1 arm (the BENCH_r06 rung), summed over resident blocks;
    {"paged": 0} when RAFT_TPU_PAGED is off. Works on FusedCluster,
    BlockedFusedCluster and MeshBlockedCluster rungs alike (the mesh's
    blocks are sharded wrappers around an inner FusedCluster)."""
    from raft_tpu.ops import paged as pgmod

    pools = []
    for b in getattr(c, "blocks", [c]):
        b = getattr(b, "inner", b)
        if getattr(b, "paged", None) is not None:
            pools.append(b.paged)
    if not pools:
        return {"paged": 0}
    stats = [pgmod.paged_stats(p) for p in pools]
    n_lanes = sum(p.pt.shape[0] for p in pools)
    side = sum(pgmod.paged_bytes_per_lane(p) * p.pt.shape[0] for p in pools)
    out = {"paged": 1, "paged_bytes_per_lane": round(side / n_lanes, 1)}
    for k in ("paged_pool_in_use", "paged_pool_pages", "paged_page_faults",
              "paged_exhausted"):
        out[k] = sum(s[k] for s in stats)
    return out


def tier_logical(n_groups: int) -> dict:
    """Constructor kwargs for the tier arm: every rung addresses
    PROBE_LOGICAL_RATIO x its resident group count in logical ids."""
    if not config.env_flag("RAFT_TPU_TIER", default=False):
        return {}
    ratio = int(os.environ.get("PROBE_LOGICAL_RATIO", 16))
    return {"logical_groups": n_groups * max(ratio, 1)}


def tier_columns(c) -> dict:
    """Logical-vs-resident occupancy columns for the PROBE_TIER=1 arm
    (ISSUE 16): how many groups the rung ADDRESSES vs how many it keeps
    resident, and the cold store's host-RAM footprint amortized over the
    logical space; {"tier": 0} when RAFT_TPU_TIER is off. Host-side
    counters only — reading them costs no device traffic."""
    t = getattr(c, "tier", None)
    if t is None:
        return {"tier": 0}
    s = t.stats()
    logical = int(getattr(t, "n_logical", 0) or s["tier_resident"])
    return {
        "tier": 1,
        "logical_groups": logical,
        "resident_groups": s["tier_resident"],
        "residency_ratio": round(logical / max(s["tier_resident"], 1), 1),
        "cold_groups": s["tier_cold"],
        "cold_host_bytes_per_logical": round(
            s["tier_cold_bytes"] / max(logical, 1), 2
        ),
        "tier_evictions": s["tier_evictions"],
        "tier_births": s["tier_births"],
    }


def lease_kwargs() -> dict:
    """Constructor kwargs for the PROBE_LEASE=1 arm: the grant predicate
    (ops/lease.py lease_round) requires check_quorum — off in the probe's
    default LaneConfig — so the lease arm flips it on; a default-config
    rung would report an all-zero lease column set."""
    if not config.env_flag("RAFT_TPU_LEASE", default=False):
        return {}
    return {"check_quorum": True}


def lease_snapshot(c) -> dict | None:
    """Summed lease counters over resident blocks (FusedCluster.lease_stats
    per block); None when RAFT_TPU_LEASE is off."""
    stats = None
    for b in getattr(c, "blocks", [c]):
        b = getattr(b, "inner", b)
        if getattr(b.state, "lease_left", None) is None:
            continue
        s = b.lease_stats()
        if stats is None:
            stats = dict(s)
        else:
            for k, v in s.items():
                stats[k] += v
    return stats


def lease_columns(s0, s1, rounds: int) -> dict:
    """Lease columns for the PROBE_LEASE=1 arm, measured as deltas over
    the TIMED window: reads_per_round counts lease-covered group-rounds
    per device round — every grant or renewal is one group able to answer
    an arbitrarily large coalesced GET batch that round without touching
    a quorum. {"lease": 0} when the plane is off."""
    if s1 is None:
        return {"lease": 0}
    d = {k: s1[k] - (s0 or {}).get(k, 0) for k in s1}
    return {
        "lease": 1,
        "reads_per_round": round(
            (d["lease_grants"] + d["lease_renewals"]) / max(rounds, 1), 1
        ),
        "lease_grants": d["lease_grants"],
        "lease_renewals": d["lease_renewals"],
        "lease_revocations": d["lease_revocations"],
        "lease_skew_revocations": d["lease_skew_revocations"],
    }


def measure(n_groups, n_voters, block=32, iters=5, w=16, e=2):
    from raft_tpu.config import Shape
    from raft_tpu.ops.fused import FusedCluster

    f = int(os.environ.get("PROBE_INFLIGHT", min(8, e)))
    r = int(os.environ.get("PROBE_READS", 4))
    shape = Shape(
        n_lanes=n_groups * n_voters,
        max_peers=n_voters,
        log_window=w,
        max_msg_entries=e,
        max_inflight=f,
        max_read_index=r,
    )
    c = FusedCluster(n_groups, n_voters, seed=42, shape=shape,
                     **tier_logical(n_groups), **lease_kwargs())
    lag = min(8, w // 2)
    t0 = time.perf_counter()
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    jax.block_until_ready(c.state.term)
    compile_s = time.perf_counter() - t0
    warm = 0
    while len(c.leader_lanes()) < n_groups and warm < 40 * 16:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm += block
    if getattr(c, "tier", None) is not None:
        # hibernate half the elected cohort before timing: the rung then
        # measures a pool whose cold half holds host-RAM records, so the
        # cold-bytes column is non-zero and the parked-lane mute rides
        # inside the timed rounds (suspend-to-RAM is bit-exact, so this
        # perturbs nothing the digest tests don't already pin)
        for g in list(c.tier.residents())[::2]:
            c.tier.request_evict(g)
        c.tier.apply(1 << 20)
    ls0 = lease_snapshot(c)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        jax.block_until_ready(c.state.term)
        best = min(best, time.perf_counter() - t0)
    ls1 = lease_snapshot(c)
    lanes = n_groups * n_voters
    round_ms = 1000 * best / block
    from raft_tpu.utils.profiling import live_buffer_bytes

    live_per_lane = live_buffer_bytes() / lanes
    mem = {}
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        mem = {
            "hbm_in_use_gb": round(ms.get("bytes_in_use", 0) / 2**30, 2),
            "hbm_peak_gb": round(ms.get("peak_bytes_in_use", 0) / 2**30, 2),
        }
    except Exception:
        pass
    print(
        json.dumps(
            {
                "groups": n_groups,
                "voters": n_voters,
                "lanes": lanes,
                "w": w,
                "e": e,
                "round_ms": round(round_ms, 3),
                "groups_ticks_per_s": round(n_groups * block / best, 1),
                "us_per_lane_round": round(1e6 * best / block / lanes, 2),
                "compile_s": round(compile_s, 1),
                "diet": int(config.env_flag("RAFT_TPU_DIET", default=False)),
                "live_bytes_per_lane": round(live_per_lane, 1),
                **paged_columns(c),
                **tier_columns(c),
                **lease_columns(ls0, ls1, iters * block),
                **mem,
            }
        ),
        flush=True,
    )
    del c


def measure_blocked(n_groups, n_voters, block_groups, block=32, iters=5,
                    w=16, e=2):
    from raft_tpu.config import Shape
    from raft_tpu.scheduler import BlockedFusedCluster

    f = int(os.environ.get("PROBE_INFLIGHT", min(8, e)))
    r = int(os.environ.get("PROBE_READS", 2))
    shape = Shape(
        n_lanes=block_groups * n_voters, max_peers=n_voters, log_window=w,
        max_msg_entries=e, max_inflight=f, max_read_index=r,
    )
    c = BlockedFusedCluster(
        n_groups, n_voters, block_groups=block_groups, seed=42, shape=shape,
        **tier_logical(n_groups), **lease_kwargs(),
    )
    lag = min(8, w // 2)
    t0 = time.perf_counter()
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()
    compile_s = time.perf_counter() - t0  # ~0 after the first ladder rung
    warm = 0
    while c.leader_count() < n_groups and warm < 40 * 16:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm += block
    ls0 = lease_snapshot(c)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        c.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    ls1 = lease_snapshot(c)
    lanes = n_groups * n_voters
    from raft_tpu.utils.profiling import live_buffer_bytes

    live_per_lane = live_buffer_bytes() / lanes
    mem = {}
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        mem = {
            "hbm_in_use_gb": round(ms.get("bytes_in_use", 0) / 2**30, 2),
            "hbm_peak_gb": round(ms.get("peak_bytes_in_use", 0) / 2**30, 2),
        }
    except Exception:
        pass
    print(
        json.dumps(
            {
                "groups": n_groups,
                "resident_blocks": c.k,
                "block_groups": block_groups,
                "voters": n_voters,
                "lanes": lanes,
                "round_ms": round(1000 * best / block, 3),
                "groups_ticks_per_s": round(n_groups * block / best, 1),
                "us_per_lane_round": round(1e6 * best / block / lanes, 2),
                "compile_s": round(compile_s, 1),
                "diet": int(config.env_flag("RAFT_TPU_DIET", default=False)),
                "live_bytes_per_lane": round(live_per_lane, 1),
                **paged_columns(c),
                **tier_columns(c),
                **lease_columns(ls0, ls1, iters * block),
                **mem,
            }
        ),
        flush=True,
    )
    del c


def measure_mesh(n_groups, n_voters, block_groups, block=32, iters=5,
                 w=16, e=2):
    """One mesh rung: K resident blocks, each sharded over EVERY local
    device (parallel/mesh.py MeshBlockedCluster). The 8M-16M-group
    north-star arm (ROADMAP item 2): on an 8-chip host, e.g.

      PROBE_MESH=1 PROBE_BLOCK_GROUPS=1048576 \\
      PROBE_GROUPS=8388608,16777216 PROBE_DIET=1 benches/scaling_probe.py

    runs 8-16 blocks of 1M groups, ~2M-6M lanes resident per chip with
    the diet carry — one compile for the whole ladder."""
    from raft_tpu.config import Shape
    from raft_tpu.parallel.mesh import MeshBlockedCluster

    f = int(os.environ.get("PROBE_INFLIGHT", min(8, e)))
    r = int(os.environ.get("PROBE_READS", 2))
    shape = Shape(
        n_lanes=block_groups * n_voters, max_peers=n_voters, log_window=w,
        max_msg_entries=e, max_inflight=f, max_read_index=r,
    )
    c = MeshBlockedCluster(
        n_groups, n_voters, block_groups=block_groups, seed=42, shape=shape,
        **tier_logical(n_groups), **lease_kwargs(),
    )
    lag = min(8, w // 2)
    t0 = time.perf_counter()
    c.run(block, auto_propose=True, auto_compact_lag=lag)
    c.block_until_ready()
    compile_s = time.perf_counter() - t0
    warm = 0
    while c.leader_count() < n_groups and warm < 40 * 16:
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        warm += block
    ls0 = lease_snapshot(c)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        c.run(block, auto_propose=True, auto_compact_lag=lag)
        c.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    ls1 = lease_snapshot(c)
    lanes = n_groups * n_voters
    from raft_tpu.utils.profiling import live_buffer_bytes

    live_per_lane = live_buffer_bytes() / lanes
    mem = {}
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        mem = {
            "hbm_in_use_gb": round(ms.get("bytes_in_use", 0) / 2**30, 2),
            "hbm_peak_gb": round(ms.get("peak_bytes_in_use", 0) / 2**30, 2),
        }
    except Exception:
        pass
    print(
        json.dumps(
            {
                "groups": n_groups,
                "resident_blocks": c.k,
                "block_groups": block_groups,
                "shards": c.n_shards,
                "lanes_per_shard": c.lanes_per_shard,
                "voters": n_voters,
                "lanes": lanes,
                "round_ms": round(1000 * best / block, 3),
                "groups_ticks_per_s": round(n_groups * block / best, 1),
                "us_per_lane_round": round(1e6 * best / block / lanes, 2),
                "compile_s": round(compile_s, 1),
                "diet": int(config.env_flag("RAFT_TPU_DIET", default=False)),
                "live_bytes_per_lane": round(live_per_lane, 1),
                **paged_columns(c),
                **tier_columns(c),
                **lease_columns(ls0, ls1, iters * block),
                **mem,
            }
        ),
        flush=True,
    )
    del c


if __name__ == "__main__":
    if os.environ.get("PROBE_DIET") is not None:
        # the ladder doubles as the diet-v2 acceptance artifact: force the
        # packed-carry knob off/on for every rung from one place
        os.environ["RAFT_TPU_DIET"] = os.environ["PROBE_DIET"]
    if os.environ.get("PROBE_TIER") is not None:
        # and for the hibernation tier (ISSUE 16): flip RAFT_TPU_TIER for
        # every rung; each rung then addresses PROBE_LOGICAL_RATIO x its
        # resident groups and grows the occupancy/cold-bytes columns
        os.environ["RAFT_TPU_TIER"] = os.environ["PROBE_TIER"]
    if os.environ.get("PROBE_PAGED") is not None:
        # same pattern for the paged entry log (ISSUE 11): flip
        # RAFT_TPU_PAGED for every rung and each JSON line grows the
        # pool-occupancy + paged_bytes_per_lane columns
        os.environ["RAFT_TPU_PAGED"] = os.environ["PROBE_PAGED"]
    if os.environ.get("PROBE_LEASE") is not None:
        # and for the leader-lease plane (ISSUE 20): flip RAFT_TPU_LEASE
        # for every rung (check_quorum rides along, see lease_kwargs) and
        # each JSON line grows reads_per_round + the lease counters
        os.environ["RAFT_TPU_LEASE"] = os.environ["PROBE_LEASE"]
    voters = int(os.environ.get("PROBE_VOTERS", 3))
    w = int(os.environ.get("PROBE_WINDOW", 16))
    e = int(os.environ.get("PROBE_ENTRIES", 2))
    block = int(os.environ.get("PROBE_BLOCK", 32))
    shapes = os.environ.get(
        "PROBE_GROUPS", "4096,16384,65536,131072,262144"
    )
    if os.environ.get("PROBE_MESH"):
        bg = int(os.environ.get("PROBE_BLOCK_GROUPS", 65536))
        for g in [int(x) for x in shapes.split(",")]:
            measure_mesh(g, voters, bg, block=block, w=w, e=e)
    elif os.environ.get("PROBE_BLOCKED"):
        bg = int(os.environ.get("PROBE_BLOCK_GROUPS", 65536))
        for g in [int(x) for x in shapes.split(",")]:
            if g % bg == 0:
                measure_blocked(g, voters, bg, block=block, w=w, e=e)
            else:
                measure(g, voters, block=block, w=w, e=e)
    else:
        for g in [int(x) for x in shapes.split(",")]:
            measure(g, voters, block=block, w=w, e=e)

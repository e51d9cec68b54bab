"""Mesh-blocked multi-chip driver: sharded × blocked dispatch composed.

The two scale-out mechanisms existed separately — ShardedFusedCluster
runs ONE resident batch under shard_map over the device mesh (per-shard
programs with no collectives except the metrics/chaos psums), and
BlockedFusedCluster holds K resident blocks on ONE device stepped
round-major through a single compiled kernel — but 10M+ groups needs
both at once: blocks bound the per-dispatch working set (HBM peak =
total carry + one block's temporaries), shards multiply resident carry
by the mesh size. `MeshBlockedCluster` is that product:

  * K resident blocks, each a ShardedFusedCluster over the SAME device
    mesh — every block's lanes are distributed over all shards, so each
    chip holds a slice of every block and the round-major sweep keeps
    all chips busy on block b+1 while block b's host work runs
    (Podracer, arxiv 2104.06272: the host loop stays off the critical
    path; the mesh runs rounds back-to-back).
  * One compiled program serves all K blocks (same shapes, same specs),
    exactly like the single-chip scheduler — the whole mesh ladder
    reuses one compile.
  * Global lane order matches BlockedFusedCluster exactly: block i owns
    global lanes [i*B*V, (i+1)*B*V); within a block, lanes shard
    contiguously over the mesh ("groups" axis), so group g of the
    cluster lives at (block = g // block_groups,
    shard = (g % block_groups) // groups_per_shard) — straddle-free
    placement by construction when groups_per_shard is whole. With
    `straddle=True` a group's voters may span a shard boundary inside
    its block and delivery rides the halo router
    (ops/fused.py route_fabric_straddle), unchanged.
  * Per-(shard, block) stream addressing: `wal=` / `egress=` take
    K-lists whose entries may be runtime.wal.ShardedWalStream /
    runtime.egress.ShardedEgressStream (one sub-stream per shard — the
    unit a per-chip storage/serving agent owns), or plain streams for a
    whole-block view; `trace=` takes K TraceStreams whose stacked
    [S, R] ring drains keep per-shard batches (TraceStream.shard_events).
  * Metrics and chaos tallies psum across shards inside each block's
    dispatch (ShardedFusedCluster's stepper), so host-side aggregation
    over blocks is identical to the single-chip scheduler's.
  * Diet auto-rebase drives from THIS host loop: each block's dispatch
    goes through ShardedFusedCluster.run, whose _diet_headroom guard
    rebases the packed index columns pre-overflow, flushing the block's
    stream fences first — the monolithic semantics, per shard.

Because each block is seeded `seed + 7919*i` (the scheduler's scheme)
and a ShardedFusedCluster is bit-identical to its monolithic
FusedCluster twin, the whole mesh trajectory is bit-identical to an
equal-total-groups BlockedFusedCluster — tests/test_mesh.py and
benches/multichip_ab.py assert the sha256 digest on a CPU-simulated
8-device mesh and gate perf on real TPUs.

The driving/inspection API mirrors BlockedFusedCluster (prepare_ops,
run, state_columns, drain_read_states, metrics_snapshot, set_chaos,
chaos_columns, restore_from_wal, ...) so ServeLoop and the chaos runner
work unchanged on top.
"""

from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import Shape
from raft_tpu.ops.fused import FusedCluster, LocalOps
from raft_tpu.parallel.sharded import ShardedFusedCluster
from raft_tpu.scheduler import BlockPlan
from raft_tpu.utils.profiling import StepStats


class MeshBlockedCluster:
    """`n_groups` total raft groups as K = n_groups/block_groups resident
    ShardedFusedClusters over one device mesh, stepped round-major with a
    single shared compiled collective program.

    block_groups must keep every block's lane count divisible by the mesh
    (block_groups % n_shards == 0 unless straddle=True). round_chunk /
    pipeline_depth carry the scheduler's exact semantics — trajectories
    are bit-identical for any chunking."""

    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        block_groups: int | None = None,
        devices=None,
        seed: int = 1,
        shape: Shape | None = None,
        round_chunk: int = 1,
        pipeline_depth: int | None = None,
        straddle: bool = False,
        logical_groups: int | None = None,
        **cfg,
    ):
        devices = list(devices) if devices is not None else jax.devices()
        self.plan = BlockPlan(
            n_groups, n_voters, block_groups,
            round_chunk=round_chunk, pipeline_depth=pipeline_depth, cfg=cfg,
        )
        self.g, self.v = self.plan.g, self.plan.v
        self.block_groups = self.plan.block_groups
        self.k = self.plan.k
        self.lanes_per_block = self.plan.lanes_per_block
        self.round_chunk = self.plan.round_chunk
        self.pipeline_depth = self.plan.pipeline_depth
        self.devices = devices
        self.n_shards = len(devices)
        self.straddle = straddle
        self._inflight: deque = deque()
        self._ops_cache = self.plan._ops_cache
        # paged geometry fails here, before any block/shard allocates (the
        # validate_round_plan contract; scheduler.py does the same) — and
        # the per-shard sub-pool split is checked against the mesh size
        if shape is not None:
            from raft_tpu.ops import paged as pgmod

            if pgmod.paged_enabled():
                pgmod.validate_page_plan(shape, self.lanes_per_block)
        # one span bag for the driver and its blocks (scheduler contract);
        # the build records `mesh.build` per block
        self.stats = StepStats()
        # the scheduler's block-seed scheme: trajectories match an
        # equal-total-groups BlockedFusedCluster bit for bit
        self.blocks = []
        for i in range(self.k):
            with self.stats.span("mesh.build", block=i):
                self.blocks.append(ShardedFusedCluster(
                    self.block_groups, n_voters, devices=devices,
                    seed=seed + 7919 * i, straddle=straddle, shape=shape,
                    **cfg
                ))
        self.lanes_per_shard = self.blocks[0].lanes_per_shard
        self.use_stats(self.stats)
        # hot/cold tiering (RAFT_TPU_TIER): per-block engines re-attached
        # with their contiguous slice of the logical id space (the
        # scheduler's exact partition), each keeping the sharded driver's
        # post-commit re-shard hook, coordinated by one ClusterTier
        self.tier = None
        if self.blocks[0].tier is not None:
            from raft_tpu.tier.engine import ClusterTier

            n_logical = logical_groups or n_groups
            engines = [
                b.attach_tier(
                    n_logical=n_logical,
                    initial=ClusterTier.initial_cohort(
                        n_logical, self.k, i, self.block_groups
                    ),
                    lane_base=i * self.lanes_per_block,
                )
                for i, b in enumerate(self.blocks)
            ]
            self.tier = ClusterTier(engines, n_logical)
        elif logical_groups is not None and logical_groups != n_groups:
            raise ValueError(
                "logical_groups > n_groups requires RAFT_TPU_TIER=1"
            )

    # -- driving ----------------------------------------------------------

    def audit_programs(self, rounds: int = 2):
        """Audit records for the mesh driver (raft_tpu/analysis): every
        block compiles the identical sharded stepper (same geometry,
        same plane set), so the first block's record covers the mesh.
        Records are named ``mesh.step.<engine>`` — the mesh drives one
        step program per block, whatever engine the block resolved."""
        recs = self.blocks[0].audit_programs(rounds)
        for r in recs:
            r["name"] = r["name"].replace("sharded.step.", "mesh.step.")
        return recs

    def use_stats(self, stats: StepStats) -> None:
        """Record the driver's and every block's host spans into `stats`."""
        self.stats = stats
        for b in self.blocks:
            b.use_stats(stats)

    def prepare_ops(self, ops: LocalOps) -> list[LocalOps]:
        """Slice a global-lane LocalOps into K per-block bindings ONCE
        (BlockedFusedCluster.prepare_ops contract; the per-shard split
        happens at dispatch via each block's lane sharding)."""
        return self.plan.prepare_ops(ops)

    def _bind_ops(self, ops) -> list | None:
        return self.plan.bind_ops(ops, self.prepare_ops)

    def _check_streams(self, streams, what: str, kind: str) -> list:
        return self.plan.check_streams(streams, what, kind)

    def _throttle(self, b: ShardedFusedCluster):
        if self.pipeline_depth is None:
            return
        self._inflight.append(b.state.term)
        while len(self._inflight) > self.pipeline_depth:
            jax.block_until_ready(self._inflight.popleft())

    def run(
        self,
        rounds: int = 1,
        ops=None,
        wal=None,
        egress=None,
        trace=None,
        do_tick: bool = True,
        auto_propose: bool = False,
        auto_compact_lag=None,
        ops_first_round_only: bool = True,
    ):
        """`rounds` fused rounds on every block, dispatched ROUND-MAJOR
        across the mesh: each sweep enqueues `round_chunk` rounds of every
        block before advancing, so the device queue on every chip always
        holds the other blocks' work while one block's host-side dispatch
        runs (the Podracer discipline).

        ops: a global-lane LocalOps, or a K-list from prepare_ops.
        wal / egress / trace: K-lists of per-block streams (each pushed
        once, after its block's last chunk). wal entries may be
        ShardedWalStream for per-(shard, block) durability payloads,
        egress entries ShardedEgressStream for per-(shard, block) ready
        bundles; plain WalStream/EgressStream give the whole-block view.
        trace entries are TraceStreams (the stacked per-shard rings keep
        per-shard batches; TraceStream.shard_events addresses them)."""
        if not ops_first_round_only:
            raise ValueError(
                "the mesh driver injects ops on the first round only (the "
                "sharded dispatch bakes ops_first_round_only=True)"
            )
        if wal is not None:
            wal = self._check_streams(wal, "wal", "WalStream")
        if egress is not None:
            egress = self._check_streams(egress, "egress", "EgressStream")
        if trace is not None:
            trace = self._check_streams(trace, "trace", "TraceStream")
        per_ops = self._bind_ops(ops)
        if self.k == 1:
            b = self.blocks[0]
            with self.stats.span("sched.dispatch", block=0, rounds=rounds):
                b.run(
                    rounds,
                    ops=None if per_ops is None else per_ops[0],
                    do_tick=do_tick, auto_propose=auto_propose,
                    auto_compact_lag=auto_compact_lag,
                    wal=None if wal is None else wal[0],
                    egress=None if egress is None else egress[0],
                    trace=None if trace is None else trace[0],
                )
            self._throttle(b)
            return
        done = 0
        for step, first, last in self.plan.sweep(rounds):
            for i, b in enumerate(self.blocks):
                o = per_ops[i] if (per_ops is not None and first) else None
                with self.stats.span(
                    "sched.dispatch", block=i, round=done, rounds=step
                ):
                    b.run(
                        step,
                        ops=o,
                        do_tick=do_tick, auto_propose=auto_propose,
                        auto_compact_lag=auto_compact_lag,
                        wal=wal[i] if (wal is not None and last) else None,
                        egress=(
                            egress[i] if (egress is not None and last) else None
                        ),
                        trace=(
                            trace[i] if (trace is not None and last) else None
                        ),
                    )
                self._throttle(b)
            done += step

    def ops(self, **kw) -> LocalOps:
        """Global-lane LocalOps (same contract as FusedCluster.ops)."""
        from raft_tpu.ops.fused import make_local_ops

        return make_local_ops(self.g * self.v, **kw)

    def block_until_ready(self):
        self._inflight.clear()
        jax.block_until_ready([b.state.term for b in self.blocks])

    # -- stream factories (per-(shard, block) addressing) ------------------

    def wal_streams(self, sink=None) -> list:
        """K ShardedWalStreams, one per block, each fanning its block's
        delta out per shard. sink(block, shard, block_seq, delta)."""
        from raft_tpu.runtime.wal import ShardedWalStream

        return [
            ShardedWalStream(
                self.n_shards, self.lanes_per_shard,
                sink=None if sink is None else (
                    lambda s, seq, d, i=i: sink(i, s, seq, d)
                ),
            )
            for i in range(self.k)
        ]

    def egress_streams(self, sink=None) -> list:
        """K ShardedEgressStreams, one per block, each fanning its block's
        ready bundle out per shard. sink(block, shard, block_seq, bundle)."""
        from raft_tpu.runtime.egress import ShardedEgressStream

        return [
            ShardedEgressStream(
                self.n_shards, self.lanes_per_shard,
                sink=None if sink is None else (
                    lambda s, seq, b, i=i: sink(i, s, seq, b)
                ),
                stats=self.stats,
            )
            for i in range(self.k)
        ]

    def trace_streams(self, counters=None) -> list:
        """K TraceStreams, one per block (the stacked [S, R] rings keep
        per-shard batches; TraceStream.shard_events addresses them)."""
        from raft_tpu.runtime.trace import TraceStream

        return [TraceStream(counters=counters) for _ in range(self.k)]

    # -- inspection (aggregate; BlockedFusedCluster contract) --------------

    @property
    def metrics_enabled(self) -> bool:
        return self.blocks[0].metrics is not None

    @property
    def chaos_enabled(self) -> bool:
        return self.blocks[0].chaos is not None

    def set_chaos(self, **cols):
        """Install chaos columns addressed in GLOBAL lane order: [n]- or
        [n, v]-leading arrays are sliced per block exactly like
        prepare_ops, then re-sharded over the mesh by each block's setter;
        scalars broadcast to every block."""
        if not self.chaos_enabled:
            raise RuntimeError(
                "chaos plane is off (RAFT_TPU_CHAOS=0); set it before "
                "constructing the cluster"
            )
        n = self.g * self.v
        for i, b in enumerate(self.blocks):
            lo = i * self.lanes_per_block
            per = {}
            for name, val in cols.items():
                xa = np.asarray(val)
                if xa.ndim >= 1 and xa.shape[0] == n:
                    per[name] = xa[lo : lo + self.lanes_per_block]
                else:
                    per[name] = xa
            b.set_chaos(**per)

    def chaos_columns(self, *names) -> dict:
        """Aggregate chaos columns over all K blocks (the scheduler's
        exact shape: per-lane columns concatenate in global lane order,
        recovery tallies sum — each block's tally is already the psum'd
        replicated global count for that block's lanes)."""
        if not self.chaos_enabled:
            return {}
        per = [b.chaos_columns(*names) for b in self.blocks]
        out = {}
        for name, v0 in per[0].items():
            vals = [p[name] for p in per]
            if np.ndim(v0) >= 1 and np.shape(v0)[0] == self.lanes_per_block:
                out[name] = np.concatenate(vals)
            elif name in ("n_reelected", "n_recommitted"):
                out[name] = sum(int(x) for x in vals)
            else:
                out[name] = v0
        return out

    def metrics_snapshot(self) -> dict | None:
        """Merged snapshot over all K blocks. Each block's device counters
        are already the psum'd cross-shard totals (replicated), so the
        per-block wraparound-aware host pull + merge is exactly the
        single-chip scheduler's aggregation."""
        if not self.metrics_enabled:
            return None
        from raft_tpu.metrics.host import merge_snapshots

        merged = merge_snapshots(
            [b.metrics_snapshot() for b in self.blocks]
        )
        if self.tier is not None:
            # the per-block folds summed once each in the merge; overwrite
            # with the coordinator's aggregate (gauge semantics) so the
            # accounting identity holds over the whole logical space
            for key, val in self.tier.stats(mirror=True).items():
                merged["counters"][key] = val
        return merged

    def state_columns(self, *names) -> dict:
        """Aggregate state_columns over all K blocks in GLOBAL lane order
        (each block's host_state gathers its sharded columns)."""
        per = [b.state_columns(*names) for b in self.blocks]
        return {
            name: np.concatenate([p[name] for p in per]) for name in names
        }

    def drain_read_states(self) -> dict:
        """Merge per-block drain_read_states into one global-lane map."""
        out = {}
        for i, b in enumerate(self.blocks):
            lo = i * self.lanes_per_block
            for lane, rs in b.drain_read_states().items():
                out[lo + lane] = rs
        return out

    def total_committed(self) -> int:
        return int(
            sum(
                int(jnp.sum(b.state.committed.astype(jnp.int32)))
                for b in self.blocks
            )
        )

    def leader_count(self) -> int:
        return int(sum(len(b.leader_lanes()) for b in self.blocks))

    def leader_lanes(self) -> np.ndarray:
        out = []
        for i, b in enumerate(self.blocks):
            out.append(b.leader_lanes() + i * self.lanes_per_block)
        return np.concatenate(out)

    def check_no_errors(self):
        for b in self.blocks:
            b.check_no_errors()

    # -- restart ----------------------------------------------------------

    @classmethod
    def restore_from_wal(
        cls,
        n_groups: int,
        n_voters: int,
        delta,
        block_groups: int | None = None,
        devices=None,
        seed: int = 1,
        shape: Shape | None = None,
        log_bytes=None,
        **cfg,
    ) -> "MeshBlockedCluster":
        """Rebuild a running mesh from WAL deltas — the multi-chip restart
        path. `delta` is either ONE global-lane delta dict (sliced per
        block here) or a K-list of per-block deltas (each possibly
        reassembled from per-shard payloads via
        runtime.wal.merge_shard_deltas). Every block restores through
        FusedCluster.restore_from_wal (same seed scheme), then re-shards
        onto the mesh."""
        c = cls(
            n_groups, n_voters, block_groups, devices=devices, seed=seed,
            shape=shape, **cfg
        )
        lpb = c.lanes_per_block
        for i, b in enumerate(c.blocks):
            if isinstance(delta, dict):
                lo = i * lpb
                d_i = {f: np.asarray(v)[lo : lo + lpb] for f, v in delta.items()}
                lb_i = (
                    None if log_bytes is None
                    else np.asarray(log_bytes)[lo : lo + lpb]
                )
            else:
                d_i = delta[i]
                lb_i = None if log_bytes is None else log_bytes[i]
            rc = FusedCluster.restore_from_wal(
                c.block_groups, n_voters, d_i, seed=seed + 7919 * i,
                shape=shape, log_bytes=lb_i, **cfg
            )
            if rc.paged is not None:
                # the mono restore allocated page ids against its own
                # segmentation, but in-dispatch paging runs segment-local
                # on the mesh grid: round-trip through the full window and
                # re-split with the mesh driver's segment count so every
                # page id lands in its segment's local id space, then
                # re-shard (device_put on the lane sharding — shard_lanes
                # routes by leading dim == n_lanes and would replicate
                # the pool)
                from raft_tpu.ops import paged as pgmod

                full = pgmod.page_in_view(rc.state, rc.paged, rc._paged_segs)
                res_st, pg_new = pgmod.page_out_host(
                    full, rc.paged, b.inner._paged_segs
                )
                b.inner.state = jax.tree.map(b._shard_lanes, res_st)
                b.inner.paged = jax.tree.map(
                    lambda x: jax.device_put(x, b.lane_sharding), pg_new
                )
            else:
                b.inner.state = jax.tree.map(b._shard_lanes, rc.state)
        return c

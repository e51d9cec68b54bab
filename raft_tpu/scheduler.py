"""Multi-block fused scheduler: K resident sub-batches, one compiled kernel.

The reference's scaling axis is many independent Raft groups per process
(reference: raft.go:244-246 "multinode which can host multiple raft group";
tracker/inflights.go:83-85 sizes its ring lazily for "thousands of Raft
groups per process"). Groups never interact, so a million-group batch does
not have to be one million-lane tensor program — and on a real chip it must
not be, for three reasons:

1. **HBM peak.** Resident *state* scales with total lanes, but the fused
   round's working set (XLA temporaries) scales with the lanes of the
   program being executed. Splitting 1M groups into K blocks keeps the
   temporaries at block size while all K blocks' slim carries
   (state.STATE_SLIM / fused.FABRIC_SLIM) stay resident: peak =
   total_carry + one block's working set, instead of K times the working
   set. With carry donation on (fused.donation_enabled, the default),
   each block's carry additionally updates in place — the old
   "un-donatable double buffer" is gone, so total_carry is ONE copy per
   block, not two.
2. **One compile.** Every block shares one (shape, static-args) signature,
   so the fused kernel compiles ONCE and serves every block — and every
   aggregate size that is a multiple of the block: the whole scaling
   ladder reuses a single 30-100 s TPU compilation.
3. **Latency + queue occupancy.** Dispatch is ROUND-MAJOR: round r of
   block b+1 is enqueued right behind round r of block b, so the device
   queue always holds work from the other K-1 blocks while one block's
   round executes — per-block host work (ops binding, WAL pushes) hides
   behind the other blocks' compute instead of draining the queue
   block-major. Quorum-commit latency at 1M aggregate groups is the
   latency of one block-sized round, not a 1M-lane kernel's.

Blocks are seeded differently so their randomized election timeouts
(reference: raft.go:1984-1990) decorrelate exactly like lanes within a
block do.

Host-side dispatch cost is kept off the hot path: per-block `ops` slices
are computed ONCE per injected ops object (`prepare_ops` / the identity
cache in `run`), not re-sliced with `jax.tree.map` on every call, and the
ops-less rounds reuse each block's cached zero-ops (fused.FusedCluster).
`pipeline_depth` bounds enqueued-but-unfinished dispatches for drivers
that need bounded device-queue memory (None = unbounded, pure async).
"""

from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import Shape
from raft_tpu.ops.fused import _SCAN_UNROLL, FusedCluster, LocalOps
from raft_tpu.utils.profiling import StepStats


class BlockPlan:
    """The blocked-dispatch plan, factored out of BlockedFusedCluster so the
    mesh driver (parallel/mesh.py) reuses it per shard: validates the
    (groups, block, chunk, pipeline) factorization up front, owns the
    global-lane ops slicing + identity LRU, the per-block stream-list
    checks, and the round-major sweep schedule. It holds no device state —
    the driver owns the blocks; the plan owns the bookkeeping every blocked
    driver would otherwise re-implement."""

    _OPS_CACHE_SLOTS = 2

    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        block_groups: int | None = None,
        round_chunk: int = 1,
        pipeline_depth: int | None = None,
        cfg: dict | None = None,
    ):
        block_groups = block_groups or n_groups
        if n_groups % block_groups:
            raise ValueError("n_groups must be a multiple of block_groups")
        if round_chunk < 1:
            raise ValueError("round_chunk must be >= 1")
        if pipeline_depth is not None and pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1 (or None)")
        # up-front RAFT_TPU_UNROLL x K x round_chunk composition check for
        # the pallas megakernel: a K that does not divide round_chunk
        # compiles an extra remainder-tail kernel per chunk, and
        # unroll x K explodes the unrolled program — fail HERE with a
        # clear error, not mid-dispatch inside Mosaic. Only a pinned K
        # (ctor kwarg or RAFT_TPU_PALLAS_ROUNDS) is checkable this early;
        # an autotuned K re-validates at resolve time.
        from raft_tpu.ops import pallas_round as plr

        cfg = cfg or {}
        if plr.resolve_engine(cfg.get("engine")) == "pallas":
            k_req = cfg.get("rounds_per_call")
            if k_req is None:
                k_req = plr.env_rounds_per_call()
            if k_req is not None:
                plr.validate_round_plan(
                    k_req, unroll=_SCAN_UNROLL, round_chunk=round_chunk
                )
        self.g, self.v = n_groups, n_voters
        self.block_groups = block_groups
        self.k = n_groups // block_groups
        self.lanes_per_block = block_groups * n_voters
        self.round_chunk = round_chunk
        self.pipeline_depth = pipeline_depth
        # small identity LRU: [(ops object, its per-block slices), ...],
        # most-recent-first, capacity _OPS_CACHE_SLOTS. Holding the ops
        # references pins their ids, so the identity test can never
        # false-positive on a recycled address. Two slots (not one) so the
        # common alternation pattern — a driver flipping between two
        # prepared ops objects round after round — hits every time instead
        # of silently re-slicing K subtrees per call.
        self._ops_cache: list = []

    def prepare_ops(self, ops: LocalOps) -> list[LocalOps]:
        """Slice a global-lane LocalOps into K per-block bindings ONCE."""
        per = []
        for i in range(self.k):
            lo = i * self.lanes_per_block
            per.append(
                jax.tree.map(
                    lambda x, lo=lo: x[lo : lo + self.lanes_per_block], ops
                )
            )
        return per

    def bind_ops(self, ops, prepare=None) -> list | None:
        """`prepare` lets the owning driver route cache misses through its
        OWN prepare_ops (so instance-level wrappers/overrides are honored);
        defaults to this plan's slicer."""
        if ops is None:
            return None
        if isinstance(ops, list):  # already per-block (prepare_ops). NOT
            # tuple: LocalOps itself is a NamedTuple.
            if len(ops) != self.k:
                raise ValueError(
                    f"per-block ops list must have one entry per resident "
                    f"block: got {len(ops)}, expected {self.k}"
                )
            return list(ops)
        for j, (obj, per) in enumerate(self._ops_cache):
            if obj is ops:
                if j:  # refresh LRU order
                    self._ops_cache.insert(0, self._ops_cache.pop(j))
                return per
        per = (prepare or self.prepare_ops)(ops)
        self._ops_cache.insert(0, (ops, per))
        del self._ops_cache[self._OPS_CACHE_SLOTS:]
        return per

    def check_streams(self, streams, what: str, kind: str) -> list:
        try:
            k = len(streams)
        except TypeError:
            raise TypeError(
                f"{what} must be a sequence of K {kind}s, one per resident "
                f"block (this scheduler holds K={self.k})"
            ) from None
        if k != self.k:
            raise ValueError(
                f"{what} must hold one stream per resident block: got {k} "
                f"stream(s), expected K={self.k} "
                f"({self.g} groups / {self.block_groups} per block)"
            )
        streams = list(streams)
        # uniqueness, not just length: the same stream object listed for
        # two blocks would silently interleave both blocks' deltas into one
        # sink sequence (and double-resolve its single pending slot) — a
        # config error, never a runtime surprise
        seen: dict[int, int] = {}
        for i, s in enumerate(streams):
            j = seen.setdefault(id(s), i)
            if j != i:
                raise ValueError(
                    f"{what}[{i}] is the same {kind} object as {what}[{j}]: "
                    f"each resident block needs its own stream (sharing one "
                    f"would interleave two blocks' deltas in its sink)"
                )
        return streams

    def sweep(self, rounds: int):
        """The round-major schedule: yields (step, first, last) chunks,
        step <= round_chunk, covering `rounds` rounds."""
        done = 0
        while done < rounds:
            step = min(self.round_chunk, rounds - done)
            yield step, done == 0, done + step >= rounds
            done += step


class BlockedFusedCluster:
    """`n_groups` total raft groups held as K = n_groups/block_groups
    resident FusedClusters stepped with one shared compiled kernel.

    The driving API mirrors FusedCluster; per-lane injections address lanes
    in global order (block i owns global lanes [i*B*V, (i+1)*B*V)).

    Engine selection (`engine=` / `RAFT_TPU_ENGINE`, ops/pallas_round.py)
    flows through **cfg to every resident block's FusedCluster: all K
    blocks share one pallas kernel signature exactly like they share the
    XLA one.

    round_chunk: rounds per dispatch in the round-major sweep (default 1 =
    strict round-major interleave; larger values amortize per-dispatch host
    overhead by letting each block scan `round_chunk` rounds between
    interleave points — trajectories are bit-identical either way).
    pipeline_depth: max enqueued-but-unfinished dispatches before the host
    blocks on the oldest (None = unbounded)."""

    _OPS_CACHE_SLOTS = 2

    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        block_groups: int | None = None,
        seed: int = 1,
        shape: Shape | None = None,
        round_chunk: int = 1,
        pipeline_depth: int | None = None,
        logical_groups: int | None = None,
        **cfg,
    ):
        # geometry + ops-slicing + sweep bookkeeping live in the shared
        # BlockPlan (also driven per shard by parallel/mesh.py)
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
        self._inflight: deque = deque()
        # alias, not copy: _bind_ops mutates the plan's LRU in place
        self._ops_cache = self.plan._ops_cache
        # paged entry log geometry fails HERE, before any block allocates
        # a carry — the validate_round_plan contract (raise, never fall
        # back); each FusedCluster below re-validates transitively
        if shape is not None:
            from raft_tpu.ops import paged as pgmod

            if pgmod.paged_enabled():
                pgmod.validate_page_plan(shape, self.lanes_per_block)
        # distinct seeds decorrelate election timeouts across blocks
        self.blocks = [
            FusedCluster(
                self.block_groups, n_voters, seed=seed + 7919 * i,
                shape=shape, **cfg
            )
            for i in range(self.k)
        ]
        # one span bag for the driver and its blocks: a sched.dispatch span
        # per block dispatch (host dispatch time — JAX async dispatch means
        # device execution rides behind it) around each block's
        # sched.enqueue; a ServeLoop hands in its own through use_stats
        self.use_stats(StepStats())
        # hot/cold tiering (RAFT_TPU_TIER, raft_tpu/tier/): re-attach each
        # block's engine with its slice of the LOGICAL id space — a
        # contiguous equal partition, so L == G is lane-identical to the
        # tier-off blocked layout — coordinated through one ClusterTier.
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

    def use_stats(self, stats: StepStats) -> None:
        """Record the driver's and every block's host spans into `stats`."""
        self.stats = stats
        for b in self.blocks:
            b.use_stats(stats)

    def prepare_ops(self, ops: LocalOps) -> list[LocalOps]:
        """Slice a global-lane LocalOps into K per-block bindings ONCE.
        The returned list can be passed to run(ops=...) any number of
        times with zero further host-side slicing (run() also caches the
        slices of the last raw LocalOps it saw, so callers that re-inject
        the same object get this for free)."""
        return self.plan.prepare_ops(ops)

    def _bind_ops(self, ops) -> list | None:
        return self.plan.bind_ops(ops, self.prepare_ops)

    def _check_streams(self, streams, what: str, kind: str) -> list:
        return self.plan.check_streams(streams, what, kind)

    def _check_wal(self, wal) -> list:
        return self._check_streams(wal, "wal", "WalStream")

    def _throttle(self, b: FusedCluster):
        if self.pipeline_depth is None:
            return
        self._inflight.append(b.state.term)
        while len(self._inflight) > self.pipeline_depth:
            jax.block_until_ready(self._inflight.popleft())

    def run(
        self, rounds: int = 1, ops=None, wal=None, egress=None, trace=None, **kw
    ):
        """`rounds` fused rounds on every block, dispatched ROUND-MAJOR:
        each sweep enqueues `round_chunk` rounds of every block before
        advancing, so block b+1's round hides block b's host-side dispatch
        work (JAX async dispatch; no syncs unless pipeline_depth bounds
        the queue).

        ops: a global-lane LocalOps, or a K-list from prepare_ops.
        wal: optional list of K runtime.wal.WalStream, one per block
        (each block's delta is pushed once, after its last round).
        egress: optional list of K runtime.egress.EgressStream, same
        per-block shape — each block's batched ready/delta bundle is
        pushed once, after its last round, and rides D2H while the next
        block computes.
        trace: optional list of K runtime.trace.TraceStream — each block's
        flight-recorder ring pushed the same way (event lane stamps are
        block-LOCAL; trace/assemble.py globalizes by block offset)."""
        if wal is not None:
            wal = self._check_wal(wal)
        if egress is not None:
            egress = self._check_streams(egress, "egress", "EgressStream")
        if trace is not None:
            trace = self._check_streams(trace, "trace", "TraceStream")
        per_ops = self._bind_ops(ops)
        ops_first = kw.get("ops_first_round_only", True)
        if self.k == 1:
            # one resident block: a single multi-round scan dispatch beats
            # any interleave (nothing to overlap with)
            b = self.blocks[0]
            with self.stats.span("sched.dispatch", block=0, rounds=rounds):
                b.run(
                    rounds,
                    ops=None if per_ops is None else per_ops[0],
                    wal=None if wal is None else wal[0],
                    egress=None if egress is None else egress[0],
                    trace=None if trace is None else trace[0],
                    **kw,
                )
            self._throttle(b)
            return
        done = 0
        for step, first, last in self.plan.sweep(rounds):
            for i, b in enumerate(self.blocks):
                o = None
                if per_ops is not None and (first or not ops_first):
                    o = per_ops[i]
                with self.stats.span(
                    "sched.dispatch", block=i, round=done, rounds=step
                ):
                    b.run(
                        step,
                        ops=o,
                        wal=wal[i] if (wal is not None and last) else None,
                        egress=(
                            egress[i] if (egress is not None and last) else None
                        ),
                        trace=(
                            trace[i] if (trace is not None and last) else None
                        ),
                        **kw,
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

    # -- inspection (aggregate) -------------------------------------------

    @property
    def metrics_enabled(self) -> bool:
        return self.blocks[0].metrics is not None

    @property
    def chaos_enabled(self) -> bool:
        return self.blocks[0].chaos is not None

    def set_chaos(self, **cols):
        """Install chaos columns addressed in GLOBAL lane order: [n]- or
        [n, v]-leading arrays are sliced per block exactly like
        prepare_ops; scalars (seed-salt-free fields like heal_round) are
        broadcast to every block."""
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
        """Aggregate chaos columns over all K blocks: per-lane columns are
        concatenated in global lane order, the recovery tallies
        (n_reelected / n_recommitted) are summed, other scalars (round,
        heal_round — identical across blocks) come from block 0."""
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
        """One merged snapshot over all K resident blocks with ONE device
        sync: the K blocks' already-lane-reduced counter/hist vectors are
        stacked into a single [K, C+B+2] pull (one transfer), then folded
        into each block's wraparound-aware host accumulator and merged
        (raft_tpu/metrics/)."""
        if not self.metrics_enabled:
            return None
        from types import SimpleNamespace

        from raft_tpu.metrics.device import COUNTERS, N_BUCKETS
        from raft_tpu.metrics.host import merge_snapshots

        nc = len(COUNTERS)
        rows = np.asarray(
            jnp.stack(
                [
                    jnp.concatenate(
                        [
                            b.metrics.counters,
                            b.metrics.hist,
                            b.metrics.lat_sum[None],
                            b.metrics.round_ctr[None],
                        ]
                    )
                    for b in self.blocks
                ]
            )
        )
        snaps = []
        for b, row in zip(self.blocks, rows):
            pulled = SimpleNamespace(
                counters=row[:nc],
                hist=row[nc : nc + N_BUCKETS],
                lat_sum=row[nc + N_BUCKETS],
                round_ctr=row[nc + N_BUCKETS + 1],
            )
            b._metrics_acc.pull(pulled)
            snaps.append(b._metrics_acc.snapshot())
        merged = merge_snapshots(snaps)
        if self.tier is not None:
            # per-block tier counters don't ride the per-block snapshots
            # here (they're pure host counters); fold the coordinator's
            # aggregate in once, mirroring onto TIER_COUNTERS
            for key, val in self.tier.stats(mirror=True).items():
                merged["counters"][key] = val
        return merged

    def state_columns(self, *names) -> dict:
        """Aggregate FusedCluster.state_columns over all K resident blocks:
        each named [N_block]-leading leaf is concatenated in GLOBAL lane
        order (block i owns lanes [i*B*V, (i+1)*B*V)). Async host copies
        start on every block's leaves before the first blocking read."""
        # per-block host_state(): packed (diet-v2) columns widen to
        # absolute int32 before concatenation (identity when diet is off)
        leaves = [
            [getattr(b.host_state(), name) for name in names]
            for b in self.blocks
        ]
        for row in leaves:
            for x in row:
                if hasattr(x, "copy_to_host_async"):
                    x.copy_to_host_async()
        return {
            name: np.concatenate([np.asarray(row[j]) for row in leaves])
            for j, name in enumerate(names)
        }

    def drain_read_states(self) -> dict:
        """Merge per-block FusedCluster.drain_read_states into one
        {global_lane: [(ctx, index), ...]} map."""
        out = {}
        for i, b in enumerate(self.blocks):
            lo = i * self.lanes_per_block
            for lane, rs in b.drain_read_states().items():
                out[lo + lane] = rs
        return out

    def total_committed(self) -> int:
        # astype before the sum: a diet-v2 packed committed column is
        # uint16 and a [N]-wide sum of it could wrap in its own dtype
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

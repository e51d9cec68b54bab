"""Double-buffered async egress of the batched ready bundle.

The serving-plane twin of runtime/wal.py: where WalStream ships each
block's durability delta D2H while the next block computes, EgressStream
ships the block's READINESS — the ops/ready_mask.py delta bundle (which
lanes' externally visible cursors moved, compacted to a dense active-lane
prefix, plus the cursor columns themselves) — so the host consumer learns
"which lanes have output" one block behind the live state without ever
scanning all N lanes or issuing per-lane scalar reads.

Built into `FusedCluster.run(egress=...)` and the BlockedFusedCluster
scheduler's `egress=` per-block list (next to `wal=`):

  push(state):  resolve + sink the PREVIOUS block's bundle (its D2H copy
                has had a whole block of compute to ride), dispatch the
                delta kernel against that block's now-host-resident
                cursors, and start the async D2H copy of the new bundle.
  flush():      resolve the in-flight tail (call when the run stops; the
                engine's donation fence calls it before any donating
                dispatch could invalidate the bundle's buffers — the same
                _wal_pending discipline fused.py applies to WalStream).

The delta baseline rides HOST-side (the resolved previous bundle feeds the
next dispatch as fresh device inputs), so donation can never invalidate
it. RAFT_TPU_EGRESS=0 disables the stream at construction: push/flush are
no-ops and the kernel is never traced (tests/test_egress.py).

The sink contract mirrors WalStream's: `sink(block_id, DeltaBundle)` in
block order, each bundle internally consistent (one atomic device state);
`bundle.active[:bundle.count]` is the dense vector of lanes that changed
since the previous block. `bundle.rs_count` marks lanes holding undrained
ReadIndex results — such lanes stay active every block until the host
drains them (FusedCluster.drain_read_states).

The first-class consumer is the serving frontend (raft_tpu/serve/): the
CompletionRouter registers as the sink, maps active lanes back to raft
groups, advances per-group commit watermarks, applies committed commands
to the host KV materialization, and resolves client futures
(propose -> commit -> notify) — the production loop ROADMAP item 3 asks
for, with the O(active) sweep this stream was built to feed.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from raft_tpu.ops import ready_mask
from raft_tpu.utils.profiling import StepStats


class EgressView(NamedTuple):
    """One shard's lane window of the cursor columns the delta kernel
    reads (ops/ready_mask.py delta_bundle) — a registered pytree, so the
    per-shard EgressStream dispatches the SAME jitted kernel at the
    [lanes_per_shard] shape: one compile serves every (shard, block)."""

    term: object
    lead: object
    state: object
    committed: object
    applied: object
    last: object
    rs_count: object
    # lease plane columns (RAFT_TPU_LEASE) — None when the plane is off,
    # so the view's pytree shape (and the jit cache key) is unchanged
    lease_left: object = None
    lease_epoch: object = None


def shard_egress_view(state, lo: int, hi: int) -> EgressView:
    """Slice a (possibly diet-packed) state's externally visible cursor
    columns to one shard's lane window; slices are lazy device views, so
    only the shard's rows ride the delta dispatch and D2H copy."""
    lease_left = lease_epoch = None
    if getattr(state, "lease_left", None) is not None:
        lease_left = state.lease_left[lo:hi]
        lease_epoch = state.lease_epoch[lo:hi]
    return EgressView(
        term=state.term[lo:hi], lead=state.lead[lo:hi],
        state=state.state[lo:hi], committed=state.committed[lo:hi],
        applied=state.applied[lo:hi], last=state.last[lo:hi],
        rs_count=state.rs_count[lo:hi],
        lease_left=lease_left, lease_epoch=lease_epoch,
    )


class EgressStream:
    """`stats` (a utils/profiling StepStats, a private one by default)
    takes the egress.resolve.wait span — the blocking host copy of a
    bundle — and the egress.sink span around each sink call."""

    def __init__(self, sink=None, stats=None):
        self.enabled = ready_mask.egress_enabled()
        self._pending = None  # (block_id, device DeltaBundle)
        self._prev = None  # resolved PrevCursors of the last pushed block
        self.sink = sink
        self.stats = StepStats() if stats is None else stats
        self.blocks = 0
        self.lanes_scanned = 0  # N per pushed block (what a scalar poll pays)
        self.lanes_active = 0  # sum of per-block active counts
        self.bytes = 0  # resolved bundle bytes shipped D2H

    def push(self, state):
        if not self.enabled:
            return
        # the previous bundle is both this push's sink output and the next
        # delta's baseline, so it resolves BEFORE the new dispatch (its
        # transfer overlapped the whole block that just ran — a cache read,
        # not a sync)
        self._resolve_pending()
        dev = ready_mask.compute_delta(state, self._prev)
        for a in dev:
            # start the D2H transfer now; it overlaps the next block's
            # device execution (JAX async dispatch + async host copy).
            # The lease columns are None when RAFT_TPU_LEASE=0
            if a is not None:
                a.copy_to_host_async()
        self._pending = (self.blocks, dev)
        self.blocks += 1

    def flush(self):
        self._resolve_pending()

    def _resolve_pending(self):
        if self._pending is None:
            return
        block_id, dev = self._pending
        self._pending = None
        with self.stats.span("egress.resolve.wait"):
            bundle = ready_mask.DeltaBundle(
                *(None if a is None else np.asarray(a) for a in dev)
            )
        self._prev = ready_mask.PrevCursors(
            term=bundle.term, lead=bundle.lead, state=bundle.state,
            committed=bundle.committed, applied=bundle.applied,
            last=bundle.last,
        )
        self.bytes += sum(a.nbytes for a in bundle if a is not None)
        self.lanes_scanned += int(bundle.changed.shape[0])
        self.lanes_active += int(bundle.count)
        if self.sink is not None:
            with self.stats.span("egress.sink"):
                self.sink(block_id, bundle)


class ShardedEgressStream:
    """Per-(shard, block) egress addressing for the mesh driver
    (parallel/mesh.py): one sub-EgressStream per shard, each pushed the
    shard's EgressView lane window, each holding its OWN host-side
    PrevCursors baseline — so every shard's bundle is the exact delta of
    its own lanes, and `merge_delta_bundles` reassembles a block's S
    bundles into the monolithic bundle (byte-identical to an unsharded
    EgressStream of the same state; the compaction's ascending-prefix
    invariant makes the offset concat exact).

    sink(shard, block_id, DeltaBundle) fires per shard in shard order."""

    def __init__(self, n_shards: int, lanes_per_shard: int | None = None,
                 sink=None, stats=None):
        self.n_shards = n_shards
        self.lanes_per_shard = lanes_per_shard
        self.streams = [
            EgressStream(
                sink=None if sink is None else (
                    lambda bid, b, s=s: sink(s, bid, b)
                ),
                stats=stats,
            )
            for s in range(n_shards)
        ]

    @property
    def enabled(self) -> bool:
        return self.streams[0].enabled

    @property
    def blocks(self) -> int:
        return self.streams[0].blocks

    @property
    def lanes_scanned(self) -> int:
        return sum(es.lanes_scanned for es in self.streams)

    @property
    def lanes_active(self) -> int:
        return sum(es.lanes_active for es in self.streams)

    @property
    def bytes(self) -> int:
        return sum(es.bytes for es in self.streams)

    def push(self, state):
        lps = self.lanes_per_shard
        if lps is None:
            lps = state.term.shape[0] // self.n_shards
        for s, es in enumerate(self.streams):
            es.push(shard_egress_view(state, s * lps, (s + 1) * lps))

    def flush(self):
        for es in self.streams:
            es.flush()


def merge_delta_bundles(bundles: list) -> "ready_mask.DeltaBundle":
    """Reassemble one block's per-shard DeltaBundles (shard order) into the
    monolithic bundle. Cursor columns concatenate lane-contiguously; the
    dense active prefix rebuilds by offsetting each shard's prefix into
    global lanes — compact_mask emits ascending lane indexes, so the
    shard-order concat of ascending per-shard prefixes IS the monolithic
    ascending prefix, sentinel tail included."""
    lens = [int(b.changed.shape[0]) for b in bundles]
    n = sum(lens)
    changed = np.concatenate([np.asarray(b.changed) for b in bundles])
    active = np.full((n,), n, np.int32)
    cnt, off = 0, 0
    for b, ln in zip(bundles, lens):
        c = int(b.count)
        active[cnt : cnt + c] = np.asarray(b.active[:c]) + off
        cnt += c
        off += ln
    cols = {
        f: np.concatenate([np.asarray(getattr(b, f)) for b in bundles])
        for f in ("term", "lead", "state", "committed", "applied", "last",
                  "rs_count")
    }
    if bundles[0].lease_ok is not None:
        for f in ("lease_ok", "lease_epoch"):
            cols[f] = np.concatenate(
                [np.asarray(getattr(b, f)) for b in bundles]
            )
    return ready_mask.DeltaBundle(
        changed=changed, active=active, count=np.int32(cnt), **cols
    )

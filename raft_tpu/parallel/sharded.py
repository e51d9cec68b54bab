"""Multi-chip scale-out: the cluster round under shard_map over a device mesh.

The group axis is the engine's data-parallel axis (SURVEY §2.3): lanes of one
raft group are contiguous, groups are distributed over the mesh's "groups"
axis, and each shard steps + routes its own groups entirely locally — the
round body contains no collectives at all, so it scales linearly over ICI,
and XLA only inserts the scalar psum for the dropped-message counter.

Cross-host/mesh raft groups (a group whose members live on different shards)
are the host runtime's job, exactly like the reference leaves transport to
the application (README.md:10-14): Ready messages addressed outside the
shard's lane range are exported by the host router (see runtime/), not the
device path.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from raft_tpu import config
from raft_tpu.cluster import (
    Cluster,
    deliver_flat,
    route,
    scan_step,
    _bytes_between,
)
from raft_tpu.messages import MsgBatch, empty_batch
from raft_tpu.ops.fused import donation_enabled as _donation_enabled
from raft_tpu.ops import log as lg
from raft_tpu.ops import step as stepmod
from raft_tpu.types import MessageType as MT, StateType

I32 = jnp.int32


def make_group_mesh(devices, n_lanes: int):
    """(mesh, lane_sharding, shard_lanes): the standard 1-D "groups" mesh and
    the device_put rule shared by every sharded engine — arrays whose leading
    dim is the lane count shard over the mesh, everything else replicates."""
    mesh = Mesh(np.asarray(devices), ("groups",))
    lane_sharding = NamedSharding(mesh, P("groups"))
    repl_sharding = NamedSharding(mesh, P())

    def shard_lanes(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == n_lanes:
            return jax.device_put(x, lane_sharding)
        return jax.device_put(x, repl_sharding)

    return mesh, lane_sharding, shard_lanes


def lane_specs(tree):
    """PartitionSpec tree: every leaf sharded over the "groups" axis."""
    return jax.tree.map(lambda _: P("groups"), tree)


def route_cross_shard(out, *, m_in, v, lanes_per_shard, n_shards):
    """Global delivery for group-sharded meshes where a group's voters MAY
    live on different shards (SURVEY §5.8): shard-local messages deliver
    locally; cross-shard messages ride ONE `lax.all_to_all` over the
    "groups" mesh axis (ICI), bucketed per destination shard.

    Runs inside shard_map. out: the shard's [L, S] outbox (canonical layout:
    member j of global group g lives at global lane g*v + j). Returns
    (inbox [L, m_in], n_dropped) — drops = inbox overflow, bad ids, or
    cross-shard bucket overflow (capacity v*S covers the worst case of one
    straddling group per shard boundary; more pathological placements are
    counted, never misdelivered)."""
    L, s = out.type.shape
    k = L * s
    my = jax.lax.axis_index("groups")
    offset = my * lanes_per_shard

    flat = jax.tree.map(lambda x: x.reshape((k,) + x.shape[2:]), out)
    src_local = jnp.repeat(jnp.arange(L, dtype=I32), s)
    g_global = (offset + src_local) // v
    valid = flat.type != MT.MSG_NONE
    in_range = (flat.to >= 1) & (flat.to <= v)
    bad_id = jnp.sum((valid & ~in_range).astype(I32))
    valid = valid & in_range
    dst_global = g_global * v + (jnp.clip(flat.to, 1, v) - 1)
    dest_shard = dst_global // lanes_per_shard

    local = valid & (dest_shard == my)
    remote = valid & (dest_shard != my)

    # bucket remote messages per destination shard: [D, cap]
    cap = v * s
    sel = remote[None, :] & (
        dest_shard[None, :] == jnp.arange(n_shards, dtype=I32)[:, None]
    )  # [D, K]
    pos = jnp.cumsum(sel.astype(I32), axis=-1) - 1
    overflow = jnp.sum((sel & (pos >= cap)).astype(I32))
    oh = sel[:, None, :] & (
        pos[:, None, :] == jnp.arange(cap, dtype=I32)[None, :, None]
    )  # [D, cap, K]

    def bucket(col):
        cast = col.dtype == jnp.bool_
        x = col.astype(I32) if cast else col
        if x.ndim == 1:
            picked = jnp.sum(jnp.where(oh, x[None, None, :], 0), axis=-1)
        else:  # [K, E]
            picked = jnp.sum(
                jnp.where(oh[..., None], x[None, None, :, :], 0), axis=-2
            )
        return picked.astype(jnp.bool_) if cast else picked

    send = jax.tree.map(bucket, flat)
    send_dst = bucket(dst_global)
    send_live = bucket(remote.astype(I32)).astype(bool)

    # the ICI hop: shard d receives what every shard bucketed for d
    recv = jax.tree.map(
        lambda x: jax.lax.all_to_all(
            x, "groups", split_axis=0, concat_axis=0, tiled=False
        ),
        (send, send_dst, send_live),
    )
    r_msgs, r_dst, r_live = recv

    # merge local + received candidate pools, deliver into [L, m_in]
    def cat(a, b):
        return jnp.concatenate(
            [a, b.reshape((n_shards * cap,) + b.shape[2:])], axis=0
        )

    pool = jax.tree.map(cat, flat, r_msgs)
    dst_local = jnp.concatenate(
        [
            jnp.where(local, dst_global - offset, -1),
            r_dst.reshape(n_shards * cap) - offset,
        ]
    )
    pool_valid = jnp.concatenate([local, r_live.reshape(n_shards * cap)])
    inbox, dropped = deliver_flat(pool, dst_local, pool_valid, L, m_in)
    return inbox, dropped + bad_id + overflow


def _round_body(
    state, inbox, group_of, lane_of, *, m_in, do_tick, lanes_per_shard, v,
    n_shards=None, straddle=False,
):
    """Shard-local cluster round (runs inside shard_map)."""
    e = inbox.ent_term.shape[-1]
    if do_tick:
        state, local = stepmod.tick(state, e)
        inbox = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=1), local, inbox
        )
    state, out_all = scan_step(state, inbox)
    state = dataclasses.replace(state, stabled=state.last)
    applied_bytes = _bytes_between(state, state.applied, state.committed)
    state = lg.applied_to(state, state.committed)
    state = dataclasses.replace(
        state,
        uncommitted_size=jnp.clip(state.uncommitted_size - applied_bytes, 0),
    )
    if straddle:
        nxt, dropped = route_cross_shard(
            out_all, m_in=m_in, v=v,
            lanes_per_shard=lanes_per_shard, n_shards=n_shards,
        )
        return state, nxt, dropped
    offset = jax.lax.axis_index("groups") * lanes_per_shard
    nxt, dropped = route(
        out_all, group_of, lane_of, m_in, lane_offset=offset, lanes_per_group=v
    )
    return state, nxt, dropped


class ShardedCluster(Cluster):
    """A Cluster whose lane axis is sharded over a jax Mesh.

    By default every group must be fully resident on one shard (delivery is
    then purely shard-local). With `straddle=True` a group's voters may
    span shard boundaries: delivery goes through `route_cross_shard`, whose
    cross-shard half is one all_to_all over ICI per round (SURVEY §5.8)."""

    def __init__(
        self, n_groups: int, n_voters: int, devices=None,
        straddle: bool = False, **kw,
    ):
        devices = devices if devices is not None else jax.devices()
        super().__init__(n_groups, n_voters, **kw)
        n = self.shape.n
        if n % len(devices):
            raise ValueError("lanes must divide evenly over devices")
        self.mesh, self.lane_sharding, shard_lanes = make_group_mesh(devices, n)
        self.repl_sharding = NamedSharding(self.mesh, P())
        self.lanes_per_shard = n // len(devices)
        self.n_shards = len(devices)
        self.straddle = straddle
        if not straddle and self.lanes_per_shard % n_voters:
            raise ValueError(
                "groups straddle shard boundaries; pass straddle=True"
            )

        self.state = jax.tree.map(shard_lanes, self.state)
        self.group_of = jax.device_put(self.group_of, self.lane_sharding)
        self.lane_of = jax.device_put(self.lane_of, self.repl_sharding)
        self._round_cache: dict = {}
        # carry donation (ops/fused.py donation_enabled), baked like the
        # fused path: the sharded state carry updates in place per shard
        self._donate = _donation_enabled()

    def _shard_mapped(self, fn):
        """shard_map + jit `fn(state, inbox, group_of, lane_of)` with the
        cluster's lane-sharded in/out specs (dropped counter replicated)."""
        sm = shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(
                lane_specs(self.state),
                lane_specs(self._pending),
                P("groups"),
                P(),
            ),
            out_specs=(
                lane_specs(self.state),
                lane_specs(self._pending),
                P(),
            ),
        )
        # only the state carry is donated: the inbox is rebuilt from the
        # host-side _pending mirror every dispatch (np -> device transfer
        # whose buffer may be host-shared), and group_of/lane_of are re-fed
        return jax.jit(sm, donate_argnums=(0,) if self._donate else ())

    def _sharded_round(self, do_tick: bool):
        if do_tick not in self._round_cache:
            def one(state, inbox, group_of, lane_of):
                state, nxt, d = _round_body(
                    state, inbox, group_of, lane_of,
                    m_in=self.m_in, do_tick=do_tick,
                    lanes_per_shard=self.lanes_per_shard, v=self.v,
                    n_shards=self.n_shards, straddle=self.straddle,
                )
                return state, nxt, jax.lax.psum(d, "groups")

            self._round_cache[do_tick] = self._shard_mapped(one)
        return self._round_cache[do_tick]

    def _do_round(self, do_tick: bool):
        inbox = jax.tree.map(jnp.asarray, self._pending)
        fn = self._sharded_round(do_tick)
        self.state, nxt, dropped = fn(
            self.state, inbox, self.group_of, self.lane_of
        )
        self._pending = jax.tree.map(lambda x: np.array(x), nxt)
        self.dropped += int(dropped)

    def _sharded_rounds(self, do_tick: bool, n_rounds: int):
        """shard_map over a lax.scan of the round body: n_rounds rounds per
        dispatch per shard, one compiled collective program."""
        key = ("scan", do_tick, n_rounds)
        if key not in self._round_cache:
            def scanned(state, inbox, group_of, lane_of):
                def body(carry, _):
                    st, inb, drops = carry
                    st, nxt, d = _round_body(
                        st, inb, group_of, lane_of,
                        m_in=self.m_in, do_tick=do_tick,
                        lanes_per_shard=self.lanes_per_shard, v=self.v,
                        n_shards=self.n_shards, straddle=self.straddle,
                    )
                    return (st, nxt, drops + d), None

                # shard-local (axis-varying) accumulator for dropped counts
                zero = jax.lax.pcast(
                    jnp.zeros((), I32), ("groups",), to="varying"
                )
                (state, inbox, dropped), _ = jax.lax.scan(
                    body, (state, inbox, zero), length=n_rounds,
                )
                # dropped accumulates shard-locally in the carry; one
                # all-reduce per dispatch, not per round
                return state, inbox, jax.lax.psum(dropped, "groups")

            self._round_cache[key] = self._shard_mapped(scanned)
        return self._round_cache[key]

    def run_scanned(self, rounds: int, do_tick: bool = True):
        """`rounds` sharded rounds in one dispatch."""
        fn = self._sharded_rounds(do_tick, rounds)
        inbox = jax.tree.map(jnp.asarray, self._pending)
        self.state, nxt, dropped = fn(
            self.state, inbox, self.group_of, self.lane_of
        )
        self._pending = jax.tree.map(lambda x: np.array(x), nxt)
        self.dropped += int(dropped)

    # device-resident fast path for benchmarking: no host mirrors
    def run_device_rounds(self, n_rounds: int, do_tick: bool = True):
        fn = self._sharded_round(do_tick)
        state = self.state
        pending = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), self.lane_sharding),
            self._pending,
        )
        total_dropped = jnp.zeros((), I32)
        for i in range(n_rounds):
            state, pending, dropped = fn(
                state, pending, self.group_of, self.lane_of
            )
            total_dropped = total_dropped + dropped
            if i % 8 == 7:  # bound in-flight executions (memory pressure)
                jax.block_until_ready(state.term)
        jax.block_until_ready(state.term)
        self.state = state
        self._pending = jax.tree.map(lambda x: np.array(x), pending)
        self.dropped += int(total_dropped)


class ShardedFusedCluster:
    """The fused round kernel under shard_map over a device mesh.

    Groups are distributed over the mesh's "groups" axis. By default every
    group is shard-resident and the per-shard program has NO collectives at
    all, scaling linearly over ICI (the dropped-counter psum of the serial
    path does not exist here: the fabric never drops). With `straddle=True`
    a group's voters may span a shard boundary: delivery rides the halo
    router (ops/fused.py route_fabric_straddle) — two nearest-neighbor
    `ppermute`s of v-1 boundary lanes per fabric field per round, the
    fused analog of the serial route_cross_shard (SURVEY §5.8).
    """

    def __init__(
        self, n_groups: int, n_voters: int, devices=None, seed: int = 1,
        straddle: bool = False, **cfg,
    ):
        from raft_tpu.ops.fused import FusedCluster, StraddleSpec, no_ops

        devices = devices if devices is not None else jax.devices()
        n_lanes = n_groups * n_voters
        self.straddle = straddle
        self._spec = None
        if straddle:
            if n_lanes % len(devices):
                raise ValueError("lanes must divide evenly over devices")
            per = n_lanes // len(devices)
            if per < n_voters:
                raise ValueError(
                    "lanes_per_shard < n_voters: a group would span more "
                    "than two shards (halo covers one boundary)"
                )
            self._spec = StraddleSpec("groups", per, len(devices))
        elif n_groups % len(devices):
            raise ValueError(
                "n_groups must divide evenly over devices "
                "(or pass straddle=True)"
            )
        self.inner = FusedCluster(n_groups, n_voters, seed=seed, **cfg)
        self.g, self.v = n_groups, n_voters
        n = n_groups * n_voters
        self.n_shards = len(devices)
        self.lanes_per_shard = n // len(devices)
        self._shard_tile = None
        self._shard_rounds = None
        if straddle and self.inner.engine == "pallas":
            # the pallas kernel's router is strictly tile-local; the halo
            # ppermute of the straddle path has no kernel analog
            raise ValueError(
                "engine='pallas' does not support straddle=True: the "
                "in-kernel router never crosses a lane tile, let alone "
                "a shard boundary (drop straddle or use engine='xla')"
            )
        self.mesh, self.lane_sharding, shard_lanes = make_group_mesh(devices, n)
        self.inner.state = jax.tree.map(shard_lanes, self.inner.state)
        self.inner.fab = jax.tree.map(shard_lanes, self.inner.fab)
        self.inner.mute = jax.device_put(self.inner.mute, self.lane_sharding)
        if self.inner.metrics is not None:
            # the latency sampler's [N] columns shard with their lanes; the
            # lane-reduced counters/hist/scalars replicate (shard_lanes
            # routes by leading dim)
            self.inner.metrics = jax.tree.map(shard_lanes, self.inner.metrics)
        if self.inner.chaos is not None:
            if straddle:
                raise ValueError(
                    "chaos + straddle is unsupported: the halo router does "
                    "not thread the fault masks across shard boundaries "
                    "(disable RAFT_TPU_CHAOS or straddle)"
                )
            # fault mask columns shard with their lanes; the seed/round/
            # heal scalars and recovery tallies replicate
            self.inner.chaos = jax.tree.map(shard_lanes, self.inner.chaos)
        if self.inner.trace is not None:
            # per-shard event rings: the monolithic [R] ring becomes a
            # stacked [S, R] column sharded over "groups" (each shard
            # appends to its own window with globally stamped lanes); the
            # per-lane stall column shards with its lanes, the round clock
            # replicates. Distinct zeros per leaf — donated carries must
            # never alias buffers.
            from raft_tpu.trace import device as trdev

            tr = self.inner.trace
            s_, r_ = self.n_shards, tr.ring_round.shape[0]
            repl = NamedSharding(self.mesh, P())

            def ring_col():
                return jax.device_put(
                    jnp.zeros((s_, r_), I32), self.lane_sharding
                )

            self.inner.trace = trdev.TraceState(
                ring_round=ring_col(),
                ring_lane=ring_col(),
                ring_kind=ring_col(),
                ring_arg=ring_col(),
                wr=jax.device_put(jnp.zeros((s_,), I32), self.lane_sharding),
                round=jax.device_put(tr.round, repl),
                stall=shard_lanes(tr.stall),
            )
        if self.inner.paged is not None:
            # every paged leaf is axis-0 group-adjacent: pt/faults/
            # exhausted lead with N, the [P, PE] pool splits into
            # per-shard sub-pools with their own local free ranges (page
            # ids are shard-local; they never cross the boundary because
            # page_out/page_in both run inside shard_map on local shapes).
            # shard_lanes routes by leading dim == n and would silently
            # REPLICATE the pool — device_put on the lane sharding
            # directly instead.
            pool_pages = self.inner.paged.pool_term.shape[0]
            if pool_pages % self.n_shards:
                raise ValueError(
                    f"pool_pages={pool_pages} must divide evenly over "
                    f"{self.n_shards} devices (each shard owns a local "
                    "sub-pool with its own trash page; pin Shape.pool_pages "
                    "/ RAFT_TPU_POOL_PAGES to a multiple of the mesh size)"
                )
            from raft_tpu.ops import paged as pgmod

            segs = self.n_shards
            if self.inner._paged_inkernel and self.inner.engine == "pallas":
                # in-kernel paging allocates per kernel grid step: each
                # (shard, tile) pair owns its own sub-pool slice (with its
                # own trash page), so the allocation segment count is
                # shards x tiles-per-shard
                tile = self._resolve_shard_tile()
                segs = self.n_shards * (self.lanes_per_shard // tile)
                pgmod.check_pool_segments(self.inner._page_plan, segs)
            if segs != self.inner._paged_segs:
                # the inner ctor split against its own (mono) segmentation;
                # rewrite the page ids for the sharded grid's segments
                st, pgl = pgmod.resegment(
                    self.inner.state, self.inner.paged,
                    self.inner._paged_segs, segs,
                )
                self.inner.state = jax.tree.map(shard_lanes, st)
                self.inner.paged = pgl
            self.inner.paged = jax.tree.map(
                lambda x: jax.device_put(x, self.lane_sharding),
                self.inner.paged,
            )
            # host-boundary paged ops (rebase / WAL view / adopt) must
            # interpret the dispatch-allocated segment-local page ids
            # against the matching sub-pool, not the global pool
            self.inner._paged_segs = segs
        self._no_ops = jax.tree.map(shard_lanes, no_ops(n))
        self._shard_lanes = shard_lanes
        self._cache = {}
        # donate the (state, fab, metrics) carry, mirroring FusedCluster;
        # ops/mute stay un-donated (self._no_ops and inner.mute are re-fed)
        self._donate = _donation_enabled()
        # hot/cold tiering (RAFT_TPU_TIER): the inner cluster attached an
        # identity-cohort engine at construction; its commits scatter
        # fresh carry buffers OUTSIDE shard_map, so hook the dispatch
        # boundary to re-shard the carry (and mute) back over the mesh
        if self.inner.tier is not None:
            self.inner.tier.post_commit = self._reshard_after_tier

    def attach_tier(self, *, n_logical=None, initial=None, lane_base=0):
        """Re-bind the inner engine (mesh driver path) keeping the
        post-commit re-shard hook attached to the fresh engine."""
        eng = self.inner.attach_tier(
            n_logical=n_logical, initial=initial, lane_base=lane_base
        )
        eng.post_commit = self._reshard_after_tier
        return eng

    def _reshard_after_tier(self):
        inner = self.inner
        inner.state = jax.tree.map(self._shard_lanes, inner.state)
        inner.fab = jax.tree.map(self._shard_lanes, inner.fab)
        inner.mute = jax.device_put(
            jnp.asarray(inner.mute), self.lane_sharding
        )

    def _resolve_shard_tile(self) -> int:
        """Lane tile for the PER-SHARD pallas grid (the kernel sees
        lanes_per_shard lanes inside shard_map). Explicit ctor tile_lanes >
        RAFT_TPU_PALLAS_TILE env > default_tile; no autotune sweep here —
        the per-shard sweep would time the whole collective program."""
        if self._shard_tile is not None:
            return self._shard_tile
        from raft_tpu.ops import pallas_round as plr

        t = self.inner._tile_req
        if t is None:
            t = config.env_int("RAFT_TPU_PALLAS_TILE", default=0) or None
        if t is None:
            t = plr.default_tile(self.lanes_per_shard, self.v)
        plr.check_tile(self.lanes_per_shard, self.v, t)
        self._shard_tile = t
        return t

    def _resolve_shard_rounds(self) -> int:
        """Megakernel K for the per-shard pallas grid. Explicit ctor
        rounds_per_call > RAFT_TPU_PALLAS_ROUNDS env > 1; no joint sweep
        here for the same reason as the tile (timing the collective
        program times the mesh, not the kernel). Validated up front."""
        if self._shard_rounds is not None:
            return self._shard_rounds
        from raft_tpu.ops import pallas_round as plr
        from raft_tpu.ops.fused import _SCAN_UNROLL

        k = self.inner._rounds_req
        if k is None:
            k = plr.env_rounds_per_call()
        if k is None:
            k = 1
        plr.validate_round_plan(k, unroll=_SCAN_UNROLL)
        self._shard_rounds = k
        return k

    def _build_stepper(self, engine, rounds, do_tick, auto_propose,
                       auto_compact_lag, rpc, tile=None, interp=None):
        """Build the jitted shard_map stepper for one
        (engine, rounds, tick/propose/compact, K) signature — the
        program run() caches and dispatches. Factored out of run() so
        the static auditor (raft_tpu/analysis) can enumerate and lower
        the sharded entry point without dispatching a round."""
        from raft_tpu.ops.fused import fused_rounds
        from raft_tpu.ops import pallas_round as plr
        from raft_tpu.trace.device import TraceState

        met = self.inner.metrics
        ch = self.inner.chaos
        tr = self.inner.trace
        pg = self.inner.paged
        has_met, has_ch = met is not None, ch is not None
        has_tr, has_pg = tr is not None, pg is not None
        extras = [x for x in (met, ch, tr, pg) if x is not None]

        # the program closes over these and not over self, so a dropped
        # cluster is freed at once and not left to the cycle collector
        v, spec, lanes_per_shard = self.v, self._spec, self.lanes_per_shard
        paged_inkernel = self.inner._paged_inkernel

        # named for the device trace: its executions show on the `XLA
        # Modules` line as jit_shard_mapped_rounds (benchmark/metrics/
        # mesh.round.device_ms.py), apart from the one-chip fused_rounds
        def shard_mapped_rounds(st, f, o, m, *ex):
            mt = ex[0] if has_met else None
            c = ex[int(has_met)] if has_ch else None
            t = ex[int(has_met) + int(has_ch)] if has_tr else None
            # the paged sidecar's shard slice is self-describing: the
            # engines derive every geometry number from the local leaf
            # shapes + the meta fields, so page ids stay shard-local
            # for free
            p_in = (
                ex[int(has_met) + int(has_ch) + int(has_tr)]
                if has_pg
                else None
            )
            t_loc = lane_off = None
            if has_tr:
                # the shard sees a [1, R] slice of the stacked ring
                # columns: collapse to the engines' monolithic [R] view
                # and record with the shard's global lane offset so
                # event lanes are cluster-global, not shard-local
                t_loc = TraceState(
                    ring_round=t.ring_round[0], ring_lane=t.ring_lane[0],
                    ring_kind=t.ring_kind[0], ring_arg=t.ring_arg[0],
                    wr=t.wr[0], round=t.round, stall=t.stall,
                )
                lane_off = (
                    jax.lax.axis_index("groups")
                    * jnp.int32(lanes_per_shard)
                )
            if engine == "pallas":
                res = plr.pallas_rounds(
                    st, f, o, m,
                    v=v, tile_lanes=tile, n_rounds=rounds,
                    rounds_per_call=rpc,
                    do_tick=do_tick, auto_propose=auto_propose,
                    auto_compact_lag=auto_compact_lag,
                    interpret=interp, metrics=mt, chaos=c,
                    trace=t_loc, trace_lane_offset=lane_off,
                    paged=p_in,
                    paged_inkernel=paged_inkernel,
                )
            else:
                res = fused_rounds(
                    st, f, o, m,
                    v=v, n_rounds=rounds, do_tick=do_tick,
                    auto_propose=auto_propose,
                    auto_compact_lag=auto_compact_lag,
                    straddle=spec, metrics=mt, chaos=c,
                    trace=t_loc, trace_lane_offset=lane_off,
                    paged=p_in,
                    paged_inkernel=paged_inkernel,
                )
            out = [res[0], res[1]]
            j = 2
            if has_met:
                mt2 = res[j]
                j += 1
                # each shard accumulated ONLY its own lanes' events on
                # top of the replicated running totals; one psum of the
                # scalar deltas per dispatch (not per round) rebuilds
                # the replicated global totals — the EQuARX-style
                # aggregate-before-export rule (PAPERS.md)
                mt2 = dataclasses.replace(
                    mt2,
                    counters=mt.counters
                    + jax.lax.psum(mt2.counters - mt.counters, "groups"),
                    hist=mt.hist
                    + jax.lax.psum(mt2.hist - mt.hist, "groups"),
                    lat_sum=mt.lat_sum
                    + jax.lax.psum(mt2.lat_sum - mt.lat_sum, "groups"),
                    # every shard steps the same round count: recompute
                    # from the replicated input
                    round_ctr=mt.round_ctr + jnp.int32(rounds),
                )
                out.append(mt2)
            if has_ch:
                c2 = res[j]
                # the recovery tallies are absolute recounts over the
                # shard's own (group-aligned) lanes, so ONE psum per
                # dispatch rebuilds the exact replicated global count
                c2 = dataclasses.replace(
                    c2,
                    n_reelected=jax.lax.psum(c2.n_reelected, "groups"),
                    n_recommitted=jax.lax.psum(
                        c2.n_recommitted, "groups"
                    ),
                )
                out.append(c2)
                j += 1
            if has_tr:
                t2 = res[j]
                j += 1
                # re-stack the shard's [R] ring back into its [1, R]
                # row of the stacked column (round stays replicated —
                # every shard steps the same count)
                out.append(TraceState(
                    ring_round=t2.ring_round[None],
                    ring_lane=t2.ring_lane[None],
                    ring_kind=t2.ring_kind[None],
                    ring_arg=t2.ring_arg[None],
                    wr=t2.wr[None], round=t2.round, stall=t2.stall,
                ))
            if has_pg:
                # per-lane counters, pool rows, page tables: all
                # shard-local, no psum — ids never leave their shard
                out.append(res[j])
            return tuple(out)

        in_specs = [
            lane_specs(self.inner.state),
            lane_specs(self.inner.fab),
            lane_specs(self._no_ops),
            P("groups"),
        ]
        out_specs = [
            lane_specs(self.inner.state),
            lane_specs(self.inner.fab),
        ]
        if has_met:
            from raft_tpu.metrics.device import MetricsState

            met_specs = MetricsState(
                counters=P(), hist=P(), lat_sum=P(), round_ctr=P(),
                samp_index=P("groups"), samp_round=P("groups"),
            )
            in_specs.append(met_specs)
            out_specs.append(met_specs)
        if has_ch:
            from raft_tpu.chaos.device import ChaosState

            ch_specs = ChaosState(
                seed=P(), round=P(),
                drop_num=P("groups"), dup_num=P("groups"),
                part_send=P("groups"), part_recv=P("groups"),
                tick_skew_num=P("groups"),
                crash_at=P("groups"), restart_at=P("groups"),
                heal_round=P(), base_committed=P("groups"),
                reelect_round=P("groups"), recommit_round=P("groups"),
                n_reelected=P(), n_recommitted=P(),
            )
            in_specs.append(ch_specs)
            out_specs.append(ch_specs)
        if has_tr:
            tr_specs = TraceState(
                ring_round=P("groups"), ring_lane=P("groups"),
                ring_kind=P("groups"), ring_arg=P("groups"),
                wr=P("groups"), round=P(), stall=P("groups"),
            )
            in_specs.append(tr_specs)
            out_specs.append(tr_specs)
        if has_pg:
            # every paged leaf is axis-0 group-adjacent (pt/counters
            # by lane, the pool by sub-pool row) — see __init__
            pg_specs = jax.tree.map(lambda _: P("groups"), pg)
            in_specs.append(pg_specs)
            out_specs.append(pg_specs)
        fn = shard_map(
            shard_mapped_rounds,
            mesh=self.mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
            **({"check_vma": False} if extras else {}),
        )
        donate = ()
        if self._donate:
            donate = (0, 1) + tuple(range(4, 4 + len(extras)))
        return jax.jit(fn, donate_argnums=donate)

    def audit_programs(self, rounds: int = 2):
        """Audit records for the sharded stepper (raft_tpu/analysis).
        Builds the exact jitted shard_map program run() would cache —
        via _build_stepper, so the two can never drift — but only hands
        it to the auditor for tracing/lowering; no dispatch happens."""
        from raft_tpu.ops import pallas_round as plr

        engine = self.inner.engine
        tile = interp = None
        rpc = 1
        if engine == "pallas":
            rpc = self._resolve_shard_rounds()
            tile = self._resolve_shard_tile()
            interp = plr.default_interpret()
        jit = self._build_stepper(
            engine, rounds, True, False, None, rpc, tile, interp,
        )
        extras = [
            x
            for x in (
                self.inner.metrics, self.inner.chaos,
                self.inner.trace, self.inner.paged,
            )
            if x is not None
        ]
        donate_argnums = (
            (0, 1) + tuple(range(4, 4 + len(extras)))
            if self._donate
            else ()
        )
        # the stepper returns its carry first in argument order (state,
        # fab, *extras) regardless of donation mode — declare the carry
        # legs explicitly so the carry-stability proof and the ledger's
        # carry-bytes accounting also cover the copying twin
        carry_argnums = (0, 1) + tuple(range(4, 4 + len(extras)))
        return [dict(
            name=f"sharded.step.{engine}",
            fn=jit,
            jit=jit,
            args=(
                self.inner.state, self.inner.fab, self._no_ops,
                self.inner.mute, *extras,
            ),
            kwargs={},
            static={},
            donate=self._donate,
            donate_argnums=donate_argnums,
            donate_argnames=(),
            lanes=self.inner.shape.n_lanes,
            rounds=rounds,
            carry_argnums=carry_argnums,
            carry_argnames=(),
        )]

    def run(self, rounds: int = 1, ops=None, do_tick: bool = True,
            auto_propose: bool = False, auto_compact_lag=None,
            wal=None, egress=None, trace=None):
        """wal / egress / trace: the same optional runtime streams
        FusedCluster.run takes — the WAL delta streams the slim-canonical
        view of the sharded carry, the egress bundle the raw carry, and the
        trace push drains the stacked per-shard rings (one host drain sees
        every shard's events, merged round-sorted by the stream). All three
        ride the INNER cluster's donation fences (_wal_pending /
        _egress_pending / _trace_pending), so a diet auto-rebase between
        dispatches flushes them exactly like the monolithic path."""
        from raft_tpu.ops import pallas_round as plr

        ops = (
            self._no_ops
            if ops is None
            else jax.tree.map(
                lambda x: self._shard_lanes(jnp.asarray(x)), ops
            )
        )
        self.inner._flush_stream_fences()
        if self.inner._diet:
            # the monolithic path guards every dispatch in FusedCluster.run;
            # this driver dispatches its own shard_map program, so the
            # packed-index overflow guard (and its automatic pre-overflow
            # rebase) must be invoked here — the sharded carry otherwise
            # runs clamp-and-flag into ERR_DIET_OVERFLOW
            self.inner._diet_headroom(rounds)
        met = self.inner.metrics
        ch = self.inner.chaos
        tr = self.inner.trace
        pg = self.inner.paged
        has_met, has_ch = met is not None, ch is not None
        has_tr, has_pg = tr is not None, pg is not None
        extras = [x for x in (met, ch, tr, pg) if x is not None]
        engine = self.inner.engine
        tile = interp = None
        rpc = 1
        if engine == "pallas":
            rpc = self._resolve_shard_rounds()
            tile = self._resolve_shard_tile()
            interp = plr.default_interpret()
        key = (engine, rounds, do_tick, auto_propose, auto_compact_lag, rpc)
        if key not in self._cache:
            self._cache[key] = self._build_stepper(
                engine, rounds, do_tick, auto_propose, auto_compact_lag,
                rpc, tile, interp,
            )
        with self.inner.stats.span("sched.enqueue"):
            res = self._cache[key](
                self.inner.state, self.inner.fab, ops, self.inner.mute,
                *extras,
            )
        self.inner.state, self.inner.fab = res[0], res[1]
        j = 2
        if has_met:
            self.inner.metrics = res[j]
            j += 1
        if has_ch:
            self.inner.chaos = res[j]
            j += 1
        if has_tr:
            self.inner.trace = res[j]
            j += 1
        if has_pg:
            self.inner.paged = res[j]
        # stream pushes land on the INNER fences so the next donating
        # dispatch — or an inner rebase — resolves the async host copies
        # before the buffers they reference are freed (FusedCluster.run's
        # exact discipline)
        if wal is not None:
            wal.push(self.inner._wal_view())
            if self._donate:
                self.inner._wal_pending = wal
        if egress is not None:
            egress.push(self.inner.state)
            if self._donate:
                self.inner._egress_pending = egress
        if trace is not None and has_tr:
            trace.push(self.inner.trace)
            if self._donate:
                self.inner._trace_pending = trace

    def set_chaos(self, **cols):
        """Install chaos columns, then re-shard them over the mesh (the
        inner setter materializes plain unsharded buffers)."""
        self.inner.set_chaos(**cols)
        self.inner.chaos = jax.tree.map(self._shard_lanes, self.inner.chaos)

    def __getattr__(self, name):
        return getattr(self.inner, name)

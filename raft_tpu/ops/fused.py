"""The fused multi-raft round kernel: one kernel invocation per round.

The serial engine (ops/step.py + cluster.scan_step) replays the reference's
one-message-at-a-time `Step` contract (raft.go:1051) — m_in sequential step
invocations per round plus a routing pass. This module is the TPU-native
re-derivation SURVEY §3.2 calls the north-star "single vmapped kernel": the
whole round — tick, delivery, every handler, the MsgAppResp fan-in +
maybeCommit pair (raft.go:1333-1526), vote tally (raft.go:1041), heartbeat
fan-in, and the coalesced append fan-out (raft.go:600-715) — is ONE tensor
program over [N] / [N, V] arrays with no scan, no sort, and no gather.

Key structural ideas:

- **Channel fabric.** Messages live in per-(source lane, destination member)
  slots: three channels (replication, heartbeat, vote) of [N, V] SoA columns
  plus a [N] self slot (the reference's msgsAfterAppend, raft.go:534-580).
  A lane emits at most one message per (dst, channel) per round — which is
  exactly what one pass of the reference's handlers can produce — so slots
  never collide.
- **Routing is a transpose.** Member j of group g receives from member i
  whatever i wrote into dst-slot j: inbox[g, j, i] = outbox[g, i, j]. One
  [G, V, V] axis swap per field; zero routing compute. This replaces the
  deliver-by-sort/compaction of cluster.route.
- **Fan-in is elementwise.** An incoming response from member i lands in
  cell [lane, i] — the same cell as the leader's Progress for that peer
  (canonical layout: member i's raft id is i+1 and its progress slot is i),
  so MaybeUpdate/Inflights/vote recording are [N, V] elementwise updates
  followed by one quorum reduction per lane.
- **At most one append per round.** Only one valid leader exists per term, so
  the winning MsgApp/MsgSnap per lane is selected by a V-way reduction and
  handled once, reusing the serial handlers (handle_append_entries etc.) on a
  composed [N] message view. Losers are stale-term messages the ladder
  already answered.

Scope: the fabric uses the canonical id layout (ids 1..V, contiguous lanes)
internally; deployments with ARBITRARY member ids ride it through the rank
re-canonicalization wrapper (ops/fused_ids.py, differential-tested against
the serial engine on the real ids), and membership changes apply to the
running batch via ops/fused_confchange.py. Everything else —
elections with PreVote/CheckQuorum, randomized timeouts, replication with
probe/replicate/snapshot flow control and inflight windows, commit/apply,
in-fabric snapshot catch-up, leadership transfer, linearizable ReadIndex at
the leader, auto-proposals for steady-state serving — runs on device.

On MsgApp pipelining (reference: raft.go:1516-1518 drain loop): the serial
RawNode path re-invokes `drain_appends` after each ack to fill the inflight
window. Here one MsgApp per peer per round IS the pipeline optimum: the
fabric delivers and acks every round (RTT = 1 round), so window occupancy
never exceeds one message — a deeper burst would only move the same entries
in the same number of rounds while widening the per-round entry gather. The
inflight machinery still gates correctness when a peer lags (snapshot
catch-up, mute masks); it is just never the steady-state constraint.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from raft_tpu import config
from raft_tpu.chaos import device as chmod
from raft_tpu.metrics import device as metmod
from raft_tpu.trace import device as trmod
from raft_tpu.ops import lease as lsmod
from raft_tpu.ops import log as lg
from raft_tpu.ops import onehot as ohm
from raft_tpu.ops import paged as pgmod
from raft_tpu.ops import progress as pg
from raft_tpu.ops import quorum as qr
from raft_tpu.ops import step as stepmod
from raft_tpu.state import RaftState
from raft_tpu.types import (
    CampaignType,
    MessageType as MT,
    ProgressState,
    StateType,
    VoteResult,
    VoteState,
)
from raft_tpu.utils.profiling import StepStats

I32 = jnp.int32
BOOL = jnp.bool_


def _dc(cls):
    fields = [f.name for f in dataclasses.fields(cls)]
    return jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])


# --------------------------------------------------------------------------
# the channel fabric


@_dc
@dataclasses.dataclass(frozen=True)
class RepChan:
    """Replication channel: MsgApp / MsgSnap / MsgAppResp per (src, dst)."""

    kind: Any  # [N, V] i32 MessageType (MSG_NONE = empty)
    term: Any  # [N, V]
    index: Any  # [N, V] APP: prev index; APPRESP: acked/rejected index
    log_term: Any  # [N, V] APP: prev term; APPRESP(rej): hint term
    commit: Any  # [N, V]
    reject: Any  # [N, V] bool
    reject_hint: Any  # [N, V]
    n_ents: Any  # [N, V]
    ent_term: Any  # [N, V, E]
    ent_type: Any  # [N, V, E]
    ent_bytes: Any  # [N, V, E]
    snap_index: Any  # [N, V]
    snap_term: Any  # [N, V]


@_dc
@dataclasses.dataclass(frozen=True)
class HbChan:
    """Heartbeat channel: MsgHeartbeat / MsgHeartbeatResp."""

    kind: Any  # [N, V]
    term: Any  # [N, V]
    commit: Any  # [N, V]
    context: Any  # [N, V] ReadIndex ctx ticket

@_dc
@dataclasses.dataclass(frozen=True)
class VoteChan:
    """Vote-request channel: MsgVote / MsgPreVote / MsgTimeoutNow."""

    kind: Any  # [N, V]
    term: Any  # [N, V]
    index: Any  # [N, V] candidate lastIndex
    log_term: Any  # [N, V] candidate lastTerm
    reject: Any  # [N, V] bool (unused for requests; kept for adapter shape)
    context: Any  # [N, V] campaign-transfer flag


@_dc
@dataclasses.dataclass(frozen=True)
class VoteRespChan:
    """Vote-response channel: Msg(Pre)VoteResp. Separate from requests so a
    lane that answers a vote AND campaigns in the same round never collides
    (the reference emits both as distinct messages)."""

    kind: Any  # [N, V]
    term: Any  # [N, V]
    reject: Any  # [N, V] bool


@_dc
@dataclasses.dataclass(frozen=True)
class SelfMsg:
    """The after-append self slot (MsgAppResp / Msg(Pre)VoteResp to self)."""

    kind: Any  # [N]
    term: Any  # [N]
    index: Any  # [N]


@_dc
@dataclasses.dataclass(frozen=True)
class Fabric:
    rep: RepChan
    hb: HbChan
    vote: VoteChan
    vresp: VoteRespChan
    self_: SelfMsg


def empty_fabric(n: int, v: int, e: int) -> Fabric:
    # Each field allocates its OWN buffer (no shared z/zb/ze Arrays): the
    # fabric is part of the donated carry (donation_enabled below) and XLA
    # rejects the same buffer donated twice within one dispatch.
    def z():
        return jnp.zeros((n, v), I32)

    def zb():
        return jnp.zeros((n, v), BOOL)

    def ze():
        return jnp.zeros((n, v, e), I32)

    def none():
        return jnp.full((n, v), MT.MSG_NONE, I32)

    return Fabric(
        rep=RepChan(none(), z(), z(), z(), z(), zb(), z(), z(), ze(), ze(), ze(), z(), z()),
        hb=HbChan(none(), z(), z(), z()),
        vote=VoteChan(none(), z(), z(), z(), zb(), z()),
        vresp=VoteRespChan(none(), z(), zb()),
        self_=SelfMsg(jnp.full((n,), MT.MSG_NONE, I32), jnp.zeros((n,), I32), jnp.zeros((n,), I32)),
    )


# Fabric carry diet (see state.STATE_SLIM): message-type and count columns
# store as int8 between rounds; term/index/commit columns stay int32. The
# paths below ("rep.kind") address nested channel fields.
FABRIC_SLIM = {
    ("rep", "kind"): jnp.int8,
    ("rep", "n_ents"): jnp.int8,
    ("rep", "ent_type"): jnp.int8,
    ("hb", "kind"): jnp.int8,
    ("vote", "kind"): jnp.int8,
    ("vresp", "kind"): jnp.int8,
    ("self_", "kind"): jnp.int8,
}


def _cast_fabric(fab: Fabric, widen: bool) -> Fabric:
    for (chan_name, field), dt in FABRIC_SLIM.items():
        chan = getattr(fab, chan_name)
        x = getattr(chan, field)
        target = jnp.int32 if widen else dt
        if x.dtype != target:
            chan = dataclasses.replace(chan, **{field: x.astype(target)})
            fab = dataclasses.replace(fab, **{chan_name: chan})
    return fab


def slim_fabric(fab: Fabric) -> Fabric:
    return _cast_fabric(fab, widen=False)


def fat_fabric(fab: Fabric) -> Fabric:
    return _cast_fabric(fab, widen=True)


# Diet-v2 fabric extension (state.pack_state's wire-side twin, applied when
# RAFT_TPU_DIET packs the carry): every bounded term/index/commit column of
# an in-flight message narrows to uint16 — messages carry values from the
# sender's (rebased) state, so the state-side u16 invariant covers them —
# and per-entry payload sizes narrow to int16 under Shape.max_entry_bytes.
# Host-ticket columns (hb.context, vote.context) stay int32. Empty slots
# are zeros (ChannelOutbox starts from empty_fabric each round), so packing
# every cell regardless of kind is exact.
FABRIC_PACK = {
    ("rep", "term"): jnp.uint16,
    ("rep", "index"): jnp.uint16,
    ("rep", "log_term"): jnp.uint16,
    ("rep", "commit"): jnp.uint16,
    ("rep", "reject_hint"): jnp.uint16,
    ("rep", "snap_index"): jnp.uint16,
    ("rep", "snap_term"): jnp.uint16,
    ("rep", "ent_term"): jnp.uint16,
    ("rep", "ent_bytes"): jnp.int16,
    ("hb", "term"): jnp.uint16,
    ("hb", "commit"): jnp.uint16,
    ("vote", "term"): jnp.uint16,
    ("vote", "index"): jnp.uint16,
    ("vote", "log_term"): jnp.uint16,
    ("vresp", "term"): jnp.uint16,
    ("self_", "term"): jnp.uint16,
    ("self_", "index"): jnp.uint16,
}


def is_packed_fabric(fab: Fabric) -> bool:
    """Diet-v2 fabric layout detector (static under jit: leaf dtype)."""
    return fab.rep.index.dtype == jnp.uint16


def fabric_diet_overflow(fab: Fabric):
    """[N] bool: any fabric cell of this (unpacked) fabric outside its
    diet-v2 storage range. Folded into state.error_bits at the store
    boundary (store_carry) — the fabric has no error column of its own."""
    from raft_tpu.state import _DIET_RANGE

    n = fab.self_.kind.shape[0]
    ovf = jnp.zeros((n,), BOOL)
    if is_packed_fabric(fab):
        return ovf
    for (chan_name, field), dt in FABRIC_PACK.items():
        x = getattr(getattr(fab, chan_name), field)
        lo, hi = _DIET_RANGE[dt]
        bad = (x < lo) | (x > hi)
        while bad.ndim > 1:
            bad = bad.any(axis=-1)
        ovf = ovf | bad
    return ovf


def _cast_fabric_map(fab: Fabric, table, widen: bool, clamp: bool) -> Fabric:
    from raft_tpu.state import _DIET_RANGE

    for (chan_name, field), dt in table.items():
        chan = getattr(fab, chan_name)
        x = getattr(chan, field)
        target = jnp.int32 if widen else dt
        if x.dtype != target:
            if clamp and not widen:
                lo, hi = _DIET_RANGE[dt]
                x = jnp.clip(x, lo, hi)
            chan = dataclasses.replace(chan, **{field: x.astype(target)})
            fab = dataclasses.replace(fab, **{chan_name: chan})
    return fab


def pack_fabric(fab: Fabric) -> Fabric:
    """Slim/fat -> diet-v2 packed fabric (idempotent). Out-of-range cells
    clamp; callers fold fabric_diet_overflow into error_bits first
    (store_carry) so a clamp is never silent."""
    if is_packed_fabric(fab):
        return fab
    return _cast_fabric_map(slim_fabric(fab), FABRIC_PACK, widen=False, clamp=True)


def unpack_fabric(fab: Fabric) -> Fabric:
    """Diet-v2 packed -> the exact slim-canonical fabric (idempotent)."""
    if not is_packed_fabric(fab):
        return fab
    return _cast_fabric_map(fab, FABRIC_PACK, widen=True, clamp=False)


def store_carry(state, fab):
    """Diet-v2 store boundary for a (state, fabric) carry pair: fold the
    fabric's overflow flags into state.error_bits (never a silent clamp),
    then pack both. The single definition every engine shares — the XLA
    scan body and the in-kernel pallas replay must cross the exact same
    dtype boundary for bit-identity."""
    from raft_tpu.state import ERR_DIET_OVERFLOW, pack_state

    ovf = fabric_diet_overflow(fab)
    state = dataclasses.replace(
        state,
        error_bits=jnp.asarray(state.error_bits)
        | jnp.where(ovf, jnp.int32(ERR_DIET_OVERFLOW), jnp.int32(0)),
    )
    return pack_state(state), pack_fabric(fab)


def load_carry(state, fab):
    """Diet-v2 load boundary: packed (state, fabric) -> fat compute view."""
    from raft_tpu.state import fat_state, unpack_state

    return fat_state(unpack_state(state)), fat_fabric(unpack_fabric(fab))


def _route_transpose_field(x, v):
    """inbox[g, j, i] = outbox[g, i, j] via an explicit [G,V,V] transpose.
    Readable, but on TPU the [G,V,V,...] intermediates get tile-padded on
    their tiny minor dims (V -> 128 lanes), turning every field into a
    physical retile — profiled at ~73% of the round's device time."""
    g = x.shape[0] // v
    y = x.reshape((g, v, v) + x.shape[2:])
    y = jnp.swapaxes(y, 1, 2)
    return y.reshape(x.shape)


def _route_shift_field(x, v):
    """Same delivery as _route_transpose_field, computed column-wise as V^2
    masked lane-shifts on the FLAT [N, ...] views, which keeps every
    intermediate in the fast lane-major T(1024) tiling (no retile):

    receiver lane l (member r = l % v) reads column i from sender lane
    (l//v)*v + i = l + (i - r), i.e. outbox column r shifted by r - i.
    jnp.roll wraps across group boundaries, but a lane only selects the
    residue case whose shifted read stays inside its own group, so the
    wrapped values are always masked out."""
    n = x.shape[0]
    res = jnp.arange(n, dtype=I32) % v  # receiver's member index
    cols = []
    for i in range(v):
        acc = None
        for r in range(v):
            src = x[:, r]
            if r != i:
                src = jnp.roll(src, r - i, axis=0)
            if acc is None:
                acc = src
            else:
                m = res == r
                m = m.reshape(m.shape + (1,) * (src.ndim - 1))
                acc = jnp.where(m, src, acc)
        cols.append(acc)
    return jnp.stack(cols, axis=1)


class StraddleSpec(NamedTuple):
    """Static description of a group-straddling shard layout (all fields
    hashable so the spec can ride jit static args). A group's v contiguous
    global lanes may cross ONE shard boundary (lanes_per_shard >= v), so a
    halo of v-1 neighbor lanes in each direction covers every cross-shard
    read."""

    axis_name: str
    lanes_per_shard: int
    n_shards: int


def _straddle_res(spec: StraddleSpec, v: int):
    """[L] receiver member index (global lane % v) for this shard. Depends
    on the shard offset because lanes_per_shard need not align to v."""
    offset = jax.lax.axis_index(spec.axis_name) * spec.lanes_per_shard
    return (offset + jnp.arange(spec.lanes_per_shard, dtype=I32)) % v


def _route_straddle_field(x, v, spec: StraddleSpec, res):
    """Cross-shard analog of _route_shift_field, run INSIDE shard_map over
    spec.axis_name: delivery is still inbox[l, i] = outbox[l + i - r, r]
    (r = global lane % v), but the read may land on a neighbor shard. Since
    |i - r| < v <= lanes_per_shard, one halo exchange — each shard fetches
    its neighbors' v-1 boundary lanes via two `lax.ppermute`s (a
    nearest-neighbor hop, the cheapest ICI pattern on a torus) — makes
    every shifted read a STATIC slice of the extended [L + 2(v-1)] array.
    No retile, no all_to_all, no per-message compute (SURVEY §5.8).

    Wrap garbage at the global ends is unreachable: lane l only selects the
    residue case r = l % v, whose read l + i - r stays inside l's own
    v-aligned global group, never below lane 0 or above lane N-1."""
    L = x.shape[0]
    h = v - 1
    if h == 0:
        return x  # single-voter groups: the only column is the self column
    fwd = [(i, (i + 1) % spec.n_shards) for i in range(spec.n_shards)]
    bwd = [(i, (i - 1) % spec.n_shards) for i in range(spec.n_shards)]
    prev_tail = jax.lax.ppermute(x[L - h :], spec.axis_name, fwd)
    next_head = jax.lax.ppermute(x[:h], spec.axis_name, bwd)
    xe = jnp.concatenate([prev_tail, x, next_head], axis=0)  # [L + 2h, V, ...]
    cols = []
    for i in range(v):
        acc = None
        for r in range(v):
            src = jax.lax.slice_in_dim(xe, h + i - r, h + i - r + L, axis=0)
            src = src[:, r]
            if acc is None:
                acc = src
            else:
                m = res == r
                m = m.reshape(m.shape + (1,) * (src.ndim - 1))
                acc = jnp.where(m, src, acc)
        cols.append(acc)
    return jnp.stack(cols, axis=1)


def straddle_peer_mute(mute, v: int, spec: StraddleSpec):
    """[L, V] peer-mute matrix for a straddling shard: cell [l, i] is the
    mute bit of global lane group(l)*v + i (the aligned-case
    mute.reshape(g, 1, v) broadcast, computed through the halo router)."""
    res = _straddle_res(spec, v)
    return _route_straddle_field(
        jnp.broadcast_to(mute[:, None], (mute.shape[0], v)), v, spec, res
    )


def route_fabric_straddle(
    out: Fabric, v: int, mute, spec: StraddleSpec, peer_mute=None
) -> Fabric:
    """route_fabric for group-straddling shard layouts (inside shard_map):
    identical delivery contract — inbox[l, i] = outbox[sender lane, r],
    self slot passes through — with cross-boundary reads riding the halo
    exchange. peer_mute: optional precomputed straddle_peer_mute (it is
    loop-invariant across a scan of rounds)."""
    res = _straddle_res(spec, v)

    def t(x):
        return _route_straddle_field(x, v, spec, res)

    if mute is not None and peer_mute is None:
        peer_mute = straddle_peer_mute(mute, v, spec)

    def deliver(chan):
        chan = jax.tree.map(t, chan)
        if mute is None:
            return chan
        cut = peer_mute | mute[:, None]
        return dataclasses.replace(
            chan, kind=jnp.where(cut, jnp.int32(MT.MSG_NONE), chan.kind)
        )

    return Fabric(
        rep=deliver(out.rep),
        hb=deliver(out.hb),
        vote=deliver(out.vote),
        vresp=deliver(out.vresp),
        self_=out.self_,
    )


# route implementation switch: "auto" (default) picks "shift" (retile-free
# masked rolls — 7-9x faster at scale, where the transpose's [G,V,V]
# retiles dominate) for batches of >=256 lanes and "transpose" (the
# original formulation, fewer kernels — wins at tiny N where everything is
# kernel-count bound; also the oracle in tests) below that. Read once per
# process at trace time; n is static under jit so the choice compiles in.
# Caveat: "shift" emits V^2 roll+select kernels per fabric field (~25
# fields), so kernel count and compile time grow quadratically in the
# voter count — benched and wins at v<=7; if larger v is ever supported,
# fold v into this heuristic (big v + small n should stay "transpose").
_ROUTE_IMPL = config.env_str("RAFT_TPU_ROUTE", default="auto")
_AUTO_SHIFT_MIN_LANES = 256

# rounds-per-scan-iteration in fused_rounds (RAFT_TPU_UNROLL): unrolling
# lets XLA fuse across adjacent rounds' slim<->fat casts and drop per-
# iteration while-loop overhead, at the cost of a proportionally bigger
# program (compile time). Not measured on the chip for today's code.
_SCAN_UNROLL = max(1, config.env_int("RAFT_TPU_UNROLL", default=1))


def aligned_peer_mute(mute, v: int):
    """[N, V] peer-mute matrix for group-aligned lanes: cell [dst, i] is
    the mute bit of group member i of dst's group — lane (dst//v)*v + i.
    Loop-invariant across a scan of rounds; compute once and pass to
    route_fabric/fused_round (the in-scan fallback recomputed it every
    round, profiled at ~6% of round time as a [G,V,V] broadcast+retile)."""
    n = mute.shape[0]
    g = n // v
    return jnp.broadcast_to(mute.reshape(g, 1, v), (g, v, v)).reshape(n, v)


def route_fabric(
    out: Fabric, v: int, mute=None, impl: str | None = None, peer_mute=None
) -> Fabric:
    """Deliver: inbox[g, j, i] = outbox[g, i, j]; the self slot passes
    through (it is the lane's own queued ack).

    mute: optional [N] bool — a muted lane neither sends nor receives (the
    fabric analog of rafttest/network.go:122-144 disconnect).
    peer_mute: optional precomputed aligned_peer_mute(mute, v) — cell
    [dst, i] is the sender's mute bit, loop-invariant across rounds."""
    impl = impl or _ROUTE_IMPL
    if impl not in ("auto", "shift", "transpose"):
        raise ValueError(
            f"route impl {impl!r}: expected 'auto', 'shift' or 'transpose' "
            "(RAFT_TPU_ROUTE)"
        )
    if impl == "auto":
        n_lanes = out.rep.kind.shape[0]
        impl = "shift" if n_lanes >= _AUTO_SHIFT_MIN_LANES else "transpose"
    field = _route_shift_field if impl == "shift" else _route_transpose_field

    def t(x):
        return field(x, v)

    def deliver(chan):
        chan = jax.tree.map(t, chan)
        if mute is None:
            return chan
        src_mute = (
            peer_mute if peer_mute is not None else aligned_peer_mute(mute, v)
        )
        cut = src_mute | mute[:, None]
        return dataclasses.replace(
            chan, kind=jnp.where(cut, jnp.int32(MT.MSG_NONE), chan.kind)
        )

    return Fabric(
        rep=deliver(out.rep),
        hb=deliver(out.hb),
        vote=deliver(out.vote),
        vresp=deliver(out.vresp),
        self_=out.self_,
    )


# --------------------------------------------------------------------------
# Outbox adapter: step.py's emission helpers write into the fabric


_REP_TYPES = (MT.MSG_APP, MT.MSG_SNAP, MT.MSG_APP_RESP)
_HB_TYPES = (MT.MSG_HEARTBEAT, MT.MSG_HEARTBEAT_RESP)
_VOTE_TYPES = (MT.MSG_VOTE, MT.MSG_PRE_VOTE, MT.MSG_TIMEOUT_NOW)
_VRESP_TYPES = (MT.MSG_VOTE_RESP, MT.MSG_PRE_VOTE_RESP)


def _family(types, mtype):
    m = jnp.zeros_like(mtype, dtype=BOOL)
    for t in types:
        m = m | (mtype == t)
    return m


class ChannelOutbox:
    """Implements the Outbox protocol (put_peers/put_self/put_reply) expected
    by step.py's maybe_send_append / bcast_heartbeat / campaign /
    handle_append_entries / ..., writing into the channel fabric. Message
    type is data, so dispatch is by per-element family masks."""

    def __init__(self, state: RaftState, max_entries: int):
        n, v = state.prs_id.shape
        self.n, self.v, self.e = n, v, max_entries
        self.fab = empty_fabric(n, v, max_entries)

    # -- internals --------------------------------------------------------

    def _merge_chan(self, chan, sel, fields):
        upd = {}
        for f in dataclasses.fields(chan):
            old = getattr(chan, f.name)
            src = fields.get("type" if f.name == "kind" else f.name)
            if src is None:
                continue
            new = jnp.asarray(src)
            if new.dtype == BOOL and old.dtype != BOOL:
                new = new.astype(old.dtype)
            m = sel
            while m.ndim < old.ndim:
                m = m[..., None]
            new = jnp.broadcast_to(new, old.shape)
            upd[f.name] = jnp.where(m, new, old)
        return dataclasses.replace(chan, **upd)

    def _put_nv(self, sel_nv, fields_nv):
        """Write [N, V]-shaped messages into their family channels."""
        mtype = jnp.broadcast_to(
            jnp.asarray(fields_nv["type"]), sel_nv.shape
        ).astype(I32)
        fields = dict(fields_nv, type=mtype)
        rep_sel = sel_nv & _family(_REP_TYPES, mtype)
        hb_sel = sel_nv & _family(_HB_TYPES, mtype)
        vote_sel = sel_nv & _family(_VOTE_TYPES, mtype)
        vresp_sel = sel_nv & _family(_VRESP_TYPES, mtype)
        self.fab = dataclasses.replace(
            self.fab,
            rep=self._merge_chan(self.fab.rep, rep_sel, fields),
            hb=self._merge_chan(self.fab.hb, hb_sel, fields),
            vote=self._merge_chan(self.fab.vote, vote_sel, fields),
            vresp=self._merge_chan(self.fab.vresp, vresp_sel, fields),
        )

    # -- Outbox protocol --------------------------------------------------

    def put_peers(self, mask_nv, **fields):
        def bc(x):
            x = jnp.asarray(x)
            if x.ndim == 1 and x.shape[0] == self.n:
                return x[:, None]
            return x

        self._put_nv(mask_nv, {k: bc(v) for k, v in fields.items()})

    def put_reply(self, mask, **fields):
        """Reply to raft id fields['to'] — dst slot = to-1 (canonical)."""
        to = jnp.broadcast_to(jnp.asarray(fields["to"]), mask.shape)
        dst = jnp.clip(to - 1, 0, self.v - 1)
        sel = (
            mask[:, None]
            & ohm.onehot(dst, self.v)
            & ((to >= 1) & (to <= self.v))[:, None]
        )
        fields_nv = {}
        for k, v in fields.items():
            if k == "to":
                continue
            x = jnp.asarray(v)
            if x.ndim >= 1 and x.shape[0] == self.n:
                x = x[:, None] if x.ndim == 1 else x[:, None, ...]
            fields_nv[k] = x
        self._put_nv(sel, fields_nv)

    def put_self(self, mask, **fields):
        """Queue the after-append self-ack (kind/term/index only)."""
        s = self.fab.self_
        mtype = jnp.broadcast_to(jnp.asarray(fields["type"]), mask.shape).astype(I32)
        term = jnp.broadcast_to(jnp.asarray(fields.get("term", 0)), mask.shape).astype(I32)
        index = jnp.broadcast_to(jnp.asarray(fields.get("index", 0)), mask.shape).astype(I32)
        self.fab = dataclasses.replace(
            self.fab,
            self_=SelfMsg(
                kind=jnp.where(mask, mtype, s.kind),
                term=jnp.where(mask, term, s.term),
                index=jnp.where(mask, index, s.index),
            ),
        )


# --------------------------------------------------------------------------
# helpers


def _w(mask, new, old):
    return jnp.where(mask, new, old)


def _select_row(chan, win, any_win):
    """Compose the [N] message view of each lane's winning member slot:
    one-hot select over the member axis; absent lanes read zeros."""
    v = chan.kind.shape[1]
    sel = ohm.onehot(jnp.clip(win, 0), v) & any_win[:, None]  # [N, V]

    def g(x):
        cast = x.dtype == BOOL
        xi = x.astype(I32) if cast else x
        s = sel if x.ndim == 2 else sel[:, :, None]
        got = jnp.sum(jnp.where(s, xi, 0), axis=1)
        return got.astype(BOOL) if cast else got

    return jax.tree.map(g, chan)


class LocalOps(NamedTuple):
    """Host-injected per-round local inputs (all optional zeros)."""

    hup: Any  # [N] bool - MsgHup
    prop_n: Any  # [N] i32 number of entries to propose this round
    prop_bytes: Any  # [N] i32 payload size per entry
    transfer_to: Any  # [N] i32 raft id (0 = none) - MsgTransferLeader
    read_ctx: Any  # [N] i32 ctx ticket (0 = none) - MsgReadIndex at leader
    forget: Any  # [N] bool - MsgForgetLeader
    # conf-change proposal: 0 = none, 1 = EnterJoint/Simple, 2 = LeaveJoint.
    # The change content stays host-side (rare path, SURVEY §7); the device
    # appends the typed entry, applies the proposal gating, and tracks
    # pendingConfIndex (raft.go:1259-1301). See ops/fused_confchange.py.
    prop_cc: Any  # [N] i32
    # host-fired MsgBeat (raft.go:1228-1230) — a heartbeat broadcast outside
    # the tick cadence, e.g. for tickless lockstep drives (testing/lockstep)
    beat: Any  # [N] bool


def no_ops(n: int) -> LocalOps:
    z = jnp.zeros((n,), I32)
    zb = jnp.zeros((n,), BOOL)
    return LocalOps(zb, z, z, z, z, zb, z, zb)


def make_local_ops(n: int, **kw) -> LocalOps:
    """LocalOps over `n` lanes with the given columns set; values may be
    dicts {lane: value} or full arrays."""
    import numpy as np

    base = {
        f: np.zeros((n,), np.bool_ if f in ("hup", "forget", "beat") else np.int32)
        for f in LocalOps._fields
    }
    for k, val in kw.items():
        if isinstance(val, dict):
            for lane, x in val.items():
                base[k][lane] = x
        else:
            base[k][:] = val
    return LocalOps(**{k: jnp.asarray(x) for k, x in base.items()})


# --------------------------------------------------------------------------
# the fused round


def fused_round(
    state: RaftState,
    inb: Fabric,
    ops: LocalOps,
    mute=None,
    *,
    peer_mute=None,
    do_tick: bool = True,
    auto_propose: bool = False,
    auto_compact_lag: int | None = None,
    tick_mask: Any = None,
    metrics: "metmod.MetricsState | None" = None,
):
    """One complete synchronous round for every lane. Returns the next state
    and the outbox fabric (route with route_fabric before the next round);
    with `metrics` set, returns (state, fabric, metrics) instead — every
    instrumentation site below is behind `if metrics is not None`, so the
    metrics-off jaxpr is byte-for-byte free of metrics ops.

    peer_mute: optional [N, V] mute bits of each lane's group members;
    defaults to the aligned reshape of `mute` — REQUIRED on straddling
    shards (straddle_peer_mute), where lanes are not group-aligned.

    tick_mask: optional [N] bool from the chaos plane (raft_tpu/chaos/) —
    lanes with False skip this round's tick entirely (crashed lanes,
    clock-skew skips). None (the default) adds ZERO ops to the trace, the
    same compile-time-elision contract as `metrics`."""
    n, v = state.prs_id.shape
    e = inb.rep.ent_term.shape[-1]
    out = ChannelOutbox(state, e)
    bag = None
    if metrics is not None:
        bag = metmod.EventBag()
        lead0 = state.lead
        committed0 = state.committed
    lanes_v = jnp.arange(v, dtype=I32)[None, :]
    ss = stepmod.self_slot(state)
    is_self = lanes_v == ss[:, None]

    send_sel = jnp.zeros((n, v), BOOL)
    send_sie = jnp.zeros((n, v), BOOL)

    def want_send(cells, sie=None):
        nonlocal send_sel, send_sie
        send_sel = send_sel | cells
        send_sie = send_sie | (cells if sie is None else (cells & sie))

    # ---- tick (reference: raft.go:823-862) ----
    fire_hup = jnp.zeros((n,), BOOL)
    fire_beat = jnp.zeros((n,), BOOL)
    fire_cq = jnp.zeros((n,), BOOL)
    if do_tick:
        is_leader0 = state.state == StateType.LEADER
        ee = state.election_elapsed + 1
        he = jnp.where(is_leader0, state.heartbeat_elapsed + 1, state.heartbeat_elapsed)
        if tick_mask is not None:
            # chaos plane: a masked-out lane's clock does not advance
            ee = jnp.where(tick_mask, ee, state.election_elapsed)
            he = jnp.where(tick_mask, he, state.heartbeat_elapsed)
        fire_hup = (
            ~is_leader0
            & stepmod.promotable(state)
            & (ee >= state.randomized_election_timeout)
        )
        lead_etick = is_leader0 & (ee >= state.cfg.election_tick)
        if tick_mask is not None:
            fire_hup = fire_hup & tick_mask
            lead_etick = lead_etick & tick_mask
        fire_cq = lead_etick & state.cfg.check_quorum
        ee = jnp.where(fire_hup | lead_etick, 0, ee)
        fire_beat = is_leader0 & (he >= state.cfg.heartbeat_tick)
        if tick_mask is not None:
            fire_beat = fire_beat & tick_mask
        he = jnp.where(fire_beat, 0, he)
        state = dataclasses.replace(
            state,
            election_elapsed=ee,
            heartbeat_elapsed=he,
            lead_transferee=_w(lead_etick, 0, state.lead_transferee),
        )

    # ---- presence ----
    rep_p = inb.rep.kind != MT.MSG_NONE
    hb_p = inb.hb.kind != MT.MSG_NONE
    vote_p = inb.vote.kind != MT.MSG_NONE
    vresp_p = inb.vresp.kind != MT.MSG_NONE
    self_p = inb.self_.kind != MT.MSG_NONE

    # ---- term ladder (reference: raft.go:1053-1139) ----
    # keep-term messages never bump us: PreVote requests and granted
    # PreVote responses (raft.go:1069-1086).
    keep_vote = inb.vote.kind == MT.MSG_PRE_VOTE
    keep_vresp = (inb.vresp.kind == MT.MSG_PRE_VOTE_RESP) & ~inb.vresp.reject
    # in-lease vote-request rejection (raft.go:1057-1066)
    force = inb.vote.context == CampaignType.TRANSFER
    in_lease = (
        state.cfg.check_quorum
        & (state.lead != 0)
        & (state.election_elapsed < state.cfg.election_tick)
    )
    is_vreq = (inb.vote.kind == MT.MSG_VOTE) | (inb.vote.kind == MT.MSG_PRE_VOTE)
    lease_ignored = (
        vote_p
        & is_vreq
        & (inb.vote.term > state.term[:, None])
        & ~force
        & in_lease[:, None]
    )

    rep_bump = jnp.max(jnp.where(rep_p, inb.rep.term, 0), axis=1)
    hb_bump = jnp.max(jnp.where(hb_p, inb.hb.term, 0), axis=1)
    vote_bump = jnp.max(
        jnp.where(vote_p & ~keep_vote & ~lease_ignored, inb.vote.term, 0), axis=1
    )
    vresp_bump = jnp.max(
        jnp.where(vresp_p & ~keep_vresp, inb.vresp.term, 0), axis=1
    )
    self_bump = jnp.where(
        self_p & (inb.self_.kind == MT.MSG_APP_RESP), inb.self_.term, 0
    )
    t_new = jnp.maximum(
        jnp.maximum(rep_bump, hb_bump),
        jnp.maximum(jnp.maximum(vote_bump, vresp_bump), self_bump),
    )
    step_down = t_new > state.term
    # leader attribution: an append-family or heartbeat sender at t_new
    from_ldr_rep = rep_p & (inb.rep.term == t_new[:, None]) & (
        (inb.rep.kind == MT.MSG_APP) | (inb.rep.kind == MT.MSG_SNAP)
    )
    from_ldr_hb = hb_p & (inb.hb.term == t_new[:, None]) & (
        inb.hb.kind == MT.MSG_HEARTBEAT
    )
    ldr_member = jnp.max(
        jnp.where(from_ldr_rep | from_ldr_hb, lanes_v + 1, 0), axis=1
    )  # raft id or 0
    state = stepmod.become_follower(state, step_down, t_new, ldr_member)

    # lower-term handling (raft.go:1087-1139)
    low_rep = rep_p & (inb.rep.term < state.term[:, None])
    low_hb = hb_p & (inb.hb.term < state.term[:, None])
    low_vote = vote_p & (inb.vote.term < state.term[:, None])
    ping = (state.cfg.check_quorum | state.cfg.pre_vote)[:, None] & (
        (low_rep & (inb.rep.kind == MT.MSG_APP)) | (low_hb & (inb.hb.kind == MT.MSG_HEARTBEAT))
    )
    out._put_nv(
        ping,
        {
            "type": jnp.full((n, v), MT.MSG_APP_RESP, I32),
            "term": state.term[:, None],
        },
    )
    low_prevote = low_vote & (inb.vote.kind == MT.MSG_PRE_VOTE)
    out._put_nv(
        low_prevote,
        {
            "type": jnp.full((n, v), MT.MSG_PRE_VOTE_RESP, I32),
            "term": state.term[:, None],
            "reject": jnp.ones((n, v), BOOL),
        },
    )
    rep_live = rep_p & ~low_rep
    hb_live = hb_p & ~low_hb
    vote_live = vote_p & ~low_vote & ~lease_ignored

    # ---- winning append-family message (reference: raft.go:1732-1795) ----
    app_cell = rep_live & (
        (inb.rep.kind == MT.MSG_APP) | (inb.rep.kind == MT.MSG_SNAP)
    ) & (inb.rep.term == state.term[:, None])
    any_app = app_cell.any(axis=1)
    win = ohm.argmax_last(app_cell)  # first hot slot
    mrow = _select_row(inb.rep, win, any_app)
    m_frm = jnp.where(any_app, win + 1, 0)

    #   candidates step down on current-term append traffic (raft.go:1639-1647)
    is_cand = (state.state == StateType.CANDIDATE) | (
        state.state == StateType.PRE_CANDIDATE
    )
    state = stepmod.become_follower(state, any_app & is_cand, state.term, m_frm)
    #   followers adopt the leader + reset timer (raft.go:1681-1692)
    adopt = any_app & (state.state == StateType.FOLLOWER)
    state = dataclasses.replace(
        state,
        lead=_w(adopt, m_frm, state.lead),
        election_elapsed=_w(adopt, 0, state.election_elapsed),
    )
    msg_ns = SimpleNamespace(
        frm=m_frm,
        index=mrow.index,
        log_term=mrow.log_term,
        commit=mrow.commit,
        n_ents=mrow.n_ents,
        ent_term=mrow.ent_term,
        ent_type=mrow.ent_type,
        ent_bytes=mrow.ent_bytes,
        snap_index=mrow.snap_index,
        snap_term=mrow.snap_term,
        context=jnp.zeros((n,), I32),
    )
    is_app = any_app & (mrow.kind == MT.MSG_APP) & (state.state == StateType.FOLLOWER)
    state = stepmod.handle_append_entries(state, is_app, msg_ns, out)
    is_snap = any_app & (mrow.kind == MT.MSG_SNAP)
    state = stepmod.handle_snapshot(state, is_snap, msg_ns, out)
    #   in-fabric snapshot transport is instantaneous: the restore IS the
    #   persisted application (sync model), so clear the pending marker
    applied_snap = is_snap & (state.pending_snap_index != 0)
    state = dataclasses.replace(
        state,
        applied=_w(applied_snap, jnp.maximum(state.applied, state.pending_snap_index), state.applied),
        applying=_w(applied_snap, jnp.maximum(state.applying, state.pending_snap_index), state.applying),
        pending_snap_index=_w(applied_snap, 0, state.pending_snap_index),
        pending_snap_term=_w(applied_snap, 0, state.pending_snap_term),
    )

    # ---- winning heartbeat (reference: raft.go:1772-1775) ----
    hb_cell = hb_live & (inb.hb.kind == MT.MSG_HEARTBEAT) & (
        inb.hb.term == state.term[:, None]
    )
    any_hb = hb_cell.any(axis=1)
    hwin = ohm.argmax_last(hb_cell)
    hrow = _select_row(inb.hb, hwin, any_hb)
    h_frm = jnp.where(any_hb, hwin + 1, 0)
    state = stepmod.become_follower(state, any_hb & is_cand, state.term, h_frm)
    adopt_h = any_hb & (state.state == StateType.FOLLOWER)
    state = dataclasses.replace(
        state,
        lead=_w(adopt_h, h_frm, state.lead),
        election_elapsed=_w(adopt_h, 0, state.election_elapsed),
    )
    hb_ns = SimpleNamespace(frm=h_frm, commit=hrow.commit, context=hrow.context)
    state = stepmod.handle_heartbeat(
        state, any_hb & (state.state == StateType.FOLLOWER), hb_ns, out
    )

    # ---- vote casting: grant at most one candidate (raft.go:1164-1212) ----
    vreq_cell = vote_live & is_vreq
    # VOTE requests bumped us to their term already; PREVOTE asks for term+1
    cur = vreq_cell & (
        ((inb.vote.kind == MT.MSG_VOTE) & (inb.vote.term == state.term[:, None]))
        | ((inb.vote.kind == MT.MSG_PRE_VOTE) & (inb.vote.term > state.term[:, None]))
    )
    cand_id = lanes_v + 1
    can_vote = (
        (state.vote[:, None] == cand_id)
        | ((state.vote == 0) & (state.lead == 0))[:, None]
        | ((inb.vote.kind == MT.MSG_PRE_VOTE) & (inb.vote.term > state.term[:, None]))
    )
    # up-to-date evaluated per candidate cell (reference log.go:428-433)
    lt = lg.last_term(state)[:, None]
    up2d_cell = (inb.vote.log_term > lt) | (
        (inb.vote.log_term == lt) & (inb.vote.index >= state.last[:, None])
    )
    grantable = cur & can_vote & up2d_cell
    # A real MSG_VOTE grant records state.vote, so at most one can win per
    # round; PreVote grants record nothing and the reference would grant
    # every qualifying request in sequence (raft.go:1164-1212) — grant all.
    is_pv_cell = inb.vote.kind == MT.MSG_PRE_VOTE
    real_grantable = grantable & ~is_pv_cell
    any_real = real_grantable.any(axis=1)
    gwin = ohm.argmax_last(real_grantable)
    real_grant_cell = real_grantable & (lanes_v == gwin[:, None]) & any_real[:, None]
    grant_cell = (grantable & is_pv_cell) | real_grant_cell
    resp_kind = jnp.where(
        inb.vote.kind == MT.MSG_PRE_VOTE,
        jnp.int32(MT.MSG_PRE_VOTE_RESP),
        jnp.int32(MT.MSG_VOTE_RESP),
    )
    out._put_nv(
        grant_cell,
        {"type": resp_kind, "term": inb.vote.term},
    )
    out._put_nv(
        vreq_cell & ~grant_cell,
        {
            "type": resp_kind,
            "term": state.term[:, None],
            "reject": jnp.ones((n, v), BOOL),
        },
    )
    real_grant = real_grant_cell.any(axis=1)
    state = dataclasses.replace(
        state,
        vote=_w(real_grant, gwin + 1, state.vote),
        election_elapsed=_w(real_grant, 0, state.election_elapsed),
    )

    # ---- TimeoutNow -> transfer campaign (raft.go:1713-1719) ----
    ton = (
        vote_live
        & (inb.vote.kind == MT.MSG_TIMEOUT_NOW)
        & (inb.vote.term == state.term[:, None])
    ).any(axis=1) & (state.state == StateType.FOLLOWER)

    # ---- leader fan-in -------------------------------------------------
    is_leader = state.state == StateType.LEADER

    # Transport feedback: the fabric IS the transport, so snapshot transfer
    # outcomes are known at the next round — the reference's app-side
    # ReportSnapshot -> MsgSnapStatus flow (raft.go:1562-1579) collapses to:
    # muted peer => failure (clear PendingSnapshot), reachable peer =>
    # success (keep it: BecomeProbe resumes at pending+1). Both: probe+pause.
    in_snap = is_leader[:, None] & (state.pr_state == ProgressState.SNAPSHOT)
    if mute is not None:
        if peer_mute is None:
            peer_mute = aligned_peer_mute(mute, v)
        snap_fail = in_snap & (mute[:, None] | peer_mute)
        state = dataclasses.replace(
            state,
            pr_pending_snapshot=_w(snap_fail, 0, state.pr_pending_snapshot),
        )
    state = pg.become_probe(state, in_snap)
    state = dataclasses.replace(
        state,
        pr_msg_app_flow_paused=_w(in_snap, True, state.pr_msg_app_flow_paused),
    )

    # MsgAppResp cells, including the self-ack (reference: raft.go:1333-1526)
    ar_cell = (
        rep_live
        & (inb.rep.kind == MT.MSG_APP_RESP)
        & (inb.rep.term == state.term[:, None])
        & is_leader[:, None]
    )
    self_ar = (
        self_p
        & (inb.self_.kind == MT.MSG_APP_RESP)
        & (inb.self_.term == state.term)
        & is_leader
    )
    ar_all = ar_cell | (self_ar[:, None] & is_self)
    ar_index = jnp.where(
        self_ar[:, None] & is_self, inb.self_.index[:, None], inb.rep.index
    )
    state = dataclasses.replace(
        state, pr_recent_active=_w(ar_all, True, state.pr_recent_active)
    )

    rej_cell = ar_cell & inb.rep.reject
    acc_cell = ar_all & ~(ar_cell & inb.rep.reject)

    def handle_rejections(st):
        next_probe = jnp.where(
            inb.rep.log_term > 0,
            _fcbt_nv(st, inb.rep.reject_hint, inb.rep.log_term),
            inb.rep.reject_hint,
        )
        st, decreased = pg.maybe_decr_to(st, rej_cell, ar_index, next_probe)
        dec_repl = decreased & (st.pr_state == ProgressState.REPLICATE)
        st = pg.become_probe(st, dec_repl)
        return st, decreased

    # rejections are rare in steady state; the whole block is conditional
    any_rej = rej_cell.any()
    state, decreased = jax.lax.cond(
        any_rej,
        handle_rejections,
        # derive the no-op mask from rej_cell so its type (incl. shard_map
        # varying-axis annotation) matches the true branch
        lambda st: (st, rej_cell & False),
        state,
    )
    want_send(decreased)

    old_paused = pg.is_paused(state)
    state, updated = pg.maybe_update(state, acc_cell, ar_index)
    probe_refresh = (
        acc_cell
        & (state.pr_match == ar_index)
        & (state.pr_state == ProgressState.PROBE)
    )
    advanced = updated | probe_refresh
    from_probe = advanced & (state.pr_state == ProgressState.PROBE)
    state = pg.become_replicate(state, from_probe)
    from_snap = (
        advanced
        & (state.pr_state == ProgressState.SNAPSHOT)
        & (state.pr_match + 1 >= state.first_index[:, None])
    )
    state = pg.become_probe(state, from_snap)
    state = pg.become_replicate(state, from_snap)
    in_repl = advanced & (state.pr_state == ProgressState.REPLICATE)
    state = pg.inflights_free_le(state, in_repl, ar_index)

    advanced_lane = advanced.any(axis=1)
    mci = qr.joint_committed(
        jnp.where(stepmod.voter_mask(state), state.pr_match, 0),
        state.voters_in,
        state.voters_out,
    )
    state, committed_adv = lg.maybe_commit(
        state, jnp.where(advanced_lane, mci, 0), state.term
    )
    all_peers = jnp.ones((n, v), BOOL)
    want_send(committed_adv[:, None] & all_peers)
    retry = advanced & ~committed_adv[:, None] & ~is_self
    want_send(retry, old_paused)

    # leadership transfer completion (raft.go:1519-1524)
    xfer_cell = (
        acc_cell
        & advanced
        & ((lanes_v + 1) == state.lead_transferee[:, None])
        & (state.pr_match == state.last[:, None])
    )
    out._put_nv(
        xfer_cell,
        {"type": jnp.full((n, v), MT.MSG_TIMEOUT_NOW, I32), "term": state.term[:, None]},
    )

    # MsgHeartbeatResp cells (raft.go:1527-1561)
    hr_cell = (
        hb_live
        & (inb.hb.kind == MT.MSG_HEARTBEAT_RESP)
        & (inb.hb.term == state.term[:, None])
        & is_leader[:, None]
    )
    state = dataclasses.replace(
        state,
        pr_recent_active=_w(hr_cell, True, state.pr_recent_active),
        pr_msg_app_flow_paused=_w(hr_cell, False, state.pr_msg_app_flow_paused),
    )
    need_app = hr_cell & (
        (state.pr_match < state.last[:, None])
        | (state.pr_state == ProgressState.PROBE)
    )
    want_send(need_app)

    # ReadIndex acks via heartbeat ctx (raft.go:1548-1561,
    # read_only.go:68-112): a quorum ack for a ctx releases the whole FIFO
    # *prefix* up to and including that request — quorum confirmation of
    # leadership at a later enqueue point covers every earlier pending
    # read. Mirrors the serial MsgHeartbeatResp block (step.py:1144-1239)
    # with the fused model's requester == self simplification. (The
    # original fused rule here released slots individually, which could
    # strand an earlier read whose acks were lost and, because freed low
    # slots are reused, emit ReadStates out of enqueue order — both caught
    # by the lockstep differential, testing/lockstep.py.)
    r_ax = state.ro_ctx.shape[1]
    hit = (
        hr_cell[:, None, :]
        & (state.ro_ctx[:, :, None] == inb.hb.context[:, None, :])
        & (state.ro_ctx[:, :, None] != 0)
    )  # [N, R, V]
    acks = state.ro_acks | hit
    ro_votes = jnp.where(acks, jnp.int32(VoteState.GRANTED), jnp.int32(VoteState.PENDING))
    ro_res = qr.joint_vote(
        ro_votes, state.voters_in[:, None, :], state.voters_out[:, None, :]
    )
    live_ro = state.ro_ctx != 0
    won = live_ro & (ro_res == VoteResult.VOTE_WON) & hit.any(axis=2)
    won_seq = jnp.max(jnp.where(won, state.ro_seq, -1), axis=1)  # [N]
    release = live_ro & (state.ro_seq <= won_seq[:, None])
    # pack released slots into the rs ring in FIFO (ro_seq) order — slot
    # order diverges from enqueue order once freed low slots are reused
    sq = state.ro_seq
    rel_rank = jnp.sum(
        release[:, None, :] & (sq[:, None, :] < sq[:, :, None]), axis=-1
    )
    dst_slot = state.rs_count[:, None] + rel_rank
    put = release & (dst_slot < r_ax)
    # only slots whose ReadState actually packed clear; an rs-ring overflow
    # keeps the (highest-seq, so still FIFO-contiguous) tail pending for a
    # later quorum hit instead of silently dropping confirmed reads —
    # mirrors the serial ok_rs gating (step.py)
    state = dataclasses.replace(
        state,
        rs_ctx=ohm.scatter_set(state.rs_ctx, jnp.clip(dst_slot, 0, r_ax - 1), state.ro_ctx, put),
        rs_index=ohm.scatter_set(state.rs_index, jnp.clip(dst_slot, 0, r_ax - 1), state.ro_index, put),
        rs_count=jnp.minimum(state.rs_count + jnp.sum(put.astype(I32), axis=1), r_ax),
        ro_ctx=_w(put, 0, state.ro_ctx),
        ro_from=_w(put, 0, state.ro_from),
        ro_index=_w(put, 0, state.ro_index),
        ro_seq=_w(put, 0, state.ro_seq),
        ro_acks=jnp.where(put[:, :, None], False, acks),
    )
    if metrics is not None:
        bag.add("read_index_served", put)

    # Msg(Pre)VoteResp cells -> poll (raft.go:1041-1049, 1647-1666)
    my_resp = jnp.where(
        state.state == StateType.PRE_CANDIDATE,
        jnp.int32(MT.MSG_PRE_VOTE_RESP),
        jnp.int32(MT.MSG_VOTE_RESP),
    )
    vresp_live = vresp_p & ~(
        vresp_p & (inb.vresp.term < state.term[:, None])
    )
    vr_cell = vresp_live & (inb.vresp.kind == my_resp[:, None]) & is_cand[:, None]
    self_vr = self_p & (
        (inb.self_.kind == my_resp) & is_cand
    )
    vr_all = vr_cell | (self_vr[:, None] & is_self)
    vr_rej = vr_cell & inb.vresp.reject  # self vote never rejects
    state = dataclasses.replace(
        state,
        votes=jnp.where(
            vr_all & stepmod.voter_mask(state),
            jnp.where(vr_rej, jnp.int32(VoteState.REJECTED), jnp.int32(VoteState.GRANTED)),
            state.votes,
        ),
    )
    res = qr.joint_vote(state.votes, state.voters_in, state.voters_out)
    tallied = vr_all.any(axis=1) & is_cand
    won = tallied & (res == VoteResult.VOTE_WON)
    lost = tallied & (res == VoteResult.VOTE_LOST)
    pre_won = won & (state.state == StateType.PRE_CANDIDATE)
    real_won = won & (state.state == StateType.CANDIDATE)
    state = stepmod.become_leader(state, real_won, out)
    want_send(real_won[:, None] & all_peers)
    state = stepmod.become_follower(state, lost, state.term, jnp.zeros((n,), I32))

    # ---- local inputs ---------------------------------------------------
    # campaign: ticks, injected hups, TimeoutNow transfers, PreVote wins
    ctype = jnp.where(
        state.cfg.pre_vote,
        jnp.int32(CampaignType.PRE_ELECTION),
        jnp.int32(CampaignType.ELECTION),
    )
    ctype = jnp.where(ton, jnp.int32(CampaignType.TRANSFER), ctype)
    ctype = jnp.where(pre_won, jnp.int32(CampaignType.ELECTION), ctype)
    # hup() itself guards against leaders/learners/pending conf changes
    state, hup_fired = stepmod.hup(
        state, fire_hup | ops.hup | ton | pre_won, ctype, out
    )
    if metrics is not None:
        bag.add("elections_started", hup_fired)
        bag.add("elections_won", real_won)

    # CheckQuorum (raft.go:1231-1243)
    is_leader = state.state == StateType.LEADER
    cq = fire_cq & is_leader
    active_m = state.pr_recent_active | is_self
    alive = qr.joint_active(active_m, state.voters_in, state.voters_out)
    state = stepmod.become_follower(state, cq & ~alive, state.term, jnp.zeros((n,), I32))
    state = dataclasses.replace(
        state,
        pr_recent_active=_w(cq[:, None] & ~is_self, False, state.pr_recent_active),
    )

    # heartbeats (MsgBeat, raft.go:1228-1230) — carry the newest pending
    # ReadIndex ctx so acks lost to a partition re-confirm on the next
    # beat (read_only.go lastPendingRequestCtx; mirrors the serial
    # MSG_BEAT block, step.py:856-868)
    is_leader = state.state == StateType.LEADER
    beat_live = state.ro_ctx != 0
    beat_newest = ohm.argmax_last(jnp.where(beat_live, state.ro_seq, -1))
    beat_ctx = jnp.where(
        beat_live.any(axis=1), ohm.gather(state.ro_ctx, beat_newest), 0
    )
    state = stepmod.bcast_heartbeat(
        state, (fire_beat | ops.beat) & is_leader, out, ctx=beat_ctx
    )

    # proposals (raft.go:1244-1302; conf-change entries excluded by scope)
    prop_n = jnp.where(auto_propose, jnp.maximum(ops.prop_n, is_leader.astype(I32)), ops.prop_n)
    prop = (
        (prop_n > 0)
        & is_leader
        & (state.lead_transferee == 0)
        & (ss >= 0)
    )
    k = jnp.arange(e, dtype=I32)[None, :]
    pn = jnp.minimum(prop_n, e)
    ent_bytes = jnp.where(
        (k < pn[:, None]) & prop[:, None], ops.prop_bytes[:, None], 0
    )
    zeros_e = jnp.zeros((n, e), I32)
    state, appended = stepmod.append_entry(
        state, prop, zeros_e, zeros_e, ent_bytes, pn, out
    )
    want_send(appended[:, None] & all_peers)
    if metrics is not None:
        bag.add("proposals", jnp.where(appended, pn, 0))
        # fused ErrProposalDropped: a lane asked to propose but nothing
        # landed (not leader, transfer in progress, or window full)
        bag.add("proposals_dropped", (prop_n > 0) & ~appended)
        metrics = metmod.arm_sample(metrics, appended, state.last)

    # conf-change proposal (raft.go:1259-1301): one ENTRY_CONF_CHANGE_V2
    # entry whose content the host holds. Gating per the reference: refuse
    # while a change is pending (pendingConfIndex > applied), refuse a
    # non-leave change while in joint, refuse leave while not joint — a
    # refused change still appends an empty NORMAL entry in its place
    # (raft.go:1284-1296). pendingConfIndex moves to the appended index.
    from raft_tpu.types import EntryType

    want_cc = (
        (ops.prop_cc > 0)
        & is_leader
        & (state.lead_transferee == 0)
        & (ss >= 0)
    )
    joint = state.voters_out.any(axis=1)
    pending_cc = state.pending_conf_index > state.applied
    refused = pending_cc | jnp.where(ops.prop_cc == 2, ~joint, joint)
    cc_ok = want_cc & ~refused
    cc_type = jnp.where(
        cc_ok[:, None] & (jnp.arange(e, dtype=I32)[None, :] == 0),
        jnp.int32(EntryType.ENTRY_CONF_CHANGE_V2),
        0,
    )
    state, cc_appended = stepmod.append_entry(
        state, want_cc, zeros_e, cc_type, zeros_e, jnp.ones((n,), I32), out
    )
    state = dataclasses.replace(
        state,
        pending_conf_index=_w(
            cc_appended & cc_ok, state.last, state.pending_conf_index
        ),
    )
    want_send(cc_appended[:, None] & all_peers)
    if metrics is not None:
        bag.add("proposals", cc_appended)
        # a refused change appends an empty entry in its place — the CC
        # content itself was still dropped (raft.go:1284-1296)
        bag.add("proposals_dropped", want_cc & (refused | ~cc_appended))

    # transfer-leadership request (raft.go:1587-1618), injected at the
    # leader. Refused for untracked or learner transferees (raft.go:
    # 1592-1596 — the serial gate at step.py:1296-1306; the learner and
    # trackedness checks here were caught by the lockstep differential).
    tt = ops.transfer_to
    t_slot = jnp.clip(tt - 1, 0, v - 1)
    t_tracked = ohm.gather(state.prs_id, t_slot) != 0
    t_learner = ohm.gather(state.learners, t_slot)
    t_ok = (
        is_leader
        & (tt != 0)
        & (tt != state.lead_transferee)
        & (tt != state.id)
        & (tt >= 1)
        & (tt <= v)
        & t_tracked
        & ~t_learner
    )
    t_cell = ohm.onehot(t_slot, v) & t_ok[:, None]
    state = dataclasses.replace(
        state,
        election_elapsed=_w(t_ok, 0, state.election_elapsed),
        lead_transferee=_w(t_ok, tt, state.lead_transferee),
    )
    t_ready = t_cell & (state.pr_match == state.last[:, None])
    out._put_nv(
        t_ready,
        {"type": jnp.full((n, v), MT.MSG_TIMEOUT_NOW, I32), "term": state.term[:, None]},
    )
    want_send(t_cell & ~t_ready)

    # ReadIndex at the leader (raft.go:1303-1332); single-voter/lease-based
    # groups answer immediately, else enqueue + ctx'd heartbeat broadcast
    ri = (ops.read_ctx != 0) & is_leader
    committed_in_term = lg.term_at(state, state.committed) == state.term
    ri_ok = ri & committed_in_term
    n_in = jnp.sum(state.voters_in.astype(I32), axis=1)
    n_out = jnp.sum(state.voters_out.astype(I32), axis=1)
    single = (n_in <= 1) & (n_out == 0)
    immediate = ri_ok & (single | state.cfg.read_only_lease_based)
    enq = ri_ok & ~immediate
    free = state.ro_ctx == 0
    first_free = ohm.argmax_last(free)
    can_enq = enq & free.any(axis=1)
    put_r = ohm.onehot(first_free, r_ax) & can_enq[:, None]
    state = dataclasses.replace(
        state,
        ro_ctx=_w(put_r, ops.read_ctx[:, None], state.ro_ctx),
        ro_from=_w(put_r, state.id[:, None], state.ro_from),
        ro_index=_w(put_r, state.committed[:, None], state.ro_index),
        ro_acks=_w(put_r[:, :, None], is_self[:, None, :], state.ro_acks),
        # enqueue sequence — the FIFO order the prefix-release rule and the
        # beat ctx pick rely on (serial counterpart: step.py:976-986)
        ro_seq=_w(put_r, state.ro_next_seq[:, None], state.ro_seq),
        ro_next_seq=state.ro_next_seq + can_enq.astype(I32),
    )
    state = stepmod.bcast_heartbeat(state, can_enq, out, ctx=ops.read_ctx)
    # immediate release -> rs ring
    imm_slot = jnp.clip(state.rs_count, 0, r_ax - 1)
    imm_put = ohm.onehot(imm_slot, r_ax) & (immediate & (state.rs_count < r_ax))[:, None]
    if metrics is not None:
        bag.add("read_index_served", immediate & (state.rs_count < r_ax))
    state = dataclasses.replace(
        state,
        rs_ctx=_w(imm_put, ops.read_ctx[:, None], state.rs_ctx),
        rs_index=_w(imm_put, state.committed[:, None], state.rs_index),
        rs_count=_w(
            immediate & (state.rs_count < r_ax), state.rs_count + 1, state.rs_count
        ),
    )

    # forget leader (raft.go:1700-1708; refused under lease-based reads,
    # matching the serial gate at step.py:1397-1403)
    state = dataclasses.replace(
        state,
        lead=_w(
            ops.forget
            & (state.state == StateType.FOLLOWER)
            & (state.lead != 0)
            & ~state.cfg.read_only_lease_based,
            0,
            state.lead,
        ),
    )

    # ---- the single coalesced append fan-out ----
    state = stepmod.maybe_send_append(state, send_sel, send_sie, out)

    # ---- synchronous persist + apply (doc.go:79-103 in the sync model) ----
    state = dataclasses.replace(state, stabled=state.last)
    applied_bytes = _bytes_between(state, state.applied, state.committed)
    state = lg.applied_to(state, state.committed)
    state = dataclasses.replace(
        state,
        uncommitted_size=jnp.clip(state.uncommitted_size - applied_bytes, 0),
    )
    if auto_compact_lag is not None:
        # the continuous-serving analog of the app's CreateSnapshot/Compact
        # loop (storage.go:227-272): snapshot at `applied` (what
        # Storage.Snapshot() returns — always fresh in the sync model, so
        # restored stragglers land within the retained window and switch to
        # streaming), then compact the window keeping `lag` entries.
        state = dataclasses.replace(
            state,
            avail_snap_index=state.applied,
            avail_snap_term=lg.term_at(state, state.applied),
        )
        target = jnp.maximum(
            state.snap_index, state.applied - jnp.int32(auto_compact_lag)
        )
        state = lg.compact(state, target, lg.term_at(state, target))

    # ---- leader-lease maintenance (RAFT_TPU_LEASE, ops/lease.py) ----
    # Runs LAST, against the round's final role/transfer/confchange state.
    # The renewal evidence is a joint quorum of THIS round's append +
    # heartbeat acks (fresh, unlike the cumulative pr_recent_active): a
    # lane that only just won leadership has no ack cells yet and cannot
    # grant itself a lease on its election round. Purely observational —
    # nothing here feeds back into a raft decision, so lease on/off walks
    # a bit-identical raft trajectory.
    if state.lease_left is not None:
        ack_now = ar_all | hr_cell | is_self
        ack_votes = jnp.where(
            ack_now, jnp.int32(VoteState.GRANTED), jnp.int32(VoteState.PENDING)
        )
        ack_quorum = (
            qr.joint_vote(ack_votes, state.voters_in, state.voters_out)
            == VoteResult.VOTE_WON
        )
        skipped = jnp.zeros((n,), BOOL)
        if do_tick and tick_mask is not None:
            skipped = ~tick_mask
        state = dataclasses.replace(
            state,
            **lsmod.lease_round(
                state,
                is_leader=state.state == StateType.LEADER,
                ack_quorum=ack_quorum,
                skipped_tick=skipped,
                margin=lsmod.lease_margin(),
            ),
        )

    if metrics is None:
        return state, out.fab
    # ---- end-of-round measurement (one fused reduction pass) ----
    # network messages emitted this round, by family (the self-ack slot is
    # local bookkeeping, not network traffic — excluded)
    rk, hk = out.fab.rep.kind, out.fab.hb.kind
    bag.add("msgs_app", (rk == MT.MSG_APP) | (rk == MT.MSG_SNAP))
    bag.add("msgs_app_resp", rk == MT.MSG_APP_RESP)
    bag.add("msgs_heartbeat", hk == MT.MSG_HEARTBEAT)
    bag.add("msgs_heartbeat_resp", hk == MT.MSG_HEARTBEAT_RESP)
    bag.add("msgs_vote", out.fab.vote.kind != MT.MSG_NONE)
    bag.add("msgs_vote_resp", out.fab.vresp.kind != MT.MSG_NONE)
    # observed-leader churn and commit progress vs the start of the round
    bag.add("leader_changes", (state.lead != lead0) & (state.lead != 0))
    bag.add("commits", state.committed - committed0)
    metrics = metmod.observe_commit_latency(metrics, state)
    metrics = metmod.commit_round(metrics, bag)
    return state, out.fab, metrics


def _fcbt_nv(state: RaftState, index_nv, term_nv):
    """find_conflict_by_term over [N, V] (leader-side rejection hints;
    reference log.go:166-194). Masked max over the window per cell."""
    n, v = index_nv.shape
    idx_w, valid_w = lg.window_indexes(state)  # [N, W]
    cand = (
        valid_w[:, None, :]
        & (idx_w[:, None, :] <= index_nv[:, :, None])
        & (state.log_term[:, None, :] <= term_nv[:, :, None])
    )
    best = jnp.max(jnp.where(cand, idx_w[:, None, :], -1), axis=-1)
    snap_ok = (state.snap_index[:, None] <= index_nv) & (
        state.snap_term[:, None] <= term_nv
    )
    best = jnp.maximum(best, jnp.where(snap_ok, state.snap_index[:, None], -1))
    above = index_nv > state.last[:, None]
    best = jnp.where(above, index_nv, best)
    below = jnp.minimum(index_nv, state.snap_index[:, None] - 1)
    best = jnp.where(best < 0, jnp.maximum(below, 0), best)
    return jnp.maximum(best, 0)


def _bytes_between(state: RaftState, lo, hi):
    idx, valid = lg.window_indexes(state)
    m = valid & (idx > lo[:, None]) & (idx <= hi[:, None])
    return jnp.sum(jnp.where(m, state.log_bytes, 0), axis=1)


# --------------------------------------------------------------------------
# index-space rebase under live traffic


def donation_enabled() -> bool:
    """Read RAFT_TPU_DONATE lazily (default ON) so tests can toggle it
    per-cluster; like metrics_enabled, the value is baked into each cluster
    at construction. When on, every fused entry point donates its
    (state, fab, metrics) carry to XLA — the carry updates in place instead
    of double-buffering, halving resident carry HBM and removing a full
    carry copy per dispatch. RAFT_TPU_DONATE=0 restores the copying
    behavior (and keeps stale host references to pre-dispatch carries
    readable, which the donating path deliberately does not)."""
    return config.env_flag("RAFT_TPU_DONATE", default=True)


def _rebase_indexes(state, mask, delta):
    from raft_tpu.ops import log as _lg

    return _lg.rebase_indexes(state, mask, delta)


_rebase_indexes_jit = jax.jit(_rebase_indexes)
# donating twin (state carry updated in place); used by FusedCluster when
# donation_enabled() was true at construction. Kept separate so callers
# that re-feed the input state (api/rawnode's serial path, tests holding
# references) can keep the copying variant.
_rebase_indexes_donate_jit = jax.jit(_rebase_indexes, donate_argnums=(0,))


def _rebase_fabric(fab: Fabric, delta) -> Fabric:
    """Shift the index-valued columns of in-flight fabric messages down by
    `delta` [N] (per SOURCE lane; all lanes of a group rebase together, and
    delivery never crosses groups, so source-lane deltas are destination
    deltas too). The i32-overflow recovery (ops/log.py rebase_indexes) can
    therefore run BETWEEN dispatch blocks without draining the fabric —
    the live-traffic rebase VERDICT r3 item 9 asks for."""
    d = jnp.asarray(delta)

    def shift(x, live, floor=0):
        return jnp.where(live, jnp.maximum(x - d[:, None], floor), x)

    rep = fab.rep
    rep_live = rep.kind != MT.MSG_NONE
    rep = dataclasses.replace(
        rep,
        index=shift(rep.index, rep_live),
        commit=shift(rep.commit, rep_live),
        reject_hint=shift(rep.reject_hint, rep_live),
        snap_index=shift(rep.snap_index, rep_live & (rep.snap_index > 0)),
    )
    hb = dataclasses.replace(
        fab.hb, commit=shift(fab.hb.commit, fab.hb.kind != MT.MSG_NONE)
    )
    vote = dataclasses.replace(
        fab.vote, index=shift(fab.vote.index, fab.vote.kind != MT.MSG_NONE)
    )
    self_live = fab.self_.kind != MT.MSG_NONE
    self_ = dataclasses.replace(
        fab.self_,
        index=jnp.where(self_live, jnp.maximum(fab.self_.index - d, 0), fab.self_.index),
    )
    return dataclasses.replace(fab, rep=rep, hb=hb, vote=vote, self_=self_)


rebase_fabric = jax.jit(_rebase_fabric)
_rebase_fabric_donate_jit = jax.jit(_rebase_fabric, donate_argnums=(0,))


# --------------------------------------------------------------------------
# scan driver


def fused_rounds(
    state: RaftState,
    fab: Fabric,
    ops: LocalOps,
    mute,
    *,
    v: int,
    n_rounds: int,
    do_tick: bool = True,
    auto_propose: bool = False,
    auto_compact_lag: int | None = None,
    ops_first_round_only: bool = True,
    straddle: StraddleSpec | None = None,
    paged_inkernel: bool = False,
    metrics: "metmod.MetricsState | None" = None,
    chaos: "chmod.ChaosState | None" = None,
    trace: "trmod.TraceState | None" = None,
    trace_lane_offset=None,
    paged: "pgmod.PagedLog | None" = None,
):
    """n_rounds fused rounds in one dispatch. `ops` applies to the first
    round only (one-shot injections) unless ops_first_round_only=False.

    The scan carry rides in the slim storage dtypes (state.STATE_SLIM /
    FABRIC_SLIM): each round widens to int32, computes, and narrows back, so
    HBM holds the dieted layout while the ALU path is unchanged. XLA fuses
    the casts into the adjacent ops.

    straddle: when set (inside shard_map over spec.axis_name), delivery
    rides the cross-shard halo router (route_fabric_straddle) so a group's
    voters may span a shard boundary.

    metrics: optional metrics carry (raft_tpu/metrics/); when set the
    return is (state, fab, metrics) and the carry threads through the scan
    (already-scalar counters — no per-lane state leaves the device).

    chaos: optional chaos carry (raft_tpu/chaos/); when set, faults apply
    around every round (drops/partitions/crashes before the step,
    duplicates + recovery probing after) and the carry is appended to the
    return tuple. None keeps every fault op out of the trace, like
    metrics=None. Requires group-aligned lanes (no straddle).

    trace: optional flight-recorder carry (raft_tpu/trace/); when set each
    round's per-lane transitions are detected from the (pre, post) fat
    state diff and ring-appended (trace/device.py record_round), and the
    carry is appended to the return tuple. trace_lane_offset (a traced
    scalar, sharded dispatch) globalizes the event lane stamps.

    paged: optional paged entry log sidecar (ops/paged.py); when set the
    incoming state carries only the resident [N, W_res] log tail — the
    full [N, W] window is reconstructed here (page_in), the scan runs on
    it unchanged, and the result re-splits (page_out) before returning,
    with the updated PagedLog appended LAST in the result tuple. None
    compiles the exact unpaged program plus a stale-slot scrub so raw
    carries and stream bytes match paged mode bit-for-bit.

    paged_inkernel (static): the XLA twin of the Pallas in-kernel paging
    mode — page_in/page_out_cond move INTO the scan body (per round, on
    the stored-domain carry) so the full [N, W] window is a scan-local
    temporary instead of a whole-dispatch one, and the allocator pass is
    elided on rounds where no lane's depth moved. Bit-identity with the
    boundary mode is structural (page_out . page_in is value-identity on
    scrubbed windows); only the faults/dirty/skipped counter cadence
    differs."""
    from raft_tpu.state import fat_state, is_packed, slim_state

    if chaos is not None and straddle is not None:
        raise ValueError(
            "chaos plane needs group-aligned lanes; straddling shards are "
            "not supported (its group reductions reshape [N] -> [G, V])"
        )
    # diet-v2: a packed carry (bitset masks + u16 indexes, state.pack_state)
    # stays packed across the scan — the branch is static under jit (leaf
    # ndim/dtype are part of the signature), so a diet-off cluster compiles
    # the exact PR-8 program
    packed = is_packed(state)
    if packed:
        state, fab = store_carry(state, fab)
    else:
        state = slim_state(state)
        fab = slim_fabric(fab)
    inkernel = paged is not None and paged_inkernel
    if inkernel:
        # allocator elision is only sound when every in-round log write
        # lands inside the resident window (see pgmod.page_out_cond)
        pg_can_skip = int(fab.rep.ent_term.shape[-1]) <= paged.w_res
    elif paged is not None:
        # reconstruct the full [N, W] window from resident tail + pool;
        # the scan below is byte-identical to the unpaged program
        state, paged = pgmod.page_in(state, paged)
    peer_mute = None
    if mute is not None:
        # loop-invariant across the scan: hoist the [N,V] sender-mute matrix
        # out of the round body (in-scan it recomputes as a [G,V,V]
        # broadcast+retile every round — profiled at ~6% of round time)
        if straddle is not None:
            peer_mute = straddle_peer_mute(mute, v, straddle)
        else:
            peer_mute = aligned_peer_mute(mute, v)

    def body(carry, i):
        st, f, met, ch, tr, pg = carry
        o = ops
        if ops_first_round_only:
            first = i == 0
            o = jax.tree.map(
                lambda x: jnp.where(
                    first, x, jnp.zeros_like(x)
                ),
                ops,
            )
        pg_last_pre = pg_snap_pre = None
        if pg is not None:
            # in-kernel twin: page in on the stored-domain carry (the
            # same order the boundary mode pages, before the diet widen)
            st, pg = pgmod.page_in(st, pg)
            pg_last_pre = st.last.astype(I32)
            pg_snap_pre = st.snap_index.astype(I32)
        if packed:
            st_fat, f_fat = load_carry(st, f)
        else:
            st_fat = fat_state(st)
            f_fat = fat_fabric(f)
        # flight recorder: the pre-round state is captured BEFORE chaos
        # begin_round, so a crash wipe diffs like any leadership loss (and
        # the pre-round chaos carry marks the fault edge itself)
        st_pre = st_fat if tr is not None else None
        ch_pre = ch
        if straddle is None:
            inb = route_fabric(f_fat, v, mute, peer_mute=peer_mute)
        else:
            inb = route_fabric_straddle(f_fat, v, mute, straddle, peer_mute)
        tick_mask = None
        if ch is not None:
            # pre-step faults: crash wipes, inbound cuts, op suppression
            ch, st_fat, inb, o, tick_mask = chmod.begin_round(
                ch, st_fat, inb, o, v
            )
        res = fused_round(
            st_fat,
            inb,
            o,
            mute,
            peer_mute=peer_mute,
            do_tick=do_tick,
            auto_propose=auto_propose,
            auto_compact_lag=auto_compact_lag,
            tick_mask=tick_mask,
            metrics=met,
        )
        st, f2 = res[0], res[1]
        met = res[2] if met is not None else None
        if ch is not None:
            # post-step faults: duplicate redelivery (re-injects last
            # round's outbox cells), recovery probing, round advance
            ch, f2 = chmod.end_round(ch, st, f_fat, f2, v)
        if tr is not None:
            tr = trmod.record_round(
                tr, st_pre, st, chaos=ch_pre, lane_offset=trace_lane_offset
            )
        if packed:
            st, f2 = store_carry(st, f2)
        else:
            st, f2 = slim_state(st), slim_fabric(f2)
        if pg is not None:
            st, pg = pgmod.page_out_cond(
                st, pg, pg_last_pre, pg_snap_pre, can_skip=pg_can_skip
            )
        return (st, f2, met, ch, tr, pg), None

    # a None metrics/chaos/trace/paged slot is an empty pytree: the scan
    # carry shape is unchanged when a plane (or in-kernel paging) is off
    (state, fab, metrics, chaos, trace, pg_out), _ = jax.lax.scan(
        body,
        (state, fab, metrics, chaos, trace, paged if inkernel else None),
        jnp.arange(n_rounds, dtype=I32),
        unroll=min(_SCAN_UNROLL, n_rounds),
    )
    if inkernel:
        # every round already re-split inside the scan body; the exit
        # state is resident and canonical, no boundary pass needed
        paged = pg_out
    elif paged is not None:
        # re-split into resident tail + pool (page_out output is
        # canonical-by-construction: stale slots read back as zeros)
        state, paged = pgmod.page_out(state, paged)
    else:
        # unpaged exit keeps the same canonical layout so raw carries,
        # WAL deltas and digests match across paged on/off
        state = lg.scrub_stale_slots(state)
    res = (state, fab)
    if metrics is not None:
        res += (metrics,)
    if chaos is not None:
        res += (chaos,)
    if trace is not None:
        res += (trace,)
    if paged is not None:
        res += (paged,)
    return res


_FUSED_STATIC = (
    "v",
    "n_rounds",
    "do_tick",
    "auto_propose",
    "auto_compact_lag",
    "ops_first_round_only",
    "straddle",
    "paged_inkernel",
)

# The default dispatch path DONATES the (state, fab, metrics) carry: XLA
# aliases each donated input buffer to the matching output, so the slim
# carry updates in place instead of double-buffering (HBM holds one carry
# + the round's temporaries, not two carries). `ops`/`mute` are never
# donated — callers re-feed them across dispatches. FusedCluster picks the
# twin below when RAFT_TPU_DONATE=0.
_fused_rounds_jit = jax.jit(
    fused_rounds,
    static_argnames=_FUSED_STATIC,
    donate_argnums=(0, 1),
    donate_argnames=("metrics", "chaos", "trace", "paged"),
)

# copying twin: inputs survive the dispatch (stale host references stay
# readable) at the cost of a full extra carry in HBM
_fused_rounds_nodonate_jit = jax.jit(fused_rounds, static_argnames=_FUSED_STATIC)


class FusedCluster:
    """G raft groups x V voters on the fused round kernel: one device
    dispatch per block of rounds, message routing as an in-device transpose.
    The throughput engine behind bench.py; the serial Cluster remains the
    conformance-exact path."""

    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        seed: int = 1,
        shape=None,
        learner_ids: tuple = (),
        engine: str | None = None,
        tile_lanes: int | None = None,
        rounds_per_call: int | None = None,
        logical_groups: int | None = None,
        **cfg,
    ):
        import numpy as np

        from raft_tpu.config import Shape
        from raft_tpu.state import init_state, make_lane_config

        # round engine: "xla" (this module's fused_rounds) or "pallas"
        # (ops/pallas_round.py — the VMEM-resident kernel). kwarg > env >
        # xla; resolved once at construction. A pallas path that fails to
        # lower raises; nothing switches engines.
        from raft_tpu.ops import pallas_round as plr

        self.engine = plr.resolve_engine(engine)
        self._tile_req = tile_lanes  # explicit tile (None = env/autotune)
        self._pallas_tile = None  # resolved lazily at first pallas dispatch
        self._pallas_interpret = None
        # megakernel rounds-per-call K (None = env/plan-cache/autotune);
        # resolved lazily alongside the tile at first pallas dispatch
        self._rounds_req = rounds_per_call
        self._pallas_rounds = None
        self.g, self.v = n_groups, n_voters
        n = n_groups * n_voters
        self.shape = shape or Shape(n_lanes=n, max_peers=n_voters)
        if self.shape.n_lanes != n or self.shape.v != n_voters:
            raise ValueError("fused layout requires n_lanes=G*V, max_peers=V")
        ids = np.tile(np.arange(1, n_voters + 1, dtype=np.int32), n_groups)
        peers = np.zeros((n, n_voters), np.int32)
        peers[:, :] = np.arange(1, n_voters + 1, dtype=np.int32)[None, :]
        # ids that start as learners in every group (membership changes can
        # later promote them — ops/fused_confchange.py)
        is_learner = np.zeros((n, n_voters), bool)
        for lid in learner_ids:
            if not (1 <= lid <= n_voters):
                raise ValueError(f"learner id {lid} outside canonical 1..{n_voters}")
            is_learner[:, lid - 1] = True
        lane_cfg = make_lane_config(self.shape, **cfg)
        from raft_tpu.state import diet_enabled, pack_state, slim_state

        # the carry lives in the slim storage dtypes from birth so every
        # run() call presents one jit signature (no fat->slim recompile)
        self.state = slim_state(
            init_state(self.shape, ids, peers, is_learner, seed=seed, cfg=lane_cfg)
        )
        self.fab = slim_fabric(empty_fabric(n, n_voters, self.shape.max_msg_entries))
        # hot/cold tiering (RAFT_TPU_TIER, raft_tpu/tier/ — read once at
        # construction like the other planes): capture the genesis row
        # template NOW, while the carry is still the slim-canonical full
        # window (pre-diet-pack, pre-paged-split) — the layout cold
        # records and late-born groups restore into. tier=None keeps
        # every tier code path (and both tier jits) out of existence.
        from raft_tpu.tier import tier_enabled

        self._seed = seed
        self.tier = None
        self._tier_template = None
        if tier_enabled():
            self._tier_template = (
                jax.tree.map(lambda x: np.asarray(x[:n_voters]).copy(), self.state),
                jax.tree.map(lambda x: np.asarray(x[:n_voters]).copy(), self.fab),
            )
        elif logical_groups is not None and logical_groups != n_groups:
            raise ValueError(
                "logical_groups > n_groups requires RAFT_TPU_TIER=1"
            )
        # diet-v2 (RAFT_TPU_DIET, read once at construction): the resident
        # carry packs down to bitset masks + uint16 rebased indexes
        # (state.pack_state / pack_fabric); every dispatch widens in-device.
        # _diet_budget is the host-side headroom counter for the automatic
        # pre-overflow rebase (_diet_headroom) — 0 forces a device read on
        # the first run() to seed it.
        self._diet = diet_enabled()
        self._diet_budget = 0
        if self._diet:
            self.state = pack_state(self.state)
            self.fab = pack_fabric(self.fab)
        self.mute = jnp.zeros((n,), BOOL)
        # carry donation (see donation_enabled): baked at construction like
        # the metrics flag so a cluster's dispatch behavior never flips
        # mid-run under an env change
        self._donate = donation_enabled()
        # ops is re-fed (never donated), so the all-zeros LocalOps for
        # ops-less rounds is built once, not per dispatch
        self._no_ops = no_ops(n)
        # the WalStream/EgressStream we last pushed to, if their deltas may
        # still hold references to our (donatable) current state — resolved
        # before the next dispatch invalidates those buffers
        self._wal_pending = None
        self._egress_pending = None
        self._trace_pending = None
        # host spans of this cluster's dispatch and read drain (sched.enqueue,
        # serve.drain_reads.*); a ServeLoop or blocked driver shares its own
        # bag through use_stats
        self.stats = StepStats()
        # metrics plane (raft_tpu/metrics/): RAFT_TPU_METRICS is read at
        # construction; metrics=None keeps every metrics op out of the jaxpr
        self.metrics = metmod.init_metrics(n) if metmod.metrics_enabled() else None
        self._metrics_acc = None
        if self.metrics is not None:
            from raft_tpu.metrics.host import CounterAccumulator

            self._metrics_acc = CounterAccumulator()
        # chaos plane (raft_tpu/chaos/): RAFT_TPU_CHAOS is read at
        # construction (default OFF); chaos=None keeps every fault op out
        # of the jaxpr — asserted by tests/test_chaos.py. The fault-PRNG
        # stream derives from this cluster's seed, so sibling blocks of a
        # BlockedFusedCluster decorrelate like their election timeouts do.
        self.chaos = (
            chmod.init_chaos(n, n_voters, seed=seed)
            if chmod.chaos_enabled()
            else None
        )
        # trace plane (raft_tpu/trace/): RAFT_TPU_TRACELOG is read at
        # construction (default OFF); trace=None keeps the whole flight
        # recorder out of the jaxpr — asserted by tests/test_trace.py
        self.trace = trmod.init_trace(n) if trmod.tracelog_enabled() else None
        # paged entry log (RAFT_TPU_PAGED, ops/paged.py — read once at
        # construction like the diet): the geometry resolves/validates NOW
        # (raise, never fall back), then the full-window carry splits into
        # resident tail + pool sidecar. paged=None keeps the split out of
        # the jaxpr entirely.
        self.paged = None
        self._page_plan = None
        # sub-pool segment count for the host-boundary paged ops: 1 here
        # (or n_tiles under in-kernel pallas paging, where the allocation
        # segment is the kernel tile); ShardedFusedCluster re-keys to its
        # own segmentation so host views always interpret the
        # dispatch-allocated segment-local page ids correctly
        self._paged_segs = 1
        # in-kernel paging (RAFT_TPU_PAGED_INKERNEL, read once like the
        # other planes): page_in/page_out fuse into the round program
        self._paged_inkernel = False
        if pgmod.paged_enabled():
            self._page_plan = pgmod.validate_page_plan(self.shape, n)
            self._paged_inkernel = pgmod.paged_inkernel_enabled()
            segs = 1
            if self._paged_inkernel and self.engine == "pallas":
                # the pool slices per grid step, so the tile is pinned
                # NOW, without autotune: the allocation segmentation is
                # part of the carry layout, not a sweepable perf knob
                t = self._tile_req
                if t is None:
                    t = config.env_int("RAFT_TPU_PALLAS_TILE", default=0) or None
                if t is None:
                    t = plr.cached_tile(
                        plr.shape_key(self.shape, jax.default_backend())
                    )
                if t is None:
                    t = plr.default_tile(n, self.v)
                plr.check_tile(n, self.v, t)
                self._pallas_tile = t
                segs = n // t
                pgmod.check_pool_segments(self._page_plan, segs)
            self._paged_segs = segs
            self.state, self.paged = pgmod.split_state(
                self.state, self._page_plan, segs
            )
        # default tier binding: identity cohort (lgids == slots). The
        # blocked/mesh drivers re-attach per-block engines with their own
        # cohorts/lane bases (scheduler.py / parallel/mesh.py).
        if self._tier_template is not None:
            self.attach_tier(n_logical=logical_groups)

    def attach_tier(self, *, n_logical=None, initial=None, lane_base=0):
        """(Re)bind this carry's TierEngine (RAFT_TPU_TIER=1 only): the
        multi-block drivers call this with per-block genesis cohorts and
        lane bases; standalone construction binds the identity cohort."""
        from raft_tpu.tier.engine import TierEngine

        if self._tier_template is None:
            raise RuntimeError(
                "tier plane is off: construct under RAFT_TPU_TIER=1"
            )
        self.tier = TierEngine(
            self,
            seed=self._seed,
            n_logical=self.g if n_logical is None else n_logical,
            initial=initial,
            lane_base=lane_base,
        )
        return self.tier

    # -- driving ----------------------------------------------------------

    def use_stats(self, stats: StepStats) -> None:
        """Record this cluster's host spans into `stats` (the ServeLoop's
        or the blocked driver's bag)."""
        self.stats = stats

    def run(
        self,
        rounds: int = 1,
        ops: LocalOps | None = None,
        do_tick: bool = True,
        auto_propose: bool = False,
        auto_compact_lag: int | None = None,
        ops_first_round_only: bool = True,
        wal=None,
        egress=None,
        trace=None,
    ):
        """wal: an optional runtime.wal.WalStream — after this block's
        dispatch its delta starts streaming to the host asynchronously
        while the next block computes (the AsyncStorageWrites=true shape
        on the fused engine; reference doc.go:172-258).

        egress: an optional runtime.egress.EgressStream — the serving-plane
        twin: the batched ready/delta bundle (ops/ready_mask.py) for this
        block rides D2H while the next block computes, handing the consumer
        a dense active-lane vector one block behind the live state.

        trace: an optional runtime.trace.TraceStream — the flight-recorder
        ring's D2H drain rides the same double-buffer discipline; a no-op
        while RAFT_TPU_TRACELOG=0 (self.trace is None)."""
        if ops is None:
            ops = self._no_ops
        self._flush_stream_fences()
        if self._diet:
            self._diet_headroom(rounds)
        with self.stats.span("sched.enqueue"):
            if self.engine == "pallas":
                res = self._run_pallas(
                    rounds,
                    ops,
                    do_tick,
                    auto_propose,
                    auto_compact_lag,
                    ops_first_round_only,
                )
            else:
                jit = (
                    _fused_rounds_jit if self._donate
                    else _fused_rounds_nodonate_jit
                )
                res = jit(
                    self.state,
                    self.fab,
                    ops,
                    self.mute,
                    v=self.v,
                    n_rounds=rounds,
                    do_tick=do_tick,
                    auto_propose=auto_propose,
                    auto_compact_lag=auto_compact_lag,
                    ops_first_round_only=ops_first_round_only,
                    paged_inkernel=self._paged_inkernel,
                    metrics=self.metrics,
                    chaos=self.chaos,
                    trace=self.trace,
                    paged=self.paged,
                )
        self.state, self.fab = res[0], res[1]
        i = 2
        if self.metrics is not None:
            self.metrics = res[i]
            i += 1
        if self.chaos is not None:
            self.chaos = res[i]
            i += 1
        if self.trace is not None:
            self.trace = res[i]
            i += 1
        if self.paged is not None:
            self.paged = res[i]
        if wal is not None:
            # the WAL streams the slim-canonical view (byte-identical diet
            # on/off); unpack_state is the identity when the carry is slim,
            # and when packed its widened columns are fresh buffers, so the
            # donation fence semantics are unchanged
            wal.push(self._wal_view())
            if self._donate:
                self._wal_pending = wal
        if egress is not None:
            egress.push(self.state)
            if self._donate:
                self._egress_pending = egress
        if trace is not None:
            trace.push(self.trace)
            if self._donate:
                self._trace_pending = trace

    def _round_static(self, rounds: int, **overrides) -> dict:
        """The static-kwarg set the round program is specialized on —
        shared by audit_programs and lower_round_program so the audited,
        budgeted, and benched lowerings can never drift apart."""
        static = dict(
            v=self.v,
            n_rounds=rounds,
            do_tick=True,
            auto_propose=False,
            auto_compact_lag=None,
            ops_first_round_only=True,
            paged_inkernel=self._paged_inkernel,
        )
        static.update(overrides)
        return static

    def lower_round_program(self, rounds: int = 1, *,
                            donate: bool | None = None, **overrides):
        """AOT-lower (never compile-and-dispatch) the exact round program
        run() dispatches for the current engine against the live carry —
        the shared entry point for the resource ledger's cost/memory
        extraction and the benches' bytes-moved probes. ``overrides``
        adjust the static kwargs (auto_propose, auto_compact_lag, ...)."""
        from raft_tpu.ops import pallas_round as plr

        donate = self._donate if donate is None else donate
        static = self._round_static(rounds, **overrides)
        kwargs = dict(
            metrics=self.metrics,
            chaos=self.chaos,
            trace=self.trace,
            paged=self.paged,
        )
        args = (self.state, self.fab, self._no_ops, self.mute)
        if self.engine == "pallas":
            if self._pallas_interpret is None:
                self._pallas_interpret = plr.default_interpret()
            return plr.round_jit_twin(donate).lower(
                *args,
                tile_lanes=self._resolve_pallas_tile(),
                rounds_per_call=self._resolve_pallas_rounds(),
                interpret=self._pallas_interpret,
                **static, **kwargs,
            )
        jit = _fused_rounds_jit if donate else _fused_rounds_nodonate_jit
        return jit.lower(*args, **static, **kwargs)

    def audit_programs(self, rounds: int = 2):
        """Enumerate this cluster's round-dispatch entry points as audit
        records for the static program auditor (raft_tpu/analysis). Each
        record carries the unjitted fn (for make_jaxpr), the jit twin the
        engine actually dispatches (for lowered-HLO donation checks), the
        live carry pytrees as example arguments, the donation signature,
        and the ledger metadata (lanes / rounds for per-lane-per-round
        normalization, the carry legs for carry-bytes accounting and the
        carry-stability fixpoint proof). Nothing here dispatches a round:
        the auditor only traces and lowers."""
        from raft_tpu.ops import pallas_round as plr

        static = self._round_static(rounds)
        kwargs = dict(
            metrics=self.metrics,
            chaos=self.chaos,
            trace=self.trace,
            paged=self.paged,
        )
        args = (self.state, self.fab, self._no_ops, self.mute)
        meta = dict(
            lanes=self.shape.n_lanes,
            rounds=rounds,
            carry_argnums=(0, 1),
            carry_argnames=("metrics", "chaos", "trace", "paged"),
        )
        if self.engine == "pallas":
            rpc = self._resolve_pallas_rounds()
            tile = self._resolve_pallas_tile()
            if self._pallas_interpret is None:
                self._pallas_interpret = plr.default_interpret()
            return [dict(
                meta,
                name="round.pallas",
                fn=plr.pallas_rounds,
                jit=plr.round_jit_twin(self._donate),
                args=args,
                kwargs=kwargs,
                static=dict(
                    static,
                    tile_lanes=tile,
                    rounds_per_call=rpc,
                    interpret=self._pallas_interpret,
                ),
                donate=self._donate,
                donate_argnums=(0, 1),
                donate_argnames=("metrics", "chaos", "trace", "paged"),
            )]
        return [dict(
            meta,
            name="round.xla",
            fn=fused_rounds,
            jit=(
                _fused_rounds_jit
                if self._donate
                else _fused_rounds_nodonate_jit
            ),
            args=args,
            kwargs=kwargs,
            static=static,
            donate=self._donate,
            donate_argnums=(0, 1),
            donate_argnames=("metrics", "chaos", "trace", "paged"),
        )]

    def _flush_stream_fences(self):
        """Resolve every in-flight D2H stream copy (WAL, egress, trace)
        before a donating dispatch — or a rebase — invalidates the buffers
        they reference. The sharded driver (parallel/sharded.py) dispatches
        its own shard_map program instead of calling run(), but its streams
        ride THESE fences so inner rebases cover them too."""
        self._flush_pending_wal()
        self._flush_pending_egress()
        self._flush_pending_trace()

    def _flush_pending_wal(self):
        """Resolve a WAL delta that still references this cluster's current
        state before a donating dispatch invalidates those buffers. The
        D2H copy started at push() time and has had a whole dispatch to
        ride, so this is (nearly always) a cache read, not a sync."""
        if self._wal_pending is not None:
            self._wal_pending.flush()
            self._wal_pending = None

    def _flush_pending_egress(self):
        """Same fence for the egress bundle: its cursor columns may alias
        the (donatable) carry, so the pending bundle resolves before the
        next donating dispatch invalidates those buffers."""
        if self._egress_pending is not None:
            self._egress_pending.flush()
            self._egress_pending = None

    def _flush_pending_trace(self):
        """Same fence for the flight-recorder ring: the TraceStream's
        in-flight copy references the (donatable) trace carry's buffers, so
        it resolves before the next donating dispatch invalidates them."""
        if self._trace_pending is not None:
            self._trace_pending.flush()
            self._trace_pending = None

    # -- pallas engine (ops/pallas_round.py) ------------------------------

    def _run_pallas(
        self,
        rounds,
        ops,
        do_tick,
        auto_propose,
        auto_compact_lag,
        ops_first_round_only,
    ):
        """One dispatch on the VMEM-resident pallas engine; returns the
        fused_rounds-shaped result tuple. A Mosaic refusal raises with the
        compiler's message — there is no silent switch to the XLA engine."""
        from raft_tpu.ops import pallas_round as plr

        # K first: the joint autotune (inside _resolve_pallas_rounds)
        # populates the tile cache, which _resolve_pallas_tile consults.
        rpc = self._resolve_pallas_rounds()
        tile = self._resolve_pallas_tile()
        if self._pallas_interpret is None:
            self._pallas_interpret = plr.default_interpret()
        return plr.round_jit_twin(self._donate)(
            self.state,
            self.fab,
            ops,
            self.mute,
            v=self.v,
            tile_lanes=tile,
            n_rounds=rounds,
            rounds_per_call=rpc,
            do_tick=do_tick,
            auto_propose=auto_propose,
            auto_compact_lag=auto_compact_lag,
            ops_first_round_only=ops_first_round_only,
            interpret=self._pallas_interpret,
            paged_inkernel=self._paged_inkernel,
            metrics=self.metrics,
            chaos=self.chaos,
            trace=self.trace,
            paged=self.paged,
        )

    def _resolve_pallas_tile(self) -> int:
        """Pick the lane tile once per cluster: explicit ctor tile_lanes >
        RAFT_TPU_PALLAS_TILE env > the process-wide (shape, backend) cache
        > TPU autotune sweep (pallas_round.autotune_tile) > default_tile.
        Interpret mode never sweeps (it would time the interpreter)."""
        if self._pallas_tile is not None:
            return self._pallas_tile
        from raft_tpu.ops import pallas_round as plr

        n = self.shape.n_lanes
        backend = jax.default_backend()
        key = plr.shape_key(self.shape, backend)
        t = self._tile_req
        if t is None:
            t = config.env_int("RAFT_TPU_PALLAS_TILE", default=0) or None
        if t is None:
            t = plr.cached_tile(key)
        if t is None:
            if backend == "tpu" and plr.autotune_enabled():
                for c in plr.tile_candidates(n, self.v):
                    plr.check_tile(n, self.v, c)
                t = plr.autotune_tile(
                    n, self.v, key=key, time_fn=self._time_tile
                )
            else:
                t = plr.default_tile(n, self.v)
        plr.check_tile(n, self.v, t)
        plr.remember_tile(key, t)
        self._pallas_tile = t
        return t

    def _resolve_pallas_rounds(self) -> int:
        """Pick the megakernel K once per cluster: explicit ctor
        rounds_per_call > RAFT_TPU_PALLAS_ROUNDS env > the process-wide
        (shape, backend) plan cache > TPU joint (tile, K) autotune sweep
        (pallas_round.autotune_plan — which also fills the tile cache the
        tile resolver consults) > 1. Every winner is validated against
        the RAFT_TPU_UNROLL composition up front."""
        if self._pallas_rounds is not None:
            return self._pallas_rounds
        from raft_tpu.ops import pallas_round as plr

        n = self.shape.n_lanes
        backend = jax.default_backend()
        key = plr.shape_key(self.shape, backend)
        k = self._rounds_req
        if k is None:
            k = plr.env_rounds_per_call()
        if k is None:
            plan = plr.cached_plan(key)
            if plan is not None:
                k = plan[1]
        if k is None:
            if backend == "tpu" and plr.autotune_enabled():
                # a pinned tile (ctor/env — or the ctor-resolved tile the
                # in-kernel paged split committed to) restricts the
                # sweep's tile axis but still sweeps K
                pinned = self._tile_req
                if pinned is None:
                    pinned = (
                        config.env_int("RAFT_TPU_PALLAS_TILE", default=0)
                        or None
                    )
                if pinned is None:
                    pinned = self._pallas_tile
                tiles = None
                if pinned is not None:
                    plr.check_tile(n, self.v, pinned)
                    tiles = (pinned,)
                else:
                    for c in plr.tile_candidates(n, self.v):
                        plr.check_tile(n, self.v, c)
                _, k = plr.autotune_plan(
                    n, self.v, key=key, time_fn=self._time_plan, tiles=tiles
                )
            else:
                k = 1
        plr.validate_round_plan(k, unroll=_SCAN_UNROLL)
        self._pallas_rounds = k
        return k

    def _time_plan(self, tile_lanes: int, rounds_per_call: int) -> float:
        """Autotune probe: seconds PER ROUND for a short warmed block on
        the copying twin (the carry is untouched)."""
        import time as _time

        from raft_tpu.ops import pallas_round as plr

        nr = 4 * rounds_per_call
        kw = dict(
            v=self.v,
            tile_lanes=tile_lanes,
            n_rounds=nr,
            rounds_per_call=rounds_per_call,
            do_tick=True,
            auto_propose=False,
            auto_compact_lag=None,
            ops_first_round_only=True,
            interpret=False,
            paged_inkernel=self._paged_inkernel,
            metrics=self.metrics,
            chaos=self.chaos,
            paged=self.paged,
        )
        args = (self.state, self.fab, self._no_ops, self.mute)
        jax.block_until_ready(
            plr._pallas_rounds_nodonate_jit(*args, **kw)
        )  # compile + warm
        t0 = _time.perf_counter()
        jax.block_until_ready(plr._pallas_rounds_nodonate_jit(*args, **kw))
        return (_time.perf_counter() - t0) / nr

    def _time_tile(self, tile_lanes: int) -> float:
        """Tile-only autotune probe (K fixed at the resolved/default K)."""
        return self._time_plan(tile_lanes, self._pallas_rounds or 1)

    def ops(self, **kw) -> LocalOps:
        """Build a LocalOps with the given per-lane columns set. Values may
        be dicts {lane: value} or full arrays."""
        return make_local_ops(self.state.id.shape[0], **kw)

    def campaign(self, lane: int):
        self.run(1, ops=self.ops(hup={lane: True}), do_tick=False)

    def conf_changer(self):
        """Membership-change driver for this batch (fused_confchange.py)."""
        from raft_tpu.ops.fused_confchange import FusedConfChanger

        return FusedConfChanger(self)

    def set_mute(self, lanes, on: bool = True):
        import numpy as np

        m = np.asarray(self.mute).copy()
        m[np.asarray(lanes, dtype=np.int64)] = on
        self.mute = jnp.asarray(m)

    def set_chaos(self, **cols):
        """Overwrite chaos-plane knob columns (chaos/device.py SETTABLE):
        [N]/[N,V] arrays in this cluster's lane order, or scalars to
        broadcast. Requires RAFT_TPU_CHAOS=1 at construction; the usual
        driver is a ChaosSchedule (raft_tpu/chaos/schedule.py)."""
        if self.chaos is None:
            raise RuntimeError(
                "chaos plane is off: construct under RAFT_TPU_CHAOS=1"
            )
        self.chaos = chmod.with_columns(self.chaos, **cols)

    def chaos_columns(self, *names) -> dict:
        """Read chaos columns back as numpy (default: the recovery-probe
        set, chaos/device.py PROBE_FIELDS). Empty dict when the plane is
        off."""
        import numpy as np

        if self.chaos is None:
            return {}
        names = names or chmod.PROBE_FIELDS
        return {k: np.asarray(getattr(self.chaos, k)) for k in names}

    def rebase_groups(self, groups, delta: int | None = None) -> dict:
        """Re-key the index space of whole groups downward by a
        window-aligned delta (the i32-overflow recovery; ops/log.py
        rebase_indexes + ERR_INDEX_NEAR_OVERFLOW) while traffic is LIVE:
        state and the in-flight fabric shift together between dispatch
        blocks — no drain, no quiesce. Returns {group: delta}. Negative
        deltas are allowed (used by tests to fast-forward a batch toward
        the 2^30 guard)."""
        import numpy as np

        w = self.shape.w
        n = self.g * self.v
        # packed snap_index (uint16) holds the same absolute values — the
        # int64 view keeps the arithmetic below width-independent
        snap = np.asarray(self.state.snap_index).astype(np.int64)
        deltas = np.zeros((n,), np.int32)
        mask = np.zeros((n,), bool)
        out = {}
        for g in groups:
            sl = slice(g * self.v, (g + 1) * self.v)
            d = delta if delta is not None else (int(snap[sl].min()) // w) * w
            if d == 0:
                continue
            if d % w:
                raise ValueError("rebase delta must be window-aligned")
            deltas[sl] = d
            mask[sl] = True
            out[g] = d
        if not out:
            return out
        self._apply_rebase(mask, deltas)
        return out

    def _apply_rebase(self, mask, deltas):
        """Shared rebase applier behind rebase_groups and the diet-v2
        automatic trigger: flush the D2H fences, run the rebase jits on the
        unpacked (absolute-int32) carry, re-narrow, and shift the
        metrics/chaos/trace side tables. The rebase arithmetic MUST see
        int32 — jnp.maximum(x - d, 0) on a packed uint16 column would wrap
        before the floor — so a packed carry unpacks around the jits."""
        from raft_tpu.state import is_packed, pack_state, slim_state, unpack_state

        dj = jnp.asarray(deltas)
        mj = jnp.asarray(mask)
        self._flush_stream_fences()
        packed = is_packed(self.state)
        carry = self.state
        if self.paged is not None:
            # rebase deltas are W-aligned but need not be M-aligned in
            # page-key space, so the page table cannot be shifted in
            # place: page in to the full window first, page out after
            # (page_out realloc-from-scratch rebuilds pool + tables)
            carry, self.paged = pgmod.page_in_host(
                carry, self.paged, self._paged_segs
            )
        st, fb = unpack_state(carry), unpack_fabric(self.fab)
        if self._donate:
            st = slim_state(_rebase_indexes_donate_jit(st, mj, dj))
            fb = slim_fabric(_rebase_fabric_donate_jit(fat_fabric(fb), dj))
        else:
            st = slim_state(_rebase_indexes_jit(st, mj, dj))
            fb = slim_fabric(rebase_fabric(fat_fabric(fb), dj))
        if packed:
            st, fb = pack_state(st), pack_fabric(fb)
        if self.paged is not None:
            st, self.paged = pgmod.page_out_host(st, self.paged, self._paged_segs)
        self.state, self.fab = st, fb
        # any rebase (manual fast-forward included) moves the index space
        # out from under the headroom counter — force a device re-sync on
        # the next dispatch rather than trusting a stale budget
        self._diet_budget = 0
        if self.metrics is not None:
            # in-flight latency samples hold absolute indexes — shift them
            # with their lanes (or drop, never mismeasure)
            self.metrics = metmod.rebase_samples(self.metrics, mj, dj)
        if self.chaos is not None:
            # the recovery baseline holds absolute committed values — it
            # shifts with its lanes like the latency samples above
            self.chaos = chmod.rebase(self.chaos, mj, dj)
        if self.trace is not None:
            # recorded events whose arg column carries a log index shift
            # with their lanes so explain() output stays in the live space
            self.trace = trmod.rebase(self.trace, mj, dj)

    # -- diet-v2 (RAFT_TPU_DIET) ------------------------------------------

    # Automatic-rebase threshold for the packed uint16 index columns: when
    # the projected max absolute index would cross this, every group
    # rebases down before the dispatch. 48k leaves 16k of clearance under
    # 2^16 (a whole max-size log_window), and sits far above anything a
    # test/bench workload reaches — digests stay comparable diet on/off.
    DIET_REBASE_AT = 48 * 1024

    def _diet_headroom(self, rounds: int):
        """Pre-dispatch overflow guard for the packed index columns. A
        host-side budget counter amortizes the device read: one dispatch
        can grow any index by at most rounds*(E+1) (E appended entries +
        one snapshot catch-up jump per round; a snapshot jump lands at a
        peer's `last`, already inside the budgeted envelope), so the
        counter spends that bound per run and only syncs max(last) off the
        device when the budget runs dry."""
        grow = rounds * (self.shape.max_msg_entries + 1)
        if self._diet_budget > grow:
            self._diet_budget -= grow
            return
        mx = int(jnp.max(self.state.last.astype(I32)))
        if mx + grow >= self.DIET_REBASE_AT:
            self._rebase_all_groups()
            mx = int(jnp.max(self.state.last.astype(I32)))
        self._diet_budget = max(self.DIET_REBASE_AT - mx - grow, 0)

    def _rebase_all_groups(self):
        """Vectorized whole-batch rebase (the diet-v2 trigger path):
        per-group window-aligned min-snap deltas computed in one numpy
        pass — rebase_groups' python per-group loop is unusable at the
        333k-group scale this exists for."""
        import numpy as np

        w = self.shape.w
        snap = np.asarray(self.state.snap_index).astype(np.int64)
        d_g = (snap.reshape(self.g, self.v).min(axis=1) // w) * w
        deltas = np.repeat(d_g, self.v).astype(np.int32)
        mask = deltas != 0
        if mask.any():
            self._apply_rebase(mask, deltas)

    def _wal_view(self):
        """The state view the WAL/host planes stream: slim-canonical
        dtypes, absolute int32 index columns, [N, V] bool masks. The
        identity when diet is off, so streamed bytes are identical diet
        on/off (asserted by tests/test_diet.py). Under RAFT_TPU_PAGED the
        full [N, W] window reconstructs from the pool first, so streamed
        bytes are identical paged on/off too (tests/test_paged.py)."""
        from raft_tpu.state import unpack_state

        carry = self.state
        if self.paged is not None:
            carry = pgmod.page_in_view(carry, self.paged, self._paged_segs)
        return unpack_state(carry)

    def host_state(self):
        """Host-reader view of the carry (see _wal_view); raw `self.state`
        may be diet-v2 packed (bitset masks, uint16 indexes)."""
        return self._wal_view()

    def adopt_state(self, st):
        """Install a host-built (slim/fat) state as the carry, re-packing
        when the current carry is diet-v2 packed — the write-side twin of
        host_state() used by the confchange driver."""
        from raft_tpu.state import is_packed, pack_state, slim_state

        st = pack_state(st) if is_packed(self.state) else slim_state(st)
        if self.paged is not None:
            # split the adopted full-window state back into resident tail
            # + pool (page_out canonicalizes stale slots on the way)
            st, self.paged = pgmod.page_out_host(st, self.paged, self._paged_segs)
        self.state = st

    @classmethod
    def restore_from_wal(
        cls,
        n_groups: int,
        n_voters: int,
        delta: dict,
        seed: int = 1,
        shape=None,
        log_bytes=None,
        **cfg,
    ) -> "FusedCluster":
        """Rebuild a running block from one WAL delta (runtime/wal.py
        WalStream.FIELDS) — the crash-restart path of the fused engine.

        The reference restart contract (doc.go:46-67, raft.go:432-477):
        come back with the persisted HardState + log + snapshot origin +
        applied ConfState; everything volatile (role, lead, votes,
        progress, read queues, the in-flight fabric) resets to follower
        defaults, which a fresh init already is. Entry payload SIZES are
        not streamed (the payload store owns bytes — WalStream.FIELDS
        note); pass `log_bytes` ([N, W] array) to restore them, else the
        size column restores as zeros and byte-based limits restart from a
        clean slate.
        """
        import dataclasses as dc

        import numpy as np

        from raft_tpu.runtime.wal import WalStream
        from raft_tpu.state import is_packed, pack_state, slim_state, unpack_state

        c = cls(n_groups, n_voters, seed=seed, shape=shape, **cfg)
        # WAL bytes are in the slim-canonical layout (_wal_view streams the
        # unpacked view) — restore into that layout, then re-pack if the
        # freshly-built carry is diet-v2 packed
        packed = is_packed(c.state)
        carry = c.state
        if c.paged is not None:
            # restore into the FULL window, then re-split below: the WAL
            # delta's log columns are [N, W], and the split repopulates
            # the page pool + tables from the restored entries
            carry = pgmod.page_in_view(carry, c.paged, c._paged_segs)
        st = unpack_state(carry)
        upd = {}
        for f in WalStream.FIELDS:  # the stream schema IS the restore set
            cur = getattr(st, f)
            upd[f] = jnp.asarray(np.asarray(delta[f]), dtype=cur.dtype)
        # durability covered everything streamed; applying rejoins applied.
        # jnp.copy, not aliasing: two state fields sharing one buffer would
        # trip the donating run path ("donate the same buffer twice")
        upd["stabled"] = jnp.copy(upd["last"])
        upd["applying"] = jnp.copy(upd["applied"])
        if log_bytes is not None:
            upd["log_bytes"] = jnp.asarray(
                np.asarray(log_bytes), dtype=st.log_bytes.dtype
            )
        st = slim_state(dc.replace(st, **upd))
        st = pack_state(st) if packed else st
        if c.paged is not None:
            st, c.paged = pgmod.page_out_host(st, c.paged, c._paged_segs)
        c.state = st
        return c

    # -- inspection -------------------------------------------------------

    def metrics_snapshot(self) -> dict | None:
        """Pull the device counters and fold them into the host's exact
        int64 totals (wraparound-aware; see metrics/host.py). Returns the
        standard snapshot dict, or None when RAFT_TPU_METRICS=0."""
        if self.metrics is None:
            return None
        self._metrics_acc.pull(self.metrics)
        snap = self._metrics_acc.snapshot()
        if self.paged is not None:
            # paged-pool pressure rides the same snapshot (this is already
            # a host sync point, so the lazy occupancy sum costs nothing
            # extra); also mirrors onto metrics/host.py PAGED_COUNTERS
            for k, val in (self.paged_stats() or {}).items():
                snap["counters"][k] = val
        if self.tier is not None:
            # tier occupancy/churn rides the same snapshot and mirrors
            # onto metrics/host.py TIER_COUNTERS (pure host counters — no
            # device sync at all)
            for k, val in self.tier.stats(mirror=True).items():
                snap["counters"][k] = val
        if self.state.lease_left is not None:
            # lease grant/renew/revoke totals ride the same snapshot and
            # mirror onto metrics/host.py LEASE_COUNTERS
            for k, val in (self.lease_stats() or {}).items():
                snap["counters"][k] = val
        return snap

    def paged_stats(self) -> dict | None:
        """Occupancy/fault/exhaustion snapshot of the paged entry log
        (ops/paged.py paged_stats; None when RAFT_TPU_PAGED=0). Mirrors
        onto the metrics host plane (metrics/host.py PAGED_COUNTERS) and
        fires the rate-limited exhaustion warning. Forces a device sync —
        call at host sync points (benches, snapshots), never per
        dispatch."""
        if self.paged is None:
            return None
        from raft_tpu.metrics.host import record_paged_stats

        stats = pgmod.paged_stats(self.paged)
        record_paged_stats(stats)
        return stats

    def lease_stats(self) -> dict | None:
        """Host sums of the per-lane lease event counters (ops/lease.py;
        None when RAFT_TPU_LEASE=0). Mirrors onto the metrics host plane
        (metrics/host.py LEASE_COUNTERS — the serve-plane reads_served /
        reads_fallback halves are pure host counters incremented by
        serve/router.py). Forces a device sync — call at host sync points
        only, like paged_stats."""
        if self.state.lease_left is None:
            return None
        import numpy as np

        from raft_tpu.metrics.host import record_lease_stats

        # counters are unpacked int32 even under diet-v2 (unbounded
        # monotone sums must not ride a uint16 cast), so sum directly
        leaves = [getattr(self.state, f) for f in lsmod.LEASE_COUNTER_FIELDS]
        for x in leaves:
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()
        stats = {
            f: int(np.asarray(x).sum())
            for f, x in zip(lsmod.LEASE_COUNTER_FIELDS, leaves)
        }
        record_lease_stats(stats)
        return stats

    def leader_lanes(self):
        import numpy as np

        return np.nonzero(np.asarray(self.state.state) == int(StateType.LEADER))[0]

    def lanes_of_group(self, g: int):
        return slice(g * self.v, (g + 1) * self.v)

    def state_columns(self, *names) -> dict:
        """Host-resident numpy copies of the named [N]-leading state leaves
        (e.g. "state", "lead", "term", "committed", "last") — the serving
        frontend's synchronous bootstrap/resync pull. One overlapped
        transfer set: copy_to_host_async on every leaf before the first
        blocking read (the compute_bundle discipline, ops/ready_mask.py)."""
        import numpy as np

        # host_state(): diet-v2 packed columns widen to absolute int32 /
        # [N, V] bool before they become host-visible (identity diet-off)
        st = self.host_state()
        leaves = [getattr(st, name) for name in names]
        for x in leaves:
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()
        return {name: np.asarray(x) for name, x in zip(names, leaves)}

    def drain_read_states(self) -> dict:
        """Consume released ReadIndex results host-side: returns
        {lane: [(ctx, index), ...]} for every lane with rs_count > 0 and
        zeroes the device rs_* ring (reference: raft.go:371 readStates,
        drained by Ready — here by the serving loop, raft_tpu/serve/).

        The zeroing writes one DISTINCT fresh buffer per field: the carry
        is donated on the next dispatch, and two leaves sharing a buffer
        trip XLA's donate-same-buffer-twice check (the lockstep harness's
        _drain_reads discipline, testing/lockstep.py)."""
        import numpy as np

        with self.stats.span("serve.drain_reads.wait"):
            cnt = np.asarray(self.state.rs_count)
        if not cnt.any():
            return {}
        with self.stats.span("serve.drain_reads.decode"):
            ctx = np.asarray(self.state.rs_ctx)
            # widen-at-read: rs_index may be diet-v2 packed (uint16, same
            # absolute values); served indexes stay absolute int32
            idx = np.asarray(self.state.rs_index).astype(np.int32)
            out = {
                int(lane): [
                    (int(ctx[lane, k]), int(idx[lane, k]))
                    for k in range(int(cnt[lane]))
                ]
                for lane in np.nonzero(cnt > 0)[0]
            }
        # a .wait span: behind a served round's queue of block programs
        # the reset's dispatch blocks until the device takes it (measured
        # on a v5e, PERF.md §5), so this is device wait, not host work
        with self.stats.span("serve.drain_reads.reset.wait"):
            self.state = dataclasses.replace(
                self.state,
                rs_ctx=jnp.zeros_like(self.state.rs_ctx),
                rs_index=jnp.zeros_like(self.state.rs_index),
                rs_count=jnp.zeros_like(self.state.rs_count),
            )
        return out

    def check_no_errors(self):
        import numpy as np

        bits = np.asarray(self.state.error_bits)
        if self.paged is not None and (bits & pgmod.ERR_PAGE_EXHAUSTED).any():
            # surface the exhaustion on the host plane (counter + rate-
            # limited warning) before the assertion below reports it
            self.paged_stats()
        assert (bits == 0).all(), (
            f"error_bits set: lanes {np.nonzero(bits)[0].tolist()}"
        )

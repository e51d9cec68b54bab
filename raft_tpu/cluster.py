"""In-device multi-group cluster: lanes = groups x voters, message delivery
as a batched sort/gather permutation.

The reference leaves transport to the application (README.md:10-14) and its
tests move messages between in-process state machines synchronously
(raft_test.go:4844 newNetwork). Here the same role is played by a device-side
router: every round, all outbox messages [N, S] are flattened, keyed by
destination lane, sorted, and re-gathered into per-lane inboxes [N, M_in] —
i.e. "delivery" is one all-to-all permutation of message tensors, exactly the
shape that pjit/shard_map turns into ICI collectives when the lane axis is
sharded (SURVEY §2.3, §5.8).

Faithful ordering contract (doc.go:75-91): messages emitted in round r are
delivered in round r+1, *after* the emitting lane's unstable entries have
been marked durable at the end of round r (the synchronous persist). The
self-addressed after-append messages (outbox slot V) ride the same delay,
which implements the reference's msgsAfterAppend/Advance rule.

Inside a round the queued messages are consumed by a lax.scan over inbox
slots — the step kernel compiles once and is reused for every slot.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import Shape
from raft_tpu.messages import MsgBatch, empty_batch
from raft_tpu.ops import log as lg
from raft_tpu.ops import step as stepmod
from raft_tpu.state import RaftState, init_state, make_lane_config
from raft_tpu.types import MessageType as MT, StateType

I32 = jnp.int32


def route(
    out: MsgBatch,
    src_group: jnp.ndarray,
    lane_of: jnp.ndarray,
    m_in: int,
    drop_mask: jnp.ndarray | None = None,
    lane_offset=0,
    lanes_per_group: int | None = None,
) -> tuple[MsgBatch, jnp.ndarray]:
    """Deliver outbox messages to per-lane inboxes.

    out: [N, S] message slots emitted this round.
    src_group: [N] group id of each lane.
    lane_of: [G, max_id+1] lane index for (group, raft id); -1 if absent.
    drop_mask: optional [N, S] bool — drop these messages (fault injection,
      the analog of rafttest/network.go:122-144 drop/disconnect).
    lane_offset: subtracted from lane_of's (global) lane numbers — inside a
      shard_map shard, pass axis_index * lanes_per_shard so delivery targets
      local rows (groups never span shards, so every destination is local).
    lanes_per_group: when set (the canonical layout: group members are
      contiguous lanes with raft ids 1..V, as Cluster builds), delivery uses
      sort-free group-local one-hot compaction — TPU-friendly; otherwise the
      general stable-sort path handles arbitrary lane_of maps.

    Returns (inbox [N, m_in], n_dropped_overflow).
    """
    if lanes_per_group is not None:
        return _route_grouped(out, m_in, lanes_per_group, drop_mask)
    return _route_sorted(out, src_group, lane_of, m_in, drop_mask, lane_offset)


def _route_grouped(out, m_in, v, drop_mask):
    """Group-local delivery: destination lane of a message to raft id `to`
    from a lane of group g is g*v + (to-1). All selection/compaction is
    one-hot compare + cumsum — no sort or gather HLOs (they serialize on
    TPU). Candidate order (src lane, slot) preserves per-sender emission
    order, matching the stable sort of the general path."""
    n, s = out.type.shape
    g = n // v
    c = v * s  # candidates per destination group

    flat = jax.tree.map(
        lambda x: x.reshape((g, c) + x.shape[2:]), out
    )  # [G, C, ...] in (src member, slot) order
    valid = flat.type != MT.MSG_NONE
    if drop_mask is not None:
        valid = valid & ~drop_mask.reshape(g, c)
    # ids outside the canonical 1..V layout are undeliverable: drop + count
    in_range = (flat.to >= 1) & (flat.to <= v)
    bad_id = jnp.sum((valid & ~in_range).astype(I32))
    valid = valid & in_range
    member = jnp.clip(flat.to - 1, 0, v - 1)  # [G, C]

    # [G, V, C]: candidate c addressed to member j
    sel = valid[:, None, :] & (
        member[:, None, :] == jnp.arange(v, dtype=I32)[None, :, None]
    )
    pos = jnp.cumsum(sel.astype(I32), axis=-1) - 1  # delivery rank
    count = jnp.sum(sel.astype(I32), axis=-1)  # [G, V]
    dropped = jnp.sum(jnp.clip(count - m_in, 0)) + bad_id

    # [G, V, m_in, C] one-hot: candidate c lands in inbox slot k
    oh = sel[:, :, None, :] & (
        pos[:, :, None, :] == jnp.arange(m_in, dtype=I32)[None, None, :, None]
    )

    def deliver(col):
        cast = col.dtype == jnp.bool_
        x = col.astype(I32) if cast else col
        if x.ndim == 2:  # [G, C]
            picked = jnp.sum(jnp.where(oh, x[:, None, None, :], 0), axis=-1)
        else:  # [G, C, E]
            picked = jnp.sum(
                jnp.where(oh[..., None], x[:, None, None, :, :], 0), axis=-2
            )
        picked = picked.reshape((n, m_in) + x.shape[2:])
        return picked.astype(jnp.bool_) if cast else picked

    inbox = jax.tree.map(deliver, flat)
    filled = (
        jnp.arange(m_in, dtype=I32)[None, None, :] < count[:, :, None]
    ).reshape(n, m_in)
    inbox = dataclasses.replace(
        inbox, type=jnp.where(filled, inbox.type, jnp.int32(MT.MSG_NONE))
    )
    return inbox, dropped


def deliver_flat(flat, dst, valid, n, m_in):
    """Deliver a flat candidate pool into per-lane inboxes [n, m_in].

    flat: pytree of [K, ...] message columns; dst: [K] local destination
    lane (values outside [0, n) while valid count as dropped); valid: [K].
    Stable sort by destination preserves candidate order. Returns
    (inbox, n_dropped)."""
    k = dst.shape[0]
    out_of_range = valid & ((dst < 0) | (dst >= n))
    undeliverable = jnp.sum(out_of_range.astype(I32))
    valid = valid & ~out_of_range

    # stable sort by destination; invalid messages sort to the end
    key = jnp.where(valid, dst, n)
    order = jnp.argsort(key, stable=True)
    sorted_dst = key[order]
    flat = jax.tree.map(lambda x: x[order], flat)

    # segment of lane i = [searchsorted(i), searchsorted(i+1))
    lanes = jnp.arange(n, dtype=I32)
    starts = jnp.searchsorted(sorted_dst, lanes)
    ends = jnp.searchsorted(sorted_dst, lanes + 1)
    count = ends - starts
    dropped = jnp.sum(jnp.clip(count - m_in, 0)) + undeliverable

    j = jnp.arange(m_in, dtype=I32)[None, :]
    pos = jnp.clip(starts[:, None] + j, 0, k - 1)
    ok = j < count[:, None]
    inbox = jax.tree.map(lambda x: x[pos], flat)
    inbox = dataclasses.replace(
        inbox, type=jnp.where(ok, inbox.type, jnp.int32(MT.MSG_NONE))
    )
    return inbox, dropped


def _route_sorted(out, src_group, lane_of, m_in, drop_mask, lane_offset):
    """General path: stable sort by destination lane (arbitrary id->lane
    maps), segment extraction via searchsorted."""
    n, s = out.type.shape
    k = n * s

    flat = jax.tree.map(lambda x: x.reshape((k,) + x.shape[2:]), out)
    src_lane = jnp.repeat(jnp.arange(n, dtype=I32), s)
    group = src_group[src_lane]
    valid = flat.type != MT.MSG_NONE
    if drop_mask is not None:
        valid = valid & ~drop_mask.reshape(k)
    # ids outside lane_of's domain are undeliverable: drop + count (never
    # clip-misdeliver to another lane)
    in_range = (flat.to >= 0) & (flat.to < lane_of.shape[1])
    to = jnp.clip(flat.to, 0, lane_of.shape[1] - 1)
    dst = jnp.where(valid & in_range, lane_of[group, to] - lane_offset, -1)
    return deliver_flat(flat, dst, valid, n, m_in)


def scan_step(state: RaftState, inbox: MsgBatch) -> tuple[RaftState, MsgBatch]:
    """Consume inbox [N, M] serially (matching the reference's one-message-
    at-a-time Step contract) via lax.scan; returns all emissions [N, M*S]."""
    xs = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), inbox)

    def body(st, msg):
        st, out = stepmod.step(st, msg)
        return st, out

    state, outs = jax.lax.scan(body, state, xs)
    m = inbox.type.shape[1]
    n = inbox.type.shape[0]
    out_all = jax.tree.map(
        lambda x: jnp.moveaxis(x, 0, 1).reshape((n, m * x.shape[2]) + x.shape[3:]),
        outs,
    )
    return state, out_all


def _cluster_round_impl(
    state: RaftState,
    inbox: MsgBatch,
    group_of,
    lane_of,
    *,
    m_in: int,
    do_tick: bool,
    v: int | None = None,
) -> tuple[RaftState, MsgBatch, jnp.ndarray]:
    """One synchronous round: [tick ->] step queued messages -> sync persist
    -> auto-apply -> route emissions for next round."""
    e = inbox.ent_term.shape[-1]
    if do_tick:
        state, local = stepmod.tick(state, e)
        inbox = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=1), local, inbox
        )
    state, out_all = scan_step(state, inbox)
    # synchronous durability: everything appended this round is persisted
    # before any message emitted this round is delivered (doc.go:79-86)
    state = dataclasses.replace(state, stabled=state.last)
    # ...including a snapshot restored this round: the async model's
    # MSG_STORAGE_APPEND_RESP snapshot ack (step.py:701-709) collapses to
    # the round boundary, clearing pending_snap_* — without this a restored
    # follower would stay unpromotable (step.py promotable) forever. The
    # fused engine's apply-phase `applied_snap` block is the same rule.
    has_ps = state.pending_snap_index != 0
    state = dataclasses.replace(
        state,
        applied=jnp.where(
            has_ps, jnp.maximum(state.applied, state.pending_snap_index), state.applied
        ),
        applying=jnp.where(
            has_ps, jnp.maximum(state.applying, state.pending_snap_index), state.applying
        ),
        pending_snap_index=jnp.where(has_ps, 0, state.pending_snap_index),
        pending_snap_term=jnp.where(has_ps, 0, state.pending_snap_term),
    )
    # auto-apply committed entries (the trivial test state machine)
    applied_bytes = _bytes_between(state, state.applied, state.committed)
    state = lg.applied_to(state, state.committed)
    state = dataclasses.replace(
        state,
        uncommitted_size=jnp.clip(state.uncommitted_size - applied_bytes, 0),
    )
    nxt, dropped = route(out_all, group_of, lane_of, m_in, lanes_per_group=v)
    return state, nxt, dropped


@partial(jax.jit, static_argnames=("m_in", "do_tick", "v"))
def cluster_round(state, inbox, group_of, lane_of, *, m_in, do_tick, v=None):
    return _cluster_round_impl(
        state, inbox, group_of, lane_of, m_in=m_in, do_tick=do_tick, v=v
    )


@partial(jax.jit, static_argnames=("m_in", "do_tick", "n_rounds", "v"))
def cluster_rounds(
    state, inbox, group_of, lane_of, *, m_in, do_tick, n_rounds, v=None
):
    """n_rounds synchronous rounds in ONE dispatch (lax.scan over the round
    body). This is the latency-amortized driver for benchmarks and steady-
    state serving: the host only sequences whole blocks of rounds, so
    dispatch latency is paid once per block instead of per round."""

    def body(carry, _):
        st, inb, drops = carry
        st, nxt, d = _cluster_round_impl(
            st, inb, group_of, lane_of, m_in=m_in, do_tick=do_tick, v=v
        )
        return (st, nxt, drops + d), None

    (state, inbox, dropped), _ = jax.lax.scan(
        body, (state, inbox, jnp.int32(0)), None, length=n_rounds
    )
    return state, inbox, dropped


def _bytes_between(state: RaftState, lo, hi):
    """Sum of payload bytes of entries in (lo, hi]."""
    idx, valid = lg.window_indexes(state)
    m = valid & (idx > lo[:, None]) & (idx <= hi[:, None])
    return jnp.sum(jnp.where(m, state.log_bytes, 0), axis=1)


class Cluster:
    """G raft groups x V voters, all resident in one lane batch.

    The minimum end-to-end slice of SURVEY §7 stage 6: host loop = {tick
    kernel, in-device routing, step kernel, sync persist}, with entry
    payloads host-side.
    """

    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        shape: Shape | None = None,
        seed: int = 1,
        group_ids=None,
        inbox_slack: int = 0,
        **cfg_overrides,
    ):
        """group_ids: optional [G][V] table of distinct member ids per group
        (reference ids are arbitrary uint64, raft.go:338-430; here the
        delivery table is dense over [0, max_id], so ids must stay modest —
        <= 2^20 enforced below. Truly sparse/huge id spaces ride the rank
        re-canonicalization wrapper, ops/fused_ids.py, whose maps are
        per-group dicts). Default: the canonical 1..V layout. With arbitrary
        ids, delivery routes through the general sorted path."""
        self.g, self.v = n_groups, n_voters
        n = n_groups * n_voters
        self.shape = shape or Shape(n_lanes=n, max_peers=max(4, n_voters))
        if self.shape.n_lanes != n:
            raise ValueError("shape.n_lanes must equal groups*voters")
        self.canonical = group_ids is None
        if self.canonical:
            group_ids = [list(range(1, n_voters + 1))] * n_groups
        self.group_ids = [list(map(int, row)) for row in group_ids]
        if len(self.group_ids) != n_groups or any(
            len(r) != n_voters or len(set(r)) != n_voters or min(r) < 1
            for r in self.group_ids
        ):
            raise ValueError("group_ids must be [G][V] distinct positive ids")
        if max(max(r) for r in self.group_ids) > 1 << 20:
            raise ValueError(
                "ids above 2^20 would blow up the dense delivery table; "
                "use ops/fused_ids.IdMappedFusedCluster for sparse id spaces"
            )
        ids = np.asarray(
            [i for row in self.group_ids for i in row], np.int32
        )
        peers = np.zeros((n, self.shape.v), np.int32)
        for g, row in enumerate(self.group_ids):
            peers[g * n_voters : (g + 1) * n_voters, :n_voters] = row
        cfg = make_lane_config(self.shape, **cfg_overrides)
        self.state = init_state(self.shape, ids, peers, seed=seed, cfg=cfg)
        self.group_of = jnp.repeat(jnp.arange(n_groups, dtype=I32), n_voters)
        max_id = max(max(r) for r in self.group_ids)
        lane_of = np.full((n_groups, max_id + 1), -1, np.int32)
        for g, row in enumerate(self.group_ids):
            for j, vid in enumerate(row):
                lane_of[g, vid] = g * n_voters + j
        self.lane_of = jnp.asarray(lane_of)
        # inbox capacity: a leader can address one lane with up to 2 fan-out
        # messages + self-ack + reply per step, and the batch-released
        # ReadIndex prefix can add up to R-1 extra MsgReadIndexResp to the
        # SAME requester in one step (step.py drain slots) — size for the
        # burst so route() never silently drops read responses.
        # inbox_slack: extra slots for host-injected local messages that
        # share the inbox with routed traffic (e.g. the lockstep harness
        # injects beat/prop/read/snap-status alongside a full fan-in).
        self.m_in = 2 * self.shape.v + 2 + (self.shape.max_read_index - 1) + inbox_slack
        # pending inbox is host-mutable so tests can inject local messages
        self._pending = jax.tree.map(
            lambda x: np.array(x), empty_batch((n, self.m_in), self.shape.max_msg_entries)
        )
        self.dropped = 0

    # -- driving ----------------------------------------------------------

    def _do_round(self, do_tick: bool):
        inbox = jax.tree.map(jnp.asarray, self._pending)
        self.state, nxt, dropped = cluster_round(
            self.state,
            inbox,
            self.group_of,
            self.lane_of,
            m_in=self.m_in,
            do_tick=do_tick,
            v=self.v if self.canonical else None,
        )
        self._pending = jax.tree.map(lambda x: np.array(x), nxt)
        self.dropped += int(dropped)

    def tick(self, n_ticks: int = 1):
        for _ in range(n_ticks):
            self._do_round(do_tick=True)

    def run(self, rounds: int = 1):
        for _ in range(rounds):
            self._do_round(do_tick=False)

    def run_scanned(self, rounds: int, do_tick: bool = True):
        """Run `rounds` rounds in a single device dispatch."""
        inbox = jax.tree.map(jnp.asarray, self._pending)
        self.state, nxt, dropped = cluster_rounds(
            self.state, inbox, self.group_of, self.lane_of,
            m_in=self.m_in, do_tick=do_tick, n_rounds=rounds,
            v=self.v if self.canonical else None,
        )
        self._pending = jax.tree.map(lambda x: np.array(x), nxt)
        self.dropped += int(dropped)

    def has_pending(self) -> bool:
        return bool((self._pending.type != MT.MSG_NONE).any())

    def settle(self, max_rounds: int = 64):
        """Run until no messages remain in flight (the reference harness's
        'stabilize' fixed point, rafttest/interaction_env_handler_stabilize.go:49)."""
        for _ in range(max_rounds):
            if not self.has_pending():
                return
            self.run(1)
        raise RuntimeError("cluster did not settle")

    # -- client ops -------------------------------------------------------

    def inject(self, lane: int, **fields):
        """Queue one locally-delivered message for a lane (MsgHup, MsgProp...).
        Field names follow MsgBatch; entries passed as ent_* lists."""
        from raft_tpu.messages import make_msg

        msg = make_msg(self.shape.max_msg_entries, **fields)
        free = np.nonzero(self._pending.type[lane] == MT.MSG_NONE)[0]
        if len(free) == 0:
            raise RuntimeError("no free inbox slot for injection")
        s = free[0]
        for f in dataclasses.fields(msg):
            arr = getattr(self._pending, f.name)
            arr[lane, s] = np.asarray(getattr(msg, f.name))

    def campaign(self, lane: int):
        self.inject(lane, type=MT.MSG_HUP, to=int(np.asarray(self.state.id)[lane]))

    def propose(self, lane: int, n_bytes: int = 0):
        self.inject(
            lane,
            type=MT.MSG_PROP,
            to=int(np.asarray(self.state.id)[lane]),
            frm=int(np.asarray(self.state.id)[lane]),
            ent_terms=[0],
            ent_sizes=[n_bytes],
        )

    # -- inspection -------------------------------------------------------

    def leader_lanes(self) -> np.ndarray:
        return np.nonzero(np.asarray(self.state.state) == int(StateType.LEADER))[0]

    def lanes_of_group(self, g: int) -> slice:
        return slice(g * self.v, (g + 1) * self.v)

    def check_no_errors(self, allow_drops: bool = False):
        bits = np.asarray(self.state.error_bits)
        assert (bits == 0).all(), f"error_bits set: lanes {np.nonzero(bits)[0].tolist()}"
        if not allow_drops:
            assert self.dropped == 0, f"{self.dropped} messages dropped on inbox overflow"

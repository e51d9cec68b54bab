"""Cross-host message bridge: raft groups whose members live on different
engine instances ("hosts").

The reference deliberately ships no transport (README.md:10-14): the
application must carry `Ready.Messages` to peers, after persisting, and feed
them to `Step`. Inside one chip/mesh this framework does that with the
in-device router (cluster.route) or the fused transpose fabric; ACROSS
hosts, message batches ride DCN and this bridge is that application-side
layer for `RawNodeBatch` instances (SURVEY §5.8): it drains each host's
Ready output — honoring the persist-before-send ordering the contract
requires (doc.go:79-86; `RawNodeBatch.ready()` only surfaces messages the
sync persist already covers) — and steps them into the destination host.

Addressing: a global raft id space; each bridge member registers which ids
it hosts and at which lane.

The DCN unit is a PACKED FRAME of messages per destination host
(codec.pack_frame: u32 count + length-prefixed byte-exact raftpb messages),
not a message: `HostBridge(wire=True)` moves whole frames between its
in-process hosts, and `BridgeEndpoint` is one process's side of the same
protocol over a real byte stream (socket/pipe standing in for DCN) — see
tests/test_bridge_process.py for a genuine two-process spanning-group
election + failover.
"""

from __future__ import annotations

from raft_tpu.api.rawnode import ErrProposalDropped, Message, RawNodeBatch
from raft_tpu.logging import warn_rate_limited
from raft_tpu.types import MessageType as MTY


class PumpResult(int):
    """`HostBridge.pump`'s return value: the int is the iteration count
    (drop-in for the plain int callers compare/print), and `truncated` is
    True when the pump stopped at `max_iters` with lanes still ready. A
    truncated pump must NOT be read as quiescent — messages are still
    pending; it is also counted in the bridge_pump_truncated metrics
    counter."""

    def __new__(cls, iters: int, truncated: bool = False):
        r = super().__new__(cls, iters)
        r.truncated = truncated
        return r


class HostBridge:
    """Synchronous bridge over any number of RawNodeBatch "hosts".

    wire=True serializes every delivery through the byte-exact raftpb codec
    (runtime/codec.py, C++ native/raftpb_codec.cc) — what real DCN transport
    does, and the same marshal/unmarshal copy the reference's test network
    performs to catch aliasing (rafttest/network.go:92-101).
    """

    def __init__(self, wire: bool = False):
        self._hosts: list[RawNodeBatch] = []
        self._route: dict[int, tuple[int, int]] = {}  # raft id -> (host, lane)
        self.delivered = 0
        self.dropped = 0
        self.pump_truncated = 0
        self.wire = wire
        # committed entries surfaced by pump(), keyed (host, lane) — the
        # application's state-machine input; ready()/advance() page entries
        # out exactly once, so pump must never drop them
        self.committed: dict[tuple[int, int], list] = {}

    def add_host(self, batch: RawNodeBatch, ids_to_lanes: dict[int, int]) -> int:
        """Register a host and the (global raft id -> lane) map it serves."""
        h = len(self._hosts)
        self._hosts.append(batch)
        for nid, lane in ids_to_lanes.items():
            if nid in self._route:
                raise ValueError(f"id {nid} already hosted")
            self._route[nid] = (h, lane)
        return h

    def deliver(self, msgs: list[Message]):
        from raft_tpu.logging import get_logger

        codec = None
        if self.wire and msgs:
            # wire mode frames every message through the native raftpb codec
            from raft_tpu.runtime import codec as _codec

            codec = _codec

        log = get_logger()
        # group per destination host, preserving per-host order
        per_host: dict[int, list] = {}
        for m in msgs:
            tgt = self._route.get(m.to)
            if tgt is None:
                self.dropped += 1
                log.debug(
                    "bridge: dropping message type=%s to unhosted id %s",
                    m.type, m.to,
                )
                continue
            per_host.setdefault(tgt[0], []).append(m)
            self.delivered += 1

        def on_drop(lane, msg):
            self.dropped += 1
            self.delivered -= 1

        for h, batch in per_host.items():
            if codec is not None:
                # the DCN shape: ONE packed frame per destination host, the
                # receiver unpacks and routes by m.to — not N marshal calls
                # interleaved with N steps
                batch = codec.unpack_frame(codec.pack_frame(batch))
            # each host steps its whole batch with amortized device
            # dispatches (RawNodeBatch.step_many, the fan-in hot path)
            self._hosts[h].step_many(
                [(self._route[m.to][1], m) for m in batch], on_drop=on_drop
            )

    def pump(self, max_iters: int = 100, on_commit=None) -> PumpResult:
        """Drain every host's Ready output and deliver until quiescent (the
        multi-host analog of the reference tests' network fixture,
        raft_test.go:4844). Committed entries — which ready()/advance() page
        out exactly once — go to `on_commit(host, lane, entry)` when given,
        else accumulate in `self.committed[(host, lane)]`. Returns the
        number of iterations used as a PumpResult; `.truncated` is True
        when the iteration cap stopped the pump with work still pending
        (also recorded in the bridge_pump_truncated counter) — never read
        a truncated pump as quiescent."""
        for it in range(max_iters):
            moved = False
            for h, b in enumerate(self._hosts):
                # only the lanes the batched egress mask marks active; a
                # lane can lose readiness mid-sweep (deliver() steps into
                # this very host), so re-check before constructing
                for lane in b.ready_lanes():
                    if not b.has_ready(lane):
                        continue
                    rd = b.ready(lane)
                    msgs = rd.messages
                    for e in rd.committed_entries:
                        if on_commit is not None:
                            on_commit(h, lane, e)
                        else:
                            self.committed.setdefault((h, lane), []).append(e)
                    # sync model: ready() already reflects the persisted
                    # prefix, so sending now preserves persist-before-send
                    b.advance(lane)
                    self.deliver(msgs)
                    moved = True
            if not moved:
                return PumpResult(it)
        self.pump_truncated += 1
        warn_rate_limited(
            "bridge_pump_truncated",
            10.0,
            "HostBridge.pump truncated at %s iterations with lanes still "
            "ready (%s total truncations) — not quiescent, pump again",
            max_iters,
            self.pump_truncated,
        )
        return PumpResult(max_iters, truncated=True)

    def tick_all(self):
        for b in self._hosts:
            for lane in range(b.shape.n):
                b.tick(lane)

    def metrics_snapshot(self) -> dict:
        """One merged snapshot over every host's Ready-surface counters,
        plus the bridge's own transport counters (raft_tpu/metrics/)."""
        from raft_tpu.metrics.host import merge_snapshots

        snap = merge_snapshots(b.metrics.snapshot() for b in self._hosts)
        snap["counters"]["bridge_delivered"] = self.delivered
        snap["counters"]["bridge_dropped"] = self.dropped
        snap["counters"]["bridge_pump_truncated"] = self.pump_truncated
        return snap


class FusedBridgeEndpoint:
    """One process's side of the cross-host protocol on the FUSED engine:
    a FusedCluster hosting every group that has at least one local member,
    with the REMOTE members' lanes resident as inert GHOST MAILBOXES.

    The per-message serial drain of BridgeEndpoint bound cross-host
    throughput to RawNodeBatch.step dispatch rates (~20-30 msgs/s end to
    end); here a whole frame is injected into the fabric as numpy writes,
    ONE fused dispatch advances every local lane a round, and the round's
    cross-host traffic is harvested from the fabric into one frame per
    destination host — the batched-injection design VERDICT r4 item 3
    prescribes. Persist-before-send holds unchanged: the fused round's
    sync persist (ops/fused.py fused_round: stabled=last before the outbox
    is returned) covers everything the exported cells reference
    (reference contract: doc.go:79-86, README.md:10-14).

    Mechanics (all between dispatches, in host numpy):
      - a group spanning hosts occupies its full V canonical lanes; lanes
        of remote members are ghosts: their own-view is_learner bit is set
        so they are never promotable (no tick can ever campaign them),
        every cell addressed to them is exported (and cleared) before the
        next dispatch so they never receive, and with an empty inbox they
        never emit — their rows are therefore free outbox space;
      - IMPORT: a received message from remote member R to local member L
        is written into fabric cell [lane(R), slot(L)] — exactly where
        R's own send would sit — so the next round's route_fabric
        transpose delivers it to L like any resident traffic;
      - EXPORT: cells [lane(local), slot(remote)] become raftpb Messages
        (global ids via the group id table) packed per destination host.

    Entry payloads: the fused engine carries (term, type, size) columns
    only; exports synthesize `size` zero bytes so sizes survive the wire,
    or real bytes via the optional `payload_of(group, index, k) -> bytes`
    hook (the EntryStore seam).
    """

    _REP = (int(MTY.MSG_APP), int(MTY.MSG_SNAP), int(MTY.MSG_APP_RESP))
    _HB = (int(MTY.MSG_HEARTBEAT), int(MTY.MSG_HEARTBEAT_RESP))
    _VOTE = (int(MTY.MSG_VOTE), int(MTY.MSG_PRE_VOTE), int(MTY.MSG_TIMEOUT_NOW))
    _VRESP = (int(MTY.MSG_VOTE_RESP), int(MTY.MSG_PRE_VOTE_RESP))

    def __init__(
        self,
        n_groups: int,
        n_voters: int,
        group_ids,  # [G][V] GLOBAL raft ids, member j of group g
        remote: dict,  # {global id -> host key} for members living elsewhere
        seed: int = 1,
        payload_of=None,
        **cfg,
    ):
        import numpy as np

        from raft_tpu.ops.fused import FusedCluster
        from raft_tpu.runtime import codec as _codec

        self.codec = _codec
        g, v = n_groups, n_voters
        self.g, self.v = g, v
        self.gids = [list(map(int, row)) for row in group_ids]
        if len(self.gids) != g or any(len(r) != v for r in self.gids):
            raise ValueError("group_ids must be [G][V]")
        self.remote = dict(remote)
        self.payload_of = payload_of
        # lane/slot maps
        self._of_gid = {}
        ghost = np.zeros((g * v,), bool)
        for gi, row in enumerate(self.gids):
            for j, nid in enumerate(row):
                if nid in self._of_gid:
                    raise ValueError(f"duplicate global id {nid}")
                self._of_gid[nid] = (gi, j)
                if nid in self.remote:
                    ghost[gi * v + j] = True
        self.ghost = ghost
        self.fc = FusedCluster(g, v, seed=seed, **cfg)
        # Ghost lanes must NEVER campaign — not merely campaign late: their
        # election_elapsed grows forever (they receive nothing), so a tick
        # pin alone would fire a hup eventually, double-voting a remote
        # member's raft id. promotable() reads the learners MASK at the
        # self slot (step.py:90-96, raft.go:1962-1966), so the ghost's OWN
        # row marks itself a learner (plus the is_learner mirror) — other
        # lanes' masks are untouched and still see the member as a voter.
        import dataclasses as dc

        import jax.numpy as jnp

        st = self.fc.state
        lrn = np.asarray(st.learners).copy()
        for gi, row in enumerate(self.gids):
            for j, nid in enumerate(row):
                if nid in self.remote:
                    lrn[gi * v + j, j] = True
        self.fc.state = dc.replace(
            st,
            learners=jnp.asarray(lrn, dtype=st.learners.dtype),
            is_learner=jnp.asarray(
                np.asarray(st.is_learner) | ghost, dtype=st.is_learner.dtype
            ),
        )
        # (group, remote slot j) export list precomputed
        self._exports = [
            (gi, j)
            for gi, row in enumerate(self.gids)
            for j, nid in enumerate(row)
            if nid in self.remote
        ]
        self.delivered = 0
        self.dropped = 0
        self.overwritten = 0

    # -- fabric <-> Message ------------------------------------------------

    def _np_fab(self):
        import dataclasses as dc

        import numpy as np

        fab = self.fc.fab
        out = {}
        for ch in dc.fields(fab):
            chan = getattr(fab, ch.name)
            out[ch.name] = {
                f.name: np.asarray(getattr(chan, f.name)).copy()
                for f in dc.fields(chan)
            }
        return out

    def _set_fab(self, np_fab):
        import dataclasses as dc

        import jax.numpy as jnp

        fab = self.fc.fab
        chans = {}
        for ch in dc.fields(fab):
            chan = getattr(fab, ch.name)
            chans[ch.name] = dc.replace(
                chan,
                **{
                    f.name: jnp.asarray(
                        np_fab[ch.name][f.name],
                        dtype=getattr(chan, f.name).dtype,
                    )
                    for f in dc.fields(chan)
                },
            )
        self.fc.fab = dc.replace(fab, **chans)

    def _export(self, nf) -> dict:
        """Harvest cross-host cells into per-host column sets (the codec's
        columnar frame schema); clears the cells so ghost lanes never
        receive. One native pack call per destination host."""
        import numpy as np

        none = int(MTY.MSG_NONE)
        v = self.v
        snap_t = int(MTY.MSG_SNAP)
        per_host: dict[object, dict] = {}

        def host_acc(h):
            acc = per_host.get(h)
            if acc is None:
                acc = per_host[h] = dict(
                    rows=[], ents=[], ent_lens=[], ent_sizes=0,
                    snap_ids=[],
                )
            return acc

        for gi, j in self._exports:
            dst_gid = self.gids[gi][j]
            host = self.remote[dst_gid]
            for sj in range(v):
                src_lane = gi * v + sj
                if self.ghost[src_lane]:
                    continue
                src_gid = self.gids[gi][sj]
                for ch_name in ("rep", "hb", "vote", "vresp"):
                    ch = nf[ch_name]
                    kind = int(ch["kind"][src_lane, j])
                    if kind == none:
                        continue
                    acc = host_acc(host)
                    row = np.zeros(11, np.uint64)
                    row[0] = kind
                    row[1] = dst_gid
                    row[2] = src_gid
                    row[3] = int(ch["term"][src_lane, j])
                    ctx = 0
                    n_e = 0
                    if ch_name == "rep":
                        prev = int(ch["index"][src_lane, j])
                        row[4] = int(ch["log_term"][src_lane, j])
                        row[5] = prev
                        row[6] = int(ch["commit"][src_lane, j])
                        row[7] = int(bool(ch["reject"][src_lane, j]))
                        row[8] = int(ch["reject_hint"][src_lane, j])
                        n_e = int(ch["n_ents"][src_lane, j])
                        for k in range(n_e):
                            size = int(ch["ent_bytes"][src_lane, j, k])
                            acc["ents"].append(
                                (
                                    int(ch["ent_type"][src_lane, j, k]),
                                    int(ch["ent_term"][src_lane, j, k]),
                                    prev + 1 + k,
                                )
                            )
                            if self.payload_of is not None:
                                data = self.payload_of(gi, prev + 1 + k, k)
                                acc.setdefault("ent_blobs", []).append(data)
                                acc["ent_lens"].append(len(data))
                                acc["ent_sizes"] += len(data)
                            else:
                                acc["ent_lens"].append(size)
                                acc["ent_sizes"] += size
                        if kind == snap_t:
                            row[10] = 1
                            acc["snap_ids"].extend(self.gids[gi])
                            acc["rows"].append(
                                (row, ctx, n_e,
                                 (int(ch["snap_index"][src_lane, j]),
                                  int(ch["snap_term"][src_lane, j]), 0),
                                 (v, 0, 0, 0))
                            )
                            ch["kind"][src_lane, j] = none
                            continue
                    elif ch_name == "hb":
                        row[6] = int(ch["commit"][src_lane, j])
                        ctx = int(ch["context"][src_lane, j])
                    elif ch_name == "vote":
                        row[4] = int(ch["log_term"][src_lane, j])
                        row[5] = int(ch["index"][src_lane, j])
                        ctx = int(ch["context"][src_lane, j])
                    else:  # vresp
                        row[7] = int(bool(ch["reject"][src_lane, j]))
                    acc["rows"].append((row, ctx, n_e, (0, 0, 0), (0, 0, 0, 0)))
                    ch["kind"][src_lane, j] = none
        out = {}
        for host, acc in per_host.items():
            k = len(acc["rows"])
            cols = dict(
                scalars=np.stack([r[0] for r in acc["rows"]]),
                ctx=np.array([r[1] for r in acc["rows"]], np.int64),
                n_ents=np.array([r[2] for r in acc["rows"]], np.int32),
                ent_scalars=np.array(acc["ents"], np.uint64).reshape(-1, 3),
                ent_lens=np.array(acc["ent_lens"], np.int64),
                ent_data=(
                    b"".join(acc["ent_blobs"])
                    if "ent_blobs" in acc
                    else bytes(acc["ent_sizes"])
                ),
                snap_meta=np.array([r[3] for r in acc["rows"]], np.uint64),
                snap_counts=np.array([r[4] for r in acc["rows"]], np.int32),
                snap_ids=np.array(acc["snap_ids"], np.uint64),
            )
            out[host] = cols
            self.delivered += k
        return out

    def _inject(self, nf, cols):
        """Write received columnar messages into the ghost senders' outbox
        cells."""
        none = int(MTY.MSG_NONE)
        v = self.v
        sc = cols["scalars"]
        ctxs = cols["ctx"]
        n_ents = cols["n_ents"]
        ent_sc = cols["ent_scalars"]
        ent_lens = cols["ent_lens"]
        snap_meta = cols["snap_meta"]
        e_off = 0
        for i in range(sc.shape[0]):
            t = int(sc[i, 0])
            dst = self._of_gid.get(int(sc[i, 1]))
            src = self._of_gid.get(int(sc[i, 2]))
            n_e = int(n_ents[i])
            row_ents = ent_sc[e_off : e_off + n_e]
            row_lens = ent_lens[e_off : e_off + n_e]
            e_off += n_e
            if src is None or dst is None or src[0] != dst[0]:
                self.dropped += 1
                continue
            gi, sj = src
            _, dj = dst
            lane = gi * v + sj
            if not self.ghost[lane] or self.ghost[gi * v + dj]:
                self.dropped += 1
                continue
            if t in self._REP:
                ch = nf["rep"]
                if ch["kind"][lane, dj] != none:
                    self.overwritten += 1
                ch["kind"][lane, dj] = t
                ch["term"][lane, dj] = int(sc[i, 3])
                ch["log_term"][lane, dj] = int(sc[i, 4])
                ch["index"][lane, dj] = int(sc[i, 5])
                ch["commit"][lane, dj] = int(sc[i, 6])
                ch["reject"][lane, dj] = bool(sc[i, 7])
                ch["reject_hint"][lane, dj] = int(sc[i, 8])
                e_ax = ch["ent_term"].shape[-1]
                ne = min(n_e, e_ax)
                ch["n_ents"][lane, dj] = ne
                ch["ent_term"][lane, dj, :] = 0
                ch["ent_type"][lane, dj, :] = 0
                ch["ent_bytes"][lane, dj, :] = 0
                for k in range(ne):
                    ch["ent_type"][lane, dj, k] = int(row_ents[k, 0])
                    ch["ent_term"][lane, dj, k] = int(row_ents[k, 1])
                    ch["ent_bytes"][lane, dj, k] = max(0, int(row_lens[k]))
                if sc[i, 10]:
                    ch["snap_index"][lane, dj] = int(snap_meta[i, 0])
                    ch["snap_term"][lane, dj] = int(snap_meta[i, 1])
                else:
                    ch["snap_index"][lane, dj] = 0
                    ch["snap_term"][lane, dj] = 0
            elif t in self._HB:
                ch = nf["hb"]
                if ch["kind"][lane, dj] != none:
                    self.overwritten += 1
                ch["kind"][lane, dj] = t
                ch["term"][lane, dj] = int(sc[i, 3])
                ch["commit"][lane, dj] = int(sc[i, 6])
                # ctx -1 = a foreign (non-8-byte) wire context: the fused
                # fabric holds int tickets only, so it is dropped here; a
                # deployment bridging Go peers' ReadIndex ids routes those
                # through the serial BridgeEndpoint, whose RawNode boundary
                # interns arbitrary byte contexts
                ch["context"][lane, dj] = max(0, int(ctxs[i]))
            elif t in self._VOTE:
                ch = nf["vote"]
                if ch["kind"][lane, dj] != none:
                    self.overwritten += 1
                ch["kind"][lane, dj] = t
                ch["term"][lane, dj] = int(sc[i, 3])
                ch["log_term"][lane, dj] = int(sc[i, 4])
                ch["index"][lane, dj] = int(sc[i, 5])
                ch["context"][lane, dj] = max(0, int(ctxs[i]))
            elif t in self._VRESP:
                ch = nf["vresp"]
                if ch["kind"][lane, dj] != none:
                    self.overwritten += 1
                ch["kind"][lane, dj] = t
                ch["term"][lane, dj] = int(sc[i, 3])
                ch["reject"][lane, dj] = bool(sc[i, 7])
            else:
                self.dropped += 1
                continue

    # -- the cycle ---------------------------------------------------------

    def cycle(self, frames=(), rounds: int = 1, ops=None, **run_kw) -> dict:
        """Inject received frames, advance `rounds` fused rounds in one
        dispatch, harvest outbound traffic. Returns {host key: frame} —
        framing is the columnar codec, ONE native call per frame either
        way.

        rounds is pinned to 1: the ghost-mailbox invariant (cells addressed
        to remote members are exported BEFORE the next in-kernel route)
        only holds at dispatch boundaries — a second in-dispatch round
        would route cross-host cells into the ghost lane, which would then
        answer as the remote member. Cross-host progress needs a frame
        exchange per round anyway."""
        if rounds != 1:
            raise ValueError(
                "FusedBridgeEndpoint.cycle runs exactly one round per "
                "dispatch (the export/clear of cross-host cells happens at "
                "dispatch boundaries)"
            )
        nf = self._np_fab()
        for frame in frames:
            self._inject(nf, self.codec.unpack_frame_cols(frame))
        self._set_fab(nf)
        self.fc.run(rounds, ops=ops, **run_kw)
        nf = self._np_fab()
        out = self._export(nf)
        self._set_fab(nf)
        return {h: self.codec.pack_frame_cols(cols) for h, cols in out.items()}

    def local_lanes(self):
        import numpy as np

        return [int(l) for l in np.nonzero(~self.ghost)[0]]

    def metrics_snapshot(self) -> dict | None:
        """The resident FusedCluster's device-plane snapshot plus this
        endpoint's transport counters; None while RAFT_TPU_METRICS=0."""
        snap = self.fc.metrics_snapshot()
        if snap is None:
            return None
        snap["counters"]["bridge_delivered"] = self.delivered
        snap["counters"]["bridge_dropped"] = self.dropped
        snap["counters"]["bridge_overwritten"] = self.overwritten
        return snap


class BridgeEndpoint:
    """One PROCESS's side of the cross-host protocol: a RawNodeBatch hosting
    the local members of (possibly spanning) groups, draining Readys into
    packed per-destination frames and stepping received frames. The byte
    transport between endpoints is the application's (socket/pipe/DCN),
    exactly as the reference prescribes (README.md:10-14).

    local_ids: {raft id -> lane} served by this batch.
    remote_ids: {raft id -> host key} for members living elsewhere; the host
    key is opaque to the endpoint (it keys the frames returned by drain()).
    """

    def __init__(self, batch: RawNodeBatch, local_ids: dict, remote_ids: dict):
        from raft_tpu.runtime import codec as _codec

        self.batch = batch
        self.local = dict(local_ids)
        self.remote = dict(remote_ids)
        self.codec = _codec
        self.delivered = 0
        self.dropped = 0
        # True when the last drain() stopped at its iteration cap with
        # lanes still ready (also counted in bridge_drain_truncated) —
        # the caller must drain again rather than read it as quiescent
        self.truncated = False
        self.committed: dict[int, list] = {}

    def drain(self, max_iters: int = 100) -> dict:
        """Run the local Ready/advance loop to its fixed point; returns
        {host key: frame bytes} of outbound traffic. Committed entries
        accumulate in self.committed[lane] (persist-before-send holds: the
        sync Ready only surfaces messages the persist already covers).
        Sets self.truncated when the cap stopped the loop early."""
        out: dict[object, list] = {}
        b = self.batch
        self.truncated = True
        for _ in range(max_iters):
            moved = False
            local_msgs = []
            # only the lanes the batched egress mask marks active; an
            # earlier lane's advance can flip a later lane's readiness,
            # so re-check before constructing
            for lane in b.ready_lanes():
                if not b.has_ready(lane):
                    continue
                rd = b.ready(lane)
                for e in rd.committed_entries:
                    self.committed.setdefault(lane, []).append(e)
                b.advance(lane)
                moved = True
                for m in rd.messages:
                    if m.to in self.local:
                        local_msgs.append(m)
                    elif m.to in self.remote:
                        out.setdefault(self.remote[m.to], []).append(m)
                    else:
                        self.dropped += 1
            if local_msgs:
                self._step_local(local_msgs)
            if not moved:
                self.truncated = False
                break
        if self.truncated:
            self.batch.metrics.inc("bridge_drain_truncated")
            warn_rate_limited(
                "bridge_drain_truncated",
                10.0,
                "BridgeEndpoint.drain truncated at %s iterations with lanes "
                "still ready (%s total truncations) — drain again",
                max_iters,
                self.batch.metrics.get("bridge_drain_truncated"),
            )
        return {h: self.codec.pack_frame(ms) for h, ms in out.items()}

    def receive(self, frame: bytes):
        """Step one received frame into the local batch."""
        msgs = self.codec.unpack_frame(frame)
        self._step_local([m for m in msgs if m.to in self.local])

    def _step_local(self, msgs):
        def on_drop(lane, msg):
            self.dropped += 1
            self.delivered -= 1  # same convention as HostBridge.deliver

        self.delivered += len(msgs)
        self.batch.step_many(
            [(self.local[m.to], m) for m in msgs], on_drop=on_drop
        )

    def tick_all(self):
        for lane in self.local.values():
            self.batch.tick(lane)

    def metrics_snapshot(self) -> dict:
        """The local batch's Ready-surface counters plus this endpoint's
        transport counters (raft_tpu/metrics/)."""
        snap = self.batch.metrics.snapshot()
        snap["counters"]["bridge_delivered"] = self.delivered
        snap["counters"]["bridge_dropped"] = self.dropped
        return snap

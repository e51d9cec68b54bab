"""Wire layer: extract bundles <-> length-prefixed frames between hosts.

Frame = 20-byte header + payload. Two payload codecs, selectable via
RAFT_TPU_FABRIC_CODEC ("pb" | "np"; default auto = pb when the native
raftpb library loads, np otherwise):

  pb  byte-exact gogoproto raftpb via runtime/codec.py's columnar frame
      codec (one native call per frame) — the bridge.py convention
      exactly: global raft id of canonical lane L is L + 1, entry rows
      are (type, term, prev_index + 1 + k) with synthesized zero
      payloads of the carried sizes, MSG_SNAP rows carry
      (snap_index, snap_term) metadata + the group's member ids. A Go
      peer can split the frame and Unmarshal each message.

  np  raw little-endian columnar dump of the superset schema — the
      dependency-free path and the seam for the EQuARX-style diet:
      RAFT_TPU_FABRIC_DIET=1 narrows every field the byte-diet layer
      already bounds below int16 (uint16 terms/indexes/commits, int8
      kinds/types/counts, int16 entry sizes) on the wire, cutting frame
      bytes ~55% (gated in benches/fabric_ab.py). Requires
      RAFT_TPU_DIET=1 — without the diet's auto-rebase those bounds
      don't hold and construction refuses.

Both codecs are exact (quantization only narrows storage of already-
bounded values, never rounds), so the digest-parity oracle holds under
either. Persist-before-send: frames are encoded from the post-round
carry, after the fused round's synchronous persist has already advanced
`stabled` past every appended entry — by the time a frame exists, its
contents are stable locally (see driver.py).

Frame header round field = the EMIT round: the absolute round whose
post-round carry the payload was extracted from (a chaos wire_delay
re-emits deferred bundles under the release round, so the tag always
matches the frame's wire slot). Lockstep receivers inject it before
round r+1; a bounded-skew receiver (RAFT_TPU_FABRIC_SKEW=D) stages it
under (peer, emit_round) and injects before round r+D+1 — the fixed
D-round wire contract driver.py's twin oracle leans on.

Telemetry summary section (RAFT_TPU_FABRIC_DIET + np codec, skewed
fleets): FLAG_SUM frames carry an EQuARX-style quantized summary of the
sender's per-edge counter deltas (int8-style: 7-bit magnitude + a
saturate flag bit) and wire-fault/recovery tallies (int4-style: two
3-bit+flag nibbles per byte) between the header and the payload.
Exactness is load-bearing only for raft state — telemetry saturates at
the rail and flags (never silently wraps), and the raft payload bytes
are untouched (tests assert byte-identity with the summary stripped).

Frame transport: `send_frame`/`recv_frame` speak multiprocessing
Connections natively (message-oriented) and raw stream sockets via a
u32le length prefix.
"""

from __future__ import annotations

import struct

import numpy as np

from raft_tpu import config
from raft_tpu.fabric.extract import Bundle, ENT_FIELDS, SCALAR_FIELDS
from raft_tpu.fabric.placement import CHANNELS
from raft_tpu.types import MessageType as MT

MAGIC = b"RFAB"
VERSION = 1
FLAG_DIET = 0x01
FLAG_PB = 0x02
FLAG_SUM = 0x04  # quantized telemetry summary section follows the header

# magic, version, flags, n_ents(E), seq, round (EMIT round), count
_HDR = struct.Struct("<4sBBHIiI")

# -- quantized telemetry summary (satellite of the skew pipeline) ----------
#
# Fixed key tables, so a key costs one byte on the wire. int8-style
# deltas: u1 key id + u1 value (low 7 bits = magnitude clamped to 0..127,
# bit 7 = saturate flag). int4-style tallies: fixed-order vector, two
# nibbles per byte (low nibble first), each nibble = 3-bit magnitude
# clamped to 0..7 + bit 3 saturate flag. Saturation is FLAGGED, never
# wrapped: the decoder folds flags into fabric_summary_saturated.
SUMMARY_DELTA_KEYS = (
    "fabric_frames_sent",
    "fabric_frames_received",
    "fabric_msgs_exported",
    "fabric_msgs_injected",
    "fabric_msgs_total",
    "fabric_frames_staged",
    "fabric_skew_current",
)
# gauge members of the delta table: emitted as the current LEVEL, not a
# since-last-frame difference (a gauge delta can be negative, which the
# unsigned 7-bit lane cannot carry honestly)
SUMMARY_LEVEL_KEYS = (
    "fabric_frames_staged",
    "fabric_skew_current",
)
SUMMARY_TALLY_KEYS = (
    "fabric_frames_dropped",
    "fabric_frames_deferred",
    "fabric_injection_drops",
    "fabric_backpressure_rounds",
)
_SUM_LEN = struct.Struct("<H")


def pack_summary(deltas: dict, tallies: dict) -> tuple[bytes, int]:
    """-> (section_bytes, n_saturated). Unknown keys are refused (the key
    table IS the schema); negative values clamp to 0 and flag."""
    out = bytearray()
    sat = 0
    items = []
    for name, v in sorted(deltas.items()):
        if name not in SUMMARY_DELTA_KEYS:
            raise ValueError(f"unknown summary delta key {name!r}")
        v = int(v)
        q = min(max(v, 0), 127)
        s = q != v
        sat += s
        items.append((SUMMARY_DELTA_KEYS.index(name), q | (0x80 if s else 0)))
    out.append(len(items))
    for kid, b in items:
        out += bytes((kid, b))
    nibbles = []
    for name in SUMMARY_TALLY_KEYS:
        v = int(tallies.get(name, 0))
        q = min(max(v, 0), 7)
        s = q != v
        sat += s
        nibbles.append(q | (0x8 if s else 0))
    out.append(len(nibbles))
    for i in range(0, len(nibbles), 2):
        lo = nibbles[i]
        hi = nibbles[i + 1] if i + 1 < len(nibbles) else 0
        out.append(lo | (hi << 4))
    return bytes(out), sat


def unpack_summary(buf: bytes) -> tuple[dict, dict, int]:
    """-> (deltas, tallies, n_saturated); the inverse of pack_summary."""
    deltas: dict = {}
    sat = 0
    off = 0
    n = buf[off]
    off += 1
    for _ in range(n):
        kid, b = buf[off], buf[off + 1]
        off += 2
        if kid >= len(SUMMARY_DELTA_KEYS):
            raise ValueError(f"unknown summary delta key id {kid}")
        sat += bool(b & 0x80)
        deltas[SUMMARY_DELTA_KEYS[kid]] = b & 0x7F
    nt = buf[off]
    off += 1
    if nt != len(SUMMARY_TALLY_KEYS):
        raise ValueError(
            f"summary tally vector length {nt} != {len(SUMMARY_TALLY_KEYS)}"
        )
    tallies: dict = {}
    for i, name in enumerate(SUMMARY_TALLY_KEYS):
        nib = (buf[off + i // 2] >> (4 * (i % 2))) & 0xF
        sat += bool(nib & 0x8)
        tallies[name] = nib & 0x7
    off += (nt + 1) // 2
    if off != len(buf):
        raise ValueError(f"trailing bytes in fabric summary: {len(buf) - off}")
    return deltas, tallies, sat

# channel classification of decoded raftpb message types (bridge.py's
# family split: requests and responses of a family share a channel)
_FAMILY = {}
for _t in (MT.MSG_APP, MT.MSG_SNAP, MT.MSG_APP_RESP):
    _FAMILY[int(_t)] = 0
for _t in (MT.MSG_HEARTBEAT, MT.MSG_HEARTBEAT_RESP):
    _FAMILY[int(_t)] = 1
for _t in (MT.MSG_VOTE, MT.MSG_PRE_VOTE, MT.MSG_TIMEOUT_NOW):
    _FAMILY[int(_t)] = 2
for _t in (MT.MSG_VOTE_RESP, MT.MSG_PRE_VOTE_RESP):
    _FAMILY[int(_t)] = 3

# np-codec column dtypes, in fixed serialization order (chan, cell, then
# extract.SCALAR_FIELDS, then ENT_FIELDS). The diet table narrows exactly
# the fields the byte diet (state.STATE_PACK / fused.FABRIC_PACK) bounds:
# uint16 index-like columns, int8 kinds/counts, int16 entry sizes.
# context stays int32 — ReadIndex tickets are not diet-bounded.
_WIDE_DT = dict(
    chan="u1",
    cell="<u4",
    kind="<i4",
    term="<i4",
    index="<i4",
    log_term="<i4",
    commit="<i4",
    reject="<i4",
    reject_hint="<i4",
    n_ents="<i4",
    context="<i4",
    snap_index="<i4",
    snap_term="<i4",
    ent_term="<i4",
    ent_type="<i4",
    ent_bytes="<i4",
)
_DIET_DT = dict(
    _WIDE_DT,
    kind="i1",
    term="<u2",
    index="<u2",
    log_term="<u2",
    commit="<u2",
    reject="u1",
    reject_hint="<u2",
    n_ents="u1",
    snap_index="<u2",
    snap_term="<u2",
    ent_term="<u2",
    ent_type="i1",
    ent_bytes="<i2",
)
_NP_ORDER = ("chan", "cell") + SCALAR_FIELDS + ENT_FIELDS


def fabric_codec() -> str:
    """RAFT_TPU_FABRIC_CODEC: "pb" (byte-exact raftpb frames via the
    native codec, the default when unset) or "np" (raw columnar,
    diet-capable)."""
    return config.env_str("RAFT_TPU_FABRIC_CODEC", default="")


def fabric_diet_enabled() -> bool:
    """RAFT_TPU_FABRIC_DIET: narrow np-codec wire columns to the byte
    diet's sub-int16 bounds (requires RAFT_TPU_DIET=1 and the np codec;
    default OFF)."""
    return config.env_flag("RAFT_TPU_FABRIC_DIET", default=False)


class FabricWire:
    """Per-host wire endpoint: encode outbound bundles into frames and
    decode inbound frames into bundles, counting frames/bytes into the
    driver's HostCounters (metrics/host.py FABRIC_COUNTERS)."""

    def __init__(self, n_voters: int, n_ents: int, counters=None, codec=None):
        self.v = int(n_voters)
        self.e = int(n_ents)
        self.counters = counters
        self.diet = fabric_diet_enabled()
        name = codec or fabric_codec() or "pb"
        if name not in ("pb", "np"):
            raise ValueError(f"RAFT_TPU_FABRIC_CODEC must be pb|np, got {name!r}")
        if self.diet:
            if name != "np":
                raise RuntimeError(
                    "RAFT_TPU_FABRIC_DIET requires the np codec (pb frames "
                    "are byte-exact raftpb and cannot narrow)"
                )
            if not config.env_flag("RAFT_TPU_DIET", default=False):
                raise RuntimeError(
                    "RAFT_TPU_FABRIC_DIET=1 requires RAFT_TPU_DIET=1: only "
                    "the byte diet's auto-rebase keeps index/term columns "
                    "inside the uint16 wire bounds"
                )
        self.codec = name
        self.seq = 0
        # decode side-channel: the last frame's telemetry summary
        # (deltas, tallies, n_saturated) or None — read it right after
        # decode() (the skewed driver folds it into peer_summaries)
        self.last_summary: tuple | None = None

    # -- frame encode/decode ----------------------------------------------

    def encode(self, bundle: Bundle | None, rnd: int, summary=None) -> bytes:
        """Frame the bundle under EMIT round `rnd`. `summary` (optional
        (deltas, tallies) dict pair) rides as a quantized telemetry
        section between header and payload — np codec + diet only, so the
        raft payload bytes and the pb interop format never change."""
        k = 0 if bundle is None else bundle.count
        if k == 0:
            payload = b""
        elif self.codec == "pb":
            payload = self._encode_pb(bundle)
        else:
            payload = self._encode_np(bundle)
        section = b""
        flags = (FLAG_DIET if self.diet else 0) | (
            FLAG_PB if self.codec == "pb" else 0
        )
        if summary is not None:
            if not self.diet:
                raise RuntimeError(
                    "fabric telemetry summaries require RAFT_TPU_FABRIC_DIET "
                    "(the quantized section is part of the wire diet)"
                )
            sec, sat = pack_summary(*summary)
            if sat and self.counters is not None:
                self.counters.inc("fabric_summary_saturated", sat)
            section = _SUM_LEN.pack(len(sec)) + sec
            flags |= FLAG_SUM
        frame = (
            _HDR.pack(MAGIC, VERSION, flags, self.e, self.seq, rnd, k)
            + section
            + payload
        )
        self.seq += 1
        if self.counters is not None:
            self.counters.inc("fabric_frames_sent")
            self.counters.inc("fabric_bytes_sent", len(frame))
        return frame

    def decode(self, frame: bytes) -> Bundle:
        magic, ver, flags, e, _seq, rnd, k = _HDR.unpack_from(frame, 0)
        if magic != MAGIC or ver != VERSION:
            raise ValueError("bad fabric frame header")
        off = _HDR.size
        self.last_summary = None
        if flags & FLAG_SUM:
            (slen,) = _SUM_LEN.unpack_from(frame, off)
            off += _SUM_LEN.size
            self.last_summary = unpack_summary(frame[off : off + slen])
            off += slen
        payload = frame[off:]
        if k == 0:
            b = Bundle.empty(self.e, rnd)
        elif flags & FLAG_PB:
            b = self._decode_pb(payload, k, rnd)
        else:
            b = self._decode_np(payload, k, e, bool(flags & FLAG_DIET), rnd)
        if self.counters is not None:
            self.counters.inc("fabric_frames_received")
            self.counters.inc("fabric_bytes_received", len(frame))
        return b

    # -- np payload --------------------------------------------------------

    def _encode_np(self, b: Bundle) -> bytes:
        dt = _DIET_DT if self.diet else _WIDE_DT
        parts = []
        for name in _NP_ORDER:
            x = b.chan if name == "chan" else b.cell if name == "cell" else b.cols[name]
            d = np.dtype(dt[name])
            if self.diet and d.itemsize < 4 and name not in ("chan", "cell"):
                info = np.iinfo(d)
                if x.min(initial=0) < info.min or x.max(initial=0) > info.max:
                    raise ValueError(
                        f"fabric diet overflow in {name}: values escape "
                        f"{d} — diet rebase invariant violated"
                    )
            parts.append(np.ascontiguousarray(x, dtype=d).tobytes())
        return b"".join(parts)

    def _decode_np(self, payload: bytes, k: int, e: int, diet: bool, rnd: int) -> Bundle:
        dt = _DIET_DT if diet else _WIDE_DT
        off = 0
        raw = {}
        for name in _NP_ORDER:
            d = np.dtype(dt[name])
            n = k * (e if name in ENT_FIELDS else 1)
            raw[name] = np.frombuffer(payload, d, count=n, offset=off)
            off += n * d.itemsize
        if off != len(payload):
            raise ValueError(f"trailing bytes in fabric frame: {len(payload) - off}")
        cols = {
            f: raw[f].astype(np.int32).reshape((k, e) if f in ENT_FIELDS else (k,))
            for f in SCALAR_FIELDS + ENT_FIELDS
        }
        return Bundle(raw["chan"].astype(np.uint8), raw["cell"].astype(np.uint32), cols, rnd)

    # -- pb payload (runtime/codec.py columnar frame schema) ---------------

    def _encode_pb(self, b: Bundle) -> bytes:
        from raft_tpu.runtime import codec as rcodec

        v = self.v
        k = b.count
        c = b.cols
        src_lane = b.cell.astype(np.int64) // v
        dst_lane = (src_lane // v) * v + (b.cell.astype(np.int64) % v)
        is_rep = b.chan == 0
        is_hb = b.chan == 1
        is_vote = b.chan == 2
        kind = c["kind"].astype(np.int64)
        is_snap = kind == int(MT.MSG_SNAP)

        sc = np.zeros((k, 11), np.uint64)
        sc[:, 0] = kind
        sc[:, 1] = dst_lane + 1  # global raft id of lane L is L + 1
        sc[:, 2] = src_lane + 1
        sc[:, 3] = c["term"]
        sc[:, 4] = np.where(is_rep | is_vote, c["log_term"], 0)
        sc[:, 5] = np.where(is_rep | is_vote, c["index"], 0)
        sc[:, 6] = np.where(is_rep | is_hb, c["commit"], 0)
        sc[:, 7] = np.where(is_hb | is_vote, 0, c["reject"]).astype(bool)
        sc[:, 8] = np.where(is_rep, c["reject_hint"], 0)
        sc[:, 10] = is_snap
        ctx = np.where(is_hb | is_vote, c["context"], 0).astype(np.int64)
        n_ents = np.where(is_rep, c["n_ents"], 0).astype(np.int32)

        ent_rows, ent_lens = [], []
        snap_ids = []
        for i in np.nonzero(n_ents)[0]:
            prev = int(c["index"][i])
            for j in range(int(n_ents[i])):
                ent_rows.append(
                    (int(c["ent_type"][i, j]), int(c["ent_term"][i, j]), prev + 1 + j)
                )
                ent_lens.append(int(c["ent_bytes"][i, j]))
        snap_meta = np.zeros((k, 3), np.uint64)
        snap_counts = np.zeros((k, 4), np.int32)
        if is_snap.any():
            snap_meta[:, 0] = np.where(is_snap, c["snap_index"], 0)
            snap_meta[:, 1] = np.where(is_snap, c["snap_term"], 0)
            snap_counts[:, 0] = np.where(is_snap, v, 0)
            for i in np.nonzero(is_snap)[0]:
                g = int(src_lane[i]) // v
                snap_ids.extend(g * v + j + 1 for j in range(v))
        return rcodec.pack_frame_cols(
            dict(
                scalars=sc,
                ctx=ctx,
                n_ents=n_ents,
                ent_scalars=np.array(ent_rows, np.uint64).reshape(-1, 3),
                ent_lens=np.array(ent_lens, np.int64),
                ent_data=bytes(int(sum(l for l in ent_lens if l > 0))),
                snap_meta=snap_meta,
                snap_counts=snap_counts,
                snap_ids=np.array(snap_ids, np.uint64),
            )
        )

    def _decode_pb(self, payload: bytes, k: int, rnd: int) -> Bundle:
        from raft_tpu.runtime import codec as rcodec

        cols = rcodec.unpack_frame_cols(payload)
        sc = cols["scalars"].astype(np.int64)
        if sc.shape[0] != k:
            raise ValueError(
                f"fabric frame count mismatch: header {k}, payload {sc.shape[0]}"
            )
        v = self.v
        e = self.e
        kind = sc[:, 0]
        chan = np.array([_FAMILY[int(t)] for t in kind], np.uint8)
        dst_lane = sc[:, 1] - 1
        src_lane = sc[:, 2] - 1
        cell = (src_lane * v + dst_lane % v).astype(np.uint32)
        is_rep = chan == 0
        is_hb = chan == 1
        is_vote = chan == 2
        ctx = np.maximum(cols["ctx"].astype(np.int64), 0)
        n_ents = np.where(is_rep, cols["n_ents"].astype(np.int64), 0)
        out = {
            "kind": kind,
            "term": sc[:, 3],
            "index": np.where(is_rep | is_vote, sc[:, 5], 0),
            "log_term": np.where(is_rep | is_vote, sc[:, 4], 0),
            "commit": np.where(is_rep | is_hb, sc[:, 6], 0),
            "reject": np.where(is_hb | is_vote, 0, sc[:, 7]),
            "reject_hint": np.where(is_rep, sc[:, 8], 0),
            "n_ents": n_ents,
            "context": np.where(is_hb | is_vote, ctx, 0),
            "snap_index": np.where(sc[:, 10] != 0, cols["snap_meta"][:, 0].astype(np.int64), 0),
            "snap_term": np.where(sc[:, 10] != 0, cols["snap_meta"][:, 1].astype(np.int64), 0),
        }
        ent_term = np.zeros((k, e), np.int64)
        ent_type = np.zeros((k, e), np.int64)
        ent_bytes = np.zeros((k, e), np.int64)
        ent_sc = cols["ent_scalars"].astype(np.int64)
        ent_lens = cols["ent_lens"].astype(np.int64)
        off = 0
        for i in np.nonzero(n_ents)[0]:
            n_e = int(n_ents[i])
            ent_type[i, :n_e] = ent_sc[off : off + n_e, 0]
            ent_term[i, :n_e] = ent_sc[off : off + n_e, 1]
            ent_bytes[i, :n_e] = np.maximum(ent_lens[off : off + n_e], 0)
            off += n_e
        out["ent_term"], out["ent_type"], out["ent_bytes"] = ent_term, ent_type, ent_bytes
        return Bundle(
            chan,
            cell,
            {f: np.asarray(x).astype(np.int32) for f, x in out.items()},
            rnd,
        )


# -- frame transport (length-prefixed on streams) -------------------------


def send_frame(conn, frame: bytes) -> None:
    """Message-oriented on mp.Connection, u32le length prefix on sockets."""
    if hasattr(conn, "send_bytes"):
        conn.send_bytes(frame)
    else:
        conn.sendall(struct.pack("<I", len(frame)) + frame)


def recv_frame(conn) -> bytes:
    if hasattr(conn, "recv_bytes"):
        return conn.recv_bytes()
    hdr = _recv_exact(conn, 4)
    (n,) = struct.unpack("<I", hdr)
    return _recv_exact(conn, n)


def _recv_exact(sock, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise EOFError("fabric peer closed")
        buf += chunk
    return buf

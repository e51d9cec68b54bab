"""raftpb.Message <-> wire bytes via the C++ codec (native/raftpb_codec.cc).

Byte-exact gogoproto encoding (reference: raftpb/raft.pb.go generated
marshal), so encoded messages interoperate with Go raft peers on the wire.
This is the serializer for cross-host transport (runtime/bridge.py over
DCN) and for applications that persist messages.
"""

from __future__ import annotations

import ctypes

import numpy as np

from raft_tpu.api.rawnode import Entry, Message, Snapshot
from raft_tpu.runtime.native import _load

_N_SCALARS = 11  # see raftpb_codec.cc scalar slots


def _lib():
    lib = _load()
    if not getattr(lib, "_codec_bound", False):
        c = ctypes.c_void_p  # keep signatures loose; numpy buffers below
        lib.msg_marshal.restype = ctypes.c_int64
        lib.msg_unmarshal.restype = ctypes.c_int64
        lib._codec_bound = True
    return lib


def _u64(x):
    return np.ascontiguousarray(x, dtype=np.uint64)


def _scalars(m: Message) -> np.ndarray:
    return _u64(
        [
            int(m.type), m.to, m.frm, m.term, m.log_term, m.index, m.commit,
            1 if m.reject else 0, m.reject_hint, getattr(m, "vote", 0),
            1 if m.snapshot is not None else 0,
        ]
    )


def marshal_message(m: Message) -> bytes:
    lib = _lib()
    scalars = _scalars(m)
    # Message.context on the wire is bytes; the engine keys requests with an
    # int ticket encoded as 8-byte big-endian (absent when 0). Foreign
    # contexts (a Go peer's ReadIndex id of any other length) are carried as
    # raw bytes end-to-end so marshal(unmarshal(x)) is byte-stable.
    if isinstance(m.context, bytes):
        ctx_b = m.context
    else:
        ctx = int(m.context)
        ctx_b = ctx.to_bytes(8, "big") if ctx else None
    ents = m.entries or []
    ent_scalars = _u64(
        [x for e in ents for x in (int(e.type), e.term, e.index)]
        or [0]
    )
    ent_lens = np.ascontiguousarray(
        [len(e.data) if e.data is not None else -1 for e in ents] or [0],
        dtype=np.int64,
    )
    ent_data = b"".join(e.data or b"" for e in ents)
    snap = m.snapshot
    if snap is not None:
        ids = (
            list(snap.voters)
            + list(snap.learners)
            + list(snap.voters_outgoing)
            + list(snap.learners_next)
        )
        snap_counts = np.ascontiguousarray(
            [
                len(snap.voters), len(snap.learners),
                len(snap.voters_outgoing), len(snap.learners_next),
            ],
            dtype=np.int32,
        )
        snap_ids = _u64(ids or [0])
        snap_meta = _u64([snap.index, snap.term, 1 if snap.auto_leave else 0])
        snap_data = snap.data or b""
        snap_data_len = len(snap_data) if snap.data is not None else -1
    else:
        snap_counts = np.zeros(4, np.int32)
        snap_ids = _u64([0])
        snap_meta = _u64([0, 0, 0])
        snap_data, snap_data_len = b"", -1
    resps = getattr(m, "responses", None) or []
    resp_scalars = _u64(
        [x for r in resps for x in _scalars(r).tolist()] or [0]
    )

    cap = 256 + len(ent_data) + 16 * max(1, len(ents)) + len(snap_data) + 512
    while True:
        out = np.zeros(cap, np.uint8)
        n = lib.msg_marshal(
            scalars.ctypes.data_as(ctypes.c_void_p),
            ctx_b, ctypes.c_int64(len(ctx_b) if ctx_b is not None else -1),
            ctypes.c_int32(len(ents)),
            ent_scalars.ctypes.data_as(ctypes.c_void_p),
            ent_lens.ctypes.data_as(ctypes.c_void_p),
            ent_data,
            snap_meta.ctypes.data_as(ctypes.c_void_p),
            snap_data, ctypes.c_int64(snap_data_len),
            snap_counts.ctypes.data_as(ctypes.c_void_p),
            snap_ids.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int32(len(resps)),
            resp_scalars.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(cap),
        )
        if n >= 0:
            return out[:n].tobytes()
        cap = int(-n)


# --------------------------------------------------------------------------
# batch framing — the DCN unit is a PACKED frame of messages per destination
# host, not a message (SURVEY §5.8: cross-host groups ship message batches).
# Layout: u32le count, then per message u32le length + raftpb wire bytes.
# (The per-message bytes stay byte-exact gogoproto, so a Go peer can split
# the frame and unmarshal each message with pb.Message.Unmarshal.)


def pack_frame(msgs) -> bytes:
    import struct

    parts = [struct.pack("<I", len(msgs))]
    for m in msgs:
        b = marshal_message(m)
        parts.append(struct.pack("<I", len(b)))
        parts.append(b)
    return b"".join(parts)


def unpack_frame(data: bytes) -> list[Message]:
    import struct

    (count,) = struct.unpack_from("<I", data, 0)
    off = 4
    out = []
    for _ in range(count):
        (ln,) = struct.unpack_from("<I", data, off)
        off += 4
        out.append(unmarshal_message(data[off : off + ln]))
        off += ln
    if off != len(data):
        raise ValueError(f"trailing bytes in frame: {len(data) - off}")
    return out


# --------------------------------------------------------------------------
# columnar frame codec — one native call per frame (the fused bridge's fast
# path; see native/raftpb_codec.cc frame_marshal/frame_unmarshal). `cols` is
# a dict of numpy arrays:
#   scalars  [K, 11] u64   (msg_marshal slot order; [10] = has_snapshot)
#   ctx      [K]     i64   int ticket, 0 = absent (-1 on unpack = foreign)
#   n_ents   [K]     i32
#   ent_scalars [sum, 3] u64  (type, term, index)
#   ent_lens [sum]   i64   (-1 = nil data)
#   ent_data bytes blob (concatenated payloads)
#   snap_meta [K, 3] u64   (index, term, auto_leave; read when has_snapshot)
#   snap_counts [K, 4] i32
#   snap_ids [sum]  u64


def _frame_lib():
    lib = _lib()
    if not getattr(lib, "_frame_bound", False):
        lib.frame_marshal.restype = ctypes.c_int64
        lib.frame_unmarshal.restype = ctypes.c_int64
        lib._frame_bound = True
    return lib


def pack_frame_cols(cols) -> bytes:
    lib = _frame_lib()
    k = int(cols["scalars"].shape[0])
    scalars = _u64(cols["scalars"]).reshape(-1)
    ctx = np.ascontiguousarray(cols["ctx"], dtype=np.int64)
    n_ents = np.ascontiguousarray(cols["n_ents"], dtype=np.int32)
    ent_scalars = _u64(cols.get("ent_scalars", np.zeros((0, 3)))).reshape(-1)
    ent_lens = np.ascontiguousarray(
        cols.get("ent_lens", np.zeros(0)), dtype=np.int64
    )
    ent_data = bytes(cols.get("ent_data", b""))
    snap_meta = _u64(cols.get("snap_meta", np.zeros((k, 3)))).reshape(-1)
    snap_counts = np.ascontiguousarray(
        cols.get("snap_counts", np.zeros((k, 4))), dtype=np.int32
    ).reshape(-1)
    snap_ids = _u64(cols.get("snap_ids", np.zeros(1)))
    if snap_ids.size == 0:
        snap_ids = _u64([0])
    if ent_scalars.size == 0:
        ent_scalars = _u64([0])
    if ent_lens.size == 0:
        ent_lens = np.zeros(1, np.int64)
    cap = 4 + k * 300 + 2 * len(ent_data) + 64
    while True:
        out = np.zeros(cap, np.uint8)
        n = lib.frame_marshal(
            ctypes.c_int32(k),
            scalars.ctypes.data_as(ctypes.c_void_p),
            ctx.ctypes.data_as(ctypes.c_void_p),
            n_ents.ctypes.data_as(ctypes.c_void_p),
            ent_scalars.ctypes.data_as(ctypes.c_void_p),
            ent_lens.ctypes.data_as(ctypes.c_void_p),
            ent_data,
            snap_meta.ctypes.data_as(ctypes.c_void_p),
            snap_counts.ctypes.data_as(ctypes.c_void_p),
            snap_ids.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int64(cap),
        )
        if n >= 0:
            return out[:n].tobytes()
        cap = int(-n)


def unpack_frame_cols(data: bytes) -> dict:
    lib = _frame_lib()
    max_msgs = len(data) // 6 + 8
    max_ents = len(data) // 2 + 8
    scalars = np.zeros((max_msgs, _N_SCALARS), np.uint64)
    ctx = np.zeros(max_msgs, np.int64)
    n_ents = np.zeros(max_msgs, np.int32)
    ent_scalars = np.zeros((max_ents, 3), np.uint64)
    ent_lens = np.zeros(max_ents, np.int64)
    ent_data = np.zeros(max(1, len(data)), np.uint8)
    snap_meta = np.zeros((max_msgs, 3), np.uint64)
    snap_counts = np.zeros((max_msgs, 4), np.int32)
    rc = lib.frame_unmarshal(
        data, ctypes.c_int64(len(data)),
        ctypes.c_int32(max_msgs), ctypes.c_int32(max_ents),
        ctypes.c_int64(ent_data.size), ctypes.c_int32(len(data) // 2 + 16),
        scalars.ctypes.data_as(ctypes.c_void_p),
        ctx.ctypes.data_as(ctypes.c_void_p),
        n_ents.ctypes.data_as(ctypes.c_void_p),
        ent_scalars.ctypes.data_as(ctypes.c_void_p),
        ent_lens.ctypes.data_as(ctypes.c_void_p),
        ent_data.ctypes.data_as(ctypes.c_void_p),
        snap_meta.ctypes.data_as(ctypes.c_void_p),
        snap_counts.ctypes.data_as(ctypes.c_void_p),
    )
    if rc < 0:
        raise ValueError(f"frame_unmarshal failed: {rc}")
    k = int(rc)
    tot = int(n_ents[:k].sum())
    return dict(
        scalars=scalars[:k],
        ctx=ctx[:k],
        n_ents=n_ents[:k],
        ent_scalars=ent_scalars[:tot],
        ent_lens=ent_lens[:tot],
        ent_data=ent_data,
        snap_meta=snap_meta[:k],
        snap_counts=snap_counts[:k],
    )


def unmarshal_message(data: bytes, max_entries: int | None = None,
                      max_responses: int | None = None) -> Message:
    lib = _lib()
    # size-derived capacities: every entry/response costs >= 2 wire bytes and
    # every ConfState id >= 2, so these bounds admit any well-formed input
    if max_entries is None:
        max_entries = len(data) // 2 + 8
    if max_responses is None:
        max_responses = len(data) // 2 + 8
    scalars = np.zeros(_N_SCALARS, np.uint64)
    context = np.zeros(max(64, len(data)), np.uint8)
    context_len = ctypes.c_int64(-1)
    n_entries = ctypes.c_int32(0)
    ent_scalars = np.zeros(max_entries * 3, np.uint64)
    ent_lens = np.zeros(max_entries, np.int64)
    ent_data = np.zeros(max(1, len(data)), np.uint8)
    snap_meta = np.zeros(3, np.uint64)
    snap_data = np.zeros(max(1, len(data)), np.uint8)
    snap_data_len = ctypes.c_int64(-1)
    snap_counts = np.zeros(4, np.int32)
    snap_ids = np.zeros(len(data) // 2 + 16, np.uint64)
    n_resp = ctypes.c_int32(0)
    resp_scalars = np.zeros(max_responses * _N_SCALARS, np.uint64)

    rc = lib.msg_unmarshal(
        data, ctypes.c_int64(len(data)),
        scalars.ctypes.data_as(ctypes.c_void_p),
        context.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(context.size),
        ctypes.byref(context_len),
        ctypes.byref(n_entries), ctypes.c_int32(max_entries),
        ent_scalars.ctypes.data_as(ctypes.c_void_p),
        ent_lens.ctypes.data_as(ctypes.c_void_p),
        ent_data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(ent_data.size),
        snap_meta.ctypes.data_as(ctypes.c_void_p),
        snap_data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(snap_data.size),
        ctypes.byref(snap_data_len),
        snap_counts.ctypes.data_as(ctypes.c_void_p),
        snap_ids.ctypes.data_as(ctypes.c_void_p), ctypes.c_int32(snap_ids.size),
        ctypes.byref(n_resp), ctypes.c_int32(max_responses),
        resp_scalars.ctypes.data_as(ctypes.c_void_p),
    )
    if rc != 0:
        raise ValueError(f"unmarshal failed: {rc}")

    def mk(sc) -> Message:
        return Message(
            type=int(sc[0]), to=int(sc[1]), frm=int(sc[2]), term=int(sc[3]),
            log_term=int(sc[4]), index=int(sc[5]), commit=int(sc[6]),
            reject=bool(sc[7]), reject_hint=int(sc[8]),
        )

    m = mk(scalars)
    m.vote = int(scalars[9])
    if context_len.value == 8:
        # 8 bytes is the engine's own ticket convention — but only values
        # inside the device's i32 ticket range are engine tickets; an
        # 8-byte FOREIGN id >= 2^31 stays raw bytes (interned at the
        # engine boundary) instead of overflowing the context column
        v = int.from_bytes(context[:8].tobytes(), "big")
        m.context = v if v < 2**31 else context[:8].tobytes()
    elif context_len.value >= 0:
        # foreign context: keep raw bytes (re-marshal emits them verbatim)
        m.context = context[: context_len.value].tobytes()
    off = 0
    for i in range(n_entries.value):
        dl = int(ent_lens[i])
        # dl < 0 = the field was absent (Go nil Data) — preserved as None so
        # re-marshal stays byte-exact (marshal maps None back to absent)
        d = ent_data[off : off + dl].tobytes() if dl >= 0 else None
        if dl > 0:
            off += dl
        m.entries.append(
            Entry(
                type=int(ent_scalars[i * 3]), term=int(ent_scalars[i * 3 + 1]),
                index=int(ent_scalars[i * 3 + 2]), data=d,
            )
        )
    if scalars[10]:
        nv, nl, no, nn = (int(x) for x in snap_counts)
        ids = [int(x) for x in snap_ids[: nv + nl + no + nn]]
        m.snapshot = Snapshot(
            index=int(snap_meta[0]), term=int(snap_meta[1]),
            data=snap_data[: max(0, snap_data_len.value)].tobytes(),
            voters=tuple(ids[:nv]),
            learners=tuple(ids[nv : nv + nl]),
            voters_outgoing=tuple(ids[nv + nl : nv + nl + no]),
            learners_next=tuple(ids[nv + nl + no :]),
            auto_leave=bool(snap_meta[2]),
        )
    resps = []
    for r in range(n_resp.value):
        sc = resp_scalars[r * _N_SCALARS : (r + 1) * _N_SCALARS]
        rm = mk(sc)
        rm.vote = int(sc[9])
        resps.append(rm)
    if resps:
        m.responses = resps
    return m

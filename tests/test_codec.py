"""C++ raftpb wire codec: golden bytes (hand-computed against the gogoproto
rules of raftpb/raft.pb.go) and round-trips."""

import pytest

from raft_tpu.api.rawnode import Entry, Message, Snapshot
from raft_tpu.types import MessageType as MT



def test_msgapp_golden_bytes():
    from raft_tpu.runtime.codec import marshal_message

    m = Message(
        type=int(MT.MSG_APP), to=2, frm=1, term=5, log_term=4, index=10,
        commit=9,
        entries=[Entry(term=5, index=11, type=0, data=b"ab")],
    )
    want = bytes.fromhex(
        "0803" "1002" "1801" "2005" "2804" "300a"
        "3a0a" "0800" "1005" "180b" "2202" "6162"
        "4009" "5000" "5800" "6800"
    )
    assert marshal_message(m) == want


def test_roundtrip_plain():
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(
        type=int(MT.MSG_APP_RESP), to=1, frm=3, term=7, log_term=2, index=42,
        commit=40, reject=True, reject_hint=17, vote=0,
    )
    got = unmarshal_message(marshal_message(m))
    assert got == m


def test_roundtrip_entries_and_context():
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(
        type=int(MT.MSG_APP), to=2, frm=1, term=3, index=5, commit=4,
        context=12345,
        entries=[
            Entry(term=3, index=6, type=0, data=b"hello"),
            Entry(term=3, index=7, type=1, data=b""),
            Entry(term=3, index=8, type=2, data=b"\x00\x01\x02"),
        ],
    )
    got = unmarshal_message(marshal_message(m))
    assert got.context == 12345
    assert [(e.term, e.index, e.type, e.data) for e in got.entries] == [
        (3, 6, 0, b"hello"), (3, 7, 1, b""), (3, 8, 2, b"\x00\x01\x02"),
    ]


def test_roundtrip_snapshot():
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(
        type=int(MT.MSG_SNAP), to=3, frm=1, term=9,
        snapshot=Snapshot(
            index=100, term=8, data=b"state-bytes",
            voters=(1, 2, 3), learners=(4,),
            voters_outgoing=(1, 2, 5), learners_next=(6,),
            auto_leave=True,
        ),
    )
    got = unmarshal_message(marshal_message(m))
    s = got.snapshot
    assert (s.index, s.term, s.data) == (100, 8, b"state-bytes")
    assert s.voters == (1, 2, 3) and s.learners == (4,)
    assert s.voters_outgoing == (1, 2, 5) and s.learners_next == (6,)
    assert s.auto_leave is True


def test_roundtrip_storage_append_with_responses():
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(
        type=int(MT.MSG_STORAGE_APPEND), to=0, frm=1, term=4, vote=2,
        commit=3,
        entries=[Entry(term=4, index=9, data=b"x")],
        responses=[
            Message(type=int(MT.MSG_APP_RESP), to=2, frm=1, term=4, index=9),
            Message(type=int(MT.MSG_STORAGE_APPEND_RESP), to=1, frm=1,
                    term=4, index=9, log_term=4),
        ],
    )
    got = unmarshal_message(marshal_message(m))
    assert got.vote == 2 and len(got.responses) == 2
    assert got.responses[0].type == int(MT.MSG_APP_RESP)
    assert got.responses[1].log_term == 4


def test_large_varints():
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(type=int(MT.MSG_HEARTBEAT), to=2**31, frm=2**40, term=2**62,
                commit=2**33 + 7)
    got = unmarshal_message(marshal_message(m))
    assert (got.to, got.frm, got.term, got.commit) == (
        2**31, 2**40, 2**62, 2**33 + 7
    )


def test_malformed_inputs_rejected_not_crashed():
    """Truncated/corrupted buffers must fail cleanly (negative rc ->
    ValueError), never read out of bounds (the codec parses network
    input)."""
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(
        type=int(MT.MSG_SNAP), to=3, frm=1, term=9,
        snapshot=Snapshot(index=100, term=8, data=b"s" * 40,
                          voters=(1, 2, 3), learners=(4,)),
        entries=[Entry(term=9, index=1, data=b"abc")],
    )
    wire = marshal_message(m)
    # every truncation either parses to some prefix-message or raises
    for cut in range(len(wire)):
        try:
            unmarshal_message(wire[:cut])
        except ValueError:
            pass
    # corrupt each byte; must never crash the process
    for i in range(len(wire)):
        bad = bytearray(wire)
        bad[i] ^= 0xFF
        try:
            unmarshal_message(bytes(bad))
        except ValueError:
            pass


def test_unknown_fields_skipped_everywhere():
    """proto2 forward compatibility: unknown fields at the top level and
    inside Snapshot/metadata must be skipped, not rejected."""
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    def varint(v):
        out = b""
        while v >= 0x80:
            out += bytes([v & 0x7F | 0x80])
            v >>= 7
        return out + bytes([v])

    m = Message(type=int(MT.MSG_SNAP), to=2, frm=1, term=3,
                snapshot=Snapshot(index=5, term=2, voters=(1, 2)))
    wire = marshal_message(m)
    # append unknown top-level field 99 (varint), field 100 (bytes), and
    # field 101 (fixed64)
    wire += varint(99 << 3 | 0) + varint(7)
    wire += varint(100 << 3 | 2) + varint(3) + b"\x01\x02\x03"
    wire += varint(101 << 3 | 1) + b"\x00" * 8
    got = unmarshal_message(wire)
    assert got.snapshot.index == 5 and got.snapshot.voters == (1, 2)


def test_nil_vs_empty_entry_data_byte_stable():
    """A Go-origin entry with nil Data (no field 4 on the wire, e.g. the
    leader's empty entry) must re-marshal byte-identically — nil survives
    unmarshal as data=None (marshal's -1 convention)."""
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    m = Message(
        type=int(MT.MSG_APP), to=2, frm=1, term=5, log_term=4, index=10,
        entries=[
            Entry(term=5, index=11, data=None),   # nil Data
            Entry(term=5, index=12, data=b""),    # present-but-empty Data
            Entry(term=5, index=13, data=b"x"),
        ],
    )
    wire = marshal_message(m)
    got = unmarshal_message(wire)
    assert got.entries[0].data is None
    assert got.entries[1].data == b""
    assert got.entries[2].data == b"x"
    assert marshal_message(got) == wire


def test_foreign_context_byte_stable():
    """Contexts that are not the engine's 8-byte int ticket (e.g. etcd
    ReadIndex ids) round-trip as raw bytes, byte-stably."""
    from raft_tpu.runtime.codec import marshal_message, unmarshal_message

    for ctx in (b"a", b"etcd-readindex-id-123", b"\x00" * 3, b""):
        m = Message(type=int(MT.MSG_READ_INDEX), to=1, frm=2, context=ctx)
        wire = marshal_message(m)
        got = unmarshal_message(wire)
        assert got.context == ctx
        assert marshal_message(got) == wire
    # the engine's own int tickets still come back as ints
    m = Message(type=int(MT.MSG_READ_INDEX), to=1, frm=2, context=77)
    wire = marshal_message(m)
    got = unmarshal_message(wire)
    assert got.context == 77
    assert marshal_message(got) == wire


def test_foreign_context_through_engine_readindex():
    """A bytes context stepped into the engine surfaces back out (ReadState)
    as the original bytes — interned to a device ticket only in between."""
    from tests.test_rawnode import drive, make_group

    b = make_group(3)
    b.campaign(0)
    drive(b)
    assert b.basic_status(0)["raft_state"] == "LEADER"
    ctx = b"foreign-ctx-not-8b"
    b.read_index(0, ctx)
    seen = []
    for _ in range(20):
        moved = False
        for lane in range(3):
            if not b.has_ready(lane):
                continue
            rd = b.ready(lane)
            b.advance(lane)
            seen += rd.read_states
            for m in rd.messages:
                if 0 <= m.to - 1 < 3:
                    b.step(m.to - 1, m)
            moved = True
        if seen or not moved:
            break
    assert any(rs.request_ctx == ctx for rs in seen)

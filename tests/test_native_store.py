"""Native (C++) payload-arena tests, cross-checked against the pure-Python
EntryStore on identical op sequences."""

import random

import pytest

from raft_tpu.api.rawnode import Entry, EntryStore
from raft_tpu.runtime import native
from raft_tpu.runtime.native import make_payload_store


def test_build_is_stamped_with_the_sources_it_was_built_from():
    # a library copied in from elsewhere (or built from other sources) is
    # not trusted: only a matching source stamp skips the rebuild
    native.build()
    with open(native._STAMP) as f:
        assert f.read().strip() == native._sources_digest()
    mtime = native.os.path.getmtime(native._SO)
    native.build()
    assert native.os.path.getmtime(native._SO) == mtime
    with open(native._STAMP, "w") as f:
        f.write("stale\n")
    native.build()
    with open(native._STAMP) as f:
        assert f.read().strip() == native._sources_digest()


def test_failed_build_raises(monkeypatch):
    monkeypatch.setattr(native, "_SRCS", native._SRCS)
    monkeypatch.setattr(native, "_DIR", "/nonexistent-native-dir")
    with pytest.raises((RuntimeError, OSError)):
        native.build(force=True)


def test_basic_roundtrip():
    s = make_payload_store(2)
    s.put(0, Entry(term=1, index=1, type=0, data=b"a"))
    s.put(1, Entry(term=3, index=1, type=2, data=b"bb"))
    assert s.get(0, 1, 1) == (0, b"a")
    assert s.get(1, 1, 3) == (2, b"bb")
    assert s.get(0, 1, 9) == (0, b"")  # term mismatch (ABA guard)
    assert s.get(0, 7, 0) == (0, b"")


def test_truncate_and_compact():
    s = make_payload_store(1)
    for i in range(1, 11):
        s.put(0, Entry(term=1, index=i, data=bytes([i])))
    s.truncate_from(0, 8)
    assert s.get(0, 8, 1) == (0, b"")
    assert s.get(0, 7, 1) == (0, b"\x07")
    s.compact_below(0, 5)
    assert s.get(0, 4, 1) == (0, b"")
    assert s.get(0, 5, 1) == (0, b"\x05")
    assert s.total_bytes() == 3  # indexes 5, 6, 7


def test_overwrite_same_index():
    s = make_payload_store(1)
    s.put(0, Entry(term=1, index=1, data=b"old"))
    s.put(0, Entry(term=2, index=1, data=b"new"))
    assert s.get(0, 1, 2) == (0, b"new")
    assert s.get(0, 1, 1) == (0, b"")
    assert s.total_bytes() == 3


def test_get_batch():
    s = make_payload_store(3)
    s.put(0, Entry(term=1, index=1, data=b"xx"))
    s.put(2, Entry(term=4, index=9, data=b"yyy"))
    payload, offs, lens, types = s.get_batch([0, 2, 1], [1, 9, 1], [1, 4, 0])
    assert lens.tolist() == [2, 3, -1]
    assert payload == b"xxyyy"
    assert payload[offs[1] : offs[1] + lens[1]] == b"yyy"


def test_fuzz_against_python_store():
    rng = random.Random(11)
    nat, ref = make_payload_store(4), EntryStore(4)
    for _ in range(3000):
        op = rng.random()
        lane = rng.randrange(4)
        if op < 0.6:
            e = Entry(
                term=rng.randrange(1, 5),
                index=rng.randrange(1, 50),
                type=rng.randrange(3),
                data=bytes(rng.randrange(0, 16)),
            )
            nat.put(lane, e)
            ref.put(lane, e)
        elif op < 0.8:
            i = rng.randrange(1, 50)
            nat.truncate_from(lane, i)
            ref.truncate_from(lane, i)
        else:
            i = rng.randrange(1, 50)
            nat.compact_below(lane, i)
            ref.compact_below(lane, i)
        # random probes
        for _ in range(3):
            li, ii, ti = rng.randrange(4), rng.randrange(1, 50), rng.randrange(0, 5)
            assert nat.get(li, ii, ti) == ref.get(li, ii, ti)

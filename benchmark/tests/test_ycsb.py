"""The request stream: YCSB's hash and Zipfian draw, and the same work
for every seed."""

from __future__ import annotations

import numpy as np

import ycsb


def fnv64_scalar(v: int) -> int:
    """YCSB's Utils.fnvhash64, one long at a time, in plain integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) & (2**64 - 1)
        v >>= 8
    signed = h - 2**64 if h >= 2**63 else h
    return abs(signed)


def test_fnv64_matches_the_scalar_definition():
    vals = np.array([0, 1, 255, 256, 12345678901, ycsb.ITEM_COUNT - 1])
    assert ycsb.fnv64(vals).tolist() == [fnv64_scalar(int(v)) for v in vals]


def test_zipfian_head_has_the_published_share():
    rng = np.random.default_rng(0)
    ranks = ycsb.zipfian(rng, 400_000, 0.99)
    share0 = np.mean(ranks == 0)
    assert abs(share0 - 1 / ycsb.ZETAN) < 0.002
    assert np.mean(ranks == 1) < share0


def test_every_seed_offers_the_same_work():
    a = ycsb.requests(1, 1, 200, 20, 0.5, 1 << 20, 0.99)
    b = ycsb.requests(2**31 + 5, 1, 200, 20, 0.5, 1 << 20, 0.99)
    assert a.due.size == b.due.size == 4000
    assert a.is_read.sum() == b.is_read.sum() == 2000
    assert np.all(np.diff(a.due) >= 0) and a.due.max() < 20
    assert not np.array_equal(a.record, b.record)
    again = ycsb.requests(1, 1, 200, 20, 0.5, 1 << 20, 0.99)
    assert np.array_equal(a.record, again.record)

"""YCSB's request stream, drawn from a seed: the scrambled Zipfian key
chooser of YCSB's CoreWorkload (`requestdistribution=zipfian`), a
read/update mix, and open-loop Poisson arrivals at a fixed rate.

The key chooser follows YCSB's `ScrambledZipfianGenerator`: a Zipfian
draw (Gray et al., "Quickly Generating Billion-Record Synthetic
Databases", as in YCSB's `ZipfianGenerator`) over YCSB's fixed item space
of 10^10 with its precomputed zeta, hashed with 64-bit FNV-1a and reduced
modulo the record count, so the hot records are spread over the keyspace.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# YCSB ScrambledZipfianGenerator: ITEM_COUNT and ZETAN for theta 0.99
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta))


def zipfian(rng: np.random.Generator, size: int, theta: float,
            items: int = ITEM_COUNT, zetan: float = ZETAN) -> np.ndarray:
    """Zipfian ranks in [0, items), rank 0 the most popular."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = zeta(2, theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ranks = (items * (eta * u - eta + 1) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1 + 0.5 ** theta, 1, ranks)
    ranks = np.where(uz < 1.0, 0, ranks)
    return np.minimum(ranks, items - 1)


def fnv64(values: np.ndarray) -> np.ndarray:
    """YCSB's Utils.fnvhash64 (FNV-1a over the 8 bytes of a long, then
    the absolute value as a signed long)."""
    h = np.full(values.shape, FNV_OFFSET, dtype=np.uint64)
    v = values.astype(np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipfian(rng, size: int, records: int, theta: float) -> np.ndarray:
    return fnv64(zipfian(rng, size, theta)) % records


@dataclass
class Requests:
    due: np.ndarray  # seconds after the start, ascending
    is_read: np.ndarray  # bool
    record: np.ndarray  # int64 record numbers


def requests(seed: int, stream: int, rate: float, seconds: float,
             read_share: float, records: int, theta: float) -> Requests:
    """Open-loop Poisson arrivals at `rate` per second for `seconds`, a
    `read_share` of them reads, on scrambled-Zipfian records. Every seed
    offers the same work: exactly rate x seconds arrivals (a Poisson
    process given its count has uniform arrival times) and exactly
    read_share of them reads, in an order and on records drawn from the
    seed. `stream` separates independent streams of one seed (warm-up,
    window)."""
    rng = np.random.default_rng([seed, stream])
    n = int(round(rate * seconds))
    reads = np.zeros(n, bool)
    reads[: int(round(read_share * n))] = True
    return Requests(
        due=np.sort(rng.uniform(0.0, seconds, n)),
        is_read=rng.permutation(reads),
        record=scrambled_zipfian(rng, n, records, theta),
    )


def key(record: int) -> str:
    """YCSB's key naming (`user` + the record number)."""
    return f"user{record}"

"""Sparse op injection: one round's RoundOps record (the lanes the
coalescer touched) expanded into the per-block LocalOps on the device, in
one dispatch.

A served round touches about a thousand lanes of a fleet of millions, so
the host never builds the [N] op columns. It splits the record by block
(`lane // lanes_per_block`), pads every block's entries to one
power-of-two capacity C (at least MIN_CAPACITY, at most the block's lane
count) and makes ONE host->device copy of a [K, C, 4] int32 array: the
block-local lane, prop_n, prop_bytes and read_ctx. Pad entries carry the
out-of-range lane `lanes_per_block`, and the scatter drops them. One
jitted program scatters the entries into zeros and returns each block's
prop_n, prop_bytes and read_ctx columns; the five columns a served round
never sets (hup, transfer_to, forget, prop_cc, beat) are each block's
cached zero ops, which the round program never donates.

The result equals make_local_ops + prepare_ops on the same lanes, column
for column, in shape, dtype and placement, so the round program runs the
executable it already compiled. The capacity adapts to the busiest
block's count. The expander compiles every capacity when it is made: a
burst after a slow round needs a capacity the rounds before it did not,
and a compile inside a served round would stall it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MIN_CAPACITY = 64


def capacities(lanes_per_block: int) -> list[int]:
    """Every capacity: the powers of two from MIN_CAPACITY, capped at the
    block's lane count, which always holds a block's distinct lanes."""
    out = [MIN_CAPACITY]
    while out[-1] < lanes_per_block:
        out.append(out[-1] * 2)
    return [min(c, lanes_per_block) for c in out]


def capacity(count: int, lanes_per_block: int) -> int:
    """The least capacity that holds `count` entries."""
    return next(c for c in capacities(lanes_per_block) if c >= count)


def pack_blocks(rec, k: int, lanes_per_block: int) -> np.ndarray:
    """[K, C, 4] int32: per block, its entries' (local lane, prop_n,
    prop_bytes, read_ctx) in record order, then pads whose lane is
    `lanes_per_block`."""
    blk = rec.lane // lanes_per_block
    counts = np.bincount(blk, minlength=k)
    order = np.argsort(blk, kind="stable")
    b = blk[order]
    pos = np.arange(b.size) - (np.cumsum(counts) - counts)[b]
    out = np.zeros((k, capacity(int(counts.max()), lanes_per_block), 4),
                   np.int32)
    out[:, :, 0] = lanes_per_block
    out[b, pos, 0] = rec.lane[order] - b * lanes_per_block
    out[b, pos, 1] = rec.prop_n[order]
    out[b, pos, 2] = rec.prop_bytes[order]
    out[b, pos, 3] = rec.read_ctx[order]
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _expand(packed, lanes_per_block: int):
    """K tuples (prop_n, prop_bytes, read_ctx) of [lanes_per_block] int32."""
    k = packed.shape[0]
    rows = jnp.arange(k)[:, None]
    lane = packed[:, :, 0]
    cols = [
        jnp.zeros((k, lanes_per_block), jnp.int32)
        .at[rows, lane]
        .set(packed[:, :, j], mode="drop")
        for j in (1, 2, 3)
    ]
    return tuple(tuple(c[i] for c in cols) for i in range(k))


class OpExpander:
    """Expands a RoundOps record into K per-block LocalOps.

    zero_ops: each block's cached all-zero LocalOps (FusedCluster._no_ops),
    in block order. Counts what it expanded: `lanes_injected` (lanes
    carrying an op, summed over rounds) and `capacity` (the C of the last
    expansion, a gauge)."""

    def __init__(self, zero_ops: list, lanes_per_block: int):
        self.zero_ops = list(zero_ops)
        self.k = len(self.zero_ops)
        self.lanes_per_block = lanes_per_block
        self.lanes_injected = 0
        self.capacity = 0
        for c in capacities(lanes_per_block):
            _expand(np.full((self.k, c, 4), lanes_per_block, np.int32),
                    lanes_per_block)

    def expand(self, rec) -> list:
        packed = pack_blocks(rec, self.k, self.lanes_per_block)
        self.lanes_injected += int(rec.lane.size)
        self.capacity = packed.shape[1]
        cols = _expand(packed, self.lanes_per_block)
        return [
            z._replace(prop_n=pn, prop_bytes=pb, read_ctx=rc)
            for z, (pn, pb, rc) in zip(self.zero_ops, cols)
        ]

"""One-hot gather/scatter/sort primitives for small index domains.

TPU (and the remote-TPU backend this engine benches on) pays a steep price for
dynamic gather/scatter HLOs — each lowers to a serialized memory op — while
compare+select+reduce chains run at full VPU rate and fuse. Every index domain
in this engine is small and static (log window W, peer slots V<=8, entries per
message E<=8, inflight ring F<=8, read slots R<=4), so indexed access is
re-expressed as one-hot arithmetic: build `idx == iota` masks and reduce.
A contiguous circular range of the log window is addressed by each slot's
offset from the range's start (`offsets`), computed once per range, not by
a rolled one-hot mask per entry.
This is the "masked lane-wise" style SURVEY §2.3/§7 prescribes; sorting uses a
fixed odd-even transposition network (quorum/majority.go:126-172's sort of
<=7 voters needs no general sort, per SURVEY §7 hard-parts).
"""

from __future__ import annotations

import jax.numpy as jnp

I32 = jnp.int32


def onehot(idx, size: int):
    """[...] int -> [..., size] bool, True where last-dim position == idx."""
    return idx[..., None] == jnp.arange(size, dtype=I32)


def argmax_last(x):
    """jnp.argmax over the (small, static) last axis, computed as an
    unrolled compare/select chain with first-max-wins tie-breaking —
    bit-identical to jnp.argmax(x, axis=-1) but without the argmax HLO,
    which Mosaic (Pallas TPU) only lowers for float32."""
    if x.dtype == jnp.bool_:
        x = x.astype(I32)
    best_v = x[..., 0]
    best_i = jnp.zeros(x.shape[:-1], I32)
    for j in range(1, x.shape[-1]):
        better = x[..., j] > best_v
        best_v = jnp.where(better, x[..., j], best_v)
        best_i = jnp.where(better, jnp.int32(j), best_i)
    return best_i


def cumsum_last(x):
    """jnp.cumsum over the (small, static) last axis as an unrolled add
    chain — Mosaic (Pallas TPU) has no cumsum lowering."""
    cols = [x[..., 0]]
    for j in range(1, x.shape[-1]):
        cols.append(cols[-1] + x[..., j])
    return jnp.stack(cols, axis=-1)


def gather(col, idx):
    """col [B..., W] indexed along its last axis by idx [B..., K...] -> idx's
    shape. col's batch dims B... must prefix idx's shape; any extra idx dims
    broadcast. Out-of-range indexes return 0 (callers mask separately)."""
    w = col.shape[-1]
    if col.dtype == jnp.bool_:
        return gather(col.astype(I32), idx).astype(jnp.bool_)
    ohm = onehot(idx, w)  # [B..., K..., W]
    extra = ohm.ndim - col.ndim
    c = col.reshape(col.shape[:-1] + (1,) * extra + (w,))
    return jnp.sum(jnp.where(ohm, c, 0), axis=-1)


def scatter_set(col, idx, vals, mask):
    """Masked one-hot scatter: col[..., idx[..., k]] = vals[..., k] where
    mask[..., k]; out-of-range idx drops. col [..., W]; idx/vals/mask [..., K].
    Duplicate in-mask indexes resolve to their sum (callers guarantee
    distinctness, as the reference's append paths do)."""
    w = col.shape[-1]
    oh = onehot(idx, w) & mask[..., None]  # [..., K, W]
    hit = oh.any(axis=-2)  # [..., W]
    val = jnp.sum(jnp.where(oh, vals[..., None], 0), axis=-2)
    return jnp.where(hit, val, col)


def offsets(start, w: int):
    """[...] slot -> [..., W]: each slot's offset from a run that starts at
    slot `start`, (w - start) mod W. A contiguous range of the circular
    window is addressed by this one compare-ready column instead of a
    rolled one-hot mask per entry."""
    s = jnp.arange(w, dtype=I32) - start[..., None]
    return s & (w - 1) if w & (w - 1) == 0 else s % w


def _rotate(x, r):
    """out[..., j] = x[..., (j - r) mod K] along the small last axis (K a
    power of two, r [...] in [0, K)): log2(K) static rolls of the [.., K]
    values, each kept where r has that bit. Never rolls a [.., W] array:
    XLA materializes every stage of such a barrel."""
    k = x.shape[-1]
    b = 1
    while b < k:
        x = jnp.where(((r & b) != 0)[..., None], jnp.roll(x, b, axis=-1), x)
        b <<= 1
    return x


def place(vals, start, w: int):
    """[..., K] run -> [..., W] in which each slot whose offset from `start`
    is below K holds its entry, vals[..., (slot - start) mod W] (K <= W, W a
    power of two); the other slots are don't-care.

    A window of a lane or more (W >= 128) and a run of 5 or more entries:
    the run, padded with zeros to a power of two P (which divides W), is
    rotated by start mod P on its small axis, then tiled W/P times, one
    select per slot. Otherwise K - 1 compares of the slot offsets, which
    the compiler's cost model puts below the tile's relayout there."""
    k = vals.shape[-1]
    p = 1 << (k - 1).bit_length()
    if w < 128 or p < 8:
        kw = offsets(start, w)
        out = vals[..., :1]
        for j in range(1, k):
            out = jnp.where(kw == j, vals[..., j : j + 1], out)
        return jnp.broadcast_to(out, kw.shape)
    if p > k:
        pad = jnp.zeros(vals.shape[:-1] + (p - k,), vals.dtype)
        vals = jnp.concatenate([vals, pad], axis=-1)
    rot = _rotate(vals, start & (p - 1))
    return jnp.tile(rot, (1,) * (rot.ndim - 1) + (w // p,))


def gather_range_multi(cols, start, e: int):
    """Contiguous circular gather over several same-shape columns at the
    SAME start: out[..., k] = col[..., (start + k) mod W] for k in [0, e).
    col [B..., W]; start [B...] or with extra dims that broadcast, as in
    `gather`. Each slot's offset from the start is computed once, and each
    output entry is one compare-and-reduce over it, shared by the columns
    (the log window's (term, type, bytes) triple always moves together)."""
    w = cols[0].shape[-1]
    kw = offsets(start, w)
    outs = []
    for col in cols:
        as_bool = col.dtype == jnp.bool_
        if as_bool:
            col = col.astype(I32)
        extra = kw.ndim - col.ndim
        c = col.reshape(col.shape[:-1] + (1,) * extra + (w,))
        out = jnp.stack(
            [jnp.sum(jnp.where(kw == k % w, c, 0), axis=-1) for k in range(e)],
            axis=-1,
        )
        outs.append(out.astype(jnp.bool_) if as_bool else out)
    return outs


def scatter_range_set_multi(cols, start, vals_list, mask):
    """Contiguous circular scatter over several same-shape columns at the
    SAME start: col[..., (start + k) mod W] = vals[..., k] where the entry
    is written. cols [..., W] (W a power of two); start [...]; vals
    [..., K], K <= W. `mask` is a [..., K] bool, or an integer count n [...]
    for the prefix k < n, which every append writes (one compare a slot).

    Each slot's offset from the start is computed once, the run is placed
    by `place` (padded with masked-off entries where K is not a power of
    two), and each column is read once and written once."""
    w = cols[0].shape[-1]
    k = vals_list[0].shape[-1]
    if k > w:
        raise ValueError(f"a run of {k} entries does not fit a window of {w}")
    count = None if mask.dtype == jnp.bool_ else mask
    kw = offsets(start, w)
    if count is not None:
        hit = kw < jnp.minimum(count, k)[..., None]
    else:
        hit = place(mask, start, w) & (kw < k)
    return [
        jnp.where(hit, place(v, start, w), col)
        for col, v in zip(cols, vals_list)
    ]


def sort_last(x, valid=None, pad=-1):
    """Ascending sort along the (small, static) last axis via an odd-even
    transposition network — elementwise min/max only, no sort HLO. Invalid
    slots are replaced by `pad` first."""
    v = x.shape[-1]
    if valid is not None:
        x = jnp.where(valid, x, pad)
    cols = [x[..., j] for j in range(v)]
    for rnd in range(v):
        start = rnd & 1
        for j in range(start, v - 1, 2):
            lo = jnp.minimum(cols[j], cols[j + 1])
            hi = jnp.maximum(cols[j], cols[j + 1])
            cols[j], cols[j + 1] = lo, hi
    return jnp.stack(cols, axis=-1)


def select_kth(sorted_x, k):
    """sorted_x [..., V], k [...] -> element at position k (clipped)."""
    v = sorted_x.shape[-1]
    kc = jnp.clip(k, 0, v - 1)
    return gather(sorted_x, kc)

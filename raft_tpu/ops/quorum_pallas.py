"""Pallas TPU kernel for the batched quorum commit-index reduction.

The reference computes a group's commit index by sorting <=9 acked indexes
and picking element n-(n/2+1) (quorum/majority.go:126-172); SURVEY §7 names
the batched form — "commit-index reduction at 1M x 7 with mixed masks/joint
configs" — as the make-or-break kernel and prescribes a fixed sorting
network. This module is that kernel: the sort is an odd-even transposition
network of elementwise min/max over [TILE] lane vectors (VPU-native, no
sort HLO, no gather), selection is a masked sum, and the joint-config form
fuses BOTH halves' reductions plus their min into one VMEM-resident pass —
zero intermediate HBM round-trips.

History: this kernel originally tiled its operands voter-major and paid a
full [N, V] -> [V, N] HBM relayout per operand before the grid even ran —
measured at 1M x 7 on a v5e-1 that relayout dominated (joint: XLA 2.49 ms
vs Pallas 5.77 ms, with ~0.1 ms of actual VPU reduction work), so the
dispatch defaulted to XLA. That relayout is gone: the kernels now read the
operands in their NATIVE lane-major [N, V] layout — [TILE, VPAD] blocks,
VPAD the 8-sublane int32 tile — and peel the V voter columns in VMEM,
where the shuffle is on-chip register traffic instead of an HBM round
trip. With the relayout eliminated the old argument for the XLA default is
obsolete, and `joint_committed_dispatch` routes joint configs to THIS
kernel by default (RAFT_TPU_QUORUM_PALLAS=0 restores XLA; outputs are
bit-identical either way, tests/test_quorum_pallas.py). A Mosaic lowering
failure raises; interpret mode is only ever chosen explicitly
(interpret=True, or RAFT_TPU_PALLAS_INTERPRET=1 on the CPU test rig).

For callers that can keep the quorum operands voter-major IN THEIR CARRY
(amortizing one layout change over many reductions), `pack_voter_major` +
`joint_committed_packed` expose the zero-relayout fast path: the packed
[VPAD, N_pad] operands feed a voter-major kernel directly and no per-call
layout work remains at all.

Note the fused round (ops/fused.py) does NOT call this dispatch: its
quorum math inlines as jnp inside the round body, where XLA fuses it into
neighboring phases — a pallas_call boundary there would break that fusion.
This kernel serves the standalone batched reduction (ops/quorum.py
callers operating outside the fused round).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu import config

I32 = jnp.int32
# plain int so kernels don't capture a traced constant
COMMITTED_INF = 2**31 - 1
_TILE = 1024
_VPAD = 8  # sublane tile for int32


def _sorted_cols(vals, v):
    """Odd-even transposition network over the leading (voter) axis of a
    list of [TILE] vectors; ascending."""
    cols = list(vals)
    for rnd in range(v):
        for j in range(rnd & 1, v - 1, 2):
            lo = jnp.minimum(cols[j], cols[j + 1])
            hi = jnp.maximum(cols[j], cols[j + 1])
            cols[j], cols[j + 1] = lo, hi
    return cols


def _reduce_half(match_cols, mask_cols, v):
    """One majority reduction over per-voter [TILE] vectors: returns
    ([TILE] committed, [TILE] n==0 flag). Layout-agnostic — the caller
    peels the voter vectors from whichever block layout it read."""
    rows = [
        jnp.where(mask_cols[j] != 0, match_cols[j], -1) for j in range(v)
    ]
    n = sum((mask_cols[j] != 0).astype(I32) for j in range(v))
    q = n // 2 + 1
    srt = _sorted_cols(rows, v)
    # element v - q of the ascending array (see quorum.py: V-n pad values of
    # -1 sort to the front, so position v-q == the reference's n-q)
    k = v - q  # [TILE]
    picked = jnp.zeros_like(srt[0])
    for j in range(v):
        picked = jnp.where(k == j, srt[j], picked)
    return picked, n == 0


def _lane_cols(ref, v):
    """Peel the V voter columns of a lane-major [TILE, VPAD] block into
    [TILE] vectors. This is the in-VMEM replacement for the old HBM
    [N, V] -> [V, N] relayout: the shuffle happens on-chip, per tile."""
    blk = ref[...]
    return [blk[:, j] for j in range(v)]


def _committed_kernel(match_ref, mask_ref, out_ref, *, v):
    picked, empty = _reduce_half(
        _lane_cols(match_ref, v), _lane_cols(mask_ref, v), v
    )
    out_ref[0, :] = jnp.where(empty, COMMITTED_INF, picked)


def _joint_kernel(match_ref, in_ref, out_m_ref, out_ref, *, v):
    m_cols = _lane_cols(match_ref, v)
    a, a_empty = _reduce_half(m_cols, _lane_cols(in_ref, v), v)
    b, b_empty = _reduce_half(m_cols, _lane_cols(out_m_ref, v), v)
    a = jnp.where(a_empty, COMMITTED_INF, a)
    b = jnp.where(b_empty, COMMITTED_INF, b)
    out_ref[0, :] = jnp.minimum(a, b)


def _vm_cols(ref, v):
    """Voter rows of a packed voter-major [VPAD, TILE] block."""
    return [ref[j, :] for j in range(v)]


def _joint_kernel_vm(match_ref, in_ref, out_m_ref, out_ref, *, v):
    m_cols = _vm_cols(match_ref, v)
    a, a_empty = _reduce_half(m_cols, _vm_cols(in_ref, v), v)
    b, b_empty = _reduce_half(m_cols, _vm_cols(out_m_ref, v), v)
    a = jnp.where(a_empty, COMMITTED_INF, a)
    b = jnp.where(b_empty, COMMITTED_INF, b)
    out_ref[0, :] = jnp.minimum(a, b)


def _pad_lanes(x, n_pad, v):
    """[N, V] -> [N_pad, VPAD] lane-major: a pure pad, layout-preserving —
    no transpose, no HBM relayout."""
    n = x.shape[0]
    return jnp.pad(x.astype(I32), ((0, n_pad - n), (0, _VPAD - v)))


def pack_voter_major(x):
    """[N, V] -> [VPAD, N_pad] voter-major, the ONE-TIME layout change for
    carries that feed joint_committed_packed many times. Padding with
    zeros is correct for both masks (0 = absent voter) and match values
    (masked before use)."""
    n, v = x.shape
    n_pad = -(-n // _TILE) * _TILE
    xt = jnp.swapaxes(x.astype(I32), 0, 1)
    return jnp.pad(xt, ((0, _VPAD - v), (0, n_pad - n)))


def _out_specs(n_pad):
    grid = (n_pad // _TILE,)
    return grid, pl.BlockSpec(
        (1, _TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def committed_pallas(match, mask, interpret: bool = False):
    """majority_committed on the Pallas path. match/mask: [N, V] -> [N],
    read lane-major (native layout, zero relayout)."""
    n, v = match.shape
    n_pad = -(-n // _TILE) * _TILE
    grid, out_spec = _out_specs(n_pad)
    spec = pl.BlockSpec(
        (_TILE, _VPAD), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(_committed_kernel, v=v),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), I32),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=out_spec,
        interpret=interpret,
    )(_pad_lanes(match, n_pad, v), _pad_lanes(mask, n_pad, v))
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def joint_committed_pallas(match, mask_in, mask_out, interpret: bool = False):
    """JointConfig.CommittedIndex fused: both halves + min in one pass,
    operands read lane-major (native layout, zero relayout)."""
    n, v = match.shape
    n_pad = -(-n // _TILE) * _TILE
    grid, out_spec = _out_specs(n_pad)
    spec = pl.BlockSpec(
        (_TILE, _VPAD), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(_joint_kernel, v=v),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), I32),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=out_spec,
        interpret=interpret,
    )(
        _pad_lanes(match, n_pad, v),
        _pad_lanes(mask_in, n_pad, v),
        _pad_lanes(mask_out, n_pad, v),
    )
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("v", "n", "interpret"))
def joint_committed_packed(
    match_vm, in_vm, out_vm, *, v: int, n: int,
    interpret: bool = False,
):
    """JointConfig.CommittedIndex over pre-packed voter-major operands
    (pack_voter_major): [VPAD, N_pad] x3 -> [n]. Zero per-call layout work
    — the fast path for carries that store the operands packed."""
    n_pad = match_vm.shape[1]
    grid, out_spec = _out_specs(n_pad)
    spec = pl.BlockSpec(
        (_VPAD, _TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(_joint_kernel_vm, v=v),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), I32),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=out_spec,
        interpret=interpret,
    )(match_vm, in_vm, out_vm)
    return out[0, :n]


def joint_committed_dispatch(
    match, mask_in, mask_out, *, engine: str | None = None,
    interpret: bool | None = None,
):
    """JointConfig.CommittedIndex, defaulting to the Pallas kernel now
    that the per-operand voter-major relayout is gone (module doc):
    engine kwarg > RAFT_TPU_QUORUM_PALLAS env (default 1) > pallas.
    RAFT_TPU_QUORUM_PALLAS=0 restores the XLA path. Outputs are
    bit-identical either way (tests/test_quorum_pallas.py). interpret=None
    reads RAFT_TPU_PALLAS_INTERPRET (default off); a kernel the backend
    cannot run raises."""
    e = engine
    if e is None:
        e = (
            "pallas"
            if config.env_flag("RAFT_TPU_QUORUM_PALLAS", default=True)
            else "xla"
        )
    if e not in ("xla", "pallas"):
        raise ValueError(f"unknown engine {e!r}: expected 'xla' or 'pallas'")
    from raft_tpu.ops.quorum import joint_committed

    if e == "xla":
        return joint_committed(match, mask_in, mask_out)
    if interpret is None:
        interpret = config.env_flag("RAFT_TPU_PALLAS_INTERPRET", default=False)
    return joint_committed_pallas(match, mask_in, mask_out, interpret=interpret)

"""VMEM-resident fused rounds: the `pallas` engine behind RAFT_TPU_ENGINE.

The round-5 profile shows the XLA fused round is HBM-bound at ~3 GB/round
moved — ~12x the one-read+one-write floor of the resident carry — because
XLA partitions the round into ~190 loop fusions that each re-read the
shared state arrays (benches/pallas_probe.py header, which this module
productionizes). The cure is the hand-fused-kernel pattern TPU serving
stacks reach for when XLA's fusion boundaries leave bandwidth on the
table: ONE Pallas kernel per group-aligned lane tile that reads every
slim-carry field into VMEM once, runs the round (route_fabric +
fused_round, unchanged jnp bodies), and writes the slim carry back once.

**The K-round megakernel.** `rounds_per_call` (K, env
RAFT_TPU_PALLAS_ROUNDS) fuses K rounds into each pallas_call: the tile's
state/fabric/metrics/chaos columns are read into VMEM once, K iterations
of route_fabric + fused_round run back-to-back with the carry resident in
VMEM (the inter-round slim<->fat casts are replayed in-register so the
trajectory stays bit-identical to K chained K=1 calls), and the tile is
written back once — eliminating K-1 HBM round-trips of the carry per
dispatch. The HBM<->VMEM tile in/out is double-buffered by Mosaic's grid
pipelining (the next tile's loads overlap the current tile's K rounds; no
manual DMA needed). An `n_rounds` that K does not divide dispatches a
second, remainder-sized megakernel after the scan of full-K calls.

Contract vs ops/fused.py fused_rounds:

- `pallas_rounds` mirrors fused_rounds' signature and return tuple
  (state, fab[, metrics][, chaos]) and is BIT-IDENTICAL to it per round
  at every K (asserted over >=33 rounds by tests/test_pallas_round.py in
  interpret mode; interpret=True is the CPU path — Mosaic only lowers on
  TPU).
- Tile invariant: `tile_lanes % v == 0` and `n % tile_lanes == 0`
  (TileError otherwise) — a raft group's voters never straddle a tile, so
  the in-tile shift router, aligned_peer_mute, and the chaos/metrics
  group reductions ([T] -> [T/v, v]) all hold within a tile.
- The metrics/chaos carries thread THROUGH the kernel: per-lane columns
  (latency sampler, fault knobs, recovery probe) tile like state; the
  lane-reduced scalars (counters/hist/lat_sum, recovery recounts) come
  back as PER-ROUND [K, n_tiles, 128] partials rows reduced OUTSIDE the
  call (metrics deltas sum over rounds and tiles — i32 wrap-add is
  associative, so the order change is exact; the chaos recounts are
  absolute, so only the LAST round's row lands in the carry). With
  `metrics=None` / `chaos=None` the partials output disappears and every
  plane op is elided from the trace exactly like the XLA path.
- The chaos PRNG is a pure function of GLOBAL (lane, round), so each tile
  passes `lane_offset = program_id * tile_lanes` into the chaos hooks
  (chaos/device.py _lane_edge) and the in-kernel round loop advances the
  absolute round counter — tiling and K are both invisible to the fault
  timeline.
- The trace plane's diff detection consumes per-round (pre, post)
  boundary states OUTSIDE the kernel (trace/device.py record_round),
  which a K-round megakernel does not export: trace-enabled runs route to
  K=1 (documented in README, asserted by tests) — same events, K-1 fewer
  fused round-trips forgone while the flight recorder is on.
- Donation composes like fused_rounds: `_pallas_rounds_jit` donates the
  (state, fab, metrics, chaos) carry; `_pallas_rounds_nodonate_jit` is
  the copying twin.
- Straddle sharding is NOT supported (groups must be shard- and
  tile-resident); parallel/sharded.py raises on pallas + straddle.

Engine selection lives in resolve_engine (RAFT_TPU_ENGINE env or the
`engine=` kwarg on FusedCluster / BlockedFusedCluster /
ShardedFusedCluster). There is no fallback: if Mosaic refuses the kernel
the dispatch raises with the compiler's message. On TPU today it does —
the round body's vector reshapes and rolls (e.g. `_route_shift_field`'s
1-D `jnp.roll`) are outside what Mosaic lowers, so this engine runs in
interpret mode only (ROADMAP C3).

Autotuner: `autotune_plan` sweeps (tile, K) jointly at first dispatch
(TPU only; sweeping interpret mode would time the interpreter), caching
the per-K tile winners under (shape, backend, K) and the overall (tile,
K) plan under (shape, backend), shared by every scheduler in the process.
RAFT_TPU_PALLAS_TILE pins the tile and RAFT_TPU_PALLAS_ROUNDS pins K
(each validated up front — validate_round_plan gives the clear error the
satellite demands instead of a mid-dispatch Mosaic failure);
RAFT_TPU_PALLAS_AUTOTUNE=0 skips every sweep (default_tile, K=1).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU memory spaces; absent on some CPU-only jaxlib builds
    from jax.experimental.pallas import tpu as pltpu

    _SMEM = pltpu.SMEM
except Exception:  # pragma: no cover - interpret mode works without SMEM
    pltpu = None
    _SMEM = None

from raft_tpu import config
from raft_tpu.chaos import device as chmod
from raft_tpu.metrics import device as metmod
from raft_tpu.ops import fused as fmod
from raft_tpu.ops import log as lgmod
from raft_tpu.ops import paged as pgmod
from raft_tpu.state import fat_state, is_packed, slim_state, unpack_state
from raft_tpu.trace import device as trmod

I32 = jnp.int32
U32 = jnp.uint32

ENGINES = ("xla", "pallas")

# Width of the per-(round, tile) partials row: one TPU lane register.
# Layout (i32):
#   [0 : C)          metrics counter deltas       (C = len(metmod.COUNTERS))
#   [C : C+B)        commit-latency hist deltas   (B = metmod.N_BUCKETS)
#   [C+B]            lat_sum delta
#   [X], [X+1]       chaos n_reelected / n_recommitted per-tile recounts
# where X = C+B+1 when metrics ride along, else 0. Deltas accumulate
# across tiles AND in-kernel rounds; the chaos recounts are absolute
# per-tile counts that sum exactly across tiles because tiles are
# group-aligned and the probe columns are group-uniform (chaos/device.py
# end_round) — only the last in-kernel round's row is consumed.
PARTIAL_WIDTH = 128

# In-kernel rounds are a Python loop the tracer unrolls: the Mosaic
# program grows ~linearly in K, so an unbounded K dies mid-compile on
# program/VMEM limits. Bound it where the knob is parsed, with a clear
# error (the RAFT_TPU_UNROLL treatment, ops/fused.py:388-394).
MAX_ROUNDS_PER_CALL = 64
# The scan unroll (RAFT_TPU_UNROLL) multiplies the in-kernel K: cap the
# product so the two knobs can't compose into an absurd program.
MAX_UNROLLED_ROUNDS = 256
# Joint autotune sweep set for K (tile candidates come from
# tile_candidates); kept small — the sweep compiles one program per pair.
ROUND_CANDIDATES = (1, 2, 4, 8)

# chaos per-lane columns that enter the kernel: host-set knobs (read-only
# in-kernel) then the recovery-probe columns (read-write, tiled outputs)
_CH_KNOBS = (
    "drop_num",
    "dup_num",
    "part_send",
    "part_recv",
    "tick_skew_num",
    "crash_at",
    "restart_at",
)
_CH_PROBE = ("base_committed", "reelect_round", "recommit_round")


class TileError(ValueError):
    """A lane tile that violates the group-alignment invariant. This is a
    configuration error."""


def resolve_engine(engine: str | None = None) -> str:
    """kwarg > RAFT_TPU_ENGINE env > "xla". Unknown names raise."""
    e = engine if engine is not None else config.env_raw("RAFT_TPU_ENGINE")
    e = (e or "xla").lower()
    if e not in ENGINES:
        raise ValueError(f"unknown engine {e!r}: expected one of {ENGINES}")
    return e


def default_interpret() -> bool:
    """Interpret mode is chosen, never inferred from the backend:
    RAFT_TPU_PALLAS_INTERPRET=1 (the CPU test rig sets it) or an explicit
    interpret= argument. Unset, kernels lower through Mosaic, and a
    backend that cannot run them fails loudly."""
    return config.env_flag("RAFT_TPU_PALLAS_INTERPRET", default=False)


def autotune_enabled() -> bool:
    return config.env_flag("RAFT_TPU_PALLAS_AUTOTUNE", default=True)


def env_rounds_per_call() -> int | None:
    """RAFT_TPU_PALLAS_ROUNDS: pin the megakernel K. None when unset;
    parse failures raise the same clear error shape as RAFT_TPU_UNROLL
    (ops/fused.py:388-394) instead of surfacing mid-dispatch."""
    raw = config.env_raw("RAFT_TPU_PALLAS_ROUNDS")
    if raw in (None, ""):
        return None
    try:
        k = int(raw)
    except ValueError:
        raise ValueError(
            f"RAFT_TPU_PALLAS_ROUNDS must be an integer >= 1, got {raw!r}"
        ) from None
    if k < 1:
        raise ValueError(
            f"RAFT_TPU_PALLAS_ROUNDS must be an integer >= 1, got {raw!r}"
        )
    return k


def validate_round_plan(
    rounds_per_call,
    *,
    unroll: int | None = None,
    round_chunk: int | None = None,
) -> None:
    """Up-front check of the RAFT_TPU_UNROLL x K x round_chunk
    composition: every failure mode here would otherwise surface as a
    mid-dispatch Mosaic program-size/VMEM error (or a silent per-chunk
    kernel-variant explosion on the blocked path) long after the knobs
    were set. Raise the clear error NOW, where the configuration is."""
    if not isinstance(rounds_per_call, int) or isinstance(
        rounds_per_call, bool
    ) or rounds_per_call < 1:
        raise ValueError(
            "rounds_per_call (RAFT_TPU_PALLAS_ROUNDS) must be an integer "
            f">= 1, got {rounds_per_call!r}"
        )
    if rounds_per_call > MAX_ROUNDS_PER_CALL:
        raise ValueError(
            f"rounds_per_call={rounds_per_call} exceeds "
            f"MAX_ROUNDS_PER_CALL={MAX_ROUNDS_PER_CALL}: the K in-kernel "
            "rounds are unrolled into the Mosaic program, so a huge K "
            "fails program/VMEM limits mid-compile; lower "
            "RAFT_TPU_PALLAS_ROUNDS"
        )
    if unroll is not None and unroll * rounds_per_call > MAX_UNROLLED_ROUNDS:
        raise ValueError(
            f"RAFT_TPU_UNROLL={unroll} x rounds_per_call={rounds_per_call} "
            f"= {unroll * rounds_per_call} unrolled rounds per dispatch "
            f"exceeds {MAX_UNROLLED_ROUNDS}: the scan unroll multiplies "
            "the in-kernel K; lower one of the two knobs"
        )
    if (
        round_chunk is not None
        and rounds_per_call > 1
        and round_chunk % rounds_per_call
    ):
        raise ValueError(
            f"round_chunk={round_chunk} is not a multiple of "
            f"rounds_per_call={rounds_per_call}: every blocked chunk would "
            "compile an extra remainder-tail kernel variant (one per "
            "distinct chunk size). Pick a K that divides round_chunk, or "
            "pin RAFT_TPU_PALLAS_ROUNDS=1"
        )


def check_tile(n: int, v: int, tile_lanes: int) -> None:
    """Enforce the tile invariant with a clear error (TileError)."""
    if tile_lanes < 1:
        raise TileError(f"tile_lanes={tile_lanes} must be >= 1")
    if tile_lanes % v:
        raise TileError(
            f"tile_lanes={tile_lanes} is not a multiple of v={v}: a raft "
            "group's voters must never straddle a lane tile (the in-tile "
            "router, aligned_peer_mute, and the chaos/metrics group "
            "reductions all reshape [T] -> [T/v, v])"
        )
    if n % tile_lanes:
        raise TileError(
            f"tile_lanes={tile_lanes} does not divide the lane count "
            f"n={n}: the tile grid must cover the batch exactly"
        )


def tile_candidates(n: int, v: int) -> list[int]:
    """Small sweep set for the autotuner: group-aligned powers-of-two
    sub-tiles plus the whole batch, every one dividing n."""
    cands = []
    for base in (256, 512, 1024, 2048, 4096):
        t = base * v
        if t < n and n % t == 0:
            cands.append(t)
    cands.append(n)
    return cands


def default_tile(n: int, v: int) -> int:
    """Largest candidate <= 1024*v (a VMEM-comfortable tile at the default
    Shape), else the smallest candidate."""
    cands = tile_candidates(n, v)
    best = None
    for t in cands:
        if t <= 1024 * v:
            best = t
    return best if best is not None else cands[0]


def shape_key(shape, backend: str, rounds: int | None = None) -> tuple:
    """Autotune cache key per (shape, backend[, K]): the 2-tuple form
    keys the overall plan/tile, the 3-tuple form (rounds=K) keys the
    per-K tile winners the joint sweep records."""
    try:
        dims = dataclasses.astuple(shape)
    except TypeError:  # pragma: no cover - non-dataclass shape stand-ins
        dims = tuple(sorted(vars(shape).items()))
    key = (dims, backend)
    if rounds is not None:
        key += (rounds,)
    return key


# winner tile per shape_key, shared process-wide (FusedCluster and the
# blocked/sharded schedulers all consult it before sweeping). Keys are
# (shape, backend) for the overall winner and (shape, backend, K) for the
# per-K winners the joint sweep also records.
_TILE_CACHE: dict[tuple, int] = {}
# overall (tile_lanes, rounds_per_call) winner per (shape, backend)
_PLAN_CACHE: dict[tuple, tuple[int, int]] = {}


def cached_tile(key: tuple) -> int | None:
    return _TILE_CACHE.get(key)


def remember_tile(key: tuple, tile_lanes: int) -> None:
    _TILE_CACHE[key] = tile_lanes


def cached_plan(key: tuple) -> tuple[int, int] | None:
    return _PLAN_CACHE.get(key)


def remember_plan(key: tuple, tile_lanes: int, rounds_per_call: int) -> None:
    _PLAN_CACHE[key] = (tile_lanes, rounds_per_call)


def autotune_tile(n: int, v: int, *, key: tuple, time_fn) -> int:
    """Sweep tile_candidates with the caller's `time_fn(tile) -> seconds`
    (warmed, post-compile) and cache the winner under `key`. Tile-only
    sweep for callers with a pinned K; autotune_plan is the joint form."""
    hit = cached_tile(key)
    if hit is not None:
        return hit
    best_t, best = None, None
    for t in tile_candidates(n, v):
        dt = time_fn(t)
        if best is None or dt < best:
            best, best_t = dt, t
    remember_tile(key, best_t)
    return best_t


def autotune_plan(
    n: int,
    v: int,
    *,
    key: tuple,
    time_fn,
    tiles=None,
    rounds=ROUND_CANDIDATES,
) -> tuple[int, int]:
    """Joint (tile, K) sweep with the caller's `time_fn(tile, k) ->
    seconds per ROUND` (warmed, post-compile). Caches the per-K tile
    winner under `key + (k,)` — the (shape, backend, K) contract — and
    the overall (tile, K) plan (plus its tile) under the plain `key`.
    `tiles` restricts the tile axis (a pinned RAFT_TPU_PALLAS_TILE still
    sweeps K)."""
    hit = cached_plan(key)
    if hit is not None:
        return hit
    tiles = tuple(tiles) if tiles is not None else tuple(tile_candidates(n, v))
    best = None  # (dt, tile, k)
    for k in rounds:
        validate_round_plan(k)
        best_k = None  # (dt, tile)
        for t in tiles:
            dt = time_fn(t, k)
            if best_k is None or dt < best_k[0]:
                best_k = (dt, t)
            if best is None or dt < best[0]:
                best = (dt, t, k)
        remember_tile(key + (k,), best_k[1])
    remember_tile(key, best[1])
    remember_plan(key, best[1], best[2])
    return best[1], best[2]


# --------------------------------------------------------------------------
# the engine


def pallas_rounds(
    state,
    fab,
    ops,
    mute,
    *,
    v: int,
    tile_lanes: int,
    n_rounds: int,
    rounds_per_call: int = 1,
    do_tick: bool = True,
    auto_propose: bool = False,
    auto_compact_lag: int | None = None,
    ops_first_round_only: bool = True,
    interpret: bool = False,
    paged_inkernel: bool = False,
    metrics=None,
    chaos=None,
    trace=None,
    trace_lane_offset=None,
    paged=None,
):
    """n_rounds fused rounds as a scan of K-round megakernel pallas_calls
    over group-aligned lane tiles (rounds_per_call = K), plus one
    remainder-sized call when K does not divide n_rounds. Same contract
    and bit-identical trajectories as ops/fused.py fused_rounds (minus
    straddle support) at every K — see module doc.

    trace: the flight-recorder carry rides the scan OUTSIDE the kernel —
    transition detection diffs the (pre, post) fat states each call
    exchanges with the scan body (trace/device.py record_round). Those
    boundary states only exist per round at K=1, so a trace-enabled run
    routes to rounds_per_call=1 (the kernel itself is unchanged, no VMEM
    growth, and the event stream is bit-identical to the XLA engine's by
    construction).

    paged: the paged entry log (ops/paged.py). Host-boundary mode
    (paged_inkernel=False) reconstructs the full [N, W] window BEFORE
    the kernel specs are built and re-splits after the scan, all inside
    this jit — the megakernel itself is untouched (it sees the same
    full-window tiles as ever), so K>1 bit-identity is structural; what
    the pool reduces is the between-dispatch resident carry, not
    in-kernel VMEM.

    paged_inkernel (RAFT_TPU_PAGED_INKERNEL, static): move the paging
    passes INTO the grid step. Each tile reads its resident-window
    columns plus ITS OWN slice of the pool ([P/n_tiles, PE] BlockSpecs,
    one segment-local trash row each) and page table, reconstructs the
    [TILE, W] window in VMEM via page_in, runs the K rounds unchanged,
    and re-splits with page_out_cond before writing back — the two
    whole-fleet [N, W] gather/scatter passes and the full-window HBM
    temporary disappear from the dispatch. Page ids become TILE-local
    (allocation segment = tile; FusedCluster._paged_segs = n_tiles),
    and the allocator pass is elided for calls where no lane's
    last/snap_index moved. Bit-identity with every other mode is
    structural: page_out . page_in is value-identity on scrubbed
    windows, so paging granularity is invisible to the trajectory (only
    the faults/dirty/skipped counters differ in cadence)."""
    validate_round_plan(rounds_per_call)
    # diet-v2: a packed carry (bitset masks + u16 indexes) rides the
    # HBM<->VMEM boundary packed — every boundary cast below replays the
    # same store_carry/load_carry pair the XLA scan crosses, so
    # trajectories stay bit-identical across engines. Static under jit
    # (leaf ndim/dtype are part of the signature).
    packed = is_packed(state)
    if packed:
        state, fab = fmod.store_carry(state, fab)
    else:
        state = slim_state(state)
        fab = fmod.slim_fabric(fab)
    inkernel = paged is not None and paged_inkernel
    if paged is not None and not inkernel:
        state, paged = pgmod.page_in(state, paged)
    n = state.term.shape[0]
    check_tile(n, v, tile_lanes)
    n_tiles = n // tile_lanes
    can_skip = False
    if inkernel:
        if paged.pool_term.shape[0] % n_tiles:
            raise TileError(
                f"pool_pages={paged.pool_term.shape[0]} does not divide "
                f"into {n_tiles} tiles of {tile_lanes} lanes: in-kernel "
                "paging slices the pool per grid step (segment-local "
                "allocation); pin RAFT_TPU_POOL_PAGES / "
                "RAFT_TPU_PALLAS_TILE so the pool splits evenly"
            )
        # allocator elision is only sound when every in-round log write
        # lands inside the resident window (append fan-in E <= W_res);
        # see pgmod.page_out_cond
        can_skip = int(fab.rep.ent_term.shape[-1]) <= paged.w_res

    has_mute = mute is not None
    has_met = metrics is not None
    has_ch = chaos is not None
    has_scal = has_met or has_ch

    flat_s, tree_s = jax.tree.flatten(state)
    flat_f, tree_f = jax.tree.flatten(fab)
    flat_o, tree_o = jax.tree.flatten(ops)
    flat_pg, tree_pg = jax.tree.flatten(paged) if inkernel else ([], None)
    ls, lf, lo = len(flat_s), len(flat_f), len(flat_o)
    lpg = len(flat_pg)
    grid = (n // tile_lanes,)

    nc = len(metmod.COUNTERS)
    nb = metmod.N_BUCKETS
    ch_off = (nc + nb + 1) if has_met else 0

    def lane_spec(x):
        bs = (tile_lanes,) + x.shape[1:]
        nd = x.ndim
        return pl.BlockSpec(bs, lambda i, nd=nd: (i,) + (0,) * (nd - 1))

    # in-kernel paging specs: per-lane pg leaves (pt, counters) tile like
    # state; the pool columns slice per grid step ([P/n_tiles, PE], each
    # tile owning its own sub-pool incl. its segment-local trash row 0).
    # Built as a PagedLog of specs so the order matches tree.flatten.
    pg_block_specs = []
    if inkernel:

        def pool_spec(x):
            return pl.BlockSpec(
                (x.shape[0] // n_tiles, x.shape[1]), lambda i: (i, 0)
            )

        pg_block_specs = jax.tree.flatten(
            pgmod.PagedLog(
                pt=lane_spec(paged.pt),
                pool_term=pool_spec(paged.pool_term),
                pool_type=pool_spec(paged.pool_type),
                pool_bytes=pool_spec(paged.pool_bytes),
                faults=lane_spec(paged.faults),
                exhausted=lane_spec(paged.exhausted),
                dirty=lane_spec(paged.dirty),
                skipped=lane_spec(paged.skipped),
                w=paged.w,
                w_res=paged.w_res,
            ),
            is_leaf=lambda x: isinstance(x, pl.BlockSpec),
        )[0]

    # -- shared specs / shapes (partials are per-K, added in make_call) ----
    in_specs = [lane_spec(x) for x in flat_s + flat_f + flat_o]
    in_specs += pg_block_specs
    if has_mute:
        in_specs.append(lane_spec(mute))
    if has_met:
        in_specs += [lane_spec(metrics.samp_index), lane_spec(metrics.samp_round)]
    if has_ch:
        in_specs += [lane_spec(getattr(chaos, k)) for k in _CH_KNOBS]
        in_specs += [lane_spec(getattr(chaos, k)) for k in _CH_PROBE]
    if has_scal:
        smem = {} if _SMEM is None else {"memory_space": _SMEM}
        in_specs.append(pl.BlockSpec((1, 4), lambda i: (0, 0), **smem))

    out_leaves = list(flat_s + flat_f)
    out_specs = [lane_spec(x) for x in out_leaves]
    if inkernel:
        out_leaves += list(flat_pg)
        out_specs += list(pg_block_specs)
    if has_met:
        extra = [metrics.samp_index, metrics.samp_round]
        out_leaves += extra
        out_specs += [lane_spec(x) for x in extra]
    if has_ch:
        extra = [getattr(chaos, k) for k in _CH_PROBE]
        out_leaves += extra
        out_specs += [lane_spec(x) for x in extra]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in out_leaves]

    def make_call(kc: int):
        """One pallas_call running kc rounds per grid step with the
        tile's carry resident in VMEM throughout (the megakernel)."""

        def kernel(*refs):
            pos = 0

            def take(m):
                nonlocal pos
                out = list(refs[pos : pos + m])
                pos += m
                return out

            s_in, f_in, o_in = take(ls), take(lf), take(lo)
            pg_in_refs = take(lpg) if inkernel else None
            mute_ref = take(1)[0] if has_mute else None
            samp_in = take(2) if has_met else None
            knob_in = take(len(_CH_KNOBS)) if has_ch else None
            probe_in = take(len(_CH_PROBE)) if has_ch else None
            scal_ref = take(1)[0] if has_scal else None
            s_out, f_out = take(ls), take(lf)
            pg_out_refs = take(lpg) if inkernel else None
            samp_out = take(2) if has_met else None
            probe_out = take(len(_CH_PROBE)) if has_ch else None
            part_ref = take(1)[0] if has_scal else None

            st_sl = jax.tree.unflatten(tree_s, [r[...] for r in s_in])
            fb_sl = jax.tree.unflatten(tree_f, [r[...] for r in f_in])
            pg_t = last_pre = snap_pre = None
            if inkernel:
                # page in this tile's window from its own pool slice, in
                # the STORED domain (the order the host-boundary twin
                # pages in, before the diet widen) so dtypes line up
                pg_t = jax.tree.unflatten(
                    tree_pg, [r[...] for r in pg_in_refs]
                )
                st_sl, pg_t = pgmod.page_in(st_sl, pg_t)
                last_pre = st_sl.last.astype(I32)
                snap_pre = st_sl.snap_index.astype(I32)
            if packed:
                st, fb = fmod.load_carry(st_sl, fb_sl)
            else:
                st = fat_state(st_sl)
                fb = fmod.fat_fabric(fb_sl)
            op = jax.tree.unflatten(tree_o, [r[...] for r in o_in])
            # in-kernel rounds k>0 of an ops_first_round_only dispatch see
            # zero ops: the one global round that applies ops is k==0 of
            # the FIRST call (the scan body zeroes the later calls' leaves)
            op_zero = (
                jax.tree.map(jnp.zeros_like, op)
                if (kc > 1 and ops_first_round_only)
                else None
            )
            mt = mute_ref[...] if has_mute else None
            pm = fmod.aligned_peer_mute(mt, v) if has_mute else None

            # global index of this tile's first lane: the chaos PRNG
            # streams are functions of global lane, so tiling is invisible
            lane_off = pl.program_id(0) * tile_lanes

            ch_t = None
            if has_ch:
                knobs = {k: r[...] for k, r in zip(_CH_KNOBS, knob_in)}
                probes = {k: r[...] for k, r in zip(_CH_PROBE, probe_in)}
                ch_t = chmod.ChaosState(
                    seed=jax.lax.bitcast_convert_type(scal_ref[0, 3], U32),
                    round=scal_ref[0, 1],
                    heal_round=scal_ref[0, 2],
                    n_reelected=jnp.zeros((), I32),
                    n_recommitted=jnp.zeros((), I32),
                    **knobs,
                    **probes,
                )
            mt_t = None
            if has_met:
                # zero-based counter slots: the kernel computes DELTAS;
                # the true running totals never enter the kernel
                mt_t = metmod.MetricsState(
                    counters=jnp.zeros((nc,), I32),
                    hist=jnp.zeros((nb,), I32),
                    lat_sum=jnp.zeros((), I32),
                    round_ctr=scal_ref[0, 0],
                    samp_index=samp_in[0][...],
                    samp_round=samp_in[1][...],
                )

            rows = []
            st2 = f2 = mt2 = None
            for k in range(kc):
                if k:
                    # replay the inter-round storage casts in-register:
                    # bit-identity with the XLA scan (and with K=1, where
                    # these casts happen across the HBM carry) depends on
                    # crossing the exact same dtype boundary every round —
                    # the diet-v2 pack/unpack pair when the carry is packed
                    if packed:
                        st, fb = fmod.load_carry(*fmod.store_carry(st2, f2))
                    else:
                        st = fat_state(slim_state(st2))
                        fb = fmod.fat_fabric(fmod.slim_fabric(f2))
                    if has_met:
                        # fresh delta slots per round (per-round partials
                        # rows); the sampler + round counter thread on
                        mt_t = dataclasses.replace(
                            mt2,
                            counters=jnp.zeros((nc,), I32),
                            hist=jnp.zeros((nb,), I32),
                            lat_sum=jnp.zeros((), I32),
                        )
                op_k = op_zero if (k and ops_first_round_only) else op
                inb = fmod.route_fabric(fb, v, mt, peer_mute=pm)
                tick_mask = None
                if has_ch:
                    ch_t, st, inb, op_k, tick_mask = chmod.begin_round(
                        ch_t, st, inb, op_k, v, lane_offset=lane_off
                    )
                res = fmod.fused_round(
                    st,
                    inb,
                    op_k,
                    mt,
                    peer_mute=pm,
                    do_tick=do_tick,
                    auto_propose=auto_propose,
                    auto_compact_lag=auto_compact_lag,
                    tick_mask=tick_mask,
                    metrics=mt_t,
                )
                st2, f2 = res[0], res[1]
                mt2 = res[2] if has_met else None
                if has_ch:
                    ch_t, f2 = chmod.end_round(
                        ch_t, st2, fb, f2, v, lane_offset=lane_off
                    )
                if has_scal:
                    parts = []
                    if has_met:
                        parts += [mt2.counters, mt2.hist, mt2.lat_sum[None]]
                    if has_ch:
                        parts += [
                            ch_t.n_reelected[None],
                            ch_t.n_recommitted[None],
                        ]
                    row = jnp.concatenate(parts)
                    rows.append(
                        jnp.pad(row, (0, PARTIAL_WIDTH - row.shape[0]))
                    )
            if packed:
                st_w, f_w = fmod.store_carry(st2, f2)
            else:
                st_w, f_w = slim_state(st2), fmod.slim_fabric(f2)
            if inkernel:
                # re-split in the stored domain (mirroring the
                # host-boundary page_out order); the conditional form
                # elides the allocator when no lane's depth moved
                st_w, pg_t = pgmod.page_out_cond(
                    st_w, pg_t, last_pre, snap_pre, can_skip=can_skip
                )
            for r, x in zip(s_out, jax.tree.leaves(st_w)):
                r[...] = x
            for r, x in zip(f_out, jax.tree.leaves(f_w)):
                r[...] = x
            if inkernel:
                for r, x in zip(pg_out_refs, jax.tree.leaves(pg_t)):
                    r[...] = x
            if has_met:
                samp_out[0][...] = mt2.samp_index
                samp_out[1][...] = mt2.samp_round
            if has_ch:
                for r, name in zip(probe_out, _CH_PROBE):
                    r[...] = getattr(ch_t, name)
            if has_scal:
                part_ref[...] = jnp.stack(rows)[:, None, :]

        out_specs_k = list(out_specs)
        out_shape_k = list(out_shape)
        if has_scal:
            out_specs_k.append(
                pl.BlockSpec((kc, 1, PARTIAL_WIDTH), lambda i: (0, i, 0))
            )
            out_shape_k.append(
                jax.ShapeDtypeStruct(
                    (kc, grid[0], PARTIAL_WIDTH), jnp.int32
                )
            )
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs_k,
            out_shape=out_shape_k,
            interpret=interpret,
        )

    # -- one K-round dispatch ----------------------------------------------
    def run_block(callee, kc, carry, first):
        fs, ff, fpg, met, ch, tr = carry
        # pre-round captures for the flight recorder (kc == 1 whenever tr
        # is not None): the carry state before the kernel, the chaos carry
        # before its round advance
        # unpack_state: identity on a slim carry; a diet-v2 packed carry
        # widens to the layout the trace diff detector expects
        st_pre = (
            fat_state(unpack_state(jax.tree.unflatten(tree_s, fs)))
            if tr is not None
            else None
        )
        ch_pre = ch
        o_leaves = flat_o
        if ops_first_round_only:
            o_leaves = [
                jnp.where(first, x, jnp.zeros_like(x)) for x in flat_o
            ]
        inputs = list(fs) + list(ff) + list(o_leaves) + list(fpg)
        if has_mute:
            inputs.append(mute)
        if has_met:
            inputs += [met.samp_index, met.samp_round]
        if has_ch:
            inputs += [getattr(ch, k) for k in _CH_KNOBS]
            inputs += [getattr(ch, k) for k in _CH_PROBE]
        if has_scal:
            z = jnp.zeros((), I32)
            inputs.append(
                jnp.stack(
                    [
                        met.round_ctr if has_met else z,
                        ch.round if has_ch else z,
                        ch.heal_round if has_ch else z,
                        jax.lax.bitcast_convert_type(ch.seed, I32)
                        if has_ch
                        else z,
                    ]
                ).reshape(1, 4)
            )
        out = list(callee(*inputs))
        pos = 0

        def take(m):
            nonlocal pos
            res = out[pos : pos + m]
            pos += m
            return res

        new_fs, new_ff = take(ls), take(lf)
        new_fpg = take(lpg) if inkernel else fpg
        if has_met:
            samp_i, samp_r = take(2)
        if has_ch:
            probes = take(len(_CH_PROBE))
        if has_scal:
            # [kc, n_tiles, W] per-round rows -> [kc, W] tile-reduced
            parts = jnp.sum(take(1)[0], axis=1)
            if has_met:
                # metrics slots are deltas: fold the kc rounds too (i32
                # wrap-add is associative — exact vs kc sequential adds)
                dsum = jnp.sum(parts, axis=0)
                met = dataclasses.replace(
                    met,
                    counters=met.counters + dsum[:nc],
                    hist=met.hist + dsum[nc : nc + nb],
                    lat_sum=met.lat_sum + dsum[nc + nb],
                    round_ctr=met.round_ctr + kc,
                    samp_index=samp_i,
                    samp_round=samp_r,
                )
            if has_ch:
                # chaos slots are absolute recounts: the LAST round's row
                ch = dataclasses.replace(
                    ch,
                    **dict(zip(_CH_PROBE, probes)),
                    n_reelected=parts[kc - 1, ch_off],
                    n_recommitted=parts[kc - 1, ch_off + 1],
                    round=ch.round + kc,
                )
        if tr is not None:
            st_post = fat_state(unpack_state(jax.tree.unflatten(tree_s, new_fs)))
            tr = trmod.record_round(
                tr,
                st_pre,
                st_post,
                chaos=ch_pre,
                lane_offset=trace_lane_offset,
            )
        return (new_fs, new_ff, new_fpg, met, ch, tr)

    # -- scan of full-K calls + remainder tail -----------------------------
    kc = rounds_per_call
    if trace is not None and kc != 1:
        # per-round boundary states for the diff detector only exist at
        # K=1 (module doc); the routing is silent and bit-exact
        kc = 1
    kc = max(1, min(kc, n_rounds)) if n_rounds else 1
    n_full, rem = divmod(n_rounds, kc)

    carry = (flat_s, flat_f, flat_pg, metrics, chaos, trace)
    if n_full:
        call_main = make_call(kc)

        def body(c, i):
            return run_block(call_main, kc, c, i == 0), None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(n_full, dtype=I32))
    if rem:
        # a second, remainder-sized megakernel program in the same trace
        carry = run_block(make_call(rem), rem, carry, n_full == 0)
    flat_s, flat_f, flat_pg, metrics, chaos, trace = carry
    state_out = jax.tree.unflatten(tree_s, flat_s)
    if inkernel:
        # the kernel already re-split each tile (state is resident and
        # canonical); no boundary page_out, no full-window temporary
        paged = jax.tree.unflatten(tree_pg, flat_pg)
    elif paged is not None:
        state_out, paged = pgmod.page_out(state_out, paged)
    else:
        # canonical layout on the unpaged exit too, mirroring fused_rounds
        state_out = lgmod.scrub_stale_slots(state_out)
    res = (
        state_out,
        jax.tree.unflatten(tree_f, flat_f),
    )
    if metrics is not None:
        res += (metrics,)
    if chaos is not None:
        res += (chaos,)
    if trace is not None:
        res += (trace,)
    if paged is not None:
        res += (paged,)
    return res


_PALLAS_STATIC = (
    "v",
    "tile_lanes",
    "n_rounds",
    "rounds_per_call",
    "do_tick",
    "auto_propose",
    "auto_compact_lag",
    "ops_first_round_only",
    "interpret",
    "paged_inkernel",
)

# donating/copying twins, mirroring ops/fused.py
_pallas_rounds_jit = jax.jit(
    pallas_rounds,
    static_argnames=_PALLAS_STATIC,
    donate_argnums=(0, 1),
    donate_argnames=("metrics", "chaos", "trace", "paged"),
)
_pallas_rounds_nodonate_jit = jax.jit(
    pallas_rounds, static_argnames=_PALLAS_STATIC
)


def round_jit_twin(donate: bool):
    """The jitted round program for one donation mode — the single
    selection point the static auditor, the resource ledger and the
    bench lowerings share, so a twin swap can never happen in one of
    them only — FusedCluster._run_pallas dispatches through it too)."""
    return _pallas_rounds_jit if donate else _pallas_rounds_nodonate_jit

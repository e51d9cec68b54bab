"""Paged entry log A/B: the page-table HBM entry pool (RAFT_TPU_PAGED=1)
vs the flat `[N, W]` log window, on a Zipfian ragged-depth workload.

The paged layer exists for exactly this profile (ROADMAP item 3): a few
hot groups run deep replication windows while most groups idle shallow,
so a flat window makes every lane pay max-W resident bytes for the hot
minority's depth. Each child elects all groups under a SHALLOW
compaction lag (every lane fits its resident window), then drives
proposals whose per-group rate follows a Zipf law at a deep lag: hot
groups ride at the deep compaction cap and spill into the pool, cold
groups stay inside their resident tail and never touch it. The paged
arm pins a pool of about one page per two lanes — a sixth of full
provisioning (AB_POOL_PAGES override); the Zipfian tail is what makes
that safe, and error_bits would flag (never silently drop) if not.

Arm matrix (fresh subprocess per arm, planes enabled like diet_ab.py):
paged off/on x engine (xla, pallas K=1, pallas K=AB_K), then the same
three engines again with RAFT_TPU_PAGED_INKERNEL=1 x diet off/on (six
more arms; the pallas in-kernel arms pin RAFT_TPU_PALLAS_TILE =
lanes/2 so the pool splits into two per-grid-step segments). One bench
JSON line per arm plus a summary, with the probes in `extra`:

  - ms_per_round: wall clock over AB_ITERS timed Zipfian sweeps
  - resident_bytes_per_lane: nbytes of the between-dispatch carry
    (state + fabric + the paged sidecar: resident tail, page table,
    pool share) / lanes — the quantity paging exists to shrink
  - paged_*: pool occupancy / fault / exhaustion counters (paged arm)

Asserted invariants:
  - ALL arms (six host-boundary + six in-kernel) end on ONE identical
    sha256 digest of the host_state trajectory INCLUDING the log
    columns — paging is invisible, across engines, at every K, at
    either paging boundary, diet on or off
  - error_bits stays zero everywhere (no silent ERR_PAGE_EXHAUSTED)
  - the pallas children really ran pallas: no engine fallback
  - paged-on resident bytes/lane STRICTLY lower than paged-off, on every
    engine, on every backend (CPU included)
  - compiled-program probe (parent process, CPU included): the
    in-kernel pallas round program moves STRICTLY fewer bytes/round
    than the host-boundary paged pallas one (ledger.round_bytes_probe,
    the same computation `--ledger` budgets) at K=1 and K=AB_K, and its
    temp allocation stays under the `round.pallas.paged_inkernel`
    record's hard cap scaled to the probe geometry — the two
    whole-fleet [N, W] gather/scatter passes and the full-window HBM
    temporary are really gone from the lowering
  - [TPU only] paged-on ms/round <= AB_TOL x paged-off per engine
    (groups*ticks/s flat or better)

Exit 0 = pass, 1 = regression. `--smoke` shrinks the workload for CI.
Env: AB_GROUPS, AB_VOTERS, AB_ROUNDS, AB_ITERS, AB_TOL, AB_K,
AB_POOL_PAGES, RAFT_TPU_* (forwarded to the children verbatim).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tpu import config

DIGEST_FIELDS = (
    "term", "vote", "lead", "state", "committed", "last",
    "log_term", "log_type", "log_bytes", "error_bits",
)

W, PAGE_WINDOW, PAGE_ENTRIES = 16, 8, 4


def default_pool(groups: int, v: int) -> int:
    """About one page per two lanes — full provisioning would be
    kmax = ceil((W - W_res) / PE) + 1 = 3 pages per lane, but only the
    Zipf-hot groups outrun their resident window at all. The fixed
    +kmax+1 headroom covers the in-kernel arms: per-ROUND reallocation
    sees transient mid-dispatch depth peaks the dispatch-boundary
    allocator never materializes (the same trajectory, paged at a finer
    boundary, briefly holds a few more pages). Even by construction, so
    the pallas in-kernel arms' two-segment split stays legal."""
    return max(16, groups * v // 2 + 8) + 4


def child():
    import time

    import jax
    import numpy as np

    from raft_tpu.config import Shape
    from raft_tpu.ops import fused

    engine = config.env_str("RAFT_TPU_ENGINE", default="xla")
    groups = int(os.environ.get("AB_GROUPS", 4096))
    v = int(os.environ.get("AB_VOTERS", 3))
    shape = Shape(
        n_lanes=groups * v, max_peers=v, log_window=W,
        max_msg_entries=2, max_inflight=2, max_read_index=2,
    )
    c = fused.FusedCluster(groups, v, seed=42, shape=shape)
    # warm-up compacts SHALLOW (every lane stays inside the resident
    # window); the Zipfian phase then lets hot groups ride a deep lag
    lag, deep_lag = PAGE_WINDOW // 2, W - 4
    rounds = int(os.environ.get("AB_ROUNDS", 16))
    iters = int(os.environ.get("AB_ITERS", 8))

    c.run(rounds, auto_propose=True, auto_compact_lag=lag)  # compile
    jax.block_until_ready(c.state.term)
    warm = 0
    while len(c.leader_lanes()) < groups:
        c.run(rounds, auto_propose=True, auto_compact_lag=lag)
        warm += rounds
        if warm > 40 * 16:
            raise RuntimeError("A/B warm-up stalled before full election")
    jax.block_until_ready(c.state.term)

    # Zipf-ranked proposal rates: group at rank r proposes every 2^min(r,
    # bucket_cap) sweeps (rank 0 = hottest, proposing 2 entries per sweep).
    # Deterministic, so every arm drives the bit-identical trajectory; the
    # rank->group assignment is a seeded shuffle so hot groups are spread
    # across the batch (and across shards/blocks if this shape is reused).
    rng = np.random.default_rng(7)
    rank_of = rng.permutation(groups)
    leader_of = {}
    for lane in c.leader_lanes():
        leader_of.setdefault(int(lane) // v, int(lane))

    def zipf_sweep(sweep: int):
        prop = {}
        for g, lane in leader_of.items():
            period = 1 << min(int(rank_of[g]).bit_length(), 5)
            if sweep % period == 0:
                prop[lane] = 2 if rank_of[g] == 0 else 1
        return c.ops(prop_n=prop)

    for s in range(4):  # shape the Zipfian depth profile before timing
        c.run(rounds, ops=zipf_sweep(s), auto_compact_lag=deep_lag)
    jax.block_until_ready(c.state.term)

    t0 = time.perf_counter()
    for s in range(iters):
        c.run(rounds, ops=zipf_sweep(s), auto_compact_lag=deep_lag)
    jax.block_until_ready(c.state.term)
    ms_per_round = (time.perf_counter() - t0) / (rounds * iters) * 1e3

    lanes = groups * v
    resident = sum(x.nbytes for x in jax.tree.leaves(c.state)) + sum(
        x.nbytes for x in jax.tree.leaves(c.fab)
    )
    if c.paged is not None:
        resident += sum(x.nbytes for x in jax.tree.leaves(c.paged))
    stats = c.paged_stats() or {}

    # digest over host_state() INCLUDING the log columns: the paged arm
    # must reconstruct the exact window the flat arm carries natively
    st = c.host_state()
    digest = hashlib.sha256()
    for name in DIGEST_FIELDS:
        digest.update(np.ascontiguousarray(np.asarray(getattr(st, name))).tobytes())
    c.check_no_errors()
    inkernel = config.env_str("RAFT_TPU_PAGED_INKERNEL", default="0")
    print(json.dumps({
        "config": (
            f"paged_ab:{engine}"
            f":paged={config.env_str('RAFT_TPU_PAGED', default='0')}"
            f":inkernel={inkernel}"
            f":diet={config.env_str('RAFT_TPU_DIET', default='0')}"
        ),
        "value": round(ms_per_round, 4),
        "unit": "ms/round",
        "extra": {
            "engine_requested": engine,
            "engine_after": c.engine,
            "paged": c.paged is not None,
            "paged_inkernel": bool(getattr(c, "_paged_inkernel", False)),
            "paged_segs": getattr(c, "_paged_segs", None),
            "ms_per_round": ms_per_round,
            "resident_bytes_per_lane": resident / lanes,
            "groups_ticks_per_s": groups * 1e3 / max(ms_per_round, 1e-9),
            "digest": digest.hexdigest(),
            "backend": jax.default_backend(),
            **stats,
        },
    }), flush=True)


# probe_gate geometry: the smallest legal in-kernel split (12 lanes,
# tile 6 -> two pool segments) at K=1, so the two AOT lowerings stay
# cheap even on a single-core CPU host. Direction of the bytes win is
# geometry-independent: in-kernel paging deletes the two whole-fleet
# [N, W] gather/scatter passes regardless of N.
PROBE_GROUPS, PROBE_V, PROBE_TILE, PROBE_POOL = 4, 3, 6, 16

# hard temp budget for the in-kernel lowering at the probe geometry,
# mirroring the `round.pallas.paged_inkernel` registry record's
# temp_cap_per_lane: measured 2430.7 B/lane; one full-window log-column
# set is 192 B/lane at W=16, so headroom (~119) is deliberately smaller
# than the smallest full-window temporary that could creep back.
PROBE_TEMP_CAP_PER_LANE = 2550.0


def probe_gate(ab_k: int) -> list[str]:
    """Parent-process compiled-program gate (every backend, CPU
    included): AOT-lower the host-boundary and in-kernel paged pallas
    round programs at a fixed small geometry and compare the ledger's
    own bytes-moved computation (`round_bytes_probe`, the number the
    `--ledger` gate budgets). The in-kernel lowering must move strictly
    fewer bytes per round — the whole-fleet page_in/page_out passes are
    really gone — and its temp allocation must stay under a hard cap
    sized so any full-window [N, W] temporary trips it."""
    from raft_tpu.config import Shape
    from raft_tpu.ops import fused
    from raft_tpu.analysis import ledger

    knobs = {
        "RAFT_TPU_PAGED": "1",
        "RAFT_TPU_PAGED_INKERNEL": "0",
        "RAFT_TPU_PAGE_WINDOW": str(PAGE_WINDOW),
        "RAFT_TPU_PAGE_ENTRIES": str(PAGE_ENTRIES),
        "RAFT_TPU_POOL_PAGES": str(PROBE_POOL),
        "RAFT_TPU_PALLAS_TILE": str(PROBE_TILE),
        "RAFT_TPU_PALLAS_AUTOTUNE": "0",
    }
    lanes = PROBE_GROUPS * PROBE_V
    shape = Shape(
        n_lanes=lanes, max_peers=PROBE_V, log_window=W,
        max_msg_entries=2, max_inflight=2, max_read_index=2,
    )
    saved = {k: os.environ.get(k) for k in knobs}
    try:
        os.environ.update(knobs)
        host = fused.FusedCluster(
            PROBE_GROUPS, PROBE_V, seed=42, shape=shape, engine="pallas"
        )
        os.environ["RAFT_TPU_PAGED_INKERNEL"] = "1"
        ink = fused.FusedCluster(
            PROBE_GROUPS, PROBE_V, seed=42, shape=shape, engine="pallas"
        )
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old

    fails = []
    b_host = ledger.round_bytes_probe(host, 1)
    # one lowering serves both probes (bytes moved + temp): interpret-
    # mode pallas compiles are minutes-slow on a small CPU host
    try:
        comp_ink = ink.lower_round_program(1, donate=False).compile()
    except Exception:
        comp_ink = None
    b_ink = None if comp_ink is None else ledger.bytes_accessed(comp_ink)
    if b_host is None or b_ink is None:
        fails.append(
            "probe: backend exposes no cost model — cannot certify the "
            "in-kernel bytes/round win"
        )
    elif b_ink >= b_host:
        fails.append(
            "probe: in-kernel pallas round program does not move strictly "
            f"fewer bytes/round ({b_host:.0f} -> {b_ink:.0f}) — the "
            "whole-fleet page_in/page_out passes are back in the lowering"
        )
    temp = (None if comp_ink is None
            else ledger.memory_metrics(comp_ink).get("temp_bytes"))
    temp_per_lane = None if temp is None else temp / lanes
    if temp_per_lane is not None and temp_per_lane > PROBE_TEMP_CAP_PER_LANE:
        fails.append(
            f"probe: in-kernel temp {temp_per_lane:.1f} B/lane exceeds the "
            f"hard cap {PROBE_TEMP_CAP_PER_LANE} — a full-window [N, W] "
            "temporary (or an allocation of that class) crept back"
        )
    print(json.dumps({
        "metric": "paged_ab_probe",
        "bytes_per_round_host_boundary": b_host,
        "bytes_per_round_inkernel": b_ink,
        "inkernel_temp_bytes_per_lane": temp_per_lane,
        "temp_cap_per_lane": PROBE_TEMP_CAP_PER_LANE,
        "ok": not fails,
    }), flush=True)
    return fails


def run_child(engine: str, paged: str, extra_env: dict | None = None) -> dict:
    groups = int(os.environ.get("AB_GROUPS", 4096))
    v = int(os.environ.get("AB_VOTERS", 3))
    env = dict(
        os.environ,
        RAFT_TPU_ENGINE=engine,
        RAFT_TPU_PAGED=paged,
        # the acceptance matrix runs with every observability plane live
        RAFT_TPU_METRICS="1",
        RAFT_TPU_CHAOS="1",
        RAFT_TPU_TRACELOG="1",
    )
    if paged == "1":
        env.setdefault("RAFT_TPU_PAGE_WINDOW", str(PAGE_WINDOW))
        env.setdefault("RAFT_TPU_PAGE_ENTRIES", str(PAGE_ENTRIES))
        env.setdefault(
            "RAFT_TPU_POOL_PAGES",
            os.environ.get("AB_POOL_PAGES", str(default_pool(groups, v))),
        )
    if extra_env:
        env.update(extra_env)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, capture_output=True, text=True, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    if "--smoke" in sys.argv:
        os.environ.setdefault("AB_GROUPS", "8")
        os.environ.setdefault("AB_ROUNDS", "4")
        os.environ.setdefault("AB_ITERS", "2")
    tol = float(os.environ.get("AB_TOL", 1.05))
    ab_k = int(os.environ.get("AB_K", 4))
    arms = {}
    for eng, kenv in (
        ("xla", None),
        ("pallas", {"RAFT_TPU_PALLAS_ROUNDS": "1"}),
        (f"pallas K={ab_k}", {"RAFT_TPU_PALLAS_ROUNDS": str(ab_k)}),
    ):
        for paged in ("0", "1"):
            r = run_child(eng.split()[0], paged, kenv)
            print(json.dumps(r), flush=True)
            arms[(eng, paged)] = r

    # in-kernel arms: same engines, paging fused into the round program,
    # crossed with diet so the storage layers are proven to compose at
    # the in-kernel boundary too. The pallas arms pin tile = lanes/2 so
    # the pool splits into two per-grid-step segments (geometry: the
    # default pool is even and each half holds >= kmax+1 pages).
    groups = int(os.environ.get("AB_GROUPS", 4096))
    v = int(os.environ.get("AB_VOTERS", 3))
    ink = {}
    for eng, kenv in (
        ("xla", None),
        ("pallas", {"RAFT_TPU_PALLAS_ROUNDS": "1"}),
        (f"pallas K={ab_k}", {"RAFT_TPU_PALLAS_ROUNDS": str(ab_k)}),
    ):
        for diet in ("0", "1"):
            extra = dict(kenv or {})
            extra["RAFT_TPU_PAGED_INKERNEL"] = "1"
            extra["RAFT_TPU_DIET"] = diet
            if eng.startswith("pallas"):
                extra["RAFT_TPU_PALLAS_TILE"] = str(groups * v // 2)
                # two pool segments, each with its own trash page and
                # its own Zipf-lumpy share of the hot lanes: give each
                # segment the same kmax+1 transient headroom the global
                # pool already gets (AB_POOL_PAGES still overrides)
                extra["RAFT_TPU_POOL_PAGES"] = os.environ.get(
                    "AB_POOL_PAGES", str(default_pool(groups, v) + 8)
                )
            r = run_child(eng.split()[0], "1", extra)
            print(json.dumps(r), flush=True)
            ink[(eng, diet)] = r

    fails = []
    base = arms[("xla", "0")]["extra"]
    on_tpu = base["backend"] == "tpu"
    for key, r in arms.items():
        ex = r["extra"]
        if ex["digest"] != base["digest"]:
            fails.append(
                f"{key}: trajectory digest diverged from xla paged-off — "
                "paging is not invisible"
            )
        if ex["engine_requested"] == "pallas" and (
            ex["engine_after"] != "pallas"
        ):
            fails.append(
                f"{key}: child fell back to {ex['engine_after']} "
            )
        if ex.get("paged_exhausted"):
            fails.append(
                f"{key}: pool exhausted {ex['paged_exhausted']} times — "
                "the Zipfian tail no longer fits the undersized pool"
            )
    for (eng, diet), r in ink.items():
        ex = r["extra"]
        key = f"inkernel:{eng}:diet={diet}"
        if ex["digest"] != base["digest"]:
            fails.append(
                f"{key}: trajectory digest diverged from xla paged-off — "
                "in-kernel paging is not invisible"
            )
        if not ex.get("paged_inkernel"):
            fails.append(f"{key}: child did not run with in-kernel paging")
        if ex["engine_requested"] == "pallas" and (
            ex["engine_after"] != "pallas"
        ):
            fails.append(
                f"{key}: child fell back to {ex['engine_after']} "
            )
        if ex["engine_after"] == "pallas" and ex.get("paged_segs") != 2:
            fails.append(
                f"{key}: expected 2 pool segments (tile = lanes/2), got "
                f"{ex.get('paged_segs')}"
            )
        if ex.get("paged_exhausted"):
            fails.append(
                f"{key}: pool exhausted {ex['paged_exhausted']} times — "
                "the Zipfian tail no longer fits the undersized pool"
            )
        if on_tpu:
            ratio = r["value"] / max(arms[(eng, "1")]["value"], 1e-9)
            if ratio > tol:
                fails.append(
                    f"{key}: in-kernel paging regressed round time vs the "
                    f"host-boundary paged arm (ratio {ratio:.3f} > tol {tol})"
                )
    fails += probe_gate(ab_k)
    for eng in ("xla", "pallas", f"pallas K={ab_k}"):
        off = arms[(eng, "0")]["extra"]
        on = arms[(eng, "1")]["extra"]
        if on["resident_bytes_per_lane"] >= off["resident_bytes_per_lane"]:
            fails.append(
                f"{eng}: paged resident bytes/lane not strictly lower "
                f"({off['resident_bytes_per_lane']:.1f} -> "
                f"{on['resident_bytes_per_lane']:.1f})"
            )
        ratio = arms[(eng, "1")]["value"] / max(arms[(eng, "0")]["value"], 1e-9)
        if on_tpu and ratio > tol:
            fails.append(
                f"{eng}: paging regressed round time "
                f"(ratio {ratio:.3f} > tol {tol})"
            )
    on_x = arms[("xla", "1")]["extra"]
    ink_x = ink[("xla", "0")]["extra"]
    print(json.dumps({
        "metric": "paged_ab",
        "ok": not fails,
        "inkernel_alloc_skipped": ink_x.get("paged_alloc_skipped"),
        "inkernel_pages_dirty": ink_x.get("paged_pages_dirty"),
        "resident_bytes_per_lane_off": base["resident_bytes_per_lane"],
        "resident_bytes_per_lane_on": on_x["resident_bytes_per_lane"],
        "shrink_pct": round(
            100 * (1 - on_x["resident_bytes_per_lane"]
                   / base["resident_bytes_per_lane"]), 1,
        ),
        "pool_in_use": on_x.get("paged_pool_in_use"),
        "pool_pages": on_x.get("paged_pool_pages"),
        "page_faults": on_x.get("paged_page_faults"),
        "megakernel_k": ab_k,
        "tpu_gates": on_tpu,
        "tol": tol,
    }), flush=True)
    for f in fails:
        print(f"FAIL: {f}", file=sys.stderr)
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        main()

"""Engine A/B: the VMEM-resident pallas round vs the XLA round.

Runs the same FusedCluster workload in fresh subprocesses —
RAFT_TPU_ENGINE=xla, =pallas at K=1, and =pallas at K=AB_K (the
RAFT_TPU_PALLAS_ROUNDS megakernel arm; default 4) — the production
selection knobs, so this harness exercises exactly what users flip — and
emits one bench JSON line per arm plus a summary, with ms/round AND the
bytes-moved probes in `extra`:

  - ms_per_round: wall clock over AB_ITERS timed dispatches
  - bytes_accessed_per_round: the compiled executable's cost-analysis
    "bytes accessed" (XLA's own HBM-traffic estimate — the quantity the
    round-5 profile showed at ~3 GB/round on the XLA path)
  - live_buffer_bytes / device_memory: allocator-level probes
    (raft_tpu/utils/profiling.py; device stats are None on XLA:CPU)

Asserted invariants:
  - all arms end on an identical slim_state digest (bit-identity,
    including the K>1 megakernel arm)
  - the pallas children really ran pallas: no silent engine fallback
  - [TPU only] pallas ms/round <= AB_TOL x XLA ms/round at the default
    tile, pallas moves strictly fewer bytes/round than XLA, and the
    K=AB_K megakernel moves strictly fewer bytes/round than K=1 (the
    K-1 eliminated carry round-trips per dispatch)

Exit 0 = pass, 1 = regression. `--smoke` shrinks the workload for CI
(CPU interpret mode: correctness + plumbing only, timings meaningless).
Env: AB_GROUPS, AB_VOTERS, AB_ROUNDS, AB_ITERS, AB_TOL, AB_K, RAFT_TPU_*
(JAX_COMPILATION_CACHE_DIR is forwarded to the children verbatim).
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
    "log_term", "error_bits",
)


def child():
    import time

    import jax
    import numpy as np

    from raft_tpu.analysis import ledger
    from raft_tpu.config import Shape
    from raft_tpu.ops import fused
    from raft_tpu.utils.profiling import device_memory_stats, live_buffer_bytes

    engine = config.env_str("RAFT_TPU_ENGINE")
    groups = int(os.environ.get("AB_GROUPS", 4096))
    v = int(os.environ.get("AB_VOTERS", 3))
    w, e = 16, 2
    shape = Shape(
        n_lanes=groups * v, max_peers=v, log_window=w,
        max_msg_entries=e, max_inflight=2, max_read_index=2,
    )
    c = fused.FusedCluster(groups, v, seed=42, shape=shape)
    lag = min(8, w // 2)
    rounds = int(os.environ.get("AB_ROUNDS", 16))
    iters = int(os.environ.get("AB_ITERS", 8))

    c.run(rounds, auto_propose=True, auto_compact_lag=lag)  # compile
    jax.block_until_ready(c.state.term)
    warm = 0
    # both engines walk the identical (bit-exact) trajectory, so this loop
    # runs the same number of sweeps in both children and the final digest
    # comparison is apples-to-apples
    while len(c.leader_lanes()) < groups:
        c.run(rounds, auto_propose=True, auto_compact_lag=lag)
        warm += rounds
        if warm > 40 * 16:
            raise RuntimeError("A/B warm-up stalled before full election")
    jax.block_until_ready(c.state.term)

    t0 = time.perf_counter()
    for _ in range(iters):
        c.run(rounds, auto_propose=True, auto_compact_lag=lag)
    jax.block_until_ready(c.state.term)
    ms_per_round = (time.perf_counter() - t0) / (rounds * iters) * 1e3

    # bytes-moved probe: the compiled round block's own cost analysis,
    # via the shared ledger helper (same lowering the static gate uses)
    bytes_per_round = ledger.round_bytes_probe(
        c, rounds, auto_propose=True, auto_compact_lag=lag
    )

    digest = hashlib.sha256()
    for name in DIGEST_FIELDS:
        digest.update(np.ascontiguousarray(getattr(c.state, name)).tobytes())
    c.check_no_errors()
    print(json.dumps({
        "config": f"pallas_ab:{engine}",
        "value": round(ms_per_round, 4),
        "unit": "ms/round",
        "extra": {
            "engine_requested": engine,
            "engine_after": c.engine,
            "tile_lanes": c._pallas_tile,
            "rounds_per_call": c._pallas_rounds,
            "interpret": c._pallas_interpret,
            "ms_per_round": ms_per_round,
            "bytes_accessed_per_round": bytes_per_round,
            "live_buffer_bytes": live_buffer_bytes(),
            "device_memory": device_memory_stats(),
            "digest": digest.hexdigest(),
            "backend": jax.default_backend(),
        },
    }), flush=True)


def run_child(engine: str, extra_env: dict | None = None) -> dict:
    env = dict(os.environ, RAFT_TPU_ENGINE=engine)  # forwards
    # JAX_COMPILATION_CACHE_DIR / RAFT_TPU_DONATE / JAX_PLATFORMS etc. verbatim
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
    xla = run_child("xla")
    pal = run_child("pallas", {"RAFT_TPU_PALLAS_ROUNDS": "1"})
    palk = run_child("pallas", {"RAFT_TPU_PALLAS_ROUNDS": str(ab_k)})
    print(json.dumps(xla), flush=True)
    print(json.dumps(pal), flush=True)
    print(json.dumps(palk), flush=True)
    xx, pp, kk = xla["extra"], pal["extra"], palk["extra"]
    on_tpu = pp["backend"] == "tpu"

    fails = []
    if pp["digest"] != xx["digest"]:
        fails.append("slim_state digest mismatch: pallas != xla trajectory")
    if kk["digest"] != xx["digest"]:
        fails.append(
            f"slim_state digest mismatch: pallas K={ab_k} megakernel "
            "!= xla trajectory"
        )
    for label, ex in (("pallas", pp), (f"pallas K={ab_k}", kk)):
        if ex["engine_after"] != "pallas":
            fails.append(
                f"{label} child fell back to {ex['engine_after']} "
            )
    ratio = pal["value"] / max(xla["value"], 1e-9)
    ratio_k = palk["value"] / max(xla["value"], 1e-9)
    if on_tpu and ratio > tol:
        fails.append(
            f"pallas regressed throughput: {pal['value']:.4f} ms/round vs "
            f"xla {xla['value']:.4f} (ratio {ratio:.3f} > tol {tol})"
        )
    if on_tpu and ratio_k > tol:
        fails.append(
            f"pallas K={ab_k} regressed throughput: {palk['value']:.4f} "
            f"ms/round vs xla {xla['value']:.4f} "
            f"(ratio {ratio_k:.3f} > tol {tol})"
        )
    if on_tpu and not (
        pp["bytes_accessed_per_round"]
        and xx["bytes_accessed_per_round"]
        and pp["bytes_accessed_per_round"] < xx["bytes_accessed_per_round"]
    ):
        fails.append(
            f"pallas does not move fewer bytes/round: "
            f"{pp['bytes_accessed_per_round']} vs {xx['bytes_accessed_per_round']}"
        )
    if on_tpu and not (
        kk["bytes_accessed_per_round"]
        and pp["bytes_accessed_per_round"]
        and kk["bytes_accessed_per_round"] < pp["bytes_accessed_per_round"]
    ):
        # the megakernel's whole point: K-1 fewer carry HBM round-trips
        # per dispatch must show up as strictly fewer bytes than K=1
        fails.append(
            f"K={ab_k} megakernel does not move fewer bytes/round than "
            f"K=1: {kk['bytes_accessed_per_round']} vs "
            f"{pp['bytes_accessed_per_round']}"
        )
    print(json.dumps({
        "metric": "pallas_ab",
        "ok": not fails,
        "ms_ratio_pallas_over_xla": round(ratio, 3),
        "ms_ratio_pallas_k_over_xla": round(ratio_k, 3),
        "megakernel_k": ab_k,
        "bytes_pallas": pp["bytes_accessed_per_round"],
        "bytes_pallas_k": kk["bytes_accessed_per_round"],
        "bytes_xla": xx["bytes_accessed_per_round"],
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

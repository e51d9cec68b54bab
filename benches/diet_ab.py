"""Byte-diet A/B: the diet-v2 packed carry (RAFT_TPU_DIET=1) vs slim.

Runs the same FusedCluster workload in fresh subprocesses over the full
arm matrix — diet off/on x engine (xla, pallas K=1, pallas K=AB_K) — with
the metrics + chaos + trace planes ENABLED, so the packed storage boundary
is exercised under every carry consumer at once. One bench JSON line per
arm plus a summary, with ms/round and the carry-byte probes in `extra`:

  - ms_per_round: wall clock over AB_ITERS timed dispatches
  - carry_bytes_per_lane: sum of nbytes over the resident (state, fabric)
    carry leaves / lanes — the quantity diet-v2 exists to shrink
  - live_buffer_bytes: the process-wide live-array probe
    (raft_tpu/utils/profiling.py), the scaling_probe.py column's source

Asserted invariants:
  - all six arms end on ONE identical digest of the slim-canonical
    (host_state) trajectory fields — packing is invisible to the
    trajectory, across engines, at every K
  - error_bits stays zero everywhere (no silent ERR_DIET_OVERFLOW clamps)
  - the pallas children really ran pallas: no engine fallback
  - diet-on carry bytes/lane <= 0.7 x diet-off (the >= 30% ISSUE-9
    acceptance floor), on every engine, on every backend (CPU included)
  - [TPU only] diet-on ms/round <= AB_TOL x diet-off per engine (round
    time flat or better)

Exit 0 = pass, 1 = regression. `--smoke` shrinks the workload for CI.
Env: AB_GROUPS, AB_VOTERS, AB_ROUNDS, AB_ITERS, AB_TOL, AB_K, RAFT_TPU_*
(forwarded to the children verbatim).
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

    from raft_tpu.config import Shape
    from raft_tpu.ops import fused

    engine = config.env_str("RAFT_TPU_ENGINE", default="xla")
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
    # every arm walks the identical (bit-exact) trajectory, so this loop
    # runs the same number of sweeps in every child and the final digest
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

    from raft_tpu.utils.profiling import live_buffer_bytes

    lanes = groups * v
    carry_bytes = sum(x.nbytes for x in jax.tree.leaves(c.state)) + sum(
        x.nbytes for x in jax.tree.leaves(c.fab)
    )

    # digest over the SLIM-CANONICAL view: the packed arm must surface the
    # exact bytes the slim arm carries natively
    st = c.host_state()
    digest = hashlib.sha256()
    for name in DIGEST_FIELDS:
        digest.update(np.ascontiguousarray(getattr(st, name)).tobytes())
    c.check_no_errors()
    print(json.dumps({
        "config": f"diet_ab:{engine}:diet={config.env_str('RAFT_TPU_DIET', default='0')}",
        "value": round(ms_per_round, 4),
        "unit": "ms/round",
        "extra": {
            "engine_requested": engine,
            "engine_after": c.engine,
            "diet": c._diet,
            "ms_per_round": ms_per_round,
            "carry_bytes_per_lane": carry_bytes / lanes,
            "live_buffer_bytes": live_buffer_bytes(),
            "digest": digest.hexdigest(),
            "backend": jax.default_backend(),
        },
    }), flush=True)


def run_child(engine: str, diet: str, extra_env: dict | None = None) -> dict:
    env = dict(
        os.environ,
        RAFT_TPU_ENGINE=engine,
        RAFT_TPU_DIET=diet,
        # the acceptance matrix runs with every observability plane live
        RAFT_TPU_METRICS="1",
        RAFT_TPU_CHAOS="1",
        RAFT_TPU_TRACELOG="1",
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
        for diet in ("0", "1"):
            r = run_child(eng.split()[0], diet, kenv)
            print(json.dumps(r), flush=True)
            arms[(eng, diet)] = r

    fails = []
    base = arms[("xla", "0")]["extra"]
    on_tpu = base["backend"] == "tpu"
    for key, r in arms.items():
        ex = r["extra"]
        if ex["digest"] != base["digest"]:
            fails.append(
                f"{key}: trajectory digest diverged from xla diet-off — "
                "packing is not invisible"
            )
        if ex["engine_requested"] == "pallas" and (
            ex["engine_after"] != "pallas"
        ):
            fails.append(
                f"{key}: child fell back to {ex['engine_after']} "
            )
    for eng in ("xla", "pallas", f"pallas K={ab_k}"):
        off = arms[(eng, "0")]["extra"]
        on = arms[(eng, "1")]["extra"]
        shrink = 1 - on["carry_bytes_per_lane"] / off["carry_bytes_per_lane"]
        if shrink < 0.30:
            fails.append(
                f"{eng}: diet shrank carry bytes/lane only "
                f"{100 * shrink:.1f}% ({off['carry_bytes_per_lane']:.1f} -> "
                f"{on['carry_bytes_per_lane']:.1f}), < 30% floor"
            )
        ratio = arms[(eng, "1")]["value"] / max(arms[(eng, "0")]["value"], 1e-9)
        if on_tpu and ratio > tol:
            fails.append(
                f"{eng}: diet regressed round time "
                f"(ratio {ratio:.3f} > tol {tol})"
            )
    print(json.dumps({
        "metric": "diet_ab",
        "ok": not fails,
        "carry_bytes_per_lane_off": base["carry_bytes_per_lane"],
        "carry_bytes_per_lane_on": arms[("xla", "1")]["extra"][
            "carry_bytes_per_lane"
        ],
        "shrink_pct": round(
            100 * (1 - arms[("xla", "1")]["extra"]["carry_bytes_per_lane"]
                   / base["carry_bytes_per_lane"]), 1,
        ),
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

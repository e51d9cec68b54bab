#!/usr/bin/env python3
"""Find the knee of a served cell once, on the chip: one process sets the
cell up, then offers its traffic at each rate in turn for `--seconds`, and
reports for each what the window saw. The knee is the highest rate with no
refused request and no backlog that grows over the window; the cell's
traffic file then fixes its rate below it. The benchmark's own runs never
sweep.

    python3 benchmark/sweep.py --workload tikv_rf3_1m.ycsb_a \\
        --seconds 15 --rates 200 400 800 1600
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run
import ycsb


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_spec(), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    from raft_tpu.serve import ServeLoop
    from raft_tpu.utils.compile_cache import enable_persistent_cache

    import fleet

    enable_persistent_cache()
    driver = run.load_module(cell.driver_path, "bench_driver")
    cfg, tr = cell.config, cell.traffic
    t0 = time.perf_counter()
    loop = ServeLoop(fleet.build(cfg, args.seed, 1),
                     auto_compact_lag=cfg["compact_lag"])
    loop.bootstrap(max_rounds=tr["bootstrap_bound_rounds"])
    client = driver.Client(loop, tr["field_bytes"], jax.profiler.TraceAnnotation)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for i, rate in enumerate(args.rates):
        reqs = ycsb.requests(args.seed, 10 + i, rate, args.seconds,
                             tr["read_share"], cfg["records"],
                             tr["zipfian_theta"])
        rejected0 = client.rejected
        quarters = []
        for q in range(4):
            part = ycsb.Requests(*(x[(reqs.due >= q * args.seconds / 4)
                                     & (reqs.due < (q + 1) * args.seconds / 4)]
                                   for x in (reqs.due, reqs.is_read,
                                             reqs.record)))
            part.due = part.due - q * args.seconds / 4
            seen = client.drive(part, args.seconds / 4)
            now = time.perf_counter()
            quarters.append({"open": len(client.pending),
                             "oldest_open_s": max(
                                 (now - due for _, _, due in client.pending),
                                 default=0.0),
                             "acked_per_s": seen["acked"] / seen["window_s"],
                             "ms_per_round": 1e3 * seen["window_s"]
                             / max(1, seen["rounds"]),
                             "put_p99_ms": _p99(seen["lat"]["put"]),
                             "get_p99_ms": _p99(seen["lat"]["get"])})
        print(json.dumps({"rate": rate,
                          "rejected": client.rejected - rejected0,
                          "quarters": quarters}), flush=True)
        client.settle(tr["answer_wait_s"])
    return 0


def _p99(xs):
    return 1e3 * float(np.percentile(xs, 99)) if xs else None


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Read the control of a cell on the chip: for each seed, run the cell once
and check both what the program produced and the control, the same run
with one guarantee broken on purpose (a commit without a majority for the
fleet's cells, reads from a replica the mix's `control_lag`
acknowledgements behind for the served cells). The benchmark's own runs
never do this.

    python3 benchmark/control.py --workload tikv_rf3_1m.tick \\
        --seconds 5 --seeds 11 12 13

Prints one JSON line per seed: the program's checks and `correct`, and
the control's readings of the same numbers against the same limits and
`control_correct`, decided as `correct` is.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def control_checks(res: dict) -> list:
    """The control's readings as checks: the run's names and limits, with
    the control's values."""
    return [dict(c, value=res["notes"]["control"][c["name"]])
            for c in res["checks"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_spec(), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False,
                           t0=time.perf_counter(), control=True)
        control = control_checks(res)
        print(json.dumps({
            "seed": seed,
            "correct": res["correct"],
            "control_correct": run.is_correct(control),
            "program": {c["name"]: c["value"] for c in res["checks"]},
            "control": {c["name"]: c["value"] for c in control},
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

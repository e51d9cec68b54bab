#!/usr/bin/env python3
"""The multi-raft benchmark: run one cell once on the chip and print its
result as one JSON line.

    python3 benchmark/run.py --workload tikv_rf3_1m.tick --seed 7 \\
        --seconds 20 --trace 0

A cell is one entry of `workloads` in `BENCHMARK.json`: one configuration
under one traffic mix. Everything the harness runs is found by name, so a
later change adds files and entries and edits none:

- a configuration: `benchmark/configs/<name>.json` (named by the `file` of
  its `configs` entry) holds the fleet's sizes, its source, what was cut
  (`reduced`), what was set without a source (`assumed`) and the
  guarantees it gives;
- a traffic mix: `benchmark/traffic/<traffic>.json` names its `driver`
  and holds the driver's parameters (rates, mixes, chunk sizes). A new mix
  for an existing driver is a data file only;
- a driver: `benchmark/drivers/<driver>.py` defines `run(ctx)`, which
  builds the fleet from the seed, warms every program its traffic uses,
  measures for `ctx.seconds` inside `ctx.window()`, and returns a record
  (`record.Record`) with the numbers the reducers read and the checks
  that decide `correct`. A new kind of traffic brings a new driver;
- a metric: `benchmark/metrics/<metric>.py` defines
  `reduce(rec, trace) -> float | None`, where `rec` is the driver's record
  and `trace` the reduced profiler trace (`trace_reduce.Reduced`, or None
  without `--trace 1`). A reducer that finds nothing to read returns None
  and the metric is left out of the line.

To add a cell, add its entry to `workloads` (and its configuration, mix or
metrics as new files and entries). With `--trace 0` a run prints the
cell's end-to-end metrics; with `--trace 1`, its per-layer metrics and the
device's busy and idle time from a `jax.profiler` trace
(`benchmark/trace_reduce.py`).

Each run prints, as its last lines on standard error and under the last
key of its result line (`checks`), every number that decides `correct`
beside its limit. The plain references that compute them live in
`benchmark/reference/` and import nothing of the program.

The benchmark's own tests run with `pytest benchmark/`. They rehearse both
drivers end to end on the CPU at a tiny size, replay a recorded trace
through the reduction, and plant faults under the timed path to see
`correct` come out false. Off a TPU, or with fewer chips than the cell
asks for, this script exits non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from record import Record, rusage_delta  # noqa: E402


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver_path: str
    end_to_end: list
    per_layer: list


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no BENCHMARK.json at {root}") from None


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, workload: str, root: str = ROOT) -> Cell:
    """Find a cell's configuration, traffic mix, driver and metrics by the
    names BENCHMARK.json gives them."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"{workload}: no configuration {w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(
        os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json")
    )
    driver = os.path.join(root, "benchmark", "drivers", traffic["driver"] + ".py")
    if not os.path.exists(driver):
        raise SpecError(f"{workload}: no driver {driver}")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        driver_path=driver,
        end_to_end=[m for m in spec["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if applies(m, workload)],
    )


def reducer_path(root: str, metric: str) -> str:
    return os.path.join(root, "benchmark", "metrics", metric + ".py")


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included), so a run
    can report any that fall inside the measured window."""

    def __init__(self):
        import jax.monitoring as jm

        self.count = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jm.register_event_duration_secs_listener(on_duration)


class Context:
    """What a driver is given: the cell's configuration and traffic, the
    seed, the window's length, and `window()`, the measured region (traced
    when `trace` is set)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t0: float, trace_dir: str | None, compiles: CompileCounter,
                 control: bool = False):
        self.cell = cell
        # set only by benchmark/control.py: the driver also reads the
        # control (a guarantee broken on purpose) from the same run
        self.control = control
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        # a traced run traces a short window: the trace of a long one is
        # large and slow to read (the mix's `trace_seconds`)
        self.seconds = (
            min(seconds, cell.traffic["trace_seconds"]) if trace else seconds
        )
        self.trace = trace
        self.t0 = t0
        self.trace_dir = trace_dir
        self.window_compiles = 0
        self.window_gc = GcPauses()
        self.window_host = {}
        self._compiles = compiles

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def freeze_host(self) -> None:
        """The end of set-up: collect, then move every object set-up made
        (the fleet's Python side, the serving loop's million group views)
        out of the collector's reach, so that a full collection in the
        window scans only what the window allocates. Without it such a
        collection stalled the served loop for seconds in some windows
        and not others."""
        gc.collect()
        gc.freeze()

    @contextlib.contextmanager
    def window(self):
        """The measured region. Under `--trace 1` the profiler records it,
        and the `bench.window` annotation marks its edges in the trace."""
        import jax

        before = self._compiles.count
        tracing = (
            jax.profiler.trace(self.trace_dir) if self.trace
            else contextlib.nullcontext()
        )
        ru = resource.getrusage(resource.RUSAGE_SELF)
        with tracing, self.annotate("bench.window"), self.window_gc:
            yield
        self.window_compiles = self._compiles.count - before
        self.window_host = rusage_delta(
            ru, resource.getrusage(resource.RUSAGE_SELF))


class GcPauses:
    """Times the interpreter's garbage collections while entered."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self) -> dict:
        full = [t for g, t in self.pauses if g == 2]
        return {
            "collections": len(self.pauses),
            "full_collections": len(full),
            "max_pause_ms": 1e3 * max((t for _, t in self.pauses), default=0.0),
        }


def device_record(devices, mem: int) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem,
    }


def is_correct(checks: list) -> bool:
    """`correct`: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             root: str = ROOT, t0: float = PROCESS_T0,
             control: bool = False) -> dict:
    """Run one cell once on jax.devices()[:cell.chips] and return the
    result line as a dict. Needs no chip itself: main() refuses to start
    without one, and the tests call this directly on the CPU."""
    import jax

    from raft_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    # every program this cell warms goes to the persistent cache, the
    # small ones too, so a run after a checkout's first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    devices = jax.devices()[: cell.chips]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        ctx = Context(cell, seed, seconds, trace, t0, trace_dir, compiles,
                      control)
        driver = load_module(cell.driver_path, "bench_driver")
        try:
            rec: Record = driver.run(ctx)
        finally:
            gc.unfreeze()  # a process that runs cells in turn frees each
        rec.values["device_kind"] = devices[0].device_kind
        reduced = None
        if trace:
            import trace_reduce

            reduced = trace_reduce.reduce_dir(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        mod = load_module(reducer_path(root, m["name"]), "bench_metric")
        value = mod.reduce(rec, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = device_record(devices, rec.memory_peak_bytes)
    result = {
        "correct": is_correct(rec.checks),
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["notes"] = {
        "setup_s": rec.setup_s,
        "window_s": rec.window_s,
        "window_compiles": ctx.window_compiles,
        "window_gc": ctx.window_gc.summary(),
        "window_host": ctx.window_host,
        **rec.notes,
    }
    result["checks"] = rec.checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve(load_spec(), args.workload)
        import raft_tpu  # noqa: F401  (the system under test)
    except (SpecError, ImportError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"benchmark: needs {cell.chips} TPU chip(s); jax found "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for c in result["checks"]:
        print(f"check {c['name']} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A cell cut to a size the CPU runs in seconds, for rehearsals: the
override lives here, in the tests, and never in run.py."""

from __future__ import annotations

import contextlib

import peaks
import run
import trace_reduce

TINY_CONFIG = dict(groups=64, block_groups=16, records=512)
TINY_TRAFFIC = dict(round_chunk=4, rate_per_s=60, warm_seconds=1.0,
                    answer_wait_s=20, readback_keys=32)


def cell(workload: str, root: str = run.ROOT) -> run.Cell:
    c = run.resolve(run.load_spec(root), workload, root)
    c.config.update(TINY_CONFIG)
    c.traffic.update({k: v for k, v in TINY_TRAFFIC.items()
                      if k in c.traffic})
    return c


@contextlib.contextmanager
def cpu_trace_names():
    """A CPU trace has no device plane: read the XLA:CPU worker threads'
    events as the device's operations, and the host's dispatch of the
    jitted round program (on the line named after the interpreter,
    `python` or `python3`) as its executions. The CPU gets a nominal peak
    so the roofline's arithmetic runs; its number means nothing."""
    saved = (trace_reduce.DEVICE_PREFIX, trace_reduce.OPS_LINE,
             trace_reduce.MODULES_LINE)
    peaks.DEVICES["cpu"] = {"hbm_bytes_per_s": 1e11}
    trace_reduce.DEVICE_PREFIX = "/host:CPU"
    trace_reduce.OPS_LINE = "tf_XLAEigen*"
    trace_reduce.MODULES_LINE = "python*"
    try:
        yield
    finally:
        (trace_reduce.DEVICE_PREFIX, trace_reduce.OPS_LINE,
         trace_reduce.MODULES_LINE) = saved
        del peaks.DEVICES["cpu"]


def run_tiny(workload: str, seed: int = 2**31 + 99, seconds: float = 1.5,
             trace: bool = False, control: bool = False) -> dict:
    c = cell(workload)
    if trace:
        with cpu_trace_names():
            return run.run_cell(c, seed, seconds, True, control=control)
    return run.run_cell(c, seed, seconds, False, control=control)

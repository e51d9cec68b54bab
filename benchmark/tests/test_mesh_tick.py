"""The four-chip cell rehearsed on four virtual CPU devices: the `mesh_tick`
driver end to end at `tiny.py`'s size, untraced and traced, its control,
and a fault planted under each of the checks it adds.

The CPU backend takes its device count once, when it starts, and this
package's conftest.py asks for none; so the runs go in one child process
that asks for four (`XLA_FLAGS`), and each test reads its part of what the
child printed."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import run

CELL = "tikv_rf3_4m_x4.tick"
HERE = os.path.dirname(os.path.abspath(__file__))

CHILD = r"""
import contextlib, json, sys
sys.path[:0] = [TESTS, BENCH, ROOT]
import run, tiny
from raft_tpu.parallel.sharded import ShardedFusedCluster as Sharded

CELL = "tikv_rf3_4m_x4.tick"
SEED = 2**31 + 23
WINDOW = {"on": False}  # set once the measured window has begun
measured = run.Context.window


@contextlib.contextmanager
def window(self):
    WINDOW["on"] = True
    with measured(self):
        yield


run.Context.window = window


def committed_altered(self, *names):
    # one lane's committed column, as the check reads the mesh's columns
    cols = self.inner.state_columns(*names)
    if WINDOW["on"] and "log_term" in names:
        cols["committed"] = cols["committed"].copy()
        cols["committed"][0] += 1
    return cols


def tally_off_by_one(self):
    snap = self.inner.metrics_snapshot()
    if WINDOW["on"]:
        snap["counters"]["commits"] += 1
    return snap


def summary(**kw):
    WINDOW["on"] = False
    res = tiny.run_tiny(CELL, **kw)
    return {
        "correct": res["correct"],
        "attempted": res["attempted"],
        "metrics": {k: m["value"] for k, m in res["metrics"].items()},
        "failed_checks": sorted(
            c["name"] for c in res["checks"] if c["value"] > c["limit"]),
        "checks": [c["name"] for c in res["checks"]],
        "control": res["notes"].get("control"),
        "notes": {k: res["notes"][k] for k in (
            "window_compiles", "window_dispatches", "twin")},
    }


out = {
    "e2e": summary(seed=SEED, control=True),
    "traced": summary(seed=SEED + 1, trace=True),
}
for name, attr, fault in (
    ("twin_fault", "state_columns", committed_altered),
    ("tally_fault", "metrics_snapshot", tally_off_by_one),
):
    orig = Sharded.__dict__.get(attr)
    setattr(Sharded, attr, fault)
    try:
        out[name] = summary(seed=SEED + 2)
    finally:
        if orig is None:
            delattr(Sharded, attr)
        else:
            setattr(Sharded, attr, orig)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    bench = os.path.dirname(HERE)
    code = (CHILD.replace("TESTS", repr(HERE))
            .replace("BENCH", repr(bench))
            .replace("ROOT", repr(os.path.dirname(bench))))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("xla_cache")),
    )
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1500)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def names(kind: str) -> set:
    cell = run.resolve(run.load_spec(), CELL)
    return {m["name"] for m in getattr(cell, kind)}


def test_mesh_tick_end_to_end_on_four_cpu_devices(child):
    res = child["e2e"]
    assert res["correct"], res["failed_checks"]
    assert res["attempted"] == 64
    assert set(res["metrics"]) == names("end_to_end")
    assert {"psum_tally_off", "mesh_twin_lanes_differ"} <= set(res["checks"])
    assert res["notes"]["window_compiles"] == 0
    assert res["notes"]["window_dispatches"] > 0


def test_mesh_tick_traced_reports_every_per_layer_metric(child):
    res = child["traced"]
    assert res["correct"], res["failed_checks"]
    assert set(res["metrics"]) == names("per_layer")
    assert 0 < res["metrics"]["mesh.round_roofline"] <= 100


def test_mesh_tick_control_is_not_correct(child):
    res = child["e2e"]
    control = [dict(name=n, value=res["control"][n], limit=0)
               for n in res["checks"]]
    assert not run.is_correct(control)
    assert res["control"]["psum_tally_off"] > 0
    assert res["control"]["mesh_twin_lanes_differ"] > 0


@pytest.mark.parametrize("fault, trips", [
    ("twin_fault", "mesh_twin_lanes_differ"),
    ("tally_fault", "psum_tally_off"),
], ids=["committed_altered", "tally_off_by_one"])
def test_mesh_tick_planted_fault_is_not_correct(child, fault, trips):
    res = child[fault]
    assert not res["correct"]
    assert trips in res["failed_checks"]

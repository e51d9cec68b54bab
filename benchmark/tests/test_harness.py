"""The harness: BENCHMARK.json resolves to files found by name, a new cell
or metric needs only new files and entries, both drivers run end to end
on the CPU at a tiny size, and run.py refuses to run without a chip."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run
import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = run.load_spec()


def test_every_cell_resolves_to_its_files():
    for w in SPEC["workloads"]:
        cell = run.resolve(SPEC, w["name"])
        assert os.path.exists(cell.driver_path)
        assert cell.chips in (1, 4)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for c in SPEC["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(run.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"] + SPEC["per_layer"], ids=lambda m: m["name"]
)
def test_every_metric_has_a_reducer_and_its_cells_report_what_it_moves(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert os.path.exists(run.reducer_path(run.ROOT, metric["name"]))
    if "moves" in metric:
        moved = {m["name"]: m for m in SPEC["end_to_end"]}[metric["moves"]]
        assert metric["layer"] and "\n" not in metric["layer"]
        for cell in metric["workloads"]:
            assert run.applies(moved, cell), (metric["name"], cell)


def test_a_new_cell_mix_and_metric_are_found_with_no_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(run.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = dict(SPEC)
    before = {p: (root / "benchmark" / p).read_bytes()
              for p in ("run.py", "drivers/fleet_tick.py")}
    (root / "benchmark/traffic/tick_long_chunks.json").write_text(json.dumps(
        {"driver": "fleet_tick", "round_chunk": 16, "elect_bound_rounds": 640,
         "steady_rounds": 32}))
    (root / "benchmark/metrics/rounds_per_s.py").write_text(
        "def reduce(rec, trace):\n"
        "    return rec.values['rounds'] / rec.window_s\n")
    spec["workloads"] = SPEC["workloads"] + [{
        "name": "tikv_rf3_1m.tick_long_chunks", "config": "tikv_rf3_1m",
        "traffic": "tick_long_chunks", "chips": 1, "why": "test"}]
    spec["per_layer"] = SPEC["per_layer"] + [{
        "name": "rounds_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "block scheduler",
        "moves": "group_ticks_per_s",
        "workloads": ["tikv_rf3_1m.tick_long_chunks"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.resolve(run.load_spec(str(root)), "tikv_rf3_1m.tick_long_chunks",
                       str(root))
    assert cell.traffic["round_chunk"] == 16
    assert [m["name"] for m in cell.per_layer] == ["rounds_per_s"]
    mod = run.load_module(run.reducer_path(str(root), "rounds_per_s"), "m")
    rec = run.Record(1.0, 2.0, 1, 0, {"rounds": 8}, [], 0)
    assert mod.reduce(rec, None) == 4.0
    for p, b in before.items():
        assert (root / "benchmark" / p).read_bytes() == b


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_tick_driver_end_to_end_on_cpu(trace):
    res = tiny.run_tiny("tikv_rf3_1m.tick", trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 64 and res["failed"] == 0
    names = set(res["metrics"])
    if trace:
        assert {"round.device_ms", "device.idle_share.tick"} <= names
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {"group_ticks_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_served_driver_end_to_end_on_cpu(trace):
    res = tiny.run_tiny("tikv_rf3_1m.ycsb_a", trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    if trace:
        assert names == {"device.idle_share.serve", "serve.host_ms_per_round",
                         "serve.dispatch_ms_per_round"}
    else:
        assert names == {"ops_per_s", "get_p99_ms", "setup_s"}
    assert res["notes"]["puts"] > 0 and res["notes"]["gets"] > 0


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tikv_rf3_1m.tick",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_py_off_a_tpu_exits_nonzero_with_no_result():
    p = _run_py(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_py_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(run.ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""The canonical-carry table behind round_roofline matches the slim,
unpacked carry the round program had when the table was derived. It pins
that derivation: a later layout may differ, and the table stays."""

from __future__ import annotations

import importlib.util
import json
import os

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def table():
    with open(os.path.join(BENCH, "carry_bytes.json")) as f:
        return json.load(f)["columns"]


def live_columns(v=3, w=16, e=2, f=4, r=5, groups=2):
    from raft_tpu.config import Shape
    from raft_tpu.ops.fused import FusedCluster

    shape = Shape(n_lanes=groups * v, max_peers=v, log_window=w,
                  max_msg_entries=e, max_inflight=f, max_read_index=r)
    c = FusedCluster(groups, v, seed=1, shape=shape)
    sym = {v: "V", w: "W", e: "E", f: "F", r: "R"}
    out = []
    for prefix, tree in (("state", c.state), ("fabric", c.fab)):
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                jax.eval_shape(lambda t: t, tree)):
            name = prefix + "." + ".".join(p.name for p in path)
            assert leaf.shape[0] == groups * v
            out.append({"column": name,
                        "per_lane": [sym[d] for d in leaf.shape[1:]],
                        "bytes": leaf.dtype.itemsize})
    return out


def test_table_matches_the_slim_carry_it_was_derived_from():
    assert table() == live_columns()


def test_roofline_reducer_reads_the_table_and_the_peaks():
    spec = importlib.util.spec_from_file_location(
        "round_roofline", os.path.join(BENCH, "metrics", "round_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rf3 = dict(voters=3, log_window=16, max_msg_entries=2, max_inflight=2,
               max_read_index=2)
    assert mod.carry_bytes_per_lane(rf3) == 748
    assert mod.carry_bytes_per_lane(dict(rf3, voters=5)) == 1016
    import peaks

    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9

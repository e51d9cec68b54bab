"""The long mesh twins, in a file of their own so that xdist's loadfile
sweep runs them beside tests/test_mesh.py rather than after it: carry
donation on the mesh dispatch path, the psum'd metrics and chaos planes,
and the benches/multichip_ab.py subprocess digest twin."""

import os
import subprocess
import sys

import numpy as np
import pytest

from raft_tpu.parallel.mesh import MeshBlockedCluster
from raft_tpu.scheduler import BlockedFusedCluster
from tests.test_mesh import (  # noqa: F401  (devices is a fixture)
    _block_shape,
    _digest,
    _drive,
    _set_env,
    devices,
)

# the largest shard_map programs abort XLA's CPU executable serializer
pytestmark = pytest.mark.usefixtures("no_persistent_cache")


def test_mesh_donation_digest(monkeypatch, devices):
    """Donated carries on the MESH dispatch path: both donation modes
    land on the same trajectory."""
    g, v, bg = 16, 3, 8

    def twin(donate):
        _set_env(monkeypatch, donate=donate)
        return _drive(
            MeshBlockedCluster(g, v, block_groups=bg, devices=devices,
                               seed=9, shape=_block_shape(bg, v)),
            g, v,
        )

    assert _digest(twin("0")) == _digest(twin("1"))


# -- psum'd planes: metrics + chaos ----------------------------------------


def test_mesh_metrics_chaos_match_blocked(monkeypatch, devices):
    """Metrics counters are psum'd across shards inside each block's
    dispatch and chaos recovery tallies recounted globally: the aggregate
    snapshots must equal the single-chip scheduler's under an identical
    deterministic fault pattern."""
    _set_env(monkeypatch, metrics="1", chaos="1")
    g, v, bg = 16, 3, 8
    n = g * v

    def build(cls, **kw):
        c = cls(g, v, block_groups=bg, seed=13, shape=_block_shape(bg, v),
                **kw)
        drops = np.zeros((n, v), np.int32)  # per-edge drop budget
        drops[:: max(n // 8, 1), 0] = 1
        c.set_chaos(drop_num=drops, heal_round=8)
        return _drive(c, g, v)

    mono = build(BlockedFusedCluster)
    mesh = build(MeshBlockedCluster, devices=devices)
    assert mesh.metrics_enabled and mesh.chaos_enabled
    assert _digest(mesh) == _digest(mono)
    ms, bs = mesh.metrics_snapshot(), mono.metrics_snapshot()
    assert ms["counters"] == bs["counters"]
    mc, bc = mesh.chaos_columns(), mono.chaos_columns()
    assert set(mc) == set(bc)
    for name in mc:
        np.testing.assert_array_equal(
            np.asarray(mc[name]), np.asarray(bc[name]), err_msg=name
        )


# -- per-(shard, block) stream payloads ------------------------------------


# -- subprocess digest twin (the full acceptance matrix) -------------------


def test_multichip_ab_subprocess_digest_twin():
    """benches/multichip_ab.py at K=1 smoke shape: mono, mesh AND the
    scalar FusedCluster arm must land on one digest with diet + metrics +
    chaos + trace + donation all on, per-(shard, block) payloads included
    (fresh subprocesses on the forced 8-device CPU mesh)."""
    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benches", "multichip_ab.py",
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        AB_GROUPS="8", AB_BLOCK_GROUPS="8",  # bg == groups: single arm too
        AB_ROUNDS="4", AB_ITERS="2",
    )
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count=8 {flags}".strip()
        )
    out = subprocess.run(
        [sys.executable, bench, "--smoke"],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert '"ok": true' in out.stdout

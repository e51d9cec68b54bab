"""Compile the main path for a described TPU v5e, with no chip attached.

Nothing here runs: each test lowers and compiles for the chip's compiler,
which refuses what interpret mode and the CPU backend accept (unaligned
kernel blocks, vector shape casts, programs that do not fit HBM). The
topology is described inside a module fixture, never at import time: only
one process may load libtpu, and xdist workers import every test file
(on-chip-measurement guide, section 2).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from raft_tpu.config import Shape
from raft_tpu.ops import fused
from raft_tpu.ops import pallas_round as plr
from raft_tpu.ops import quorum_pallas as qp
from raft_tpu.ops.fused import FusedCluster

V = 3
BENCH_BLOCK = 65_536  # bench.py / chip_smoke.py block: 16 of them = 1M groups
QUORUM_LANES = 196_608
HBM_BYTES = 16 * 1024**3  # one v5e chip


def _shape(groups: int) -> Shape:
    return Shape(
        n_lanes=groups * V, max_peers=V, log_window=16, max_msg_entries=2,
        max_inflight=2, max_read_index=2,
    )


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def test_xla_round_compiles_at_the_bench_block(one_chip):
    """The default engine's donating round program at one 65,536 x 3
    block (W=16, E=2, metrics plane on): compiles, and sixteen resident
    blocks' carries plus one block's temporaries fit one chip's HBM."""
    c = FusedCluster(BENCH_BLOCK, V, seed=1, shape=_shape(BENCH_BLOCK))
    assert c._donate and c.metrics is not None
    compiled = fused._fused_rounds_jit.lower(
        *_specs((c.state, c.fab, c._no_ops, c.mute), one_chip),
        v=V, n_rounds=8, do_tick=True, auto_propose=True,
        auto_compact_lag=8, ops_first_round_only=True, paged_inkernel=False,
        metrics=_specs(c.metrics, one_chip), chaos=None, trace=None,
        paged=None,
    ).compile()
    mem = compiled.memory_analysis()
    resident = 16 * mem.argument_size_in_bytes
    assert resident + mem.temp_size_in_bytes < HBM_BYTES
    assert "input_output_alias" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["committed", "joint_committed"])
def test_quorum_kernel_compiles_to_mosaic(one_chip, kernel):
    match = jax.ShapeDtypeStruct((QUORUM_LANES, V), jnp.int32,
                                 sharding=one_chip)
    mask = jax.ShapeDtypeStruct((QUORUM_LANES, V), jnp.bool_,
                                sharding=one_chip)
    fn = {"committed": qp.committed_pallas,
          "joint_committed": qp.joint_committed_pallas}[kernel]
    args = (match, mask) if kernel == "committed" else (match, mask, mask)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_round_is_refused_by_mosaic(one_chip, monkeypatch):
    """ROADMAP C3: Mosaic does not lower the round body (its 1-D rolls
    and vector reshapes), so engine="pallas" raises on a TPU with the
    compiler's message. When this starts compiling, the engine can be
    timed on the chip: turn this test around."""
    monkeypatch.setenv("RAFT_TPU_METRICS", "0")
    g = 1024
    c = FusedCluster(g, V, seed=1, shape=_shape(g))
    lowered = plr._pallas_rounds_nodonate_jit.lower(
        *_specs((c.state, c.fab, c._no_ops, c.mute), one_chip),
        v=V, tile_lanes=plr.default_tile(g * V, V), n_rounds=1,
        rounds_per_call=1, do_tick=True, auto_propose=True,
        auto_compact_lag=8, ops_first_round_only=True, interpret=False,
        paged_inkernel=False, metrics=None, chaos=None, trace=None,
        paged=None,
    )
    with pytest.raises(Exception, match="Mosaic failed to compile"):
        lowered.compile()


def test_sharded_stepper_compiles_on_a_2x2_mesh(topo, one_chip):
    """The shard_map stepper MeshBlockedCluster dispatches per block,
    lowered over the four described chips with NamedSharding inputs:
    every lane column splits over the mesh, and the metrics psum is the
    only collective."""
    from raft_tpu.parallel.sharded import ShardedFusedCluster

    g = 4096
    s = ShardedFusedCluster(g, V, seed=1, shape=_shape(g),
                            devices=jax.devices()[:4])
    mesh = Mesh(np.asarray(topo.devices), ("groups",))
    s.mesh = mesh
    jit = s._build_stepper("xla", 8, True, True, 8, 1)
    n = g * V
    lanes, repl = NamedSharding(mesh, P("groups")), NamedSharding(mesh, P())

    def spec(x):
        lead = x.ndim >= 1 and x.shape[0] == n
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=lanes if lead else repl)

    inner = s.inner
    args = (inner.state, inner.fab, s._no_ops, inner.mute, inner.metrics)
    compiled = jit.lower(*jax.tree.map(spec, args)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    assert "all-to-all" not in text and "all-gather" not in text

"""Pallas quorum kernel == XLA quorum ops, bit-exact (interpret mode on the
CPU test mesh; the same kernel compiles for real on TPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.ops import pallas_round as plr
from raft_tpu.ops import quorum as qr
from raft_tpu.ops import quorum_pallas as qp
from raft_tpu.ops.quorum_pallas import (
    committed_pallas,
    joint_committed_dispatch,
    joint_committed_packed,
    joint_committed_pallas,
    pack_voter_major,
)


@pytest.mark.parametrize("v", [1, 3, 5, 7, 8])
def test_committed_matches_xla(v):
    rng = np.random.default_rng(v)
    n = 1500  # non-multiple of the tile to exercise padding
    match = jnp.asarray(rng.integers(0, 1 << 20, (n, v)), jnp.int32)
    mask = jnp.asarray(rng.random((n, v)) < 0.7)
    got = committed_pallas(match, mask, interpret=True)
    want = qr.majority_committed(match, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("v", [3, 5, 7])
def test_joint_matches_xla(v):
    rng = np.random.default_rng(10 + v)
    n = 2048
    match = jnp.asarray(rng.integers(0, 1 << 20, (n, v)), jnp.int32)
    m_in = jnp.asarray(rng.random((n, v)) < 0.8)
    m_out = jnp.asarray(rng.random((n, v)) < 0.4)
    got = joint_committed_pallas(match, m_in, m_out, interpret=True)
    want = qr.joint_committed(match, m_in, m_out)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _joint_case(seed=99, n=513, v=5):
    rng = np.random.default_rng(seed)
    match = jnp.asarray(rng.integers(0, 1 << 20, (n, v)), jnp.int32)
    m_in = jnp.asarray(rng.random((n, v)) < 0.8)
    m_out = jnp.asarray(rng.random((n, v)) < 0.4)
    return match, m_in, m_out


def test_joint_dispatch_defaults_to_pallas(monkeypatch):
    """With the lane-major kernels the per-operand relayout is gone and the
    joint dispatch defaults to the Pallas kernel (RAFT_TPU_QUORUM_PALLAS
    unset -> pallas; =0 restores XLA) — both agree bit-exactly."""
    match, m_in, m_out = _joint_case()
    monkeypatch.delenv("RAFT_TPU_QUORUM_PALLAS", raising=False)
    want = qr.joint_committed(match, m_in, m_out)
    np.testing.assert_array_equal(
        np.asarray(
            joint_committed_dispatch(match, m_in, m_out, interpret=True)
        ),
        np.asarray(want),
    )
    monkeypatch.setenv("RAFT_TPU_QUORUM_PALLAS", "0")
    np.testing.assert_array_equal(
        np.asarray(joint_committed_dispatch(match, m_in, m_out)),
        np.asarray(want),
    )
    # explicit kwarg beats env either way
    np.testing.assert_array_equal(
        np.asarray(
            joint_committed_dispatch(
                match, m_in, m_out, engine="pallas", interpret=True
            )
        ),
        np.asarray(want),
    )
    monkeypatch.setenv("RAFT_TPU_QUORUM_PALLAS", "1")
    np.testing.assert_array_equal(
        np.asarray(
            joint_committed_dispatch(match, m_in, m_out, engine="xla")
        ),
        np.asarray(want),
    )
    with pytest.raises(ValueError, match="unknown engine"):
        joint_committed_dispatch(match, m_in, m_out, engine="bogus")


def test_joint_dispatch_kernel_failure_raises(monkeypatch):
    """A pallas kernel failure propagates: no silent switch to XLA."""
    match, m_in, m_out = _joint_case(seed=7)

    def boom(*a, **k):
        raise RuntimeError("forced quorum kernel failure")

    monkeypatch.setattr(qp, "joint_committed_pallas", boom)
    with pytest.raises(RuntimeError, match="forced quorum kernel failure"):
        joint_committed_dispatch(match, m_in, m_out, engine="pallas")


def test_interpret_is_never_inferred_from_the_backend(monkeypatch):
    """Off a TPU, a kernel not explicitly interpreted fails instead of
    quietly running in the interpreter."""
    match, m_in, m_out = _joint_case(seed=11)
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "0")
    assert not plr.default_interpret()
    with pytest.raises(Exception):
        joint_committed_dispatch(match, m_in, m_out, engine="pallas")
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    assert plr.default_interpret()


def test_joint_dispatch_delegation_via_quorum():
    """ops/quorum.py re-exports the dispatch for callers that never import
    the pallas module directly."""
    match, m_in, m_out = _joint_case(seed=13, n=257, v=3)
    want = qr.joint_committed(match, m_in, m_out)
    got = qr.joint_committed_dispatch(
        match, m_in, m_out, engine="pallas", interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("v", [3, 7])
def test_joint_packed_matches_xla(v):
    """The zero-relayout packed path: pack_voter_major once, reduce many
    times — bit-identical to the XLA joint reduction."""
    rng = np.random.default_rng(20 + v)
    n = 1500  # non-multiple of the tile to exercise padding
    match = jnp.asarray(rng.integers(0, 1 << 20, (n, v)), jnp.int32)
    m_in = jnp.asarray(rng.random((n, v)) < 0.8)
    m_out = jnp.asarray(rng.random((n, v)) < 0.4)
    got = joint_committed_packed(
        pack_voter_major(match),
        pack_voter_major(m_in),
        pack_voter_major(m_out),
        v=v,
        n=n,
        interpret=True,
    )
    want = qr.joint_committed(match, m_in, m_out)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_empty_config_is_inf():
    n, v = 8, 3
    match = jnp.zeros((n, v), jnp.int32)
    mask = jnp.zeros((n, v), bool)
    got = committed_pallas(match, mask, interpret=True)
    assert (np.asarray(got) == np.iinfo(np.int32).max).all()

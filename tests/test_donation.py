"""Carry donation (ops/fused.py donation_enabled) + the round-major
scheduler (scheduler.BlockedFusedCluster).

Three contracts from PR 2's acceptance bar:

1. RAFT_TPU_DONATE=0 and =1 produce bit-identical state/fabric/metrics
   trajectories — donation changes WHERE the carry lives, never a value.
2. Stale host references to donated buffers are never silently re-read:
   the old carry is deleted (reads raise), and every post-run inspection
   API works off the rebound current carry only.
3. The donating jit's lowering actually carries the input-output aliasing
   annotation (and the copying twin doesn't) — the HBM saving is real,
   not a Python-side fiction.

Plus the scheduler satellites: up-front wal length validation, per-block
ops pre-slicing, round_chunk dispatch equivalence, pipeline_depth.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from raft_tpu.ops import fused
from raft_tpu.ops.fused import FusedCluster
from raft_tpu.runtime.wal import WalStream
from raft_tpu.scheduler import BlockedFusedCluster


def _np_tree(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


def _assert_tree_equal(a, b, what):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), what


def _drive(c):
    """A trajectory exercising ops injection, ops-less rounds, and the
    donated metrics carry."""
    c.run(2, auto_propose=True, auto_compact_lag=4)
    c.run(1, ops=c.ops(hup={0: True}), do_tick=False)
    c.run(2, auto_propose=True, auto_compact_lag=4)


# -- 1. bit-identity ------------------------------------------------------


def test_trajectory_bit_identical_donate_on_vs_off(monkeypatch):
    runs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("RAFT_TPU_DONATE", flag)
        c = FusedCluster(4, 3, seed=11)
        assert c._donate == (flag == "1")
        _drive(c)
        runs[flag] = (_np_tree(c.state), _np_tree(c.fab), c.metrics_snapshot())
    _assert_tree_equal(runs["0"][0], runs["1"][0], "state diverged")
    _assert_tree_equal(runs["0"][1], runs["1"][1], "fabric diverged")
    assert runs["0"][2] == runs["1"][2], "metrics diverged"


def test_blocked_trajectory_bit_identical_donate_on_vs_off(monkeypatch):
    runs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("RAFT_TPU_DONATE", flag)
        c = BlockedFusedCluster(4, 3, block_groups=2, seed=5)
        c.run(2, auto_propose=True, auto_compact_lag=4)
        c.run(1, ops=c.ops(hup={0: True, 8: True}), do_tick=False)
        runs[flag] = [_np_tree(b.state) for b in c.blocks]
    for s0, s1 in zip(runs["0"], runs["1"]):
        _assert_tree_equal(s0, s1, "blocked state diverged")


# -- 2. stale references --------------------------------------------------


def test_donated_inputs_are_deleted_not_rereadable():
    c = FusedCluster(2, 3, seed=3)
    assert c._donate  # donation is the default
    st0, fab0, met0 = c.state, c.fab, c.metrics
    c.run(1, auto_propose=True)
    assert st0.term.is_deleted()
    assert fab0.rep.kind.is_deleted()
    if met0 is not None:
        assert met0.counters.is_deleted()
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(st0.term)
    # the rebound current carry serves every inspection API
    c.check_no_errors()
    c.leader_lanes()
    snap = c.metrics_snapshot()
    assert snap is None or snap["rounds"] == 1


def test_donate_off_keeps_inputs_alive(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_DONATE", "0")
    c = FusedCluster(2, 3, seed=3)
    st0 = c.state
    c.run(1, auto_propose=True)
    assert not st0.term.is_deleted()
    np.asarray(st0.term)  # still readable


def test_wal_delta_resolves_before_donating_dispatch():
    # WalStream.push holds device references one block behind the live
    # state; the cluster must resolve them before the next dispatch
    # invalidates the buffers (FusedCluster._flush_pending_wal)
    got = []
    wal = WalStream(sink=lambda bid, d: got.append(bid))
    c = FusedCluster(2, 3, seed=7)
    for _ in range(3):
        c.run(2, auto_propose=True, auto_compact_lag=4, wal=wal)
    wal.flush()
    assert got == [0, 1, 2]


def test_rebase_groups_under_donation():
    c = FusedCluster(2, 3, seed=9)
    c.run(4, auto_propose=True, auto_compact_lag=4)
    st0 = c.state
    out = c.rebase_groups([0, 1], delta=-(1 << 20))
    assert set(out) == {0, 1}
    assert st0.term.is_deleted()  # rebase donates too
    c.run(2, auto_propose=True, auto_compact_lag=4)
    c.check_no_errors()


# -- 3. lowering annotation ----------------------------------------------


def _has_donation_annotation(text: str) -> bool:
    return ("tf.aliasing_output" in text) or ("jax.buffer_donor" in text)


def test_lowering_carries_donation_annotation():
    c = FusedCluster(2, 3, seed=1)
    kw = dict(
        v=3, n_rounds=1, do_tick=True, auto_propose=False,
        auto_compact_lag=None, ops_first_round_only=True, straddle=None,
        metrics=c.metrics,
    )
    donating = fused._fused_rounds_jit.lower(
        c.state, c.fab, c._no_ops, c.mute, **kw
    ).as_text()
    copying = fused._fused_rounds_nodonate_jit.lower(
        c.state, c.fab, c._no_ops, c.mute, **kw
    ).as_text()
    assert _has_donation_annotation(donating)
    assert not _has_donation_annotation(copying)


# -- 4. donating programs read back from the persistent compile cache ----

# One fresh process: drive a donating (or copying) FusedCluster, then print
# the donation flag, its persistent-cache hits and misses, and a digest of
# every state + fabric leaf.
_CACHE_CHILD = r"""
import collections
import hashlib
import jax
import numpy as np
seen = collections.Counter()
jax.monitoring.register_event_listener(lambda name, **kw: seen.update([name]))
from raft_tpu.ops.fused import FusedCluster
c = FusedCluster(8, 3, seed=11)
for _ in range(3):
    c.run(8)
for _ in range(2):
    c.run(8, auto_propose=True, auto_compact_lag=4)
c.check_no_errors()
h = hashlib.sha256()
for x in jax.tree_util.tree_leaves((c.state, c.fab)):
    h.update(np.asarray(x).tobytes())
hits = seen["/jax/compilation_cache/cache_hits"]
asked = seen["/jax/compilation_cache/compile_requests_use_cache"]
print(int(c._donate), hits, asked - hits, h.hexdigest())
"""


def cache_children(*runs):
    """Run one _CACHE_CHILD process per (cache_dir, donate) pair, all at
    once, and return their (donate, hits, misses, digest) lines."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CACHE_CHILD], cwd=repo,
            env=dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(cache_dir),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
                RAFT_TPU_DONATE=donate,
                XLA_FLAGS="",
            ),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for cache_dir, donate in runs
    ]
    out = []
    for p in procs:
        so, se = p.communicate(timeout=600)
        assert p.returncode == 0, se[-4000:]
        d, hits, misses, digest = so.split()[-4:]
        out.append((int(d), int(hits), int(misses), digest))
    return out


def test_donating_program_from_warm_cache_matches_copying_twin(tmp_path):
    """On jax 0.4.37 a donating executable read back from the persistent
    cache mis-executed in about a third of warm processes (donated-adjacent
    inputs read as zeros), so donating programs once compiled behind a
    cache fence. That fence is gone: fresh processes that load the donating
    round from a warm cache must land on the copying twin's digest."""
    warm = tmp_path / "donate"
    (d0, _, _, copying), (d1, cold_hits, _, cold) = cache_children(
        (tmp_path / "copy", "0"), (warm, "1")
    )
    assert (d0, d1, cold_hits) == (0, 1, 0)
    assert cold == copying
    for d, hits, misses, digest in cache_children(*[(warm, "1")] * 4):
        # every program, the donating round included, came from the cache
        assert (d, misses) == (1, 0) and hits > 0
        assert digest == copying


# -- scheduler: wal validation, ops binding, dispatch equivalence ---------


def test_blocked_wal_wrong_length_rejected_up_front():
    c = BlockedFusedCluster(4, 3, block_groups=2, seed=2)
    with pytest.raises(ValueError, match="one stream per resident block"):
        c.run(1, wal=[WalStream()])
    with pytest.raises(ValueError, match="expected K=2"):
        c.run(1, wal=[WalStream(), WalStream(), WalStream()])
    with pytest.raises(TypeError, match="sequence of K WalStreams"):
        c.run(1, wal=WalStream())


def test_blocked_ops_preslice_cache_and_list_binding():
    c = BlockedFusedCluster(4, 3, block_groups=2, seed=4)
    ops = c.ops(hup={0: True, 6: True})  # lane 6 lives in block 1
    per = c.prepare_ops(ops)
    assert len(per) == 2
    # re-injecting the same object hits the identity LRU (slot 0 = MRU)
    c.run(1, ops=ops, do_tick=False)
    assert c._ops_cache and c._ops_cache[0][0] is ops
    cached = c._ops_cache[0][1]
    c.run(1, ops=ops, do_tick=False)
    assert c._ops_cache[0][1] is cached
    # a prepare_ops list binds as-is; wrong length is rejected
    c.run(1, ops=per, do_tick=False)
    with pytest.raises(ValueError, match="per-block ops list"):
        c.run(1, ops=per[:1], do_tick=False)


def test_blocked_round_chunk_dispatch_equivalent():
    final = []
    for chunk in (1, 4):
        c = BlockedFusedCluster(4, 3, block_groups=2, seed=6, round_chunk=chunk)
        c.run(5, ops=c.ops(hup={0: True, 7: True}), auto_propose=True,
              auto_compact_lag=4)
        final.append([_np_tree(b.state) for b in c.blocks])
    for s0, s1 in zip(final[0], final[1]):
        _assert_tree_equal(s0, s1, "round_chunk changed the trajectory")


def test_blocked_pipeline_depth():
    c = BlockedFusedCluster(4, 3, block_groups=2, seed=8, pipeline_depth=1)
    c.run(3, auto_propose=True, auto_compact_lag=4)
    c.block_until_ready()
    c.check_no_errors()
    ref = BlockedFusedCluster(4, 3, block_groups=2, seed=8)
    ref.run(3, auto_propose=True, auto_compact_lag=4)
    for b0, b1 in zip(c.blocks, ref.blocks):
        _assert_tree_equal(_np_tree(b0.state), _np_tree(b1.state),
                           "pipeline_depth changed the trajectory")
    with pytest.raises(ValueError, match="pipeline_depth"):
        BlockedFusedCluster(4, 3, block_groups=2, pipeline_depth=0)
    with pytest.raises(ValueError, match="round_chunk"):
        BlockedFusedCluster(4, 3, block_groups=2, round_chunk=0)

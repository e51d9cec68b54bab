"""What decides `correct`, shown to fail: the control (a guarantee broken on
purpose) and faults planted under the timed path each turn a run's
`correct` false, while the sound run at the same size stays true.

Everything runs on the CPU at a tiny size; on the chip the same controls
run at the cells' own sizes through benchmark/control.py."""

from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp
import pytest

import control
import run
import tiny
from reference import kv_history

TICK, SERVE = "tikv_rf3_1m.tick", "tikv_rf3_1m.ycsb_a"


@contextlib.contextmanager
def in_window(monkeypatch):
    """A flag that is set while the driver's measured window runs."""
    state = {"on": False}
    orig = run.Context.window

    @contextlib.contextmanager
    def window(self):
        with orig(self):
            state["on"] = True
            try:
                yield
            finally:
                state["on"] = False

    monkeypatch.setattr(run.Context, "window", window)
    yield state


def failed_checks(res) -> set:
    return {c["name"] for c in res["checks"] if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload", [TICK, SERVE])
def test_control_is_not_correct(workload):
    res = tiny.run_tiny(workload, seed=2**31 + 7, control=True)
    assert res["correct"], res["checks"]
    assert not run.is_correct(control.control_checks(res)), res["notes"]["control"]


def _fault_unchanged_state(monkeypatch, flag):
    from raft_tpu.scheduler import BlockedFusedCluster

    orig = BlockedFusedCluster.run

    def frozen(self, rounds=1, **kw):
        if not flag["on"]:
            return orig(self, rounds, **kw)

    monkeypatch.setattr(BlockedFusedCluster, "run", frozen)


def _fault_half_the_blocks(monkeypatch, flag):
    from raft_tpu.scheduler import BlockedFusedCluster

    orig = BlockedFusedCluster.run

    def half(self, rounds=1, **kw):
        if not flag["on"]:
            return orig(self, rounds, **kw)
        blocks, k = self.blocks, self.k
        self.blocks, self.k, self.plan.k = blocks[: k // 2], k // 2, k // 2
        try:
            orig(self, rounds, **kw)
        finally:
            self.blocks, self.k, self.plan.k = blocks, k, k

    monkeypatch.setattr(BlockedFusedCluster, "run", half)


def _fault_altered_entry(monkeypatch, flag):
    from raft_tpu.scheduler import BlockedFusedCluster

    orig = BlockedFusedCluster.run

    def altered(self, rounds=1, **kw):
        orig(self, rounds, **kw)
        if flag["on"]:
            b = self.blocks[0]
            b.state = dataclasses.replace(
                b.state, log_term=b.state.log_term.at[1].add(jnp.int32(1)))

    monkeypatch.setattr(BlockedFusedCluster, "run", altered)


def _fault_leader_demoted(monkeypatch, flag):
    from raft_tpu.scheduler import BlockedFusedCluster

    orig = BlockedFusedCluster.run

    def demoted(self, rounds=1, **kw):
        orig(self, rounds, **kw)
        if flag["on"]:
            b = self.blocks[0]
            b.state = dataclasses.replace(
                b.state, state=jnp.zeros_like(b.state.state))

    monkeypatch.setattr(BlockedFusedCluster, "run", demoted)


def _fault_error_flag(monkeypatch, flag):
    from raft_tpu.scheduler import BlockedFusedCluster

    orig = BlockedFusedCluster.run

    def flagged(self, rounds=1, **kw):
        orig(self, rounds, **kw)
        if flag["on"]:
            b = self.blocks[0]
            b.state = dataclasses.replace(
                b.state, error_bits=b.state.error_bits.at[0].set(1))

    monkeypatch.setattr(BlockedFusedCluster, "run", flagged)


@pytest.mark.parametrize("fault, trips", [
    (_fault_unchanged_state, {"commit_advance_off"}),
    (_fault_half_the_blocks, {"commit_advance_off"}),
    # the engine repairs an altered log while the window runs, so the
    # replica is caught either holding it or behind in its commits
    (_fault_altered_entry, {"committed_log_mismatch", "commit_advance_off"}),
    (_fault_leader_demoted, {"leader_faults"}),
    (_fault_error_flag, {"error_lanes"}),
], ids=["state_unchanged", "half_the_blocks", "entry_altered",
        "leader_demoted", "error_flag"])
def test_tick_fault_under_the_window_is_not_correct(monkeypatch, fault, trips):
    with in_window(monkeypatch) as flag:
        fault(monkeypatch, flag)
        res = tiny.run_tiny(TICK, seed=2**31 + 11)
    assert not res["correct"]
    assert trips & failed_checks(res)


def _serve_step_unchanged(monkeypatch, flag):
    from raft_tpu.serve import ServeLoop

    orig = ServeLoop._step_one
    dead = {"on": False}

    def frozen(self):
        dead["on"] = dead["on"] or flag["on"]
        if not dead["on"]:
            orig(self)

    monkeypatch.setattr(ServeLoop, "_step_one", frozen)


def _serve_half_the_proposals(monkeypatch, flag):
    from raft_tpu.serve.coalescer import ProposalCoalescer

    orig = ProposalCoalescer.enqueue
    count = {"n": 0}

    def drop_half(self, ticket):
        if flag["on"]:
            count["n"] += 1
            if count["n"] % 2:
                return None  # accepted, and never proposed
        return orig(self, ticket)

    monkeypatch.setattr(ProposalCoalescer, "enqueue", drop_half)


def _serve_answer_altered(monkeypatch, flag):
    from raft_tpu.serve.router import CompletionRouter

    orig = CompletionRouter._finish_read

    def altered(self, rt, index):
        orig(self, rt, index)
        if flag["on"]:
            rt.value = "altered"

    monkeypatch.setattr(CompletionRouter, "_finish_read", altered)


def _serve_write_lost(monkeypatch, flag):
    from raft_tpu.serve.kv import KVStore

    orig = KVStore.apply
    count = {"n": 0}

    def lose_half(self, group, cmd, now):
        if flag["on"]:
            count["n"] += 1
            if count["n"] % 2:
                return True  # acknowledged, and never applied
        return orig(self, group, cmd, now)

    monkeypatch.setattr(KVStore, "apply", lose_half)


@pytest.mark.parametrize("fault, trips", [
    (_serve_step_unchanged, "unanswered"),
    (_serve_write_lost, "readback_mismatch"),
    (_serve_half_the_proposals, "unanswered"),
    (_serve_answer_altered, "reads_not_allowed"),
], ids=["step_unchanged", "write_lost", "half_the_proposals",
        "answer_altered"])
def test_served_fault_under_the_window_is_not_correct(monkeypatch, fault, trips):
    monkeypatch.setitem(tiny.TINY_TRAFFIC, "answer_wait_s", 3)
    with in_window(monkeypatch) as flag:
        fault(monkeypatch, flag)
        res = tiny.run_tiny(SERVE, seed=2**31 + 13)
    assert not res["correct"]
    assert trips in failed_checks(res)


def test_served_request_open_after_the_wait_is_unanswered(monkeypatch):
    """A request still open when the answer wait runs out never came: it
    counts as unanswered, and no record is read back beside a put that is
    still queued, even though later rounds would answer it."""
    orig_load = run.load_module

    def load(path, name):
        mod = orig_load(path, name)
        if name == "bench_driver":
            settle, calls = mod.Client.settle, {"n": 0}

            def runs_out(self, max_s):
                calls["n"] += 1  # the warm-up's wait, then the window's
                return settle(self, 0.0 if calls["n"] == 2 else max_s)

            monkeypatch.setattr(mod.Client, "settle", runs_out)
        return mod

    monkeypatch.setattr(run, "load_module", load)
    monkeypatch.setitem(tiny.TINY_TRAFFIC, "rate_per_s", 200)
    res = tiny.run_tiny(SERVE, seed=2**31 + 17)
    assert res["notes"]["open_after_wait"] > 0
    assert res["notes"]["readback_keys"] == 0
    assert not res["correct"]
    assert "unanswered" in failed_checks(res)


def test_history_reference_on_a_hand_made_history():
    h = kv_history.History()
    p1 = h.submit("put", "k", "a")
    h.complete(p1)
    g_old = h.submit("get", "k")
    p2 = h.submit("put", "k", "b")
    h.complete(g_old, "a")  # concurrent with p2: "a" or "b" both allowed
    h.complete(p2)
    g_new = h.submit("get", "k")
    h.complete(g_new, "a")  # stale: p2 was acknowledged before it began
    g_none = h.submit("get", "other")
    h.complete(g_none, None)
    rb = kv_history.Op("get", "k", "b", h.tick(), h.tick())
    res = kv_history.check(h, [rb])
    assert res == {"unanswered": 0, "reads_not_allowed": 1,
                   "readback_mismatch": 0}

"""Building a configuration's fleet and reading it back: shared by the
drivers. The fleet is the program's `BlockedFusedCluster`, built on the
device from the seed at the configuration's sizes."""

from __future__ import annotations

import numpy as np


def fleet_seed(seed: int) -> int:
    """The engine's seed: any whole number folded into 31 bits."""
    return seed % (1 << 31)


def build(config: dict, seed: int, round_chunk: int):
    from raft_tpu.config import Shape
    from raft_tpu.scheduler import BlockedFusedCluster

    v, bg = config["voters"], config["block_groups"]
    shape = Shape(
        n_lanes=bg * v,
        max_peers=v,
        log_window=config["log_window"],
        max_msg_entries=config["max_msg_entries"],
        max_inflight=config["max_inflight"],
        max_read_index=config["max_read_index"],
    )
    return BlockedFusedCluster(
        config["groups"], v, block_groups=bg, seed=fleet_seed(seed),
        shape=shape, round_chunk=round_chunk,
        heartbeat_tick=config["heartbeat_tick"],
        election_tick=config["election_tick"],
    )


def groups_led(cluster) -> int:
    from raft_tpu.types import StateType

    v, leader = cluster.v, int(StateType.LEADER)
    return sum(
        int((np.asarray(b.state.state).reshape(-1, v) == leader).any(1).sum())
        for b in cluster.blocks
    )


def elect(cluster, rounds_per_step: int, bound: int, lag: int) -> int:
    """Rounds of auto-propose until every group has a leader; raises past
    `bound` rounds."""
    rounds = 0
    while groups_led(cluster) < cluster.g:
        if rounds >= bound:
            raise RuntimeError(
                f"election stalled: {groups_led(cluster)}/{cluster.g} groups "
                f"led after {rounds} rounds"
            )
        cluster.run(rounds_per_step, auto_propose=True, auto_compact_lag=lag)
        rounds += rounds_per_step
    return rounds


def block_columns(cluster, names) -> list:
    """One dict of host columns per resident block."""
    return [b.state_columns(*names) for b in cluster.blocks]

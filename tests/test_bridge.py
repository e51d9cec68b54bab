"""A raft group spanning three engine instances ("hosts") over the
HostBridge: election, replication, payload commit, and failover all cross
host boundaries (SURVEY §5.8 cross-host transport)."""

import numpy as np

from raft_tpu.api.rawnode import RawNodeBatch
from raft_tpu.config import Shape
from raft_tpu.runtime.bridge import HostBridge


def one_lane_host(nid: int, peer_ids):
    shape = Shape(n_lanes=1, max_peers=max(4, len(peer_ids)))
    peers = np.zeros((1, shape.v), np.int32)
    peers[0, : len(peer_ids)] = peer_ids
    # distinct seed per host: each host draws its own randomized election
    # timeouts (same-seed hosts would split-vote in lockstep forever)
    return RawNodeBatch(shape, [nid], peers, seed=nid)


def make_spanning_group():
    """3-voter group, one member per host."""
    bridge = HostBridge()
    hosts = []
    for nid in (1, 2, 3):
        b = one_lane_host(nid, [1, 2, 3])
        bridge.add_host(b, {nid: 0})
        hosts.append(b)
    return bridge, hosts


def test_election_and_commit_across_hosts():
    bridge, hosts = make_spanning_group()
    hosts[0].campaign(0)
    bridge.pump()
    assert hosts[0].basic_status(0)["raft_state"] == "LEADER"
    assert hosts[1].basic_status(0)["lead"] == 1
    assert hosts[2].basic_status(0)["lead"] == 1

    hosts[0].propose(0, b"cross-host-payload")
    bridge.pump()
    got = {
        h: [e.data for e in ents if e.data]
        for (h, lane), ents in bridge.committed.items()
    }
    assert got[0] == got[1] == got[2] == [b"cross-host-payload"], got
    assert bridge.dropped == 0


def test_leader_host_failure_and_failover():
    """Kill the leader's host (stop delivering to/from it): the remaining
    hosts elect a new leader across the bridge."""
    bridge, hosts = make_spanning_group()
    hosts[0].campaign(0)
    bridge.pump()
    assert hosts[0].basic_status(0)["raft_state"] == "LEADER"

    # "fail" host 0: rebuild the bridge with only hosts 1 and 2
    b2 = HostBridge()
    b2.add_host(hosts[1], {2: 0})
    b2.add_host(hosts[2], {3: 0})
    # followers time out and campaign; messages to the dead host drop.
    # With only two live voters BOTH must agree, so split votes can repeat
    # for several randomized timeouts before one candidate fires first.
    for _ in range(300):
        hosts[1].tick(0)
        hosts[2].tick(0)
        b2.pump()
        states = [
            hosts[1].basic_status(0)["raft_state"],
            hosts[2].basic_status(0)["raft_state"],
        ]
        if "LEADER" in states:
            break
    assert "LEADER" in states, states
    assert b2.dropped > 0  # traffic to the failed host was dropped


def test_bridge_over_wire_codec():
    """Same spanning-group election/commit, but every message crosses the
    bridge as raftpb wire bytes through the C++ codec."""
    bridge, hosts = make_spanning_group()
    bridge.wire = True
    hosts[0].campaign(0)
    bridge.pump()
    assert hosts[0].basic_status(0)["raft_state"] == "LEADER"
    hosts[0].propose(0, b"wire-payload")
    bridge.pump()
    got = {
        h: [e.data for e in ents if e.data]
        for (h, lane), ents in bridge.committed.items()
    }
    assert got[0] == got[1] == got[2] == [b"wire-payload"], got

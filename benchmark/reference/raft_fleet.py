"""Plain reference for a fleet of raft groups stepped in rounds: from every
replica's term, role, log and commit index, what raft allows, group by
group, written with numpy alone.

Raft's guarantees, as the etcd-raft paper and library state them
(Ongaro & Ousterhout, "In Search of an Understandable Consensus
Algorithm", section 5):

- election safety: one leader per group, and every voter follows it;
- the commit rule: an index is committed only once a majority of voters
  hold the leader's entry at that index;
- log matching and state machine safety: every voter's committed entries
  equal the leader's at the same indexes;
- applied never passes committed.

And one accounting fact of the traffic: each round the leader of every
group appends one entry, so once the fleet is steady, R rounds commit R
entries in every replica.

The columns are those a replica keeps (raft's HardState and log): per
lane, `id`, `term`, `state` (0 follower, 1 candidate, 2 leader,
3 pre-candidate, as in etcd-raft's StateType), `lead`, `last`,
`committed`, `applied`, `snap_index` (the first index is snap_index + 1),
`error_bits`, and the log's terms `log_term` [lane, W], where entry i sits
in slot i mod W. Lanes are grouped V at a time.
"""

from __future__ import annotations

import numpy as np

LEADER = 2
FIELDS = (
    "id", "term", "state", "lead", "last", "committed", "applied",
    "snap_index", "log_term", "error_bits",
)
CHECKS = (
    "leader_faults", "commit_beyond_quorum", "committed_log_mismatch",
    "commit_advance_off", "error_lanes",
)


def _groups(cols: dict, v: int) -> dict:
    out = {}
    for k, x in cols.items():
        x = np.asarray(x).astype(np.int64)
        out[k] = x.reshape((-1, v) + x.shape[1:])
    return out


def _term_at(log_term, index):
    """Term of entry `index` [g, v] in each lane's window [g, v, W]."""
    w = log_term.shape[-1]
    return np.take_along_axis(log_term, (index % w)[..., None], axis=-1)[..., 0]


def check_block(end: dict, committed_start, rounds: int, v: int) -> dict:
    """Count, over one block of groups, the groups that break each
    guarantee. `end` holds the FIELDS columns after the window,
    `committed_start` the committed column before it."""
    g = _groups(end, v)
    w = g["log_term"].shape[-1]
    n_groups = g["term"].shape[0]
    is_leader = g["state"] == LEADER
    one = is_leader.sum(axis=1) == 1
    li = np.argmax(is_leader, axis=1)  # the leader's slot (0 where none)
    pick = lambda x: np.take_along_axis(  # noqa: E731
        x, li.reshape((-1, 1) + (1,) * (x.ndim - 2)), axis=1
    )
    l_term, l_id = pick(g["term"]), pick(g["id"])
    l_last, l_snap = pick(g["last"]), pick(g["snap_index"])
    l_log = pick(g["log_term"])  # [g, 1, W]
    leader_faults = ~one | (g["term"] != l_term).any(1) | (g["lead"] != l_id).any(1)

    # every voter's longest prefix that agrees with the leader's log, as
    # far as both windows show it; entries below both snapshots were
    # committed and are agreed by construction
    floor = np.maximum(g["snap_index"], l_snap)
    top = np.minimum(g["last"], l_last)
    matched = floor.copy()
    for k in range(w - 1, -1, -1):
        idx = top - k
        valid = (idx > floor) & (idx <= top)
        agree = _term_at(g["log_term"], idx) == _term_at(
            np.broadcast_to(l_log, g["log_term"].shape), idx
        )
        extends = valid & agree & (matched == idx - 1)
        matched = np.where(extends, idx, matched)
    majority = v // 2 + 1
    quorum = np.sort(matched, axis=1)[:, v - majority][:, None]
    beyond = (g["committed"] > quorum).any(1)

    # committed entries equal the leader's, and applied never passes them
    mismatch = (g["committed"] > g["last"]) | (g["applied"] > g["committed"])
    for k in range(w):
        idx = g["committed"] - k
        valid = (idx > floor) & (idx <= l_last)
        differ = _term_at(g["log_term"], idx) != _term_at(
            np.broadcast_to(l_log, g["log_term"].shape), idx
        )
        mismatch |= valid & differ
    start = np.asarray(committed_start).astype(np.int64).reshape(-1, v)
    advance_off = (g["committed"] - start != rounds).any(1)
    lane_errors = np.asarray(end["error_bits"]).reshape(-1, v) != 0
    bad = leader_faults | beyond | mismatch.any(1) | advance_off | lane_errors.any(1)
    return {
        "groups": n_groups,
        "bad_groups": int(bad.sum()),
        "leader_faults": int(leader_faults.sum()),
        "commit_beyond_quorum": int(beyond.sum()),
        "committed_log_mismatch": int(mismatch.any(1).sum()),
        "commit_advance_off": int(advance_off.sum()),
        "error_lanes": int(lane_errors.sum()),
    }


def merge(parts: list) -> dict:
    out = {k: 0 for k in ("groups", "bad_groups") + CHECKS}
    for p in parts:
        for k in out:
            out[k] += p[k]
    return out


def control_columns(end: dict, v: int) -> dict:
    """The control: the same fleet with one guarantee broken, as a system
    that commits on the leader's own append, without waiting for a
    majority, would report it: every replica's committed index is the
    leader's last index."""
    g = _groups({k: end[k] for k in ("state", "last")}, v)
    li = np.argmax(g["state"] == LEADER, axis=1)
    l_last = np.take_along_axis(g["last"], li[:, None], axis=1)
    out = dict(end)
    out["committed"] = np.broadcast_to(l_last, g["last"].shape).reshape(-1).astype(
        np.asarray(end["committed"]).dtype
    )
    out["applied"] = out["committed"]
    return out

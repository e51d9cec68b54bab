"""Plain reference for a replicated key-value store under linearizable
reads: a register per key, and the history of what clients saw.

The store's guarantees (as the configuration states them): a put is
acknowledged only once it is committed, so it is never lost; a get is
linearizable, so it returns the value of the last put acknowledged before
the get was issued, or of a later put issued before the get was answered;
before any put, a get finds nothing (None).

Every put to a key comes from that key's one client session, in order, so
the puts to a key form one sequence and the store applies them in it.
Times are the client's event counter: it rises at each submission and at
each completion the client observes, so "before" is exact.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

CHECKS = ("unanswered", "reads_not_allowed", "readback_mismatch")


@dataclass
class Op:
    kind: str  # "put" | "get"
    key: str
    value: object  # the put's value, or what the get returned
    submit: int
    done: int | None = None  # event counter at completion, None if never


@dataclass
class History:
    ops: list = field(default_factory=list)
    clock: int = 0

    def tick(self) -> int:
        self.clock += 1
        return self.clock

    def submit(self, kind: str, key: str, value=None) -> Op:
        op = Op(kind, key, value, self.tick())
        self.ops.append(op)
        return op

    def complete(self, op: Op, value=None) -> None:
        op.done = self.tick()
        if op.kind == "get":
            op.value = value


def allowed_values(puts: list, get: Op) -> set:
    """Values a linearizable get may return, given the key's puts in
    order."""
    done_before = [i for i, p in enumerate(puts) if p.done is not None
                   and p.done < get.submit]
    first = done_before[-1] if done_before else None
    horizon = get.done if get.done is not None else float("inf")
    lo = 0 if first is None else first
    vals = {p.value for p in puts[lo:] if p.submit < horizon}
    if first is None:
        vals.add(None)
    return vals


def check(history: History, readback: list) -> dict:
    """Count what breaks the guarantees. `readback` holds, after every
    request was answered, one completed get per key sampled for the
    read-back: its value must be the key's last put."""
    by_key: dict[str, list] = {}
    for op in history.ops:
        if op.kind == "put":
            by_key.setdefault(op.key, []).append(op)
    unanswered = sum(1 for op in history.ops if op.done is None)
    bad_reads = 0
    for op in history.ops:
        if op.kind == "get" and op.done is not None:
            if op.value not in allowed_values(by_key.get(op.key, []), op):
                bad_reads += 1
    mismatch = 0
    for op in readback:
        puts = by_key.get(op.key, [])
        last = puts[-1].value if puts else None
        if op.done is None or op.value != last:
            mismatch += 1
    return {
        "unanswered": unanswered + sum(1 for op in readback if op.done is None),
        "reads_not_allowed": bad_reads,
        "readback_mismatch": mismatch,
    }


def stale_control(history: History, lag: int) -> History:
    """The control: the same requests answered by a store that serves
    gets from a replica `lag` acknowledgements behind (a follower read
    without ReadIndex), breaking linearizability: each get returns the
    value its key had when the acknowledgement `lag` steps before the
    get's submission was observed."""
    acks = sorted(op.done for op in history.ops
                  if op.kind == "put" and op.done is not None)
    puts_by_key: dict[str, list] = {}
    for op in history.ops:
        if op.kind == "put" and op.done is not None:
            puts_by_key.setdefault(op.key, []).append(op)
    out = History(clock=history.clock)
    for op in history.ops:
        if op.kind != "get" or op.done is None:
            out.ops.append(op)
            continue
        k = bisect.bisect_left(acks, op.submit) - lag
        seen = acks[k - 1] if k > 0 else 0
        vals = [p.value for p in puts_by_key.get(op.key, []) if p.done <= seen]
        out.ops.append(Op("get", op.key, vals[-1] if vals else None,
                          op.submit, op.done))
    return out

"""Host-side trace assembler: one Perfetto-loadable timeline from the
three observability planes this repo grows —

  device lane events   flight-recorder rings (trace/device.py), drained by
                       runtime/trace.py TraceStream as (round, lane, kind,
                       arg) rows; placed on the ROUND axis (ts = round *
                       round_us) as instant events, one track per lane
  host round spans     utils/profiling.py SpanRecorder tuples from the
                       ServeLoop's StepStats.span calls ("serve.coalesce",
                       "serve.dispatch", "serve.drain_reads" and its
                       ".wait" / ".decode" / ".reset.wait",
                       "serve.host_drain", ...), the blocked scheduler's
                       "sched.dispatch" / "sched.enqueue" per block and
                       round, and the egress streams' "egress.resolve.wait"
                       / "egress.sink"; placed on the WALL-CLOCK axis (the
                       same spans are jax.profiler annotations, always
                       emitted, free without a profiler session)
  proposal lifecycle   serve/router.py CompletionRouter.lifecycle tuples
                       (group, submit, inject, commit, notify); rendered as
                       stacked queued -> replicating -> notify_lag slices
                       per group on the round axis

Device rounds and host wall time are different clocks with no common
epoch, so they land in SEPARATE Chrome-trace processes ("device rounds",
"serve lifecycle" vs "host spans") — Perfetto shows them side by side but
the assembler never pretends to correlate them.

The output is the Chrome trace-event JSON flavor Perfetto ingests
directly (load ui.perfetto.dev -> open file, or chrome://tracing).

`explain(group, ...)` answers the operator question the raw JSON cannot:
"what happened to group G, in order?" — a merged, human-readable round
timeline of that group's lane transitions and proposal lifecycles.

CLI (zero-setup demo: builds a traced cluster, runs it, writes the JSON):

    python -m raft_tpu.trace.assemble --out /tmp/raft_trace.json \
        --groups 8 --voters 3 --rounds 64 --explain 0
"""

from __future__ import annotations

import json

import numpy as np

from raft_tpu.trace.device import (
    CHAOS_FAULT,
    COMMIT_STALL,
    KIND_NAMES,
    LEADER_ELECTED,
    LEASE_GRANTED,
    LEASE_REVOKED,
    SNAPSHOT_INSTALL,
    TERM_BUMP,
)

# Chrome-trace process ids: one per clock domain / plane
PID_DEVICE = 0   # lane events, round axis
PID_SERVE = 1    # proposal lifecycles, round axis
PID_HOST = 2     # SpanRecorder spans, wall-clock axis

# default synthetic round width: 1ms per device round keeps 4k-round
# soaks readable at Perfetto's default zoom
ROUND_US = 1000.0


def merge_block_events(block_events, lanes_per_block: int) -> np.ndarray:
    """Globalize block-local lane ids (the scheduler's per-block TraceStream
    contract: each resident block records lanes [0, lanes_per_block)) and
    merge the per-block event arrays round-sorted (stable, so within a
    round block 0's lanes come first — the monolithic order)."""
    rows = []
    for bi, ev in enumerate(block_events):
        ev = np.asarray(ev, dtype=np.int64)
        if ev.size == 0:
            continue
        ev = ev.copy()
        ev[:, 1] += bi * lanes_per_block
        rows.append(ev)
    if not rows:
        return np.zeros((0, 4), dtype=np.int64)
    out = np.concatenate(rows)
    return out[np.argsort(out[:, 0], kind="stable")]


def _meta(pid: int, name: str) -> dict:
    return {
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
        "args": {"name": name},
    }


def assemble(
    events=None,
    *,
    v: int = 1,
    spans=None,
    lifecycle=None,
    round_us: float = ROUND_US,
) -> dict:
    """Build the Chrome-trace dict. `events` is an [M, 4] (round, lane,
    kind, arg) array (TraceStream.events — pre-merge blocked output with
    merge_block_events); `spans` a SpanRecorder.spans list; `lifecycle`
    a CompletionRouter.lifecycle list. All three optional."""
    tev = [
        _meta(PID_DEVICE, "device rounds (flight recorder)"),
        _meta(PID_SERVE, "serve lifecycle (rounds)"),
        _meta(PID_HOST, "host spans (wall clock)"),
    ]
    if events is not None:
        for rnd, lane, kind, arg in np.asarray(events).tolist():
            rnd, lane, kind, arg = int(rnd), int(lane), int(kind), int(arg)
            tev.append({
                "name": KIND_NAMES[kind] if 0 <= kind < len(KIND_NAMES)
                else f"kind{kind}",
                "ph": "i", "s": "t",
                "ts": rnd * round_us,
                "pid": PID_DEVICE, "tid": lane,
                "args": {
                    "round": rnd, "lane": lane, "group": lane // v,
                    "voter": lane % v, "arg": arg,
                },
            })
    if lifecycle is not None:
        for group, submit, inject, commit, notify in lifecycle:
            # a ticket can notify without ever being injected only on
            # bugs; keep the assembler total anyway
            inject = submit if inject is None else inject
            commit = inject if commit is None else commit
            notify = commit if notify is None else notify
            for name, a, b in (
                ("queued", submit, inject),
                ("replicating", inject, commit),
                ("notify_lag", commit, notify),
            ):
                tev.append({
                    "name": name, "ph": "X",
                    "ts": int(a) * round_us,
                    "dur": max(int(b) - int(a), 0) * round_us,
                    "pid": PID_SERVE, "tid": int(group),
                    "args": {
                        "group": int(group), "submit_round": int(submit),
                        "inject_round": int(inject),
                        "commit_round": int(commit),
                        "notify_round": int(notify),
                    },
                })
    if spans is not None and spans:
        t_base = min(t0 for _, t0, _, _ in spans)
        for name, t0, dur, labels in spans:
            tev.append({
                "name": name, "ph": "X",
                "ts": (t0 - t_base) * 1e6,
                "dur": dur * 1e6,
                "pid": PID_HOST, "tid": int(labels.get("block", 0)),
                "args": dict(labels),
            })
    return {"traceEvents": tev, "displayTimeUnit": "ms"}


def from_serve(loop, round_us: float = ROUND_US) -> dict:
    """Assemble straight off a (traced) ServeLoop: per-block flight
    recorder streams, the loop's SpanRecorder, the router's lifecycle log.
    Call loop.flush() first so the stream tails resolved."""
    ev = None
    if loop.traces is not None:
        ev = merge_block_events(
            [t.events for t in loop.traces], loop.lanes_per_block
        )
    return assemble(
        ev,
        v=loop.v,
        spans=loop.spans.spans if loop.spans is not None else None,
        lifecycle=loop.router.lifecycle,
        round_us=round_us,
    )


def explain(
    group: int,
    *,
    events=None,
    lifecycle=None,
    spans=None,
    lease=None,
    v: int = 1,
) -> list[str]:
    """Round-ordered, human-readable timeline of one raft group: its
    lanes' recorded transitions plus its proposals' lifecycles, plus —
    when a host SpanRecorder (or its span list) is passed — the group's
    tier transitions (tier_evict / tier_admit, RAFT_TPU_TIER) and its
    cross-host fabric hops (fabric_tx / fabric_rx, RAFT_TPU_FABRIC,
    labeled by spanning group). `lease` takes the router's lease_log
    (serve-plane lease routing: reads served off the leader lease vs
    bounced to ReadIndex — the device-side grant/revoke edges already
    narrate through `events` as lease_granted/lease_revoked). Under the
    tier, `group` is the LOGICAL id for lifecycle/span lines; device
    event lanes are physical and follow the group's current slot."""
    lines: list[tuple[int, int, str]] = []  # (round, order, text)
    if events is not None:
        for rnd, lane, kind, arg in np.asarray(events).tolist():
            rnd, lane, kind, arg = int(rnd), int(lane), int(kind), int(arg)
            if lane // v != group:
                continue
            name = (
                KIND_NAMES[kind] if 0 <= kind < len(KIND_NAMES)
                else f"kind{kind}"
            )
            extra = _ARG_LABEL.get(kind, "arg")
            lines.append((
                rnd, 0,
                f"r{rnd:05d}  lane {lane} (voter {lane % v}): "
                f"{name} ({extra}={arg})",
            ))
    if lifecycle is not None:
        for g, submit, inject, commit, notify in lifecycle:
            if int(g) != group:
                continue
            lines.append((
                int(submit), 1,
                f"r{int(submit):05d}  proposal: submitted r{int(submit)}, "
                f"injected r{inject}, committed r{commit}, "
                f"notified r{notify} "
                f"(+{int(notify) - int(submit)} rounds)",
            ))
    if spans is not None:
        for name, _t0, _dur, labels in getattr(spans, "spans", spans):
            sname = str(name)
            if not labels:
                continue
            if sname.startswith("fabric_"):
                # cross-host hops (raft_tpu/fabric driver): one span per
                # frame exchanged, labeled with the spanning groups whose
                # cells rode that frame
                if group not in tuple(labels.get("groups", ())):
                    continue
                rnd = int(labels.get("round", 0))
                if sname == "fabric_wait":
                    # skew backpressure (RAFT_TPU_FABRIC_SKEW): the round
                    # blocked because this peer ran > D rounds behind
                    lines.append((
                        rnd, 3,
                        f"r{rnd:05d}  fabric: waited on host "
                        f"{labels.get('peer')} "
                        f"({labels.get('ms', 0)} ms backpressure)",
                    ))
                    continue
                verb = (
                    f"fabric: frame out to host {labels.get('peer')}"
                    if sname == "fabric_tx"
                    else f"fabric: frame in from host {labels.get('peer')}"
                )
                lines.append((
                    rnd, 3,
                    f"r{rnd:05d}  {verb} ({labels.get('msgs', 0)} msgs, "
                    f"{labels.get('bytes', 0)} B)",
                ))
                continue
            if not sname.startswith("tier_"):
                continue
            if int(labels.get("group", -1)) != group:
                continue
            rnd = int(labels.get("round", 0))
            if name == "tier_evict":
                verb = "tier: evicted to cold store"
            elif labels.get("genesis"):
                verb = "tier: born (genesis admission)"
            else:
                verb = "tier: re-admitted from cold store"
            extra = ", ".join(
                f"{k}={labels[k]}"
                for k in sorted(labels)
                if k not in ("group", "round")
            )
            lines.append((
                rnd, 2,
                f"r{rnd:05d}  {verb}" + (f" ({extra})" if extra else ""),
            ))
    if lease is not None:
        for rnd, g, event, n in lease:
            if int(g) != group:
                continue
            rnd, n = int(rnd), int(n)
            verb = (
                f"lease: served {n} read(s) from the leader lease "
                "(no ReadIndex round-trip)"
                if event == "lease_reads_served"
                else f"lease: {n} read(s) fell back to ReadIndex "
                "(lease lapsed or epoch moved)"
            )
            lines.append((rnd, 4, f"r{rnd:05d}  {verb}"))
    lines.sort(key=lambda t: (t[0], t[1]))
    return [s for _, _, s in lines]


_ARG_LABEL = {
    LEADER_ELECTED: "term",
    TERM_BUMP: "term",
    SNAPSHOT_INSTALL: "snap_index",
    COMMIT_STALL: "committed",
    CHAOS_FAULT: "crash+2*restart",
    LEASE_GRANTED: "epoch",
    LEASE_REVOKED: "epoch",
}


def main(argv=None) -> int:
    import argparse
    import os

    p = argparse.ArgumentParser(
        description="run a traced demo cluster and write a Perfetto JSON"
    )
    p.add_argument("--groups", type=int, default=8)
    p.add_argument("--voters", type=int, default=3)
    p.add_argument("--rounds", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ring", type=int, default=4096)
    p.add_argument("--out", default="/tmp/raft_trace.json")
    p.add_argument(
        "--explain", type=int, default=None, metavar="GROUP",
        help="also print the round timeline of one group",
    )
    args = p.parse_args(argv)

    # the flight recorder is opt-in; the demo IS the opt-in (must be set
    # before the cluster builds its carry)
    os.environ["RAFT_TPU_TRACELOG"] = "1"
    os.environ.setdefault("RAFT_TPU_TRACE_RING", str(args.ring))
    from raft_tpu.ops.fused import FusedCluster
    from raft_tpu.runtime.trace import TraceStream

    fc = FusedCluster(args.groups, args.voters, seed=args.seed)
    ts = TraceStream()
    for _ in range(max(args.rounds // 8, 1)):
        fc.run(8, trace=ts)
    ts.flush()
    doc = assemble(ts.events, v=args.voters)
    with open(args.out, "w") as f:
        json.dump(doc, f)
    n_ev = sum(1 for e in doc["traceEvents"] if e["ph"] != "M")
    print(f"wrote {args.out}: {n_ev} events, {ts.dropped} dropped")
    if args.explain is not None:
        for line in explain(
            args.explain, events=ts.events, v=args.voters
        ):
            print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

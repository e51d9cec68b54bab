"""Driver `served_open_loop`: clients of the replicated store, open loop.

The program's `ServeLoop` runs over the configuration's fleet. Requests
arrive on a Poisson schedule at the mix's fixed rate, drawn from the seed
(`ycsb.py`): each a linearizable GET or a PUT of one field, on a
scrambled-Zipfian record. Each record is its own tenant with one client
session, so it lives in the group the program places it in. The client
submits every request that is due, steps the loop one round, and notes
each completion it then observes. Latency runs from when a request was
due to when its completion was observed; a request still open when the
window closes counts with the window's end.

Set-up bootstraps the loop (the election and the router's attach), then
runs the same traffic for `warm_seconds` from a separate stream of the
seed, which writes the hot records and compiles every program the window
uses, and drains it.

Correctness: after the window every request is waited for (at most
`answer_wait_s` past the close); one still open then is unanswered. Once
every request was answered, a sample of the written records is read back.
The plain reference (`reference/kv_history.py`) checks the whole history.
"""

from __future__ import annotations

import resource
import time

import numpy as np

import fleet
import ycsb
from record import Record, memory_peak, rusage_delta
from reference import kv_history

HOST_PHASES = ("admission", "coalesce", "drain_reads", "resync", "tier")
LONGEST = 3  # the window's longest served rounds kept in the notes
# the loop's own counters kept in the notes: leader changes, refusals,
# retried reads and audited double notifications
LOOP_COUNTERS = ("epoch_resyncs", "notify_violations", "proposals_rejected",
                 "reads_retried", "rejected_queue_full", "rejected_tenant_rate",
                 "rejected_no_leader", "rejected_read_batch_full")


class Client:
    """Open-loop clients of one ServeLoop, with the history they saw."""

    def __init__(self, loop, field_bytes: int, annotate):
        self.loop = loop
        self.field_bytes = field_bytes
        self.annotate = annotate
        self.sessions: dict[int, object] = {}
        self.history = kv_history.History()
        self.pending: list = []  # (op, ticket, due)
        self.puts = 0
        self.rejected = 0

    def session(self, record: int):
        s = self.sessions.get(record)
        if s is None:
            s = self.sessions[record] = self.loop.open_session(ycsb.key(record))
        return s

    def value(self) -> str:
        self.puts += 1
        head = f"{self.puts:016x}"
        return head + "x" * (self.field_bytes - len(head))

    def submit(self, is_read: bool, record: int, due: float) -> None:
        s, key = self.session(record), ycsb.key(record)
        if is_read:
            op = self.history.submit("get", key)
            t = self.loop.get(s, key)
        else:
            value = self.value()
            op = self.history.submit("put", key, value)
            t = self.loop.put(s, key, value, nbytes=self.field_bytes)
        if not t:  # Rejected: counted as failed, never answered
            self.history.ops.pop()
            self.rejected += 1
            return
        self.pending.append((op, t, due))

    def step(self, flush: bool = False) -> list:
        """One round; returns [(op, due, observed), ...] completed in it.
        `flush` also resolves the round's own completions at once."""
        with self.annotate("bench.step"):
            self.loop.step()
            if flush:
                self.loop.flush()
        now = time.perf_counter()
        done, still = [], []
        for op, t, due in self.pending:
            if t.done:
                self.history.complete(op, t.value if op.kind == "get" else None)
                done.append((op, due, now))
            else:
                still.append((op, t, due))
        self.pending = still
        return done

    def drive(self, reqs: ycsb.Requests, seconds: float) -> dict:
        """Offer `reqs` on their schedule for `seconds`; returns what the
        window saw."""
        lat = {"put": [], "get": []}
        late, longest = [], []
        i, n, rounds, acked = 0, reqs.due.size, 0, 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            with self.annotate("bench.submit"):
                while i < n and reqs.due[i] <= now:
                    late.append(time.perf_counter() - t0 - reqs.due[i])
                    self.submit(bool(reqs.is_read[i]), int(reqs.record[i]),
                                t0 + reqs.due[i])
                    i += 1
            t, ru = time.perf_counter(), resource.getrusage(resource.RUSAGE_SELF)
            phases = dict(self.loop.stats.seconds)
            for op, due, seen in self.step():
                acked += 1
                if due >= t0:
                    lat[op.kind].append(seen - due)
            rounds += 1
            took = time.perf_counter() - t
            if len(longest) < LONGEST or took > longest[-1]["ms"] / 1e3:
                longest.append(self._round_note(t - t0, took, phases, ru))
                longest.sort(key=lambda r: -r["ms"])
                del longest[LONGEST:]
        end = time.perf_counter()
        # open at the close: submitted and not yet answered, or due and
        # not yet submitted; each counts until the window's end
        still = [(op.kind, due) for op, _, due in self.pending if due >= t0]
        while i < n and t0 + reqs.due[i] < end:
            still.append(("get" if reqs.is_read[i] else "put", t0 + reqs.due[i]))
            i += 1
        for kind, due in still:
            lat[kind].append(end - due)
        return {"lat": lat, "late": late, "due": i, "rounds": rounds,
                "acked": acked, "window_s": end - t0,
                "open_at_close": len(still), "longest_rounds": longest}

    def _round_note(self, at: float, took: float, phases: dict, ru) -> dict:
        """One served round: when in the window it began, how long it
        took, its host phases and what the process spent on the host."""
        now = self.loop.stats.seconds
        return {
            "at_s": at,
            "ms": 1e3 * took,
            "phase_ms": {k: 1e3 * (v - phases.get(k, 0.0))
                         for k, v in now.items() if v > phases.get(k, 0.0)},
            **rusage_delta(ru, resource.getrusage(resource.RUSAGE_SELF)),
        }

    def settle(self, max_s: float) -> tuple[float, int]:
        """Step until every request is answered, for at most max_s; returns
        the seconds and rounds it took."""
        t0, rounds = time.perf_counter(), 0
        while self.pending and time.perf_counter() - t0 < max_s:
            self.step(flush=True)
            rounds += 1
        return time.perf_counter() - t0, rounds


def read_back(client: Client, loop, seed: int, tr: dict) -> list:
    """Read back a sample, drawn from the seed, of the written records,
    once every request was answered; returns the completed gets."""
    rng = np.random.default_rng([seed, 2])
    written = sorted({op.key for op in client.history.ops if op.kind == "put"})
    sample = rng.choice(len(written), min(tr["readback_keys"], len(written)),
                        replace=False)
    readback = []
    for j in sorted(sample):
        key = written[j]
        op = kv_history.Op("get", key, None, client.history.tick())
        t = loop.get(client.session(int(key[len("user"):])), key)
        if t:
            client.pending.append((op, t, 0.0))
        readback.append(op)
    client.settle(tr["answer_wait_s"])
    return readback


def mismatches(history, readback: list, most: int = 5) -> list:
    """The first read-backs that did not find their key's last put: the
    key, what it read, and the key's puts as (value, submitted, answered)
    on the client's event counter, for the notes."""
    keys = {op.key for op in readback}
    by_key: dict[str, list] = {}
    for p in history.ops:
        if p.kind == "put" and p.key in keys:
            by_key.setdefault(p.key, []).append(p)
    out = []
    for op in readback:
        puts = by_key.get(op.key, [])
        if op.done is not None and puts and op.value == puts[-1].value:
            continue
        out.append({"key": op.key, "read": op.value, "read_at": [op.submit, op.done],
                    "puts": len(puts),
                    "last_puts": [[p.value[:16], p.submit, p.done] for p in puts[-3:]]})
        if len(out) == most:
            break
    return out


def percentile(xs, q: float) -> float | None:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def run(ctx) -> Record:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    lag = cfg["compact_lag"]
    marks = {"start": time.perf_counter() - ctx.t0}
    with ctx.annotate("bench.setup"):
        from raft_tpu.serve import ServeLoop

        cluster = fleet.build(cfg, ctx.seed, 1)
        marks["built"] = time.perf_counter() - ctx.t0
        loop = ServeLoop(cluster, auto_compact_lag=lag)
        loop.bootstrap(max_rounds=tr["bootstrap_bound_rounds"])
        marks["bootstrapped"] = time.perf_counter() - ctx.t0
        client = Client(loop, tr["field_bytes"], ctx.annotate)
        mix = dict(rate=tr["rate_per_s"], read_share=tr["read_share"],
                   records=cfg["records"], theta=tr["zipfian_theta"])
        client.drive(ycsb.requests(ctx.seed, 0, seconds=tr["warm_seconds"], **mix),
                     tr["warm_seconds"])
        client.settle(tr["answer_wait_s"])
        marks["warmed"] = time.perf_counter() - ctx.t0
        reqs = ycsb.requests(ctx.seed, 1, seconds=ctx.seconds, **mix)
        rejected0 = client.rejected
        stats0 = dict(loop.stats.seconds)
        ctx.freeze_host()
    setup_s = time.perf_counter() - ctx.t0

    with ctx.window():
        seen = client.drive(reqs, ctx.seconds)
    stats1 = dict(loop.stats.seconds)
    mem = memory_peak(jax.devices()[: ctx.cell.chips])

    with ctx.annotate("bench.check"):
        wait_s, wait_rounds = client.settle(tr["answer_wait_s"])
        # A request still open once the wait is over never came: it stays
        # unanswered, and the written records are read back only when
        # every put was answered, since a read-back issued beside a put
        # still queued would find the value before it.
        open_after_wait = len(client.pending)
        readback = []
        if not open_after_wait:
            readback = read_back(client, loop, ctx.seed, tr)
        res = kv_history.check(client.history, readback)
        control = None
        if ctx.control:
            control = kv_history.check(
                kv_history.stale_control(client.history, tr["control_lag"]),
                readback)
    phase = {k: stats1.get(k, 0.0) - stats0.get(k, 0.0) for k in stats1}
    rounds = max(1, seen["rounds"])
    lat_ms = {k: [x * 1e3 for x in v] for k, v in seen["lat"].items()}
    late_ms = [x * 1e3 for x in seen["late"]]
    return Record(
        setup_s=setup_s,
        window_s=seen["window_s"],
        attempted=seen["due"],
        failed=client.rejected - rejected0,
        values={
            "lat_ms": lat_ms,
            "rounds": seen["rounds"],
            "acked": seen["acked"],
            "host_s": sum(phase.get(k, 0.0) for k in HOST_PHASES),
            "dispatch_s": phase.get("dispatch", 0.0),
        },
        checks=[{"name": k, "value": res[k], "limit": 0}
                for k in kv_history.CHECKS],
        memory_peak_bytes=mem,
        notes={
            "puts": len(lat_ms["put"]),
            "gets": len(lat_ms["get"]),
            "put_p50_ms": percentile(lat_ms["put"], 50),
            "put_p99_ms": percentile(lat_ms["put"], 99),
            "get_p50_ms": percentile(lat_ms["get"], 50),
            "open_at_close": seen["open_at_close"],
            "generator_late_p99_ms": percentile(late_ms, 99),
            "generator_late_max_ms": max(late_ms) if late_ms else None,
            "served_rounds": seen["rounds"],
            "readback_keys": len(readback),
            "answer_wait_s": wait_s,
            "answer_wait_rounds": wait_rounds,
            "open_after_wait": open_after_wait,
            "loop_counters": {k: v for k, v in sorted(loop.metrics.counters.counts.items())
                              if k in LOOP_COUNTERS},
            "readback_mismatches": mismatches(client.history, readback),
            "ms_per_round": 1e3 * seen["window_s"] / rounds,
            "longest_rounds": seen["longest_rounds"],
            "phase_ms_per_round": {k: 1e3 * v / rounds
                                   for k, v in sorted(phase.items())},
            "setup_marks_s": marks,
            **({"control": control} if control else {}),
        },
    )

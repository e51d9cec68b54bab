"""Host half of the metrics plane: accumulate device counter pulls into
exact int64 totals, merge sources, and export.

Snapshot schema (every producer in the tree speaks it):

    {
      "counters": {name: int, ...},            # cumulative totals
      "hist": {
        "edges": [int, ...],                    # le bucket upper bounds
        "buckets": [int, ...],                  # per-bucket counts (+Inf last)
        "sum": int,                             # sum of observed latencies
        "count": int,                           # == sum(buckets)
      },
      "hist_name": str,                         # optional: the family name
                                                #   of "hist" (defaults to
                                                #   commit_latency_rounds)
      "hists": {name: hist, ...},               # optional: named families
      "rounds": int,                            # device rounds stepped
    }

Histograms are namespaced BY NAME when merged: merge_snapshots collects
each source's histogram under its family name ("hists" entries plus the
legacy "hist" keyed by "hist_name"), summing only same-named families and
raising only when one NAME carries conflicting edges. The serve registry
(notify_latency_rounds) and the engine plane (commit_latency_rounds) can
therefore merge into one scrape without silent bucket collisions — the
hazard serve/http.py used to work around by keeping sources separate.

Exporters: `prometheus_text` renders the standard exposition format
(counter `_total` families + one cumulative-bucket histogram), and
`JsonlWriter` appends timestamped snapshots as a JSONL time series — the
shapes Grafana/offline analysis ingest without an adapter.
"""

from __future__ import annotations

import bisect
import json
import time

import numpy as np

from raft_tpu.metrics.device import COUNTERS, HIST_EDGES, N_BUCKETS


def empty_snapshot() -> dict:
    return {
        "counters": {name: 0 for name in COUNTERS},
        "hist": {
            "edges": list(HIST_EDGES),
            "buckets": [0] * N_BUCKETS,
            "sum": 0,
            "count": 0,
        },
        "rounds": 0,
    }


class CounterAccumulator:
    """Exact int64 totals from a stream of wrapping-int32 device pulls.

    The device counters wrap at 2^31; the host computes each pull's delta
    in uint32 arithmetic — `(cur - prev) mod 2^32` — which is the true
    event count provided fewer than 2^31 events occurred between pulls.
    lat_sum/round_ctr ride the same rule."""

    def __init__(self):
        self._prev_counters = np.zeros(len(COUNTERS), np.int64)
        self._prev_hist = np.zeros(N_BUCKETS, np.int64)
        self._prev_lat_sum = 0
        self._prev_rounds = 0
        self.counters = np.zeros(len(COUNTERS), np.int64)
        self.hist = np.zeros(N_BUCKETS, np.int64)
        self.lat_sum = 0
        self.rounds = 0

    @staticmethod
    def _delta(cur, prev):
        return (
            np.asarray(cur, np.int64).astype(np.uint32)
            - np.asarray(prev, np.int64).astype(np.uint32)
        ).astype(np.uint32).astype(np.int64)

    def pull(self, metrics) -> None:
        """Fold one device MetricsState into the totals."""
        cur_c = np.asarray(metrics.counters, np.int64)
        cur_h = np.asarray(metrics.hist, np.int64)
        cur_s = int(metrics.lat_sum)
        cur_r = int(metrics.round_ctr)
        self.counters += self._delta(cur_c, self._prev_counters)
        self.hist += self._delta(cur_h, self._prev_hist)
        self.lat_sum += int(self._delta(cur_s, self._prev_lat_sum))
        self.rounds += int(self._delta(cur_r, self._prev_rounds))
        self._prev_counters = cur_c
        self._prev_hist = cur_h
        self._prev_lat_sum = cur_s
        self._prev_rounds = cur_r

    def snapshot(self) -> dict:
        return {
            "counters": {
                name: int(self.counters[i]) for i, name in enumerate(COUNTERS)
            },
            "hist": {
                "edges": list(HIST_EDGES),
                "buckets": [int(x) for x in self.hist],
                "sum": int(self.lat_sum),
                "count": int(self.hist.sum()),
            },
            "rounds": int(self.rounds),
        }


# egress-plane counter families (host plane only — the serving loops count
# them at the Ready surface, raft_tpu/ops/ready_mask.py):
#   egress_lanes_scanned   lanes the HOST examined per poll (N on the
#                          scalar sweep, only the active set on the
#                          batched mask path — their ratio is the
#                          O(N) -> O(active) win benches/egress_ab.py
#                          asserts)
#   egress_lanes_active    lanes surfaced as ready
#   egress_bytes           ready-bundle bytes shipped D2H
#   bridge_pump_truncated  HostBridge.pump stopped at its iteration cap
#                          with lanes still ready (NOT quiescent)
#   bridge_drain_truncated same for BridgeEndpoint.drain
EGRESS_COUNTERS = (
    "egress_lanes_scanned",
    "egress_lanes_active",
    "egress_bytes",
    "bridge_pump_truncated",
    "bridge_drain_truncated",
)

# serving-frontend counter families (host plane — counted at the
# raft_tpu/serve/ surfaces, exported under the raft_tpu_serve prefix with
# the notify-latency histogram; see serve/http.py):
#   proposals_admitted     client puts/deletes/lease-grants past admission
#   proposals_rejected     typed Rejected(reason) results (never silent —
#                          per-reason breakdown rides rejected_<reason>)
#   reads_admitted         linearizable GETs accepted into a ReadIndex batch
#   reads_served           GETs answered after quorum release + apply
#   reads_retried          ReadIndex tickets re-injected after a release
#                          timeout (dropped beat, ring overflow, pre-commit)
#   proposals_notified     futures resolved propose -> commit -> notify
#   epoch_resyncs          groups re-attached after a leader/term change
#                          (in-flight tickets re-proposed, dedup collapses)
#   sessions_active        open client sessions (gauge: set, not inc)
#   notify_violations      a future completed more than once (must stay 0;
#                          the exactly-once bar benches/serve_bench.py gates)
# plus one `rejected_<reason>` family per admission.py REJECT_* reason.
SERVE_COUNTERS = (
    "proposals_admitted",
    "proposals_rejected",
    "proposals_notified",
    "reads_admitted",
    "reads_served",
    "reads_retried",
    "epoch_resyncs",
    "sessions_active",
    "notify_violations",
)

# trace-plane counter families (host plane — counted by runtime/trace.py
# TraceStream as it resolves ring copies):
#   trace_events           flight-recorder events drained from device rings
#   trace_events_dropped   ring-overflow drops (oldest-first; exact, from
#                          the monotone device write cursor vs the host
#                          read cursor — trace/device.py module doc)
TRACE_COUNTERS = (
    "trace_events",
    "trace_events_dropped",
)

# paged-entry-log counter families (host plane — computed lazily from the
# PagedLog sidecar by FusedCluster.paged_stats / metrics_snapshot, never
# per dispatch: the device arrays are monotone accumulators, the host
# plane just mirrors the latest snapshot):
#   paged_pool_in_use      gauge: pool pages currently mapped by any lane's
#                          page table (occupancy, not cumulative)
#   paged_page_faults      cumulative pages gathered from the pool at
#                          dispatch entry (page_in), summed over lanes
#   paged_exhausted        cumulative page_out clamp events (lane x
#                          dispatch); nonzero means ERR_PAGE_EXHAUSTED is
#                          set on some lane — raise pool_pages
#   paged_pages_dirty      cumulative pages written back to the pool by
#                          the allocator (page_out scatter volume), summed
#                          over lanes
#   paged_alloc_skipped    dispatches (or in-kernel rounds) where the
#                          conditional allocator pass was elided because
#                          no lane's log moved (RAFT_TPU_PAGED_INKERNEL)
PAGED_COUNTERS = (
    "paged_pool_in_use",
    "paged_page_faults",
    "paged_exhausted",
    "paged_pages_dirty",
    "paged_alloc_skipped",
)

# hot/cold tier counter families (host plane — pure python counters from
# raft_tpu/tier/engine.py, mirrored by FusedCluster.metrics_snapshot /
# TierEngine.stats(mirror=True); no device sync involved). The
# accounting identity the tier tests gate on:
#   tier_evictions - tier_admissions == tier_cold   (exactly — genesis
#   admissions count as tier_births, never tier_admissions)
#   tier_evictions         groups suspended to the cold store (cumulative)
#   tier_admissions        groups restored FROM the cold store (cumulative)
#   tier_births            groups admitted by genesis synthesis — first
#                          residency of a late-born logical id (cumulative)
#   tier_resident          gauge: logical groups currently on resident lanes
#   tier_cold              gauge: cold-store population (RAM + spilled)
#   tier_cold_bytes        gauge: cold-record bytes (host RAM + disk spill)
#   tier_thrash_suppressed evictions blocked ONLY by the minimum-residency
#                          cooldown — the hysteresis doing work
#   paged_pressure_evictions  victims that held mapped pool pages when
#                          picked under paged pool pressure (the scorer's
#                          page_weight bias doing work; 0 with paging off)
TIER_COUNTERS = (
    "tier_evictions",
    "tier_admissions",
    "tier_births",
    "tier_resident",
    "tier_cold",
    "tier_cold_bytes",
    "tier_thrash_suppressed",
    "paged_pressure_evictions",
)

# cross-host fabric counter families (host plane — pure python counters
# from the raft_tpu/fabric wire + driver layers, folded into
# FabricHost.metrics_snapshot; no device sync beyond the O(active)
# extract trim the driver already pays):
#   fabric_frames_sent      frames encoded + handed to the wire (one per
#                           (peer, round) in the lockstep driver — empty
#                           frames double as the round barrier)
#   fabric_frames_received  frames decoded from peers
#   fabric_bytes_sent       wire bytes out (header + payload)
#   fabric_bytes_received   wire bytes in
#   fabric_msgs_exported    cross-host messages pulled by the extract
#                           kernel (cumulative)
#   fabric_msgs_injected    messages scattered into the carry at a round
#                           boundary (== exported minus drops, fabric-wide)
#   fabric_msgs_total       ALL messages emitted by owned lanes (local +
#                           cross) — the mostly-local denominator
#                           benches/fabric_ab.py gates cross/total on
#   fabric_injection_drops  decoded rows refused by inject validation
#                           (wrong-host dst, non-ghost src, bad cell)
#   fabric_frames_dropped   whole frames dropped by a chaos wire partition
#                           (ChaosSchedule.wire_partition) or refused by
#                           receive()'s staging-window validation
#   fabric_frames_deferred  frames delayed by a chaos wire delay
#                           (ChaosSchedule.wire_delay)
#   fabric_skew_current     gauge: rounds this host currently runs ahead
#                           of its slowest peer (RAFT_TPU_FABRIC_SKEW)
#   fabric_skew_max         gauge: high-water mark of fabric_skew_current
#   fabric_backpressure_rounds  rounds this host blocked because a due
#                           frame was more than D rounds late
#   fabric_frames_staged    gauge: frames parked in the receive-side
#                           staging map, not yet due for injection
#   fabric_summary_saturated  int8/int4 telemetry-summary fields that hit
#                           the saturation rail (flagged, never wrapped —
#                           RAFT_TPU_FABRIC_DIET summary sections)
FABRIC_COUNTERS = (
    "fabric_frames_sent",
    "fabric_frames_received",
    "fabric_bytes_sent",
    "fabric_bytes_received",
    "fabric_msgs_exported",
    "fabric_msgs_injected",
    "fabric_msgs_total",
    "fabric_injection_drops",
    "fabric_frames_dropped",
    "fabric_frames_deferred",
    "fabric_skew_current",
    "fabric_skew_max",
    "fabric_backpressure_rounds",
    "fabric_frames_staged",
    "fabric_summary_saturated",
)

# leader-lease counter families (RAFT_TPU_LEASE). The first four are
# host sums of the per-lane device event counters (ops/lease.py, pulled
# by FusedCluster.lease_stats at host sync points); the last two are pure
# host counters incremented by the serve plane (serve/router.py) as it
# routes reads:
#   lease_grants           fresh leases granted (lease_left 0 -> window)
#   lease_renewals         in-flight leases extended by a fresh ack quorum
#   lease_revocations      conservative revocations (leadership loss,
#                          transfer, confchange, or accumulated tick skew)
#   lease_skew_revocations the skew-only subset of revocations — the
#                          chaos clock-skew soak gates on this being > 0
#                          (leases measurably revoked, not never granted)
#   lease_reads_served     batched GETs answered from the lease fast path
#                          (1 bundle round, no ReadIndex quorum touch)
#   lease_reads_fallback   lease-routed GETs bounced back to the ReadIndex
#                          path (lease lapsed/epoch moved between snapshot
#                          and serve)
LEASE_COUNTERS = (
    "lease_grants",
    "lease_renewals",
    "lease_revocations",
    "lease_skew_revocations",
    "lease_reads_served",
    "lease_reads_fallback",
)


class HostCounters:
    """Plain host-side counter bag speaking the snapshot schema — the
    RawNodeBatch/bridge analog of the device counters (no histogram).
    Thread-safe: the skewed fabric driver increments from per-peer wire
    threads concurrently with the main loop."""

    def __init__(self):
        self.counts: dict[str, int] = {}
        import threading

        self._lock = threading.Lock()

    def __getstate__(self):
        return {"counts": self.counts}

    def __setstate__(self, state):
        import threading

        self.counts = state["counts"]
        self._lock = threading.Lock()

    def inc(self, name: str, n: int = 1):
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + n

    def set(self, name: str, value: int):
        """Gauge write (e.g. sessions_active): the exported value is the
        level itself, not an accumulation."""
        with self._lock:
            self.counts[name] = int(value)

    def set_max(self, name: str, value: int):
        """Gauge high-water write: keep the larger of the stored and new
        value (fabric_skew_max)."""
        with self._lock:
            self.counts[name] = max(self.counts.get(name, 0), int(value))

    def get(self, name: str) -> int:
        return self.counts.get(name, 0)

    def snapshot(self) -> dict:
        snap = empty_snapshot()
        for name, v in self.counts.items():
            snap["counters"][name] = snap["counters"].get(name, 0) + v
        return snap


class HostHistogram:
    """Host-side le-bucket histogram speaking the snapshot "hist" schema —
    the serving plane's notify-latency (propose -> commit -> notify, in
    device rounds) uses the device plane's round edges so host and device
    latency panels share an x-axis. Safe to merge with device snapshots
    as long as the producer stamps a distinct "hist_name" (serve/loop.py
    does): merge_snapshots namespaces families by name."""

    def __init__(self, edges=HIST_EDGES):
        self.edges = tuple(edges)
        self.buckets = [0] * (len(self.edges) + 1)
        self.sum = 0

    def observe(self, value, n: int = 1):
        """File `value` (rounds, or wall ms for the serving plane's stage
        histograms) n times; the exported sum is truncated to an int."""
        # the first bucket whose le edge holds the value (+Inf past the last)
        self.buckets[bisect.bisect_left(self.edges, value)] += n
        self.sum += value * n

    def snapshot(self) -> dict:
        return {
            "edges": list(self.edges),
            "buckets": list(self.buckets),
            "sum": int(self.sum),
            "count": int(sum(self.buckets)),
        }


DEFAULT_HIST_NAME = "commit_latency_rounds"


def merge_snapshots(snaps, default_hist_name: str = DEFAULT_HIST_NAME) -> dict:
    """Sum snapshots from several sources (blocks, hosts) into one.

    Histograms merge BY FAMILY NAME: a source's "hists" entries plus its
    legacy "hist" (keyed by its "hist_name", default_hist_name when
    absent). Same-named families sum bucketwise and must agree on edges
    (ValueError otherwise); differently-named families coexist in the
    output's "hists". The merged "hist"/"hist_name" keys keep the legacy
    single-histogram view when exactly one family (or the default-named
    one) is present, so pre-namespacing consumers read what they always
    did."""
    out = empty_snapshot()
    hists: dict[str, dict] = {}
    for s in snaps:
        if s is None:
            continue
        for name, v in s.get("counters", {}).items():
            out["counters"][name] = out["counters"].get(name, 0) + int(v)
        named = dict(s.get("hists") or {})
        h = s.get("hist")
        if h and h.get("buckets"):
            named.setdefault(str(s.get("hist_name", default_hist_name)), h)
        for hname, h in named.items():
            cur = hists.get(hname)
            if cur is None:
                hists[hname] = {
                    "edges": list(h["edges"]),
                    "buckets": [int(b) for b in h["buckets"]],
                    "sum": int(h.get("sum", 0)),
                    "count": int(h.get("count", 0)),
                }
            else:
                if list(h["edges"]) != cur["edges"]:
                    raise ValueError(
                        f"cannot merge histograms named {hname!r} "
                        "with different edges"
                    )
                cur["buckets"] = [
                    a + int(b) for a, b in zip(cur["buckets"], h["buckets"])
                ]
                cur["sum"] += int(h.get("sum", 0))
                cur["count"] += int(h.get("count", 0))
        out["rounds"] = max(out["rounds"], int(s.get("rounds", 0)))
    if hists:
        out["hists"] = hists
        if len(hists) == 1:
            ((only_name, only_hist),) = hists.items()
            out["hist"] = dict(only_hist)
            out["hist_name"] = only_name
        elif default_hist_name in hists:
            out["hist"] = dict(hists[default_hist_name])
    return out


class MetricsRegistry:
    """Named snapshot sources -> one merged snapshot + deltas.

    A source is any zero-arg callable returning a snapshot dict (or None
    while disabled): `FusedCluster.metrics_snapshot`,
    `HostCounters.snapshot`, a bridge endpoint's combined view, ...
    `delta()` returns counters accumulated since the previous delta() call
    — the scrape-interval view a rate() panel wants."""

    def __init__(self):
        self._sources: dict[str, object] = {}
        self._last: dict | None = None

    def register(self, name: str, source) -> None:
        if name in self._sources:
            raise ValueError(f"metrics source {name!r} already registered")
        self._sources[name] = source

    def unregister(self, name: str) -> None:
        self._sources.pop(name, None)

    def snapshot(self) -> dict:
        return merge_snapshots(src() for src in self._sources.values())

    def delta(self) -> dict:
        cur = self.snapshot()
        prev = self._last or empty_snapshot()
        self._last = cur
        out = empty_snapshot()
        for name, v in cur["counters"].items():
            out["counters"][name] = int(v) - int(prev["counters"].get(name, 0))
        out["hist"]["buckets"] = [
            int(a) - int(b)
            for a, b in zip(cur["hist"]["buckets"], prev["hist"]["buckets"])
        ]
        out["hist"]["sum"] = cur["hist"]["sum"] - prev["hist"]["sum"]
        out["hist"]["count"] = cur["hist"]["count"] - prev["hist"]["count"]
        out["rounds"] = cur["rounds"] - prev["rounds"]
        return out


def _render_hist(lines: list, prefix: str, hist_name: str, h: dict) -> None:
    fam = f"{prefix}_{hist_name}"
    lines.append(f"# TYPE {fam} histogram")
    cum = 0
    for edge, count in zip(h["edges"], h["buckets"]):
        cum += int(count)
        lines.append(f'{fam}_bucket{{le="{edge}"}} {cum}')
    cum += int(h["buckets"][-1])
    lines.append(f'{fam}_bucket{{le="+Inf"}} {cum}')
    lines.append(f"{fam}_sum {int(h['sum'])}")
    lines.append(f"{fam}_count {int(h['count'])}")


def prometheus_text(
    snap: dict,
    prefix: str = "raft_tpu",
    hist_name: str = DEFAULT_HIST_NAME,
) -> str:
    """Render a snapshot in the Prometheus text exposition format.

    A snapshot with named families ("hists", the merge_snapshots output)
    renders every family under its own name; a legacy single-"hist"
    snapshot renders under hist_name (the engine plane's commit latency,
    the serving plane's notify latency)."""
    lines = []
    for name, v in sorted(snap["counters"].items()):
        fam = f"{prefix}_{name}_total"
        lines.append(f"# TYPE {fam} counter")
        lines.append(f"{fam} {int(v)}")
    hs = snap.get("hists")
    if hs:
        for hname in sorted(hs):
            _render_hist(lines, prefix, hname, hs[hname])
    else:
        h = snap.get("hist")
        if h is not None:
            _render_hist(lines, prefix, snap.get("hist_name", hist_name), h)
    return "\n".join(lines) + "\n"


class JsonlWriter:
    """Append snapshots to a JSONL file, one timestamped record per write —
    the bench/driver time-series sink (RAFT_TPU_METRICS_JSONL)."""

    def __init__(self, path: str):
        self.path = path

    def write(self, snap: dict, **extra) -> None:
        rec = {"ts": round(time.time(), 3), **extra, **snap}
        with open(self.path, "a") as f:
            f.write(json.dumps(rec) + "\n")


# --------------------------------------------------------------------------
# paged entry log (host plane)

# Process-wide mirror of the PagedLog device accumulators. Updated by
# record_paged_stats at the host sync points that already touch the device
# (metrics_snapshot, check_no_errors, benches) — the gauges are levels
# (set, not inc) so re-recording the same snapshot is idempotent.
PAGED_EVENTS = HostCounters()


def record_paged_stats(stats: dict) -> None:
    """Mirror one ops/paged.py paged_stats() snapshot onto the host plane;
    warn (rate-limited, never silent) when exhaustion clamps appeared."""
    from raft_tpu.logging import warn_rate_limited

    for name in PAGED_COUNTERS:
        PAGED_EVENTS.set(name, int(stats.get(name, 0)))
    if stats.get("paged_exhausted", 0):
        warn_rate_limited(
            "paged_exhausted",
            60.0,
            "paged entry pool exhausted: %d lane-dispatch clamp events so "
            "far (ERR_PAGE_EXHAUSTED set on the affected lanes; raise "
            "Shape.pool_pages / RAFT_TPU_POOL_PAGES — pool holds %d pages)",
            int(stats.get("paged_exhausted", 0)),
            int(stats.get("paged_pool_pages", 0)),
        )


# process-wide mirror of the latest tier stats (the PAGED_EVENTS twin):
# /metrics exports scrape this without holding a cluster reference
TIER_EVENTS = HostCounters()


def record_tier_stats(stats: dict) -> None:
    """Mirror one tier/engine.py stats() snapshot onto the host plane."""
    for name in TIER_COUNTERS:
        TIER_EVENTS.set(name, int(stats.get(name, 0)))


# process-wide mirror of this host's fabric counters (the TIER_EVENTS
# twin): /metrics exports scrape the latest cross-host wire totals
# without holding a FabricHost reference
FABRIC_EVENTS = HostCounters()


def record_fabric_stats(stats: dict) -> None:
    """Mirror one fabric driver counter snapshot onto the host plane."""
    for name in FABRIC_COUNTERS:
        FABRIC_EVENTS.set(name, int(stats.get(name, 0)))


# process-wide mirror of the lease plane's counters. The device-derived
# four are set (levels) by record_lease_stats; the serve-plane pair is
# incremented in place by serve/router.py — so the mirror only sets the
# keys present in the stats dict, never zeroing the host-owned halves
LEASE_EVENTS = HostCounters()


def record_lease_stats(stats: dict) -> None:
    """Mirror one FusedCluster.lease_stats() snapshot onto the host
    plane (device-derived counters only — lease_reads_served/_fallback
    are owned and incremented by the serve plane directly)."""
    for name in LEASE_COUNTERS:
        if name in stats:
            LEASE_EVENTS.set(name, int(stats[name]))

"""Host-side spans for the serving path, on the profiler's clock (SURVEY §5:
the reference ships no tracing — "add its own (jax.profiler traces), no
parity required").

`StepStats.span(name)` is the one span primitive. One call:

  - adds the region's seconds and count to the StepStats bag — the analog
    of the reference's MemoryStorage.callStats (storage.go:92-94, which
    feeds BenchmarkRawNode's storage-access metrics, rawnode_test.go:1244)
    — exported as the `step_<key>_count` / `step_<key>_micros` counters;
  - always opens a `jax.profiler.TraceAnnotation` named `<layer>.<phase>`.
    With no profiler session open it costs well under a microsecond; under
    `jax.profiler.trace` the span lands on the host plane, on the same
    clock as the device's operations;
  - appends `(name, t0, dur_s, labels)` to an attached SpanRecorder, the
    trace assembler's host-span list (opt-in: ServeLoop attaches one only
    under RAFT_TPU_TRACELOG=1).

Span names are stable, because metrics read them: `serve.*` for the
ServeLoop's phases, `sched.*` for per-block dispatch, `egress.*` for the
egress stream and its sink, `node.*` for NodeHost's operations. A child
ending in `.wait` covers one point where the host blocks on the device (a
device->host pull, or a dispatch held back by the device's queue), so a
layer's self time is its span minus the `.wait` spans inside it.
"""

from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation


class StepStats:
    """Host-side span counts + wall seconds, keyed by span name.

    A bag owns one layer: its own spans are keyed without the layer prefix
    (a `StepStats("serve")` keys `serve.coalesce` as `coalesce` and
    `serve.drain_reads.wait` as `drain_reads.wait`), while spans of the
    layers below it that it is shared with keep their full dotted names
    (`sched.enqueue`, `egress.sink`):

        stats = StepStats("serve")
        with stats.span("serve.coalesce"):
            ...
        print(stats.as_dict())
    """

    def __init__(self, layer: str | None = None, spans=None):
        self.counts: dict[str, int] = {}
        self.seconds: dict[str, float] = {}
        self._own = f"{layer}." if layer else ""
        self.spans = spans  # optional SpanRecorder

    def key(self, name: str) -> str:
        if self._own and name.startswith(self._own):
            return name[len(self._own):]
        return name

    @contextlib.contextmanager
    def span(self, name: str, **labels):
        key = self.key(name)
        t0 = time.perf_counter()
        with TraceAnnotation(name):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.seconds[key] = self.seconds.get(key, 0.0) + dt
                self.counts[key] = self.counts.get(key, 0) + 1
                if self.spans is not None:
                    self.spans.spans.append((name, t0, dt, labels))

    def as_dict(self) -> dict:
        return {
            k: {
                "count": self.counts.get(k, 0),
                "seconds": round(self.seconds.get(k, 0.0), 6),
            }
            for k in sorted(set(self.counts) | set(self.seconds))
        }

    def snapshot(self) -> dict:
        """Metrics-plane snapshot (registerable with MetricsRegistry): each
        key becomes step_<key>_count / step_<key>_micros counters, dots as
        underscores (Prometheus names). Monotone like every other counter
        source, so registry deltas and Prometheus scrapes work unchanged."""
        counters: dict[str, int] = {}
        for k in sorted(set(self.counts) | set(self.seconds)):
            name = k.replace(".", "_")
            counters[f"step_{name}_count"] = self.counts.get(k, 0)
            counters[f"step_{name}_micros"] = int(
                self.seconds.get(k, 0.0) * 1e6
            )
        return {"counters": counters, "rounds": 0}


class SpanRecorder:
    """Host-side span log for the trace assembler (trace/assemble.py): a
    StepStats with a recorder attached appends one (name, t0, dur_s,
    labels) tuple per span; the tier and the fabric driver append their
    point events (tier_evict / fabric_tx ...) to the same list."""

    def __init__(self):
        self.spans: list[tuple[str, float, float, dict]] = []

    def clear(self):
        self.spans = []


def live_buffer_bytes() -> int:
    """Total bytes of live (not-deleted) device arrays in this process —
    the host-visible live-buffer analog of an HBM-peak probe. Donated
    inputs count as deleted even while Python still references them, so
    a donation-on dispatch shows strictly lower live bytes than the same
    dispatch with RAFT_TPU_DONATE=0 holding the pre-dispatch carry."""
    import jax

    return int(
        sum(x.nbytes for x in jax.live_arrays() if not x.is_deleted())
    )


def device_memory_stats() -> dict | None:
    """Allocator stats of device 0 ({bytes_in_use, peak_bytes_in_use, ...})
    or None where the backend exposes none (XLA:CPU)."""
    import jax

    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()}

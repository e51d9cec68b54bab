"""The one reduction from a `jax.profiler` trace to the numbers the
benchmark reports: device busy time as the union of operation intervals,
per-program device time, and the `breakdown` of a traced run.

It reads the trace with `jax.profiler.ProfileData` and nothing else. On a
TPU each chip is a plane named `/device:TPU:<n>`; its `XLA Ops` line holds
one event per operation executed and its `XLA Modules` line one event per
program execution, named after the jitted function. The host plane holds
the benchmark's own `TraceAnnotation`s: `bench.window` marks the measured
region, and the other `bench.*` spans say what the host was doing, which
labels the device's idle gaps.
"""

from __future__ import annotations

import glob
import gzip
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
TOP = 10


@dataclass
class Reduced:
    """A trace reduced to intervals. Times are in nanoseconds on the
    trace's clock; `window` is the `bench.window` span."""

    window: tuple
    ops: list  # per device: [(name, start, end), ...]
    modules: list  # per device: [(name, start, end), ...]
    host: list = field(default_factory=list)  # [(name, start, end), ...]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, averaged over
        the devices traced."""
        lo, hi = self.window
        per = [union_ns(evs, lo, hi) for evs in self.ops]
        return sum(per) / len(per) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> float | None:
        """Device seconds of the programs whose name contains `pattern`,
        inside the window, averaged over devices; None when no program
        matches."""
        lo, hi = self.window
        per = []
        for evs in self.modules:
            hits = [clip(e, lo, hi) for e in evs if pattern in e[0]]
            if hits:
                per.append(sum(e - s for s, e in hits))
        return sum(per) / len(per) / 1e9 if per else None

    def module_count(self, pattern: str) -> int:
        lo, hi = self.window
        return sum(
            1 for e in self.modules[0]
            if pattern in e[0] and e[1] < hi and e[2] > lo
        ) if self.modules else 0

    def breakdown(self) -> dict:
        """The device operations that took most time (summed by name over
        the window on the first device, counting only operations that hold
        no other, so a loop does not hide its body), and the longest idle
        gaps, labelled by the innermost `bench.*` host span at the gap's
        middle."""
        lo, hi = self.window
        by_name: dict[str, int] = {}
        for name, s, e in leaves(self.ops[0]):
            cs, ce = clip((name, s, e), lo, hi)
            if ce > cs:
                by_name[name] = by_name.get(name, 0) + ce - cs
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = idle_gaps(self.ops[0], lo, hi)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        labelled = [
            [self.host_label((s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:TOP]
        ]
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": labelled,
        }

    def host_label(self, t: int) -> str:
        best = None
        for name, s, e in self.host:
            if name != WINDOW and s <= t < e:
                if best is None or e - s < best[2] - best[1]:
                    best = (name, s, e)
        return best[0] if best else "host:unlabelled"


def leaves(events) -> list:
    """The events that contain no other event of their line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][1] >= e[2]]


def short_name(name: str) -> str:
    """An HLO operation's name without its shape and operands
    (`%fusion.12 = s32[...] fusion(...)` -> `fusion.12`)."""
    return name.split(" = ", 1)[0].lstrip("%")


def clip(ev, lo: int, hi: int) -> tuple:
    return max(ev[1], lo), min(ev[2], hi)


def union_ns(events, lo: int, hi: int) -> int:
    """Length of the union of the events' intervals inside [lo, hi)."""
    spans = sorted(clip(e, lo, hi) for e in events)
    total, cur_s, cur_e = 0, None, None
    for s, e in spans:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(events, lo: int, hi: int) -> list:
    """The intervals of [lo, hi) that no event covers."""
    gaps, t = [], lo
    for s, e in sorted(clip(ev, lo, hi) for ev in events):
        if e <= s:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def events_of(line) -> list:
    return [
        (short_name(ev.name), int(ev.start_ns),
         int(ev.start_ns + ev.duration_ns))
        for ev in line.events
    ]


def reduce_profile(pd) -> Reduced:
    """Reduce a ProfileData by this module's plane and line names, which
    are a TPU trace's; a name ending in `*` matches by prefix."""

    def match(name: str, want: str) -> bool:
        return name.startswith(want[:-1]) if want.endswith("*") else name == want

    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            o, m = [], []
            for line in plane.lines:
                if match(line.name, OPS_LINE):
                    o.extend(events_of(line))
                if match(line.name, MODULES_LINE):
                    m.extend(events_of(line))
            if o:
                ops.append(o)
                modules.append(m)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(e for e in events_of(line) if e[0].startswith("bench."))
    windows = [e for e in host if e[0] == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    if not ops:
        raise ValueError(f"the trace holds no {DEVICE_PREFIX}* plane with ops")
    _, lo, hi = max(windows, key=lambda e: e[2] - e[1])
    return Reduced((lo, hi), ops, modules, host)


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def find_trace(trace_dir: str) -> str:
    found = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}: {found}")
    return found[0]


def reduce_dir(trace_dir: str) -> Reduced:
    return reduce_profile(load(find_trace(trace_dir)))

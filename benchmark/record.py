"""What a driver hands back to the harness."""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Record:
    """One run's outcome. `values` holds the raw numbers the metric
    reducers read; `checks` the {name, value, limit} entries that decide
    `correct` (a check passes when value <= limit); `notes` numbers a run
    prints beside its metrics for the reader (sample counts, medians, how
    late the generator ran)."""

    setup_s: float
    window_s: float
    attempted: int
    failed: int
    values: dict
    checks: list
    memory_peak_bytes: int
    notes: dict = field(default_factory=dict)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no allocator statistics)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats:
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def rusage_delta(a, b) -> dict:
    """What the process spent between two `getrusage` readings: CPU
    seconds, page faults, and context switches (involuntary ones mean
    another process held the core)."""
    return {
        "cpu_s": (b.ru_utime + b.ru_stime) - (a.ru_utime + a.ru_stime),
        "minor_faults": b.ru_minflt - a.ru_minflt,
        "major_faults": b.ru_majflt - a.ru_majflt,
        "ctx_switches": b.ru_nvcsw - a.ru_nvcsw,
        "ctx_switches_involuntary": b.ru_nivcsw - a.ru_nivcsw,
    }

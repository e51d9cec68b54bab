"""Engine configuration.

The reference configures each node with a single `Config` struct validated at
construction (reference: raft.go:124-336). The TPU engine splits that into:

- `Shape`: the *static* capacities that determine array shapes and therefore
  XLA program identity. Changing any of these recompiles the step kernel.
  These are the reference's unbounded dynamic structures pinned to fixed
  sizes, per SURVEY §7 ("the reference's own size limits become the static
  shapes").
- `LaneConfig` (see state.py): per-lane *dynamic* tunables (election ticks,
  feature flags, byte limits). Kept as device arrays so heterogeneous
  per-group configs never trigger a recompile — the batched analog of the
  reference constructing each node with its own Config.
"""

from __future__ import annotations

import dataclasses
import os

# ---------------------------------------------------------------------------
# Environment knobs.
#
# Every RAFT_TPU_* read in the package goes through these accessors — that is
# a lint rule (raft_tpu/analysis/lint.py), not a convention: a stray
# os.environ.get() elsewhere fails `python -m raft_tpu.analysis`. Centralizing
# the reads keeps flag semantics uniform (what counts as "off"), gives the
# README env-table cross-check one source of truth, and leaves exactly one
# place to add knob instrumentation.
#
# Flag grammar: unset -> the knob's default; "0", "" and "off" are false;
# anything else is true. Tri-state knobs (default/on/off with an
# auto-detection arm, e.g. RAFT_TPU_DONATE) use env_raw and keep their
# three-way logic at the call site.

_FALSY = ("0", "", "off")


def env_raw(name: str, default: str | None = None) -> str | None:
    """Raw tri-state read: None (unset) vs the literal string value."""
    return os.environ.get(name, default)


def env_flag(name: str, default: bool) -> bool:
    """Boolean knob: unset -> default; "0"/""/"off" -> False; else True."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw not in _FALSY


def env_str(name: str, default: str = "") -> str:
    """String knob with a default for unset/empty."""
    return os.environ.get(name) or default


def env_int(name: str, default: int = 0) -> int:
    """Integer knob: unset/empty -> default; non-integer raises with the
    knob name so a typo'd export fails loudly at the read site."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from None


def env_float(name: str, default: float = 0.0) -> float:
    """Float knob (tolerance multipliers and the like): unset/empty ->
    default; non-numeric raises with the knob name, same contract as
    env_int."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a float, got {raw!r}") from None

# Diet-v2 stores rebased index columns as uint16; the post-rebase index
# space is a few windows plus the between-rebase growth budget, so the
# window itself must stay far under 2^16. Named here so the validation
# message and the pack-boundary docs point at one constant.
MAX_LOG_WINDOW = 1 << 14


@dataclasses.dataclass(frozen=True)
class Shape:
    """Static capacities of the batched engine.

    Attributes:
      n_lanes: number of raft nodes hosted in this batch ("N"). For an
        in-process simulated cluster this is groups*voters; for a production
        shard it is the number of group-members homed on this host.
      max_peers: max voters+learners per group ("V"). The reference
        optimizes for <=7 voters (quorum/majority.go:137-141); 8 keeps the
        lane count a power of two with learner headroom.
      log_window: entries resident on device per lane ("W", circular).
        Mirrors the bounded in-memory log the reference keeps between
        compactions (storage.go:98-120 + log_unstable.go); older entries
        live host-side. Must be a power of two. The default stays 64 —
        deep enough that the serve/chaos planes never hit
        ERR_WINDOW_OVERFLOW out of the box — while the benches and the
        residency probes pin W=16 explicitly (that is the measured
        capacity shape, not the default; see benches/scaling_probe.py).
        Under RAFT_TPU_PAGED only page_window entries of W stay in the
        resident carry; the rest live in the paged HBM pool.
      page_window: paged entry log (RAFT_TPU_PAGED) resident entries per
        lane ("W_res", power of two, 2 <= W_res < W). 0 -> derived at
        cluster construction: env RAFT_TPU_PAGE_WINDOW, else min(8, W/2).
      page_entries: entries per pool page ("PE", power of two <= W).
        0 -> derived: env RAFT_TPU_PAGE_ENTRIES, else min(4, page_window).
      pool_pages: total pages in the shared HBM entry pool ("P"; page 0 is
        a reserved trash row, ids are uint16). 0 -> derived: env
        RAFT_TPU_POOL_PAGES, else full provisioning (never exhausts) —
        see ops/paged.py resolve_page_plan.
      max_msg_entries: entries carried per MsgApp ("E") — the static-shape
        version of Config.MaxSizePerMsg's "limit in entries" role
        (reference: raft.go:188-192).
      max_inflight: per-peer in-flight MsgApp window ("F") — the static
        capacity of tracker.Inflights (reference: tracker/inflights.go:28-40,
        Config.MaxInflightMsgs raft.go:211-215).
      outbox: max messages one lane can emit from a single step call. A
        leader stepping one message can fan out at most one MsgApp/heartbeat
        per peer plus a self-ack and a commit-triggered re-broadcast.
    """

    n_lanes: int
    max_peers: int = 8
    log_window: int = 64
    max_msg_entries: int = 8
    max_inflight: int = 8
    max_read_index: int = 4  # outstanding ReadIndex requests per lane ("R")
    # Largest single entry payload (bytes) the diet-v2 packed carry can
    # store: log_bytes / rep.ent_bytes narrow to int16 under RAFT_TPU_DIET
    # (state.pack_state / fused.pack_fabric). A bound, not a shape — it
    # exists so the int16 claim is validated where the configuration is.
    max_entry_bytes: int = 32767
    outbox: int = 0  # 0 -> derived
    # Paged entry log geometry (RAFT_TPU_PAGED, ops/paged.py). 0 -> derived
    # at cluster construction (env knob, then a safe default); nonzero
    # values are validated here so a bad explicit geometry fails at
    # config time from every cluster constructor, never at dispatch.
    page_window: int = 0
    page_entries: int = 0
    pool_pages: int = 0

    def __post_init__(self):
        if self.log_window & (self.log_window - 1):
            raise ValueError("log_window must be a power of two")
        if self.outbox == 0:
            object.__setattr__(self, "outbox", 2 * self.max_peers + 2)
        # the slim carry (state.STATE_SLIM / fused.FABRIC_SLIM) stores these
        # counters as int8
        for f in ("max_inflight", "max_read_index", "max_msg_entries"):
            if not 1 <= getattr(self, f) <= 127:
                raise ValueError(f"{f} must be in 1..127 (int8 carry diet; "
                                 "inbox sizing assumes at least 1)")
        # a message never carries more entries than the window holds, and
        # the log's range ops address a run inside one window
        if self.max_msg_entries > self.log_window:
            raise ValueError("max_msg_entries must be <= log_window")
        # the diet-v2 packed carry (state.pack_state) stores the per-peer
        # bool masks as one bitset word per lane and the rebased index
        # columns as uint16: V must fit one 32-bit word, and the window
        # must leave the post-rebase index space far under 2^16
        if not 1 <= self.max_peers <= 32:
            raise ValueError(
                "max_peers must be in 1..32 (diet-v2 packs the [N, V] bool "
                "masks into one bitset word per lane)"
            )
        if self.log_window > MAX_LOG_WINDOW:
            raise ValueError(
                f"log_window must be <= MAX_LOG_WINDOW={MAX_LOG_WINDOW} "
                "(diet-v2 stores rebased index columns as uint16; the "
                "post-rebase space is a few windows plus the between-rebase "
                "growth budget)"
            )
        if not 1 <= self.max_entry_bytes <= 32767:
            raise ValueError(
                "max_entry_bytes must be in 1..32767 (diet-v2 stores entry "
                "size columns as int16)"
            )
        # paged entry log geometry: each nonzero field validates on its own
        # here (config-time, constructor-independent); the cross-field plan
        # (derived defaults, pool-vs-lanes sizing) is resolved and validated
        # by ops/paged.py validate_page_plan from the cluster constructors.
        if self.page_window:
            if self.page_window & (self.page_window - 1):
                raise ValueError("page_window must be a power of two")
            if not 2 <= self.page_window < self.log_window:
                raise ValueError(
                    "page_window must be in 2..log_window/2 (the paged "
                    "resident window is a strict subset of log_window)"
                )
        if self.page_entries:
            if self.page_entries & (self.page_entries - 1):
                raise ValueError("page_entries must be a power of two")
            if not 1 <= self.page_entries <= self.log_window:
                raise ValueError(
                    "page_entries must be in 1..log_window (a page never "
                    "holds more than one window)"
                )
        if self.pool_pages:
            if not 2 <= self.pool_pages <= (1 << 16):
                raise ValueError(
                    "pool_pages must be in 2..65536 (page ids are uint16 "
                    "with page 0 reserved as the trash row)"
                )

    @property
    def n(self) -> int:
        return self.n_lanes

    @property
    def v(self) -> int:
        return self.max_peers

    @property
    def w(self) -> int:
        return self.log_window


# Defaults mirroring reference raft.go:288-336 validate() fallbacks.
DEFAULT_ELECTION_TICK = 10
DEFAULT_HEARTBEAT_TICK = 1
DEFAULT_MAX_SIZE_PER_MSG = 1 << 20
DEFAULT_MAX_UNCOMMITTED_SIZE = 1 << 30
DEFAULT_MAX_COMMITTED_SIZE_PER_READY = 1 << 20

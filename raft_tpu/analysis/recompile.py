"""Compile-watch sentinel: catch silent recompiles of the entry points.

jax 0.4.37 logs one line per fresh XLA compilation when
``jax_log_compiles`` is on::

    Finished XLA compilation of jit(fused_rounds) in 0.41 sec

(logger ``jax._src.dispatch``, WARNING level). The sentinel attaches a
handler there, drives the canonical bench smoke twice with the SAME
cluster objects, and holds two invariants:

- **warmup**: each manifest entry point compiles at most its
  ``compile_budget`` (one signature per entry in the smoke, so the
  budget is 1 everywhere — a second compile means something in the
  dispatch path perturbs the jit signature per call: a python-scalar
  static that changes, a re-wrapped closure, a fresh jit object).
- **steady**: a second pass over the same smoke compiles NOTHING.
  Every re-dispatch must hit the in-memory executable cache; one fresh
  compile here is the classic perf cliff (a per-call lambda, an
  unhashable static, an aval flip like weak_type drift).

Unbudgeted compile names (eager-op jits, init-time packing helpers) are
collected but never fail the run — they're reported so a new entry
point showing up here is visible before someone adds it to the
manifest.
"""

from __future__ import annotations

import logging
import re

from raft_tpu.analysis.jaxpr_audit import Finding

# "Finished XLA compilation of jit(fused_rounds) in 0.41 sec"
_COMPILE_RE = re.compile(r"Finished XLA compilation of (.+?) in [0-9.eE+-]+ sec")
_JIT_NAME_RE = re.compile(r"jit\(([^)]*)\)")

# manifest entry -> the exact jit names its dispatch path may compile
ENTRY_JIT_NAMES = {
    "round.xla": ("fused_rounds",),
    "round.pallas": ("pallas_rounds",),
    "sharded.step.xla": ("shard_mapped_rounds",),
    "quorum.pallas": ("joint_committed_pallas", "committed_pallas"),
    "quorum.xla": ("joint_committed",),
    "egress.ready_bundle": ("ready_bundle",),
    "egress.delta": ("delta_bundle",),
    "rebase.indexes": ("_rebase_indexes",),
    "rebase.fabric": ("_rebase_fabric",),
    "paged.page_in": ("page_in_host", "page_in"),
    "paged.page_out": ("page_out_host", "page_out"),
    "tier.gather": ("_tier_gather",),
    "tier.scatter": ("_tier_scatter",),
}


class CompileWatch(logging.Handler):
    """Counts fresh XLA compilations per jit name while attached."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.counts: dict[str, int] = {}

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if not m:
            return
        jm = _JIT_NAME_RE.search(m.group(1))
        name = jm.group(1) if jm else m.group(1)
        self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self):
        self.counts = {}

    def __enter__(self):
        import jax

        self._prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._logger = logging.getLogger("jax._src.dispatch")
        # the compile log is our signal, not the operator's: keep the
        # firehose (dispatch's "Finished ..." lines and pxla's
        # "Compiling ..." companions) out of stderr while the watch owns it
        self._pxla = logging.getLogger("jax._src.interpreters.pxla")
        self._propagate = (self._logger.propagate, self._pxla.propagate)
        self._logger.propagate = False
        self._pxla.propagate = False
        # a handler-less non-propagating logger falls through to
        # logging.lastResort (stderr): park a NullHandler on pxla
        self._null = logging.NullHandler()
        self._pxla.addHandler(self._null)
        self._logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self)
        self._pxla.removeHandler(self._null)
        self._logger.propagate, self._pxla.propagate = self._propagate
        import jax

        jax.config.update("jax_log_compiles", False if not self._prev else True)
        return False


def _bucket(counts: dict) -> tuple[dict, dict]:
    """Split raw jit-name counts into (per-entry counts, untracked)."""
    per_entry = {e: 0 for e in ENTRY_JIT_NAMES}
    untracked = {}
    owner = {}
    for entry, names in ENTRY_JIT_NAMES.items():
        for n in names:
            owner[n] = entry
    for name, c in counts.items():
        e = owner.get(name)
        if e is None:
            untracked[name] = untracked.get(name, 0) + c
        else:
            per_entry[e] += c
    return per_entry, untracked


def _smoke_context():
    """Build the canonical smoke's clusters and operands ONCE — steady
    state only holds if the second pass reuses the same objects (a fresh
    ShardedFusedCluster owns a fresh stepper jit; a fresh jax.jit
    wrapper is a fresh cache)."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    from raft_tpu.analysis.registry import PROFILES, env_profile
    from raft_tpu.ops import quorum as qr
    from raft_tpu.ops import quorum_pallas as qp
    from raft_tpu.ops import ready_mask as rm
    from raft_tpu.ops import paged as pgmod
    from raft_tpu.ops import fused as fmod
    from raft_tpu.state import unpack_state

    ctx = {}
    with env_profile(PROFILES["planes_on"]):
        ctx["xla"] = fmod.FusedCluster(n_groups=4, n_voters=3, engine="xla")
        ctx["pallas"] = fmod.FusedCluster(
            n_groups=4, n_voters=3, engine="pallas", rounds_per_call=2
        )
        if len(jax.devices()) >= 2:
            from raft_tpu.parallel.sharded import ShardedFusedCluster

            ctx["sharded"] = ShardedFusedCluster(n_groups=16, n_voters=3)
    with env_profile(PROFILES["paged"]):
        ctx["paged"] = fmod.FusedCluster(n_groups=4, n_voters=3, engine="xla")

    rng = np.random.default_rng(3)
    n, v = 256, 3
    ctx["match"] = jnp.asarray(rng.integers(0, 1 << 20, (n, v)), jnp.int32)
    ctx["m_in"] = jnp.asarray(rng.random((n, v)) < 0.8)
    ctx["m_out"] = jnp.asarray(rng.random((n, v)) < 0.4)
    ctx["quorum_xla_jit"] = jax.jit(qr.joint_committed)

    cl = ctx["xla"]
    nl = cl.state.term.shape[0]
    z = np.zeros((nl,), np.int32)
    f = np.zeros((nl,), bool)
    ctx["host"] = rm.HostCursors(
        prev_term=z, prev_vote=z, prev_commit=z, prev_lead=z,
        prev_state=z, host_pending=f, is_async=f, inprog=z,
        snap_inprog=z, applying=z,
    )
    # rebase operands come from a cluster that never dispatches: unpack/
    # fat-fabric pass already-wide leaves through by reference, and the
    # donating smoke clusters delete their pre-dispatch buffers
    with env_profile(PROFILES["planes_off"]):
        base = fmod.FusedCluster(n_groups=4, n_voters=3, engine="xla")
    st = unpack_state(base.state)
    ctx["rebase_args"] = (
        st,
        jnp.asarray(np.ones((nl,), bool)),
        jnp.asarray(np.zeros((nl,), np.int32)),
    )
    ctx["rebase_fab"] = fmod.fat_fabric(fmod.unpack_fabric(base.fab))
    # tier gather/scatter operands: one group's voter lanes pow2-padded,
    # rows sliced host-side so building them compiles nothing
    from raft_tpu.tier import engine as tmod

    with env_profile(PROFILES["tier"]):
        tcl = fmod.FusedCluster(n_groups=4, n_voters=3, engine="xla")
    tst = unpack_state(tcl.state)
    tfb = fmod.unpack_fabric(tcl.fab)
    tlanes, _ = tmod._pad_rows(np.arange(tcl.v, dtype=np.int32), None)
    trows = lambda t: jax.tree.map(
        lambda x: jnp.asarray(np.asarray(x)[tlanes]), t
    )
    ctx["tier_args"] = (tst, tfb, jnp.asarray(tlanes))
    ctx["tier_rows"] = (trows(tst), trows(tfb))
    ctx["tmod"] = tmod
    ctx["rm"] = rm
    ctx["qp"] = qp
    ctx["fmod"] = fmod
    ctx["pgmod"] = pgmod
    return ctx


def _drive(ctx):
    """One pass of the canonical smoke: every manifest entry point
    dispatches once with one fixed signature."""
    import jax

    rm, qp, fmod, pgmod = ctx["rm"], ctx["qp"], ctx["fmod"], ctx["pgmod"]
    ctx["xla"].run(2)
    ctx["pallas"].run(2)
    if "sharded" in ctx:
        ctx["sharded"].run(2)
    qp.joint_committed_pallas(
        ctx["match"], ctx["m_in"], ctx["m_out"], interpret=True
    )
    ctx["quorum_xla_jit"](ctx["match"], ctx["m_in"], ctx["m_out"])
    rm.compute_bundle(ctx["xla"].state, ctx["host"])
    rm.compute_delta(ctx["xla"].state, None)
    st, mask, delta = ctx["rebase_args"]
    jax.block_until_ready(fmod._rebase_indexes_jit(st, mask, delta))
    jax.block_until_ready(fmod.rebase_fabric(ctx["rebase_fab"], delta))
    pg = ctx["paged"]
    full, _ = pgmod.page_in_host(pg.state, pg.paged)
    jax.block_until_ready(pgmod.page_out_host(full, pg.paged))
    # the tier pair via the copying scatter twin (same jit name as the
    # donating one, and the operands stay valid for the steady pass)
    tg, tsc, _ = ctx["tmod"]._jits()
    tst, tfb, tlanes = ctx["tier_args"]
    jax.block_until_ready(tg(tst, tfb, tlanes))
    jax.block_until_ready(tsc(tst, tfb, tlanes, *ctx["tier_rows"]))


def run_sentinel() -> tuple[list, dict]:
    """Run the two-pass compile sentinel. Returns (findings, report):
    findings is the Finding list (empty = clean), report carries the
    per-phase per-entry compile counts for ANALYSIS.json."""
    from raft_tpu.analysis.registry import ENTRIES

    budgets = {
        e.name: e.compile_budget
        for e in ENTRIES
        if e.name in ENTRY_JIT_NAMES
    }
    findings = []
    with CompileWatch() as watch:
        ctx = _smoke_context()
        watch.reset()  # construction-time eager compiles are not the smoke
        _drive(ctx)
        warm, warm_untracked = _bucket(watch.counts)
        watch.reset()
        _drive(ctx)
        steady, steady_untracked = _bucket(watch.counts)

    driven = set(warm) if "sharded" in ctx else set(warm) - {"sharded.step.xla"}
    for entry in sorted(driven):
        budget = budgets.get(entry, 1)
        if warm[entry] > budget:
            findings.append(Finding(entry, "recompile", (
                f"warmup compiled {warm[entry]}x (budget {budget}) — the "
                "dispatch path perturbs the jit signature per call"
            )))
        if warm[entry] == 0:
            findings.append(Finding(entry, "recompile", (
                "the smoke never compiled this entry point — the sentinel "
                "lost coverage of it (smoke and manifest drifted)"
            )))
        if steady.get(entry, 0) > 0:
            findings.append(Finding(entry, "recompile", (
                f"steady-state re-run compiled {steady[entry]}x — a warm "
                "re-dispatch missed the executable cache (per-call "
                "closure, unhashable static, or aval drift)"
            )))
    report = {
        "warmup": warm,
        "warmup_untracked": warm_untracked,
        "steady": steady,
        "steady_untracked": steady_untracked,
    }
    return findings, report

"""Repo AST lint: env-knob routing, README cross-check, host hygiene.

Five rules, all pure ``ast`` walks — no jax import, no execution:

- **A — env routing**: every *read* of a ``RAFT_TPU_*`` environment
  variable must go through the typed accessors in ``raft_tpu/config.py``
  (``env_flag``/``env_int``/``env_float``/``env_str``/``env_raw``),
  which own the falsy-token grammar (``"0"``/``""``/``"off"``) and the
  numeric parsing.
  A stray ``os.environ.get("RAFT_TPU_X")`` grows a knob with its own
  private truthiness — the exact drift this rule exists to stop.
  *Writes* stay legal: benches pin planes with
  ``os.environ["RAFT_TPU_X"] = "1"`` / ``setdefault`` / subprocess
  ``dict(os.environ, RAFT_TPU_X=...)`` envs, and none of those reads
  the knob.
- **B — README cross-check**: the set of ``RAFT_TPU_*`` names passed
  as literals to the config accessors anywhere in scope must equal the
  set of rows in README.md's env tables (``| `RAFT_TPU_X` | ... |``).
  A knob the README doesn't list is invisible to operators; a row no
  accessor reads is stale documentation.
- **C — host-plane hygiene**: the host-plane modules (the serving
  router and loop, the WAL/egress/trace stream resolvers, the metrics
  puller, the trace assembler) must not touch device values outside the
  named resolve points: no ``jnp.*`` usage, and no implicit-sync call
  (``np.asarray``/``np.array``/``jax.block_until_ready``/
  ``jax.device_get``/``.item()``/``.tolist()``) outside the allowlist.
  Everything else in those modules must stay plain-numpy/pure-python so
  a dispatch block never gains a hidden device round-trip.
- **C' — bench hygiene**: the same visitor runs over ``benches/*.py``
  with a per-file function allowlist (``BENCH_ALLOW``).  Bench drivers
  *are* supposed to dispatch and block — but only inside the named
  measurement functions, so a stray sync in argument parsing or report
  printing can't silently join the timed region.  A new bench file must
  add its own row.
- **D — donation escape (host view)**: the donation escape proof in
  ``jaxpr_audit.py`` covers the compiled program; this rule covers the
  host side of the same invariant.  In the modules that consume device
  views (``ESCAPE_SCOPE``), a ``self.X = ...`` assignment whose value
  calls a device-view producer (``DEVICE_VIEW_CALLS``) must also pass
  it through a host copy (``HOST_COPY_CALLS``) — otherwise the object
  holds a live reference into a buffer the next donated dispatch will
  invalidate.  Attributes ending ``_pending``/``_inflight`` are exempt:
  that suffix *is* the repo's declared discipline for intentionally
  deferred device handles resolved before the next dispatch.
"""

from __future__ import annotations

import ast
import os
import re

from raft_tpu.analysis.jaxpr_audit import Finding

_KNOB = "RAFT_TPU_"
_ACCESSORS = ("env_flag", "env_int", "env_float", "env_str", "env_raw")

# README env-table rows: | `RAFT_TPU_X` | default | effect |
_README_ROW_RE = re.compile(r"^\|\s*`(RAFT_TPU_[A-Z0-9_]+)`", re.MULTILINE)

# rule C scope: module path (repo-relative) -> allowlisted functions.
# These are the stream/bundle RESOLVE points where a host copy of device
# data is the whole job, and the op expansion's jitted body; bridge.py
# (state reconstruction) and the device planes themselves are out of
# scope by design.
HOST_PLANE_ALLOW = {
    "raft_tpu/serve/router.py": {"on_bundle"},
    "raft_tpu/serve/loop.py": set(),
    "raft_tpu/serve/inject.py": {"_expand"},
    "raft_tpu/runtime/wal.py": {"_resolve"},
    "raft_tpu/runtime/egress.py": {"_resolve_pending", "merge_delta_bundles"},
    "raft_tpu/runtime/trace.py": {"_resolve_pending"},
    "raft_tpu/metrics/host.py": {"_delta", "pull"},
    "raft_tpu/trace/assemble.py": {"merge_block_events", "assemble", "explain"},
}

# rule C' scope: bench file (repo-relative under benches/) -> functions
# allowed to dispatch/sync.  Benches are drivers, so device traffic is
# the point — but it must live in the named measurement functions, not
# leak into argument parsing or report printing.  A bench file absent
# from this table lints with an empty allowlist until a row is added.
BENCH_ALLOW = {
    "benches/__init__.py": set(),
    "benches/baseline_configs.py": {
        "config1_single_group_proposals", "config2_1k_groups_heartbeat",
        "config3_fanin_100k_x5", "config4_joint_consensus_replace_leader",
    },
    "benches/bridge_bench.py": set(),
    "benches/bridge_fused_bench.py": {"_host_b", "main"},
    "benches/chaos_soak.py": set(),
    "benches/confchange_soak.py": set(),
    "benches/diet_ab.py": {"child"},
    "benches/dispatch_ab.py": set(),
    "benches/egress_ab.py": set(),
    "benches/fabric_ab.py": {"child"},
    "benches/hlo_sources.py": set(),
    "benches/latency_probe.py": {"measure", "measure_blocked"},
    "benches/lease_ab.py": {"child"},
    "benches/metrics_smoke.py": set(),
    "benches/multichip_ab.py": set(),
    "benches/paged_ab.py": {"child"},
    "benches/pallas_ab.py": {"child"},
    "benches/pallas_probe.py": {"main"},
    "benches/roundtime.py": {"main"},
    "benches/scaling_probe.py": {"measure"},
    "benches/serve_bench.py": {"pct"},
    "benches/soak.py": {"main"},
    "benches/tier_ab.py": {"child"},
    "benches/trace_ab.py": {"child"},
    "benches/wal_ab.py": {"fetch_delta", "run"},
}

# rule D scope: host modules that consume device views produced by the
# donated round programs.  Keep in sync with the audit-side escape
# proof in jaxpr_audit.check_donation_escape.
ESCAPE_SCOPE = (
    "raft_tpu/runtime/wal.py",
    "raft_tpu/runtime/egress.py",
    "raft_tpu/runtime/trace.py",
    "raft_tpu/serve/router.py",
    "raft_tpu/serve/loop.py",
)

# Producers whose return values alias (or may alias) donated device
# buffers...
DEVICE_VIEW_CALLS = {
    "host_state", "state_columns", "drain_read_states", "_wal_view",
    "compute_delta", "compute_bundle", "ready_bundle", "delta_bundle",
    "shard_events", "unpack_state", "shard_egress_view", "page_in_view",
}
# ...and the calls that sever the alias by materialising a host copy.
HOST_COPY_CALLS = {
    "asarray", "array", "ascontiguousarray", "device_get", "copy",
    "deepcopy",
}
_ESCAPE_EXEMPT_SUFFIXES = ("_pending", "_inflight")

_SYNC_METHODS = ("item", "tolist")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def scope_files(root: str | None = None) -> list[str]:
    """Rule A/B scope: the package, bench.py, benches/** — not tests/
    (tests legitimately poke raw env to build fixtures)."""
    root = root or repo_root()
    out = []
    for base in ("raft_tpu", "benches"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, base)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            out.extend(
                os.path.join(dirpath, f)
                for f in filenames
                if f.endswith(".py")
            )
    bench = os.path.join(root, "bench.py")
    if os.path.exists(bench):
        out.append(bench)
    return sorted(out)


def _rel(path: str, root: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


def _is_os_environ(node) -> bool:
    """node is the expression `os.environ`."""
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "environ"
        and isinstance(node.value, ast.Name)
        and node.value.id == "os"
    )


def _literal_knob(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        if node.value.startswith(_KNOB):
            return node.value
    return None


def check_env_routing(files: list[str], root: str) -> list[Finding]:
    """Rule A. config.py itself is the one legal home for raw reads."""
    out = []
    for path in files:
        rel = _rel(path, root)
        if rel == "raft_tpu/config.py":
            continue
        tree = ast.parse(open(path).read(), filename=path)
        for node in ast.walk(tree):
            knob = None
            # os.environ["RAFT_TPU_X"] in Load context
            if (
                isinstance(node, ast.Subscript)
                and _is_os_environ(node.value)
                and isinstance(node.ctx, ast.Load)
            ):
                knob = _literal_knob(node.slice)
            # os.environ.get("RAFT_TPU_X") / os.getenv("RAFT_TPU_X")
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                f = node.func
                is_environ_get = f.attr == "get" and _is_os_environ(f.value)
                is_os_getenv = (
                    f.attr == "getenv"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "os"
                )
                if (is_environ_get or is_os_getenv) and node.args:
                    knob = _literal_knob(node.args[0])
            if knob:
                out.append(Finding(rel, "env-routing", (
                    f"line {node.lineno}: raw environment read of {knob} — "
                    "route it through raft_tpu.config (env_flag/env_int/"
                    "env_str/env_raw) so the falsy grammar stays uniform"
                )))
    return out


def collect_knobs(files: list[str]) -> set[str]:
    """Every RAFT_TPU_* literal passed to a config accessor in scope."""
    knobs = set()
    for path in files:
        tree = ast.parse(open(path).read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            f = node.func
            name = None
            if isinstance(f, ast.Attribute) and f.attr in _ACCESSORS:
                name = f.attr
            elif isinstance(f, ast.Name) and f.id in _ACCESSORS:
                name = f.id
            if name is None:
                continue
            knob = _literal_knob(node.args[0])
            if knob:
                knobs.add(knob)
    return knobs


def check_readme(files: list[str], root: str) -> list[Finding]:
    """Rule B, both directions."""
    readme = os.path.join(root, "README.md")
    rows = set(_README_ROW_RE.findall(open(readme).read()))
    knobs = collect_knobs(files)
    out = []
    for k in sorted(knobs - rows):
        out.append(Finding("README.md", "readme-table", (
            f"knob {k} is read via config accessors but has no row in "
            "README's env tables — operators can't discover it"
        )))
    for k in sorted(rows - knobs):
        out.append(Finding("README.md", "readme-table", (
            f"README documents {k} but no config accessor reads it — "
            "stale row (or the knob bypasses config.py)"
        )))
    return out


class _HostPlaneVisitor(ast.NodeVisitor):
    def __init__(self, rel, allow):
        self.rel = rel
        self.allow = allow
        self.stack = []
        self.findings = []

    def _allowed(self) -> bool:
        return any(fn in self.allow for fn in self.stack)

    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _flag(self, node, what):
        self.findings.append(Finding(self.rel, "host-hygiene", (
            f"line {node.lineno}: {what} in host-plane module outside the "
            f"resolve allowlist ({', '.join(sorted(self.allow)) or 'none'})"
            " — host code must stay off the device except at stream "
            "resolve points"
        )))

    def visit_Attribute(self, node):
        if isinstance(node.value, ast.Name):
            base = node.value.id
            if base == "jnp" and not self._allowed():
                self._flag(node, f"jnp.{node.attr} usage")
            elif base == "jax" and node.attr in (
                "block_until_ready", "device_get", "device_put"
            ) and not self._allowed():
                self._flag(node, f"jax.{node.attr} call")
            elif base == "np" and node.attr in ("asarray", "array") \
                    and not self._allowed():
                self._flag(node, f"np.{node.attr} (device sync when fed a"
                           " jax array)")
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr in _SYNC_METHODS
            and not isinstance(f.value, ast.Name)  # x.item() on expressions
            and not self._allowed()
        ):
            self._flag(node, f".{f.attr}() call")
        self.generic_visit(node)


def check_host_plane(root: str) -> list[Finding]:
    """Rule C."""
    out = []
    for rel, allow in HOST_PLANE_ALLOW.items():
        path = os.path.join(root, rel)
        if not os.path.exists(path):  # pragma: no cover - layout drift
            out.append(Finding(rel, "host-hygiene",
                               "module listed in HOST_PLANE_ALLOW is gone"))
            continue
        v = _HostPlaneVisitor(rel, allow)
        v.visit(ast.parse(open(path).read(), filename=path))
        out.extend(v.findings)
    return out


def check_bench_hygiene(root: str) -> list[Finding]:
    """Rule C'. Same visitor as rule C, per-file allowlists."""
    out = []
    bench_dir = os.path.join(root, "benches")
    if not os.path.isdir(bench_dir):  # pragma: no cover - layout drift
        return out
    present = {
        "benches/" + f
        for f in os.listdir(bench_dir)
        if f.endswith(".py")
    }
    for rel in sorted(BENCH_ALLOW.keys() - present):
        out.append(Finding(rel, "bench-hygiene",
                           "file listed in BENCH_ALLOW is gone — drop "
                           "the stale row"))
    for rel in sorted(present):
        allow = BENCH_ALLOW.get(rel, set())
        if rel not in BENCH_ALLOW:
            out.append(Finding(rel, "bench-hygiene", (
                "new bench file has no BENCH_ALLOW row — name the "
                "functions allowed to dispatch/sync so stray device "
                "traffic outside them keeps getting flagged"
            )))
        v = _HostPlaneVisitor(rel, allow)
        v.visit(ast.parse(open(os.path.join(root, rel)).read(),
                          filename=rel))
        out.extend(v.findings)
    return out


class _EscapeVisitor(ast.NodeVisitor):
    """Rule D: self.X = <device view> without a host copy."""

    def __init__(self, rel):
        self.rel = rel
        self.findings = []

    @staticmethod
    def _call_names(expr) -> set[str]:
        names = set()
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                names.add(f.attr)
            elif isinstance(f, ast.Name):
                names.add(f.id)
        return names

    def _check(self, node, targets, value):
        attrs = [
            t.attr for t in targets
            if isinstance(t, ast.Attribute)
            and isinstance(t.value, ast.Name)
            and t.value.id == "self"
            and not t.attr.endswith(_ESCAPE_EXEMPT_SUFFIXES)
        ]
        if not attrs or value is None:
            return
        calls = self._call_names(value)
        views = calls & DEVICE_VIEW_CALLS
        if views and not (calls & HOST_COPY_CALLS):
            self.findings.append(Finding(self.rel, "view-escape", (
                f"line {node.lineno}: self.{attrs[0]} stores the result "
                f"of {'/'.join(sorted(views))} without a host copy — the "
                "view aliases a donated device buffer that the next "
                "dispatch invalidates; copy it (np.asarray/…) or use a "
                "*_pending/*_inflight slot resolved before the next "
                "dispatch"
            )))

    def visit_Assign(self, node):
        self._check(node, node.targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        self._check(node, [node.target], node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check(node, [node.target], node.value)
        self.generic_visit(node)


def check_view_escape(root: str) -> list[Finding]:
    """Rule D."""
    out = []
    for rel in ESCAPE_SCOPE:
        path = os.path.join(root, rel)
        if not os.path.exists(path):  # pragma: no cover - layout drift
            out.append(Finding(rel, "view-escape",
                               "module listed in ESCAPE_SCOPE is gone"))
            continue
        v = _EscapeVisitor(rel)
        v.visit(ast.parse(open(path).read(), filename=path))
        out.extend(v.findings)
    return out


def run_lint(root: str | None = None) -> tuple[list[Finding], dict]:
    """All five rules; returns (findings, report)."""
    root = root or repo_root()
    files = scope_files(root)
    findings = []
    findings += check_env_routing(files, root)
    findings += check_readme(files, root)
    findings += check_host_plane(root)
    findings += check_bench_hygiene(root)
    findings += check_view_escape(root)
    report = {
        "files_scanned": len(files),
        "knobs": sorted(collect_knobs(files)),
        "host_plane_modules": sorted(HOST_PLANE_ALLOW),
        "bench_modules": sorted(BENCH_ALLOW),
        "escape_modules": sorted(ESCAPE_SCOPE),
    }
    return findings, report

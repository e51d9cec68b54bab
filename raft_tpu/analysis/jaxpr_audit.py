"""Static jaxpr/HLO invariant checks over the registry's entry points.

Every check here runs WITHOUT executing a round: programs are traced with
`jax.make_jaxpr` and lowered with `jit.lower(...)`, never called. The
checks encode the compile-time contracts the rest of the repo asserts
ad-hoc in whichever test first needed them (see ISSUE/README):

- **elision** — with a plane's env knob off, zero primitives attributable
  to that plane anywhere in the program. Proven via the shared trace-time
  CallCounters (raft_tpu/testing/counters.py): the plane's device fn bumps
  its counter when TRACED, so a flat counter across the `make_jaxpr` of an
  entry point means the plane contributed nothing to the jaxpr. A plane
  that is ON must bump (positive sanity — a counter that never moves
  can't prove elision).
- **donation** — a donating twin's lowering must carry an input-output
  alias for every donated carry leaf; a donated leaf that LOST its alias
  (read from the lowered @main signature: a `jax.buffer_donor` argument
  that no unpaired output of its exact type can take over) is a silent
  HBM doubling. The copying twin must alias nothing.
- **dtype discipline** — under RAFT_TPU_DIET=1 the packed carry columns
  (uint16 indexes/terms, int8 ids, int16 sizes, uint8/16/32 bitsets) must
  ride the scan carry / pallas operands in their packed dtypes. The
  in-body widen/compute/narrow cycle is by design; what must never happen
  is a packed column riding the BETWEEN-rounds carry widened to int32 —
  so the check asserts every narrow leaf of the actual carry appears
  among the program's scan-carry/kernel-operand avals.
- **constant capture** — no jaxpr consts feeding a `pallas_call` (the
  jax 0.4.37 lifted-literal hazard from PR 4: enum scalars and array
  literals become constvars that Mosaic rejects or bakes into the
  kernel), and no large (>16 KiB) const anywhere in the program (a
  captured table silently re-uploads per executable).
- **host-boundary hygiene** — no host callbacks/infeed/outfeed inside a
  round-dispatch program: the round must be pure device code; a stray
  `debug_callback`/`pure_callback` forces a host sync per dispatch.
- **carry stability** — the between-rounds carry is a FIXPOINT: every
  carry leaf (the record's `carry_argnums`/`carry_argnames` legs) must
  come back out of the program with the identical (shape, dtype) aval.
  A silent uint16→int32 widen between rounds would quietly undo the
  diet's savings while every value-level test stays green.
- **donation escape** — the per-leaf refinement of the donation check:
  parse the lowered @main signature and name exactly WHICH donated leaf
  lost its `tf.aliasing_output` alias (the count check says how many;
  this one says which column, which is what you need to fix it).
- **paged roundtrip** — `page_out(full) -> (resident, paged)` and
  `page_in(resident, paged) -> (full, paged)` must be aval-inverse:
  each one's outputs match the other's inputs leaf for leaf, so the
  host boundary can cycle the window forever without a reshape/upcast
  creeping in (records declare the pairing via a `roundtrip` key).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import re
from collections import Counter

import jax

from raft_tpu.testing import counters as ctr

# dtypes the diet-v2 pack boundary may produce; anything in the carry with
# one of these is a "packed column" the program must preserve
NARROW_DTYPES = ("uint8", "uint16", "uint32", "int8", "int16")

# one const bigger than this anywhere in a program is a capture bug (the
# engine passes all real data as arguments; consts should be iota/scalars)
MAX_CONST_BYTES = 16 * 1024

# primitives that cross the host boundary inside a device program
_HOST_PRIMS = ("infeed", "outfeed")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One invariant violation. `entry` names the manifest entry point,
    `check` the auditor pass, `detail` the human-readable evidence."""

    entry: str
    check: str
    detail: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# --------------------------------------------------------------------------
# program tracing


def trace_entry(rec) -> "jax.core.ClosedJaxpr":
    """Trace a registry program record to its closed jaxpr without
    executing it. Static kwargs close over the fn; array args/kwargs are
    passed as tracer arguments so real data never becomes a jaxpr const
    (which would defeat the constant-capture check)."""
    fn = functools.partial(rec["fn"], **rec.get("static", {}))
    return jax.make_jaxpr(fn)(*rec["args"], **rec.get("kwargs", {}))


def traced_counter_deltas(rec) -> tuple["jax.core.ClosedJaxpr", dict]:
    """(closed_jaxpr, {plane: trace-time counter delta}) for one record."""
    before = ctr.snapshot()
    jaxpr = trace_entry(rec)
    after = ctr.snapshot()
    return jaxpr, {k: after[k] - before.get(k, 0) for k in after}


# --------------------------------------------------------------------------
# jaxpr walking


def iter_jaxprs(jaxpr):
    """Yield (jaxpr, constvar_set) for the top jaxpr and every sub-jaxpr
    reachable through eqn params (scan/cond/pjit/pallas bodies)."""
    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        yield jx, set(jx.constvars)
        for eqn in jx.eqns:
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub)

    yield from walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for item in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr  # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item  # raw Jaxpr


def iter_eqns(jaxpr):
    for jx, _ in iter_jaxprs(jaxpr):
        yield from jx.eqns


def _aval_key(aval) -> tuple:
    return (tuple(aval.shape), str(aval.dtype))


def storage_avals(jaxpr) -> set:
    """The program's "storage" avals: scan-carry avals (what HBM holds
    between rounds) plus pallas_call operand avals (what the kernel is
    fed). These are the positions where the diet's packed dtypes must
    survive."""
    out = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            nc = eqn.params.get("num_consts", 0)
            ncar = eqn.params.get("num_carry", 0)
            body = eqn.params.get("jaxpr")
            invars = body.jaxpr.invars if hasattr(body, "jaxpr") else body.invars
            for v in invars[nc : nc + ncar]:
                out.add(_aval_key(v.aval))
        elif name == "pallas_call":
            for v in eqn.invars:
                if hasattr(v, "aval"):
                    out.add(_aval_key(v.aval))
    return out


def narrow_carry_avals(tree) -> set:
    """The (shape, dtype) set of every packed-dtype leaf in an actual
    carry pytree — what the program's storage avals must cover."""
    out = set()
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and str(leaf.dtype) in NARROW_DTYPES:
            out.add((tuple(leaf.shape), str(leaf.dtype)))
    return out


# --------------------------------------------------------------------------
# checks (each returns a list of Findings)


def check_elision(name, deltas, expect_on: dict) -> list:
    """expect_on: {plane: bool} — planes expected IN the program must have
    bumped their trace-time counter during the trace; planes expected OFF
    must not have."""
    out = []
    for plane, on in expect_on.items():
        d = deltas.get(plane, 0)
        if on and d <= 0:
            out.append(Finding(name, "elision", (
                f"plane '{plane}' is enabled but its device fn was never "
                "traced into the program (counter flat) — the plane "
                "silently dropped out"
            )))
        if not on and d > 0:
            out.append(Finding(name, "elision", (
                f"plane '{plane}' is disabled but its device fn was traced "
                f"{d}x into the program — elision is broken, the knob no "
                "longer compiles the plane out"
            )))
    return out


def check_dtype_discipline(name, jaxpr, carry) -> list:
    """Every packed (narrow-dtype) leaf of the real carry must appear among
    the program's scan-carry / pallas-operand avals with its packed shape
    and dtype. A missing one means some path widened it (usually to int32)
    for the ride between rounds — the silent byte-diet regression."""
    have = storage_avals(jaxpr)
    if not have:
        return []  # no scan/kernel in this program — nothing rides a carry
    out = []
    for shape, dtype in sorted(narrow_carry_avals(carry)):
        if (shape, dtype) not in have:
            out.append(Finding(name, "dtype", (
                f"packed carry column {dtype}{list(shape)} does not appear "
                "in any scan carry / kernel operand — a cast widened it "
                "between rounds (diet regression)"
            )))
    return out


def check_constant_capture(name, jaxpr) -> list:
    out = []
    for jx, constvars in iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name != "pallas_call":
                continue
            for v in eqn.invars:
                if not hasattr(v, "aval"):
                    continue
                if v in constvars:
                    out.append(Finding(name, "capture", (
                        f"pallas_call operand {v.aval.str_short()} is a "
                        "lifted jaxpr const (captured closure/enum "
                        "constant) — pass it as an argument or register "
                        "the literal (types.register_literal_enums)"
                    )))
    top = jaxpr if hasattr(jaxpr, "consts") else None
    if top is not None:
        for c in top.consts:
            nbytes = getattr(c, "nbytes", 0)
            if nbytes > MAX_CONST_BYTES:
                out.append(Finding(name, "capture", (
                    f"program captures a {nbytes}-byte const "
                    f"{getattr(c, 'dtype', '?')}{list(getattr(c, 'shape', ()))}"
                    " — real data must ride as an argument, not a closure"
                )))
    return out


def check_host_hygiene(name, jaxpr) -> list:
    out = []
    for eqn in iter_eqns(jaxpr):
        pname = eqn.primitive.name
        if "callback" in pname or pname in _HOST_PRIMS:
            out.append(Finding(name, "hygiene", (
                f"primitive '{pname}' inside the round-dispatch program — "
                "a host round-trip per dispatch; move it to the host plane "
                "or behind a stream drain"
            )))
    return out


def carry_leaves(rec) -> list:
    """The record's between-rounds carry legs as flat leaves, in program
    order (positional carry args first, then the carry kwargs in their
    declared order). Falls back to the donation signature when a record
    predates the explicit carry metadata — for the engine twins the
    donated legs ARE the carry."""
    argnums = rec.get("carry_argnums")
    argnames = rec.get("carry_argnames")
    if argnums is None and argnames is None:
        argnums = rec.get("donate_argnums", ())
        argnames = rec.get("donate_argnames", ())
    legs = [rec["args"][i] for i in (argnums or ())]
    kw = rec.get("kwargs", {})
    legs += [kw.get(k) for k in (argnames or ())]
    return jax.tree.leaves(legs)


def check_carry_stability(name, jaxpr, rec) -> list:
    """Carry-in avals must equal the program's leading out avals leaf for
    leaf — the fused round, the rebase jits and the sharded stepper all
    return their carry first, in argument order, so a positional prefix
    compare proves the fixpoint."""
    ins = [(tuple(leaf.shape), str(leaf.dtype))
           for leaf in carry_leaves(rec)]
    if not ins:
        return []
    outs = [(tuple(a.shape), str(a.dtype)) for a in jaxpr.out_avals]
    if len(outs) < len(ins):
        return [Finding(name, "carry", (
            f"program returns {len(outs)} leaves but the carry has "
            f"{len(ins)} — the round no longer round-trips its own carry"
        ))]
    out = []
    for idx, (want, got) in enumerate(zip(ins, outs)):
        if want != got:
            out.append(Finding(name, "carry", (
                f"carry leaf {idx} enters as {want[1]}{list(want[0])} but "
                f"exits as {got[1]}{list(got[0])} — the carry fixpoint is "
                "broken (a widen/reshape rides between rounds)"
            )))
    return out


def check_paged_roundtrip(rec_a, rec_b) -> list:
    """The two host-boundary programs must be aval-inverses: each one's
    out avals equal the other's example-arg avals positionally (both
    sides are (state, paged) pytrees of the same classes, so flatten
    order lines up by construction)."""
    def arg_avals(rec):
        return [(tuple(l.shape), str(l.dtype))
                for l in jax.tree.leaves(rec["args"])]

    def out_avals(rec):
        return [(tuple(a.shape), str(a.dtype))
                for a in trace_entry(rec).out_avals]

    out = []
    for src, dst in ((rec_a, rec_b), (rec_b, rec_a)):
        name = f"{src['name']}->{dst['name']}"
        got, want = out_avals(src), arg_avals(dst)
        if len(got) != len(want):
            out.append(Finding(name, "roundtrip", (
                f"{src['name']} returns {len(got)} leaves but "
                f"{dst['name']} consumes {len(want)} — the paged "
                "roundtrip no longer closes"
            )))
            continue
        for idx, (g, w) in enumerate(zip(got, want)):
            if g != w:
                out.append(Finding(name, "roundtrip", (
                    f"leaf {idx}: {src['name']} emits {g[1]}{list(g[0])} "
                    f"but {dst['name']} expects {w[1]}{list(w[0])} — a "
                    "reshape/upcast crept into the paged window cycle"
                )))
    return out


# --------------------------------------------------------------------------
# donation (lowered-HLO level)


_TENSOR_RE = re.compile(r"tensor<[^>]*>")


def unusable_donors(text: str) -> dict:
    """{flat arg position: tensor type} of donated arguments that no
    output can take over, read from the lowered @main signature. jax
    marks a donated argument it paired with an output
    `tf.aliasing_output = k`, and one it left for XLA to pair
    `jax.buffer_donor`; a donor is usable only while an unpaired output
    of its exact type remains."""
    attrs = _main_arg_attrs(text)
    m = re.search(
        r"func\.func public @main\(.*?\)\s*->\s*(.*?)\s*\{\s*$",
        text, re.S | re.M,
    )
    if attrs is None or m is None:
        return {}
    results = _TENSOR_RE.findall(m.group(1))
    claimed = {
        int(k) for k in re.findall(r"tf\.aliasing_output = (\d+)", text)
    }
    free = Counter(t for i, t in enumerate(results) if i not in claimed)
    bad = {}
    for i, a in sorted(attrs.items()):
        if "jax.buffer_donor" not in a:
            continue
        t = _TENSOR_RE.search(a).group(0)
        if free[t]:
            free[t] -= 1
        else:
            bad[i] = t
    return bad


def lower_text(rec) -> str:
    """Lower the record's jit twin for its example args; returns the
    StableHLO text."""
    kwargs = {**rec.get("static", {}), **rec.get("kwargs", {})}
    return rec["jit"].lower(*rec["args"], **kwargs).as_text()


def donated_leaf_count(rec) -> int:
    """Leaves of the donated portion of the example args: positional
    donate_argnums (0, 1) = (state, fab) plus the donated plane kwargs
    that are not None."""
    donated = [rec["args"][i] for i in rec.get("donate_argnums", ())]
    for k in rec.get("donate_argnames", ()):
        val = rec.get("kwargs", {}).get(k)
        if val is not None:
            donated.append(val)
    return len(jax.tree.leaves(donated))


def check_donation(name, rec, text: str | None = None) -> list:
    """Donating twin: every donated carry leaf aliases an output (count
    `tf.aliasing_output`/`jax.buffer_donor` markers, less the donors no
    output can take). Copying twin: aliases nothing. ``text`` lets the
    caller share one lowering with the escape check."""
    if text is None:
        text = lower_text(rec)
    dropped = unusable_donors(text)
    aliased = (
        text.count("tf.aliasing_output") + text.count("jax.buffer_donor")
        - len(dropped)
    )
    out = []
    if rec["donate"]:
        expected = donated_leaf_count(rec)
        if dropped:
            i, t = next(iter(dropped.items()))
            out.append(Finding(name, "donation", (
                f"{len(dropped)} donated leaf/leaves lost their alias "
                f"(silent HBM doubling): flat arg {i} ({t}) has no "
                "output of its type left to take it"
            )))
        if aliased < expected:
            out.append(Finding(name, "donation", (
                f"lowering aliases {aliased} buffers but the donated carry "
                f"has {expected} leaves — some donated leaf is not updated "
                "in place"
            )))
    else:
        if aliased:
            out.append(Finding(name, "donation", (
                f"copying twin aliases {aliased} buffers — stale host "
                "references to the pre-dispatch carry would read garbage"
            )))
    return out


# --------------------------------------------------------------------------
# donation escape (per-leaf alias accounting in the lowered signature)

_MAIN_ARG_RE = re.compile(r"%arg(\d+):")


def _main_arg_attrs(text: str) -> dict | None:
    """{flat arg position: signature span text} for the lowered
    program's public @main signature; None when the signature can't be
    found. Each span runs from this ``%argN:`` to the next — attr dicts
    can nest braces inside quoted strings (mhlo.sharding does), so
    span-slicing beats brace-matching."""
    m = re.search(r"func\.func public @main\((.*?)\)\s*->", text, re.S)
    if m is None:
        return None
    sig = m.group(1)
    marks = list(_MAIN_ARG_RE.finditer(sig))
    out = {}
    for i, am in enumerate(marks):
        end = marks[i + 1].start() if i + 1 < len(marks) else len(sig)
        out[int(am.group(1))] = sig[am.start():end]
    return out


def flat_arg_names(rec) -> tuple[list, set]:
    """(per-flat-leaf human names, donated flat positions) for a record's
    example arguments, in jax's flatten order: positional args in order,
    then kwargs sorted by key (how pjit flattens (args, kwargs))."""
    try:
        params = list(inspect.signature(rec["fn"]).parameters)
    except (TypeError, ValueError):  # pragma: no cover - exotic callables
        params = []
    names, donated = [], set()
    pos = 0
    for i, a in enumerate(rec["args"]):
        prefix = params[i] if i < len(params) else f"arg{i}"
        paths = jax.tree_util.tree_flatten_with_path(a)[0]
        for path, _ in paths:
            names.append(prefix + jax.tree_util.keystr(path))
        if i in rec.get("donate_argnums", ()):
            donated.update(range(pos, pos + len(paths)))
        pos += len(paths)
    kw = rec.get("kwargs", {})
    for k in sorted(kw):
        paths = jax.tree_util.tree_flatten_with_path(kw[k])[0]
        for path, _ in paths:
            names.append(k + jax.tree_util.keystr(path))
        if k in rec.get("donate_argnames", ()):
            donated.update(range(pos, pos + len(paths)))
        pos += len(paths)
    return names, donated


def check_donation_escape(name, rec, text: str | None = None) -> list:
    """Per-leaf donation escape analysis: every donated flat argument of
    the lowered program must carry an input-output alias attribute. A
    leaf without one ESCAPED donation — its buffer is both donated (the
    host must not read it after dispatch) and not reused (HBM doubles),
    the worst of both. Names the leaf via the record's example pytrees
    so the finding points at a column, not a position."""
    if text is None:
        text = lower_text(rec)
    bad = unusable_donors(text)
    names, donated = flat_arg_names(rec)
    if not donated:
        return []
    attrs = _main_arg_attrs(text)
    if attrs is None:  # pragma: no cover - lowering layout drift
        return [Finding(name, "escape", (
            "could not locate the public @main signature in the lowered "
            "program — escape analysis can't run (lowering layout drift)"
        ))]

    def aliased(i: int, a: str) -> bool:
        return "tf.aliasing_output" in a or (
            "jax.buffer_donor" in a and i not in bad
        )

    if len(attrs) != len(names):
        # the lowering pruned unused args: flat positions shifted, so
        # degrade to count-level accounting rather than misname leaves
        n_aliased = sum(1 for i, a in attrs.items() if aliased(i, a))
        missing = len(donated) - n_aliased
        if missing > 0:
            return [Finding(name, "escape", (
                f"{missing} donated leaf/leaves have no input-output "
                "alias in the lowered program (argument pruning hides "
                "which) — donated buffers escape"
            ))]
        return []
    out = []
    for i in sorted(donated):
        if not aliased(i, attrs.get(i, "")):
            leaf = names[i] if i < len(names) else f"flat arg {i}"
            out.append(Finding(name, "escape", (
                f"donated leaf '{leaf}' (flat arg {i}) has no "
                "input-output alias in the lowered program — the donated "
                "buffer escapes: HBM doubles and any host view of it "
                "dangles after dispatch"
            )))
    return out


# --------------------------------------------------------------------------
# one record end-to-end


def audit_record(rec, *, expect_on=None, diet: bool = False) -> list:
    """Run every applicable static check on one program record; returns
    the finding list (empty = clean). Purely static: make_jaxpr +
    jit.lower only, nothing executes."""
    name = rec["name"]
    checks = rec.get("checks")
    jaxpr, deltas = traced_counter_deltas(rec)
    out = []

    def want(c):
        return checks is None or c in checks

    if want("elision") and expect_on:
        out += check_elision(name, deltas, expect_on)
    if want("dtype") and diet:
        # dtype_carry overrides the default (state, fab) pair when the
        # program's in-flight storage avals legitimately differ from the
        # between-dispatch carry (the paged profile: log columns ride the
        # scan at the paged-in full-window shape)
        carry = rec.get("dtype_carry") or [rec["args"][0], rec["args"][1]]
        out += check_dtype_discipline(name, jaxpr, carry)
    if want("capture"):
        out += check_constant_capture(name, jaxpr)
    if want("hygiene"):
        out += check_host_hygiene(name, jaxpr)
    if want("carry"):
        out += check_carry_stability(name, jaxpr, rec)
    if rec.get("jit") is not None and (want("donation") or want("escape")):
        lowered = lower_text(rec)
        if want("donation"):
            out += check_donation(name, rec, text=lowered)
        if want("escape") and rec.get("donate"):
            out += check_donation_escape(name, rec, text=lowered)
    return out


def audit_entries(pairs) -> tuple[list, list]:
    """Audit every (entry, record) pair plus the cross-record proofs
    (the paged roundtrip pairing records declare via ``roundtrip``).
    Returns (findings, per-entry report rows) — the shared driver for
    ``python -m raft_tpu.analysis`` and the all-green matrix test."""
    findings, rows = [], []
    recs = {rec["name"]: rec for _, rec in pairs}
    for entry, rec in pairs:
        fs = audit_record(rec, expect_on=entry.expect_on, diet=entry.diet)
        findings += fs
        rows.append({
            "name": entry.name,
            "profile": entry.profile,
            "compile_budget": entry.compile_budget,
            "findings": len(fs),
        })
    seen = set()
    for _, rec in pairs:
        peer = rec.get("roundtrip")
        if not peer or peer not in recs:
            continue
        key = frozenset((rec["name"], peer))
        if key in seen:
            continue
        seen.add(key)
        findings += check_paged_roundtrip(rec, recs[peer])
    return findings, rows

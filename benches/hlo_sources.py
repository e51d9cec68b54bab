"""Name the source lines behind each fusion of the round program, with no chip.

Lowers and compiles `ops/fused.py`'s `_fused_rounds_jit` for a described
TPU v5e (one chip of a `v5e:2x2` topology) at one block of a benchmark
configuration (`benchmark/configs/<name>.json`: its voters, block groups,
log window, entries per message, in-flight and read-index slots and
compaction lag), 8 auto-proposing rounds per dispatch with the metrics
plane on, as the tick cells dispatch it. Then, for each fusion of the
round loop's body, it prints the compiler's cycle estimate, the
`[lanes, W]` arrays the fusion writes, and the source lines of the ops
inside it, most ops first: the innermost frame, then up to two callers,
each the next frame in another file, read from the compiled module's
`StackFrames` table.

The trace's `breakdown.device_ops` in `PERF_LEDGER.jsonl` names fusions
(`select_reduce_fusion.121`); this names their code. The fusion numbers
match the chip's only while the program and the shapes match the cell.

    JAX_PLATFORMS=cpu python benches/hlo_sources.py tikv_rf3_1m
    JAX_PLATFORMS=cpu python benches/hlo_sources.py tikv_rf3_1m \\
        --fusion select_reduce_fusion.121 --fusion fusion.842 --top 6

Nothing runs: the compile of tikv_rf3_1m's 65,536-group block takes about
a minute and a half on the host CPU and about 1.5 GB of host memory.
`tests/test_chip_compile.py` pins `window_arrays` at its own block.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_COMP_RE = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$")
_INSTR_RE = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_ARRAY_RE = re.compile(r"\b(pred|[subf]\d+|bf16)\[([\d,]*)\]")
_FIELDS_RE = re.compile(r"(\w+)=(\d+)")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}

Instr = collections.namedtuple("Instr", "name arrays opcode calls frame cycles")


def _split_type(rest: str) -> tuple[str, str]:
    """`<type> <opcode>(...)...` -> (type, remainder): a tuple type is a
    balanced `(...)`, an array type one token."""
    if not rest.startswith("("):
        ty, _, tail = rest.partition(" ")
        return ty, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[: i + 1], rest[i + 2:]
    return rest, ""


def _instr(line: str) -> Instr | None:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    ty, tail = _split_type(line[m.end():])
    arrays = [(dt, tuple(int(d) for d in dims.split(",") if d))
              for dt, dims in _ARRAY_RE.findall(ty)]
    opcode = tail.partition("(")[0]
    calls = re.findall(r"(?:calls|body|to_apply)=%([\w.\-]+)", tail)
    frame = re.search(r"stack_frame_id=(\d+)", tail)
    cycles = re.search(r'"estimated_cycles":"(\d+)"', tail)
    return Instr(m.group(1), arrays, opcode, calls,
                 int(frame.group(1)) if frame else 0,
                 int(cycles.group(1)) if cycles else 0)


class Module:
    """A compiled module's text: its computations and its source tables."""

    def __init__(self, text: str):
        self.tables = {t: {} for t in _TABLES}
        self.comps: dict[str, list[Instr]] = {}
        self.entry = ""
        table, comp = None, None
        for line in text.splitlines():
            if line in self.tables:
                table = line
                continue
            if table is not None:
                if line[:1].isdigit():
                    key, _, val = line.partition(" ")
                    self.tables[table][int(key)] = val
                    continue
                table = None
            m = _COMP_RE.match(line)
            if m:
                comp = self.comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    self.entry = m.group(1)
                continue
            if comp is not None:
                ins = _instr(line)
                if ins is not None:
                    comp.append(ins)

    def loop_body(self) -> list[Instr]:
        """The instructions of the entry's largest while loop body (the
        round scan)."""
        bodies = [ins.calls for ins in self.comps[self.entry]
                  if ins.opcode == "while"]
        names = [c for calls in bodies for c in calls if c in self.comps]
        return max((self.comps[n] for n in names), key=len, default=[])

    def frames(self, frame_id: int) -> list[tuple[str, int, str]]:
        """(file, line, function) from the innermost frame out. A frame's
        printed parent is one above the parent's id; 0 ends the chain."""
        out = []
        while frame_id > 0 and frame_id in self.tables["StackFrames"]:
            sf = dict(_FIELDS_RE.findall(self.tables["StackFrames"][frame_id]))
            loc = dict(_FIELDS_RE.findall(
                self.tables["FileLocations"][int(sf["file_location_id"])]))
            path = self.tables["FileNames"][int(loc["file_name_id"])]
            func = self.tables["FunctionNames"][int(loc["function_name_id"])]
            out.append((path.strip('"'), int(loc["line"]), func.strip('"')))
            frame_id = int(sf["parent_frame_id"]) - 1
        return out

    def fused_ops(self, fusion: Instr) -> list[Instr]:
        """The ops inside a fusion, its nested computations included."""
        out, todo = [], list(fusion.calls)
        while todo:
            body = self.comps.get(todo.pop(), [])
            out.extend(body)
            todo.extend(c for ins in body for c in ins.calls)
        return out


def window_arrays(text: str, lanes: int, w: int) -> tuple[int, int]:
    """(count, bytes) of the `[lanes, w]` arrays the fusions of the round
    loop's body write per iteration, tuple outputs counted one by one."""
    count = size = 0
    for ins in Module(text).loop_body():
        if ins.opcode != "fusion":
            continue
        for dt, dims in ins.arrays:
            if dims == (lanes, w):
                count += 1
                size += lanes * w * _BYTES.get(dt, 4)
    return count, size


def _site(frames, callers: int = 2) -> str:
    """`file:line func < caller < caller`, the repo's frames but this
    file's: the innermost frame, then each next frame in another file."""
    ours = [(os.path.relpath(p, REPO), ln, fn) for p, ln, fn in frames
            if os.path.abspath(p).startswith(REPO + os.sep)
            and os.path.abspath(p) != os.path.abspath(__file__)]
    if not ours:
        return "(no source frame)"
    chain = [ours[0]]
    for f in ours[1:]:
        if len(chain) > callers:
            break
        if f[0] != chain[-1][0]:
            chain.append(f)
    return " < ".join(f"{p}:{ln} {fn}" for p, ln, fn in chain)


def report(text: str, lanes: int, w: int, only=(), top: int = 4) -> str:
    mod = Module(text)
    body = [ins for ins in mod.loop_body() if ins.opcode == "fusion"]
    lines = []
    n, size = window_arrays(text, lanes, w)
    lines.append(
        f"loop body: {len(body)} fusions, {sum(i.cycles for i in body):,} "
        f"estimated cycles, {n} [{lanes},{w}] arrays written "
        f"({size / 1e9:.2f} GB)")
    chosen = [i for i in body if not only or i.name in only]
    chosen.sort(key=lambda i: -i.cycles)
    for ins in chosen:
        nw = sum(dims == (lanes, w) for _, dims in ins.arrays)
        lines.append(f"{ins.name}: {ins.cycles:,} cycles, {nw} [{lanes},{w}] "
                     f"of {len(ins.arrays)} outputs")
        sites = collections.Counter(
            _site(mod.frames(op.frame)) for op in mod.fused_ops(ins)
            if op.frame and op.opcode not in ("parameter", "constant"))
        for site, k in sites.most_common(top):
            lines.append(f"    {k:4d}  {site}")
    return "\n".join(lines)


def compile_block(config: dict, n_rounds: int = 8):
    """The round program compiled for one described v5e chip at one block
    of `config`; returns the compiled executable."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from raft_tpu.config import Shape
    from raft_tpu.ops import fused
    from raft_tpu.ops.fused import FusedCluster

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    g, v = config["block_groups"], config["voters"]
    shape = Shape(
        n_lanes=g * v, max_peers=v, log_window=config["log_window"],
        max_msg_entries=config["max_msg_entries"],
        max_inflight=config["max_inflight"],
        max_read_index=config["max_read_index"],
    )
    c = FusedCluster(g, v, seed=1, shape=shape,
                     heartbeat_tick=config["heartbeat_tick"],
                     election_tick=config["election_tick"])

    def specs(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    return fused._fused_rounds_jit.lower(
        *specs((c.state, c.fab, c._no_ops, c.mute)),
        v=v, n_rounds=n_rounds, do_tick=True, auto_propose=True,
        auto_compact_lag=config["compact_lag"], ops_first_round_only=True,
        paged_inkernel=False, metrics=specs(c.metrics), chaos=None,
        trace=None, paged=None,
    ).compile()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a name under benchmark/configs/")
    ap.add_argument("--fusion", action="append", default=[],
                    help="report only this fusion (repeatable)")
    ap.add_argument("--top", type=int, default=4,
                    help="source sites printed per fusion")
    ap.add_argument("--hlo", help="also write the compiled module's text here")
    args = ap.parse_args(argv)
    with open(os.path.join(REPO, "benchmark", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    compiled = compile_block(config)
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    mem = compiled.memory_analysis()
    lanes = config["block_groups"] * config["voters"]
    print(f"{args.config}: block of {lanes} lanes, W={config['log_window']}, "
          f"temp {mem.temp_size_in_bytes:,} B, "
          f"arguments {mem.argument_size_in_bytes:,} B")
    print(report(text, lanes, config["log_window"], set(args.fusion),
                 args.top))


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    main()

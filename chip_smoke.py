#!/usr/bin/env python3
"""On-chip smoke: drive the multi-raft engine once on a TPU through its
public entry points, at the fleet size of the north star, and check what
comes out against the repo's plain references.

    python chip_smoke.py             # one chip: the phases below
    python chip_smoke.py --chips 4   # four chips: the mesh fleet only

Phases (one chip):

  fleet      1,048,576 RF-3 groups held as 16 resident blocks of 65,536
             (scheduler.BlockedFusedCluster, carry donation at its default):
             every group elects within a bound, then 64 rounds of
             auto-propose + compaction advance `committed` in every group
  served     a ServeLoop over the same fleet: >= 1,000 crc32-placed tenant
             sessions each put, then linearizably get, their key; every
             acknowledged put reads back, digest == scalar twin digest
  reference  a 4,096-group fused fleet on the chip and on the host CPU in
             this process: equal trajectory digests
  pallas     the Pallas quorum kernel on the chip == ops/quorum.py

The Pallas round engine does not lower: Mosaic refuses its round body on
TPU (ROADMAP C3). The pallas phase checks that `engine="pallas"` raises
there with the compiler's message instead of falling back to XLA.

--chips 4 runs only MeshBlockedCluster at the same fleet over the host's
four chips and checks its fleet digest against the one-chip
BlockedFusedCluster on device 0.

Every earlier line is one JSON object describing a phase (sizes, compile
seconds, peak device bytes, counts, digests); the last line is the result.
Compile seconds are jax's backend-compile durations, persistent-cache
loads included. Any time printed is informational, not a benchmark.
The script exits non-zero, and prints no result, off a TPU.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time


# the deployment: 1,048,576 RF-3 groups as 16 resident blocks of 65,536
GROUPS = 1_048_576
BLOCK_GROUPS = 65_536
VOTERS = 3
WINDOW = 16
ENTRIES = 2
ROUND_CHUNK = 8
ELECT_BOUND = 640
STEADY_ROUNDS = 64
SESSIONS = 1024
SERVE_BOUND = 256
REF_GROUPS = 4096
REF_ROUNDS = 64
QUORUM_LANES = 196_608
MESH_ROUNDS = 64
SEED = 42
# compaction trails the commit by half the window (bench.py's choice)
LAG = min(8, WINDOW // 2)


class CompileClock:
    """Sums jax's backend-compile durations (cache loads included) and
    counts persistent-cache hits, so each phase reports its own share."""

    def __init__(self):
        import jax.monitoring as jm

        self.secs = 0.0
        self.hits = 0
        self.compiles = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jm.register_event_duration_secs_listener(on_duration)
        jm.register_event_listener(on_event)

    def mark(self):
        return (self.secs, self.compiles, self.hits)

    def since(self, mark) -> dict:
        return {
            "compile_s": self.secs - mark[0],
            "programs": self.compiles - mark[1],
            "cache_hits": self.hits - mark[2],
        }


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def peak_bytes(dev, key: str = "peak_bytes_in_use") -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get(key)


def block_shape(block_groups: int):
    from raft_tpu.config import Shape

    return Shape(
        n_lanes=block_groups * VOTERS,
        max_peers=VOTERS,
        log_window=WINDOW,
        max_msg_entries=ENTRIES,
        max_inflight=ENTRIES,
        max_read_index=2,
    )


def trajectory_digest(cluster) -> str:
    from benches.multichip_ab import DIGEST_FIELDS
    import numpy as np

    cols = cluster.state_columns(*DIGEST_FIELDS)
    h = hashlib.sha256()
    for name in DIGEST_FIELDS:
        h.update(np.ascontiguousarray(cols[name]).tobytes())
    return h.hexdigest()


def groups_with_leader(cluster) -> int:
    from raft_tpu.types import StateType

    st = cluster.state_columns("state")["state"].reshape(-1, VOTERS)
    return int((st == int(StateType.LEADER)).any(axis=1).sum())


def group_committed(cluster):
    return cluster.state_columns("committed")["committed"].reshape(
        -1, VOTERS
    ).max(axis=1)


def native_phase() -> dict:
    from raft_tpu.runtime import native

    t0 = time.perf_counter()
    native.build(force=True)
    store = native.make_payload_store(4)
    return {
        "phase": "native",
        "build_s": time.perf_counter() - t0,
        "payload_store": type(store).__name__,
    }


def fleet_phase(clock: CompileClock, dev):
    from raft_tpu.ops.fused import donation_enabled
    from raft_tpu.scheduler import BlockedFusedCluster

    if not donation_enabled():
        raise RuntimeError("carry donation is off; the smoke runs its default")
    mark, t0 = clock.mark(), time.perf_counter()
    c = BlockedFusedCluster(
        GROUPS, VOTERS, block_groups=BLOCK_GROUPS, seed=SEED,
        shape=block_shape(BLOCK_GROUPS), round_chunk=ROUND_CHUNK,
    )
    step = ROUND_CHUNK * 4
    rounds = 0
    while True:
        c.run(step, auto_propose=True, auto_compact_lag=LAG)
        rounds += step
        elected = groups_with_leader(c)
        if elected == GROUPS:
            break
        if rounds >= ELECT_BOUND:
            raise RuntimeError(
                f"election stalled: {elected}/{GROUPS} groups have a "
                f"leader after {rounds} rounds"
            )
    before = group_committed(c)
    c.run(STEADY_ROUNDS, auto_propose=True, auto_compact_lag=LAG)
    after = group_committed(c)
    c.check_no_errors()
    advanced = int((after > before).sum())
    if advanced != GROUPS:
        raise RuntimeError(
            f"committed advanced in {advanced}/{GROUPS} groups over "
            f"{STEADY_ROUNDS} rounds"
        )
    emit({
        "phase": "fleet",
        "groups": GROUPS,
        "voters": VOTERS,
        "blocks": c.k,
        "block_groups": BLOCK_GROUPS,
        "donate": True,
        "rounds_to_elect": rounds,
        "groups_elected": elected,
        "steady_rounds": STEADY_ROUNDS,
        "groups_committing": advanced,
        "min_commit_advance": int((after - before).min()),
        "total_committed": c.total_committed(),
        **clock.since(mark),
        "wall_s": time.perf_counter() - t0,
        "peak_bytes_in_use": peak_bytes(dev),
    })
    return c


def served_phase(clock: CompileClock, dev, cluster) -> None:
    from raft_tpu.serve import Rejected, ServeLoop

    mark, t0 = clock.mark(), time.perf_counter()
    loop = ServeLoop(cluster, auto_compact_lag=LAG)
    loop.bootstrap(max_rounds=SERVE_BOUND)
    sessions = [loop.open_session(f"tenant-{i}") for i in range(SESSIONS)]
    value = {s.id: f"value-{s.tenant}" for s in sessions}
    puts = [loop.put(s, f"key/{s.tenant}", value[s.id]) for s in sessions]
    rejected = [p for p in puts if isinstance(p, Rejected)]
    if rejected:
        raise RuntimeError(f"{len(rejected)} puts rejected: {rejected[:3]}")
    if not loop.drain(SERVE_BOUND):
        raise RuntimeError(f"{loop.outstanding} puts unacknowledged")
    if not all(t.done and t.applied for t in puts):
        raise RuntimeError("a put was notified without being applied")
    gets = [loop.get(s, f"key/{s.tenant}") for s in sessions]
    rejected = [g for g in gets if isinstance(g, Rejected)]
    if rejected:
        raise RuntimeError(f"{len(rejected)} gets rejected: {rejected[:3]}")
    if not loop.drain(SERVE_BOUND):
        raise RuntimeError(f"{loop.outstanding} gets unanswered")
    wrong = [
        g for s, g in zip(sessions, gets)
        if not g.done or g.value != value[s.id]
    ]
    if wrong:
        raise RuntimeError(f"{len(wrong)} acknowledged puts not read back")
    counters = loop.metrics_snapshot()["counters"]
    violations = counters.get("notify_violations", 0)
    digest, twin = loop.digest(), loop.twin_digest()
    if violations or digest != twin:
        raise RuntimeError(
            f"notify_violations={violations}, digest {digest} != twin {twin}"
        )
    cluster.check_no_errors()
    emit({
        "phase": "served",
        "groups": GROUPS,
        "sessions": len(sessions),
        "groups_touched": len({s.group for s in sessions}),
        "puts_acked": len(puts),
        "gets_read_back": len(gets),
        "rounds": loop.round,
        "notify_violations": violations,
        "digest": digest,
        "twin_digest": twin,
        **clock.since(mark),
        "wall_s": time.perf_counter() - t0,
        "peak_bytes_in_use": peak_bytes(dev),
    })


def reference_phase(clock: CompileClock, dev) -> None:
    import jax

    from raft_tpu.ops.fused import FusedCluster

    def run_on(d):
        with jax.default_device(d):
            c = FusedCluster(
                REF_GROUPS, VOTERS, seed=SEED,
                shape=block_shape(REF_GROUPS),
            )
            for _ in range(REF_ROUNDS // ROUND_CHUNK):
                c.run(ROUND_CHUNK, auto_propose=True,
                      auto_compact_lag=LAG)
        if c.state.term.devices() != {d}:
            raise RuntimeError(f"reference fleet left {d}")
        c.check_no_errors()
        return trajectory_digest(c), len(c.leader_lanes())

    mark, t0 = clock.mark(), time.perf_counter()
    chip, chip_leaders = run_on(dev)
    cpu, cpu_leaders = run_on(jax.devices("cpu")[0])
    if chip != cpu:
        raise RuntimeError(f"chip digest {chip} != cpu digest {cpu}")
    emit({
        "phase": "reference",
        "groups": REF_GROUPS,
        "rounds": REF_ROUNDS,
        "leaders": chip_leaders,
        "chip_digest": chip,
        "cpu_digest": cpu,
        **clock.since(mark),
        "wall_s": time.perf_counter() - t0,
    })


def pallas_phase(clock: CompileClock, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.ops import quorum, quorum_pallas

    mark, t0 = clock.mark(), time.perf_counter()
    rng = np.random.default_rng(SEED)
    n, v = QUORUM_LANES, VOTERS
    match = jnp.asarray(rng.integers(0, 1 << 20, (n, v), dtype=np.int32))
    m_in = jnp.asarray(rng.random((n, v)) < 0.8)
    m_out = jnp.asarray(rng.random((n, v)) < 0.5)
    hlo = quorum_pallas.joint_committed_pallas.lower(match, m_in, m_out).as_text()
    if "tpu_custom_call" not in hlo:
        raise RuntimeError("the Pallas quorum kernel did not lower to Mosaic")
    joint = quorum.joint_committed_dispatch(match, m_in, m_out)
    joint_ref = quorum.joint_committed(match, m_in, m_out)
    single = quorum_pallas.committed_pallas(match, m_in)
    single_ref = quorum.majority_committed(match, m_in)
    for got, want, name in (
        (joint, joint_ref, "joint_committed_dispatch"),
        (single, single_ref, "committed_pallas"),
    ):
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise RuntimeError(f"{name} differs from ops/quorum.py")
    if joint.devices() != {dev}:
        raise RuntimeError("the quorum kernel ran off the chip")
    refusal = round_engine_refusal()
    emit({
        "phase": "pallas",
        "quorum_lanes": n,
        "joint_committed_dispatch": "equal",
        "committed_pallas": "equal",
        "round_engine": f"raises (ROADMAP C3): {refusal}",
        **clock.since(mark),
        "wall_s": time.perf_counter() - t0,
    })


def round_engine_refusal() -> str:
    """engine="pallas" must raise on the chip with Mosaic's message (its
    round body does not lower, ROADMAP C3) — never fall back to XLA."""
    from raft_tpu.ops import pallas_round as plr
    from raft_tpu.ops.fused import FusedCluster

    g = 1024
    c = FusedCluster(
        g, VOTERS, seed=SEED, shape=block_shape(g),
        engine="pallas", tile_lanes=plr.default_tile(g * VOTERS, VOTERS),
        rounds_per_call=1,
    )
    try:
        c.run(1)
    except Exception as e:  # the expected refusal; anything else re-raises
        if "Mosaic" not in str(e):
            raise
        return str(e).strip().splitlines()[0][:160]
    raise RuntimeError("the pallas round engine ran: time it (ROADMAP A1)")


def mesh_phase(clock: CompileClock, devices) -> None:
    import jax

    from raft_tpu.parallel.mesh import MeshBlockedCluster
    from raft_tpu.scheduler import BlockedFusedCluster

    def drive(c):
        for _ in range(MESH_ROUNDS // ROUND_CHUNK):
            c.run(ROUND_CHUNK, auto_propose=True, auto_compact_lag=LAG)
        c.check_no_errors()
        return trajectory_digest(c), groups_with_leader(c)

    shape = block_shape(BLOCK_GROUPS)
    mark, t0 = clock.mark(), time.perf_counter()
    mesh = MeshBlockedCluster(
        GROUPS, VOTERS, block_groups=BLOCK_GROUPS, devices=devices,
        seed=SEED, shape=shape, round_chunk=ROUND_CHUNK,
    )
    built = [peak_bytes(d, "bytes_in_use") for d in devices]
    mesh_digest, mesh_elected = drive(mesh)
    emit({
        "phase": "mesh",
        "groups": GROUPS,
        "chips": len(devices),
        "blocks": mesh.k,
        "rounds": MESH_ROUNDS,
        "groups_elected": mesh_elected,
        "bytes_in_use_after_build": built,
        "peak_bytes_in_use": [peak_bytes(d) for d in devices],
        "digest": mesh_digest,
        **clock.since(mark),
        "wall_s": time.perf_counter() - t0,
    })
    del mesh
    mark, t0 = clock.mark(), time.perf_counter()
    with jax.default_device(devices[0]):
        mono = BlockedFusedCluster(
            GROUPS, VOTERS, block_groups=BLOCK_GROUPS,
            seed=SEED, shape=shape, round_chunk=ROUND_CHUNK,
        )
        mono_digest, mono_elected = drive(mono)
    if mono.blocks[0].state.term.devices() != {devices[0]}:
        raise RuntimeError("the one-chip fleet left device 0")
    emit({
        "phase": "one_chip",
        "groups": GROUPS,
        "groups_elected": mono_elected,
        "digest": mono_digest,
        **clock.since(mark),
        "wall_s": time.perf_counter() - t0,
    })
    if mesh_digest != mono_digest:
        raise RuntimeError(
            f"mesh digest {mesh_digest} != one-chip digest {mono_digest}"
        )


def run(chips: int = 1) -> dict:
    """Run the smoke's phases on jax.devices()[:chips]; raises on any
    failure. Returns the device record of the last line."""
    import jax

    from raft_tpu.utils.compile_cache import enable_persistent_cache

    cache = enable_persistent_cache()
    clock = CompileClock()
    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise RuntimeError(f"need {chips} devices, have {len(devices)}")
    dev = devices[0]
    emit({
        "phase": "device",
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "visible": len(jax.devices()),
        "jax": jax.__version__,
        "compile_cache": cache,
    })
    if chips == 1:
        emit(native_phase())
        fleet = fleet_phase(clock, dev)
        served_phase(clock, dev, fleet)
        del fleet
        reference_phase(clock, dev)
        pallas_phase(clock, dev)
    else:
        mesh_phase(clock, devices)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    # the reference phase needs the host CPU next to the chip
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    device = run(chips=args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

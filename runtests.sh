#!/bin/bash
# Run the test suite on a virtual 8-device CPU mesh.
#
# XLA:CPU reproducibly segfaults/aborts on a fresh compile once a few
# hundred programs were compiled earlier in the same process; the suite
# therefore spreads over multiple worker processes. With pytest-xdist
# installed, 6 loadfile workers do that in parallel; on 1-core rigs
# without xdist (this container), the fallback below runs the same suite
# as a CHUNKED SERIAL LADDER — ~6 sequential pytest processes, each well
# under the per-process compile-count crash threshold, with
# test_sharded.py LAST in its own process (its big 8-device shard_map
# programs are the original crash trigger and its autouse fixture
# disables the persistent compile cache).
#
# JAX_COMPILATION_CACHE_DIR, when set, is jax's own compile-cache setting
# and reaches the tests and bench smokes unchanged.

run() {
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python -m pytest "$@" -x -q
}

# serial-ladder invocation: neutralize pytest.ini's xdist addopts
run_chunk() {
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python -m pytest "$@" -x -q -o addopts= -p no:cacheprovider -p no:randomly
}

# the CPU rehearsal of the bench smokes runs pallas kernels interpreted,
# chosen explicitly (the engine never infers interpret mode)
run_bench() {
  env JAX_PLATFORMS=cpu RAFT_TPU_PALLAS_INTERPRET=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python "$@"
}

static_gate() {
  # static analysis gate (raft_tpu/analysis): repo lint + jaxpr/HLO
  # invariant audit over every manifest entry point + the recompile
  # sentinel + the compiled-program resource ledger (--ledger:
  # AOT-compiles every entry and diffs per-lane HBM/FLOP budgets
  # against LEDGER.json; RAFT_TPU_LEDGER_PATH/_TOL tune it, and
  # `python -m raft_tpu.analysis --update-ledger` re-baselines after an
  # intentional change), in its own process BEFORE any test chunk — a
  # broken compile-time contract fails in ~a minute instead of
  # surfacing as a flaky assert deep in the suite. Emits ANALYSIS.json
  # and LEDGER_DIFF.txt next to the bench JSONs.
  env JAX_PLATFORMS=cpu RAFT_TPU_PALLAS_INTERPRET=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python -m raft_tpu.analysis --json ANALYSIS.json --ledger
}

smokes() {
  # device-metrics smoke + the donation A/B dispatch smoke (fails if
  # donation-on regresses throughput or stops lowering live buffers) +
  # the egress A/B serving smoke (scalar-poll vs batched-mask Ready
  # streams must be digest-identical while the mask path scans strictly
  # fewer lanes) + the chaos recovery-SLO smoke (two same-seed soaks must
  # be bit-identical; RAFT_TPU_CHAOS / CHAOS_SEED / CHAOS_BUDGET inherit
  # through run_bench) + the serving-frontend
  # smoke (closed-loop p50/p99 + open-loop saturation: exactly-once
  # notify, digest == admission-ordered scalar twin, typed rejections
  # under overload with no deadlock)
  # ... + the pallas engine A/B smoke (xla vs pallas K=1 vs the K=AB_K
  # megakernel: all three arms must land the identical slim_state digest
  # with no silent engine fallback; the ms/round and bytes-moved gates —
  # including K>1 moving strictly fewer carry bytes than K=1 — arm on
  # TPU only) + the trace A/B smoke (flight recorder on vs off must be
  # digest-identical, TRACELOG=0 must trace zero recorder sites, and the
  # drained events must equal the scalar-twin transition stream) + the
  # byte-diet A/B smoke (diet on vs off over xla / pallas K=1 / pallas
  # K=AB_K with every observability plane live: one identical trajectory
  # digest across all six arms, >= 30% smaller carry bytes/lane with diet
  # on, round-time regression gate arms on TPU only) + the multi-chip A/B
  # smoke (mesh-blocked driver vs the monolithic blocked scheduler on the
  # forced 8-device CPU mesh: one identical trajectory digest, per-(shard,
  # block) WAL/egress payloads byte-identical after host-side merge; the
  # mesh throughput-gain gate arms on real multi-chip TPU only)
  run_bench benches/metrics_smoke.py \
    && run_bench benches/dispatch_ab.py \
    && run_bench benches/egress_ab.py \
    && run_bench benches/pallas_ab.py --smoke \
    && run_bench benches/chaos_soak.py --smoke \
    && run_bench benches/serve_bench.py --smoke \
    && run_bench benches/trace_ab.py \
    && run_bench benches/diet_ab.py --smoke \
    && run_bench benches/multichip_ab.py --smoke \
    && run_bench benches/paged_ab.py --smoke \
    && run_bench benches/tier_ab.py --smoke \
    && run_bench benches/fabric_ab.py --smoke \
    && run_bench benches/lease_ab.py --smoke
}

if [ $# -eq 0 ] || [ "$*" = "tests/" ]; then
  static_gate || exit 1
  if python -c "import xdist" >/dev/null 2>&1; then
    # pytest-xdist, one file per worker (--dist loadfile): 6 worker
    # processes keep every process's XLA:CPU compile count far under the
    # crash threshold and the wall time drops ~4x.
    run -n 6 --dist loadfile --max-worker-restart 0 \
      $(ls tests/test_*.py | grep -v -e test_sharded -e test_mesh) \
      && run tests/test_mesh.py tests/test_mesh_twins.py \
      && run tests/test_sharded.py \
      && smokes
  else
    # chunked serial ladder (1-core rigs; see header). Chunk boundaries
    # only balance compile counts — adjust freely as the corpus grows.
    set -e
    run_chunk tests/test_backpressure.py tests/test_bridge.py \
      tests/test_bridge_fused.py tests/test_bridge_process.py \
      tests/test_chaos.py tests/test_codec.py tests/test_confchange.py \
      tests/test_confchange_datadriven.py tests/test_confchange_scenarios.py
    run_chunk tests/test_donation.py tests/test_e2e.py \
      tests/test_egress.py \
      tests/test_fast_log_rejection.py tests/test_flow_control.py \
      tests/test_fused.py tests/test_fused_confchange.py tests/test_fused_ids.py
    run_chunk tests/test_fused_invariants.py tests/test_fused_rebase.py \
      tests/test_fused_restore.py tests/test_go_frame_parse.py \
      tests/test_go_interop.py tests/test_interaction.py tests/test_learner.py \
      tests/test_lockstep.py tests/test_lockstep_more.py
    run_chunk tests/test_log.py tests/test_log_tables.py \
      tests/test_logoracle_fuzz.py tests/test_metrics.py \
      tests/test_native_store.py tests/test_network_sim.py \
      tests/test_node_api.py tests/test_node_ports.py tests/test_pagination.py
    run_chunk tests/test_paper.py tests/test_prevote.py tests/test_progress.py \
      tests/test_quorum.py tests/test_quorum_datadriven.py \
      tests/test_quorum_pallas.py tests/test_rawnode.py \
      tests/test_rawnode_ports.py tests/test_readindex.py tests/test_rebase.py
    run_chunk tests/test_restart.py tests/test_restore.py \
      tests/test_scenarios.py tests/test_scenarios_r4.py tests/test_slim.py \
      tests/test_snapshot.py tests/test_status.py tests/test_transfer.py \
      tests/test_unstable.py tests/test_util_ports.py tests/test_vote_states.py \
      tests/test_wal.py
    # the compile-for-a-described-v5e tests get their own process: only
    # one process may load libtpu, and their 65,536-group round compile
    # is the slowest single program in the suite
    run_chunk tests/test_chip_compile.py
    # the auditor suite gets its own process: its all-green matrix
    # builds every manifest entry (incl. the 8-device sharded stepper)
    # and its purity gate counts compiles process-wide
    run_chunk tests/test_analysis.py
    # the serving frontend gets its own process: its module-scoped
    # ServeLoop fixtures compile fused programs for two cluster shapes
    run_chunk tests/test_serve.py
    # the flight recorder gets its own process: its traced clusters are
    # distinct programs (trace carry changes every scan signature) across
    # three engines plus a ServeLoop
    run_chunk tests/test_trace.py
    # the pallas interpret-mode engine smoke gets its own process: each of
    # its kernel variants is one large interpreted scan program, and the
    # CI-asserted bit-identity (pallas vs XLA trajectories) lives here
    run_chunk tests/test_pallas_round.py
    # the diet-v2 packed-carry suite gets its own process: its twin runs
    # compile every engine/donation variant twice (diet off vs on are
    # distinct dtype signatures) plus one K=4 interpreted megakernel on a
    # packed carry
    run_chunk tests/test_diet.py
    # the paged entry-log suite mirrors the diet profile one storage
    # layer down: paged off/on twins per engine are distinct carry
    # signatures, plus one K=4 interpreted megakernel on a paged carry,
    # an 8-device sharded identity run, and the in-kernel paging block
    # (kernel-level K=1/K=4 bit-identity, segment twins, tier x paged
    # conservation); the slow-marked sharded pallas in-kernel twin is
    # interpret-mode under shard_map — minutes on CPU, excluded here
    # like everywhere else
    run_chunk tests/test_paged.py -m "not slow"
    # the hot/cold tiering suite gets its own process: module-scoped tier
    # clusters + ServeLoops (tier carries are their own jit signatures),
    # the mid-election/mid-confchange eviction chaos soak, and the 1M
    # logical-group Zipfian serve acceptance demo
    run_chunk tests/test_tier.py
    # the leader-lease suite gets its own process: lease-on carries are
    # distinct jit signatures per engine (7 extra columns), and the suite
    # mixes fused clusters, ServeLoops, a blocked cluster, and one
    # interpreted pallas tile twin; the minutes-long skew/confchange
    # soaks and the blocked/diet twins are slow-marked and excluded
    # here like everywhere else
    run_chunk tests/test_lease.py -m "not slow"
    # the cross-host fabric suite gets its own process: it spawns real
    # per-host engine processes (mp spawn children each compile the fused
    # program) for the digest-parity and failover oracles, plus the
    # in-process lockstep twins and the wire-chaos probes
    run_chunk tests/test_fabric.py
    # the mesh-blocked driver gets its own process before test_sharded:
    # its sharded x blocked twins are all 8-device shard_map programs,
    # same crash profile as test_sharded, same no-persistent-cache
    # fixture; the long twins (donation, metrics + chaos, the subprocess
    # A/B child trio) are test_mesh_twins.py
    run_chunk tests/test_mesh.py
    run_chunk tests/test_mesh_twins.py
    run_chunk tests/test_sharded.py
    smokes
  fi
else
  run "$@"
fi

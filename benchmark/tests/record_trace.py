"""Records `testdata/cpu_rounds.xplane.pb.gz`: a CPU trace of a tiny fleet
(16 groups x 3 voters, one block) running two dispatches of 2 rounds
inside a `bench.window` span, with a `bench.dispatch` span around each.

    JAX_PLATFORMS=cpu python benchmark/tests/record_trace.py
"""

import glob
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(os.path.dirname(HERE), "testdata", "cpu_rounds.xplane.pb.gz")
DISPATCHES = 2


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    import jax

    from raft_tpu.config import Shape
    from raft_tpu.scheduler import BlockedFusedCluster

    shape = Shape(n_lanes=48, max_peers=3, log_window=16, max_msg_entries=2,
                  max_inflight=2, max_read_index=2)
    c = BlockedFusedCluster(16, 3, block_groups=16, seed=3, shape=shape)
    c.run(2, auto_propose=True, auto_compact_lag=8)
    c.block_until_ready()
    d = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(DISPATCHES):
                    with jax.profiler.TraceAnnotation("bench.dispatch"):
                        c.run(2, auto_propose=True, auto_compact_lag=8)
                    with jax.profiler.TraceAnnotation("bench.sync"):
                        c.block_until_ready()
        (pb,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        with open(pb, "rb") as f, gzip.open(OUT, "wb") as g:
            g.write(f.read())
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()

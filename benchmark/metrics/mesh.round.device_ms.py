"""mesh.round.device_ms: device milliseconds of the mesh round program per
fleet round, from the trace: the summed durations of the executions of the
`shard_map` round program (`shard_mapped_rounds`, parallel/sharded.py) in
the traced window on each chip, averaged over the chips, over the fleet
rounds the window ran. Each chip runs its own slice of every block, so
this is one chip's time for a whole fleet round."""

PROGRAM = "shard_mapped_rounds"


def reduce(rec, trace):
    rounds = rec.values.get("rounds")
    if trace is None or not rounds:
        return None
    secs = trace.module_seconds(PROGRAM)
    return None if secs is None else secs * 1e3 / rounds

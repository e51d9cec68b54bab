"""round.device_ms: device milliseconds of the round program per fleet
round, from the trace: the summed durations of the executions of the
jitted round program (`fused_rounds`, ops/fused.py) in the traced window,
over the fleet rounds the window ran."""

PROGRAM = "fused_rounds"


def reduce(rec, trace):
    rounds = rec.values.get("rounds")
    if trace is None or not rounds:
        return None
    secs = trace.module_seconds(PROGRAM)
    return None if secs is None else secs * 1e3 / rounds

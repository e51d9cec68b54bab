"""device.idle_share.tick: percent of the traced window in which no
operation ran on the device (1 - union of op intervals / window), in the
fleet's device-path cells."""


def reduce(rec, trace):
    return None if trace is None else 100.0 * trace.idle_share

"""setup_s: seconds from process start to the first timed operation:
building the fleet on the device, compiling or loading every program the
cell uses, the election, and the traffic's warm-up."""


def reduce(rec, trace):
    return rec.setup_s

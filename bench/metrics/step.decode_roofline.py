"""Least time the chip could take for the traced decode calls (the larger
of needed bytes over peak bandwidth and needed FLOPs over peak), over the
device time of the decode program, in percent."""


def read(run):
    return run.trace and run.trace.get("decode_roofline")

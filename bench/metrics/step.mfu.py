"""FLOPs the traced window's prefill and decode work needs, counted from
shapes, over (traced window x peak FLOP/s), in percent."""


def read(run):
    return run.trace and run.trace.get("mfu")

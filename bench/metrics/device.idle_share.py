"""Share of the traced window in which no operation ran on the device, in
percent (busy is the union of device operation intervals)."""


def read(run):
    return run.trace and run.trace.get("idle_share")

"""Output tokens of the requests that met both the cell's TTFT and mean-ITL
limits, per second of the window."""
from bench.stats import met_limits


def read(run):
    return sum(r.true_length for r in run.requests
               if met_limits(run, r)) / run.seconds

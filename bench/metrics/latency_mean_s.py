"""Mean time from due to last token over every request due in the window
(the quantity shortest-job-first ordering minimises)."""
from bench.stats import latencies


def read(run):
    return float(latencies(run).mean())

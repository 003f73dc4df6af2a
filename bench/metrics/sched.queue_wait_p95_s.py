"""95th percentile of due -> first admission to the running batch (read
after every serving cycle); a request never admitted waits to the end."""
import numpy as np


def read(run):
    adm = run.probe.admitted
    return float(np.percentile(
        [adm.get(r.req_id, run.end) - r.arrival_time for r in run.requests],
        95))

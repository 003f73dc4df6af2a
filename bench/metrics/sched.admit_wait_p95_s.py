"""95th percentile of due -> first admission to the running batch, read
from the program's own ``Request.first_admit_time``; a request never
admitted waits to the end. None where the program keeps no such time."""
import numpy as np


def read(run):
    if not all(hasattr(r, "first_admit_time") for r in run.requests):
        return None
    return float(np.percentile(
        [(run.end if r.first_admit_time is None else r.first_admit_time)
         - r.arrival_time for r in run.requests], 95))

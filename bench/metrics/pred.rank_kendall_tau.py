"""Kendall's tau-b between the ranker's scores and the true output lengths
of the requests due in the window."""
import math

from bench.stats import kendall_tau_b


def read(run):
    scored = [r for r in run.requests if r.scored]
    if len(scored) < 2:
        return None
    tau = kendall_tau_b([r.score for r in scored],
                        [r.true_length for r in scored])
    return None if math.isnan(tau) else tau

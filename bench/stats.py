"""Arithmetic the metric readers share, over every request due in the
window: a request that never finished counts as missing every limit, and
its times run to the end of the run (so it sorts among the worst)."""
from __future__ import annotations

import numpy as np


def ttfts(run) -> np.ndarray:
    """Due time -> first token, per request; none yet -> the worst."""
    got = [r.first_token_time - r.arrival_time for r in run.requests
           if r.first_token_time is not None]
    worst = max(got, default=0.0)
    return np.asarray([r.first_token_time - r.arrival_time
                       if r.first_token_time is not None
                       else max(run.end - r.arrival_time, worst)
                       for r in run.requests])


def latencies(run) -> np.ndarray:
    """Due time -> last token, per request; unfinished -> end of the run."""
    return np.asarray([(r.finish_time if r.finish_time is not None
                        else run.end) - r.arrival_time for r in run.requests])


def token_gaps(r) -> np.ndarray:
    return np.diff(np.asarray(r.token_times, np.float64))


def met_limits(run, r) -> bool:
    lim = run.limits
    if r.finish_time is None or r.first_token_time is None:
        return False
    gaps = token_gaps(r)
    itl = float(gaps.mean()) if len(gaps) else 0.0
    return (r.first_token_time - r.arrival_time <= lim["ttft_s"]
            and itl * 1e3 <= lim["itl_ms"])


def kendall_tau_b(x, y) -> float:
    """Kendall's tau-b between two sequences (ties corrected)."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    dx = np.sign(x[:, None] - x[None, :])
    dy = np.sign(y[:, None] - y[None, :])
    iu = np.triu_indices(len(x), 1)
    dx, dy = dx[iu], dy[iu]
    den = np.sqrt(np.count_nonzero(dx) * np.count_nonzero(dy))
    return float((dx * dy).sum() / den) if den else float("nan")

"""95th percentile of time to first token, from each request's due time,
over every request due in the window (a request with no token yet counts
as the worst). A per-layer reading: its order-driven spread from seed to
seed is too wide for an end-to-end bound."""
import numpy as np

from bench.stats import ttfts


def read(run):
    return float(np.percentile(ttfts(run), 95))

"""95th percentile of the gaps between consecutive output tokens, over all
gaps of all requests due in the window, in milliseconds."""
import numpy as np

from bench.stats import token_gaps


def read(run):
    gaps = np.concatenate([token_gaps(r) for r in run.requests] or [[]])
    return float(np.percentile(gaps, 95) * 1e3) if len(gaps) else None

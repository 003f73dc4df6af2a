"""Median host milliseconds of one decode call (it ends in
block_until_ready, so the device's step is inside)."""
import numpy as np


def read(run):
    d = run.probe.decode_s
    return float(np.median(d) * 1e3) if d else None

"""Mean share of the running batch's slots in use after each serving
cycle, in percent."""
import numpy as np


def read(run):
    occ = run.probe.occupancy
    return float(np.mean(occ) * 100.0) if occ else None

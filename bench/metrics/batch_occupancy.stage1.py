"""Admission and batching: mean ``BatchRecord.occupancy`` (real requests
over batch size) of the window's stage-1 batches."""
import numpy as np


def read(run):
    occ = [b.occupancy for b in run.batches]
    return float(np.mean(occ)) if occ else None

"""Frontier: mean ``BatchRecord.total_rounds`` per batch (reveal rounds
summed over the batch's queries)."""
import numpy as np


def read(run):
    r = [b.total_rounds for b in run.batches]
    return float(np.mean(r)) if r else None

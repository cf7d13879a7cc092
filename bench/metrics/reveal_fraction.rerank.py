"""Frontier (``core/frontier.py``): mean ``BatchRecord.reveal_fraction``
(MaxSim cells revealed over cells of the real candidates) of the window's
batches."""
import numpy as np


def read(run):
    f = [b.reveal_fraction for b in run.batches]
    return float(np.mean(f)) if f else None

"""Mean, over the measured requests checked against the reference, of the
share of each returned top-k that is in the exhaustive f32 MaxSim top-k
over the same candidates (a failed request counts 0)."""
import numpy as np


def read(run):
    return float(np.mean(run.overlaps)) if run.overlaps else None

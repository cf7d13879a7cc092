"""Stage-1 (``retrieval/ann.py``, ``serve/engine.py``): mean
``BatchRecord.stage1_s`` of the window's batches (ms): how long the admit
thread waited from launching stage-1 until its candidates were on the
host, the scan itself and whatever ran ahead of it on the device."""
import numpy as np


def read(run):
    s = [b.stage1_s for b in run.batches
         if getattr(b, "stage1_s", 0.0) > 0.0]
    return float(np.mean(s)) * 1e3 if s else None

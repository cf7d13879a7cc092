"""Per-batch stage times from the engine's ``BatchRecord`` stamps, on the
engine's clock: released -> prepared -> dispatched -> ready -> delivered.

The four stages of a batch sum to its ``t_delivered - t_release``; the
readers average each over the same batches, so their means sum the same
way. A program without the stamps gives no batches, and the readers stay
silent.
"""
from typing import List, Optional

import numpy as np

STAMPS = ("t_release", "t_prepared", "t_dispatched", "t_ready",
          "t_delivered")


def stamped(batches) -> List:
    """The batches that carry every stamp, in stage order (a batch not yet
    delivered has ``t_delivered`` 0 and is left out)."""
    out = []
    for b in batches:
        t = [getattr(b, s, None) for s in STAMPS]
        if None not in t and all(x <= y for x, y in zip(t, t[1:])):
            out.append(b)
    return out


def mean_stage_ms(run, start: str, end: str) -> Optional[float]:
    """Mean of ``end - start`` over the window's stamped batches (ms)."""
    bs = stamped(run.batches)
    if not bs:
        return None
    return float(np.mean([getattr(b, end) - getattr(b, start)
                          for b in bs])) * 1e3

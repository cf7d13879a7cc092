"""Frontier across shards: mean over the window's batches served with every
shard healthy (``BatchRecord.shards_healthy``) of the busiest shard's
bandit rounds over the shards' mean (``BatchRecord.shard_rounds``): 1.0
when skewed topics load every shard alike; the merge waits for the
busiest."""
import numpy as np


def read(run):
    skew = []
    for b in run.batches:
        rounds = getattr(b, "shard_rounds", None)
        if rounds is None or getattr(b, "shards_healthy", None) != len(rounds):
            continue
        r = np.asarray(rounds, np.float64)
        if r.mean() > 0:
            skew.append(r.max() / r.mean())
    return float(np.mean(skew)) if skew else None

"""95th percentile of latency over every request due in the window (ms),
from its intended send time. A per-layer reading, not a bounded one: at
0.8 of the knee the engine's queue episodes after Python's full
collections set it, and it spreads by tens of percent between seeds."""
from bench.stats import percentile_ms


def read(run):
    return percentile_ms([s.latency_s for s in run.window.measured], 95)

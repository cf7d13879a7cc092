"""Median latency over every request due in the window (ms), from its
intended send time."""
from bench.stats import percentile_ms


def read(run):
    return percentile_ms([s.latency_s for s in run.window.measured], 50)

"""Admission and batching (``serve/engine.py``): 95th percentile of the
engine's ``Completion.queue_wait_s`` (arrival to batch release, ms) over
the measured requests."""
from bench.stats import percentile_ms


def read(run):
    return percentile_ms([s.completion.queue_wait_s
                          for s in run.window.measured if not s.failed], 95)

"""Open loop: requests sent on a Poisson schedule at ``rate_qps``,
whether or not earlier ones have been answered.

The run sends ``rate_qps * seconds`` requests. Their gaps are the
exponential distribution's quantiles at (i + 0.5) / n, in a seeded order,
so every seed offers the same load and the same set of gaps. Latency runs
from each request's intended send time, so a server that falls behind
pays for the queue it made.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import Sent, Window, span, submit


def schedule(rate: float, seconds: float, rng: np.random.Generator):
    """Send offsets (s from the window's opening), first at 0."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def drive(engine, templates, mix, seconds, rng, tracer, *, k):
    offsets = schedule(mix["rate_qps"], seconds, rng)
    order = np.resize(rng.permutation(len(templates)), len(offsets))
    t_open = time.monotonic()
    tracer.arm(t_open, seconds)
    sent = []
    for off, tpl in zip(offsets, order):
        rec = Sent(template=int(tpl), due=t_open + off)
        wait = rec.due - time.monotonic()
        if wait > 0:
            with span("bench.wait"):
                time.sleep(wait)
        submit(engine, templates, rec, k)
        sent.append(rec)
    with span("bench.drain"):
        surfaced = engine.drain()
    return Window(sent=sent, t_open=t_open, t_close=t_open + seconds,
                  surfaced=surfaced)

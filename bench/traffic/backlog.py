"""Closed loop that keeps ``outstanding`` requests in flight: a new one
is sent as each answer arrives, so the engine always has full batches
queued (offline query sets, index-time evaluation).

The window opens when the first batch of answers is in, so that it starts
at a batch boundary, and closes ``seconds`` later; requests answered in it
count towards the rate. Sending stops at the close and what is in flight
is drained and checked.
"""
from __future__ import annotations

import queue
import time

from bench.harness import Sent, Window, span, submit

STALL_S = 300.0      # no answer for this long: stop sending, let drain fail


def drive(engine, templates, mix, seconds, rng, tracer, *, k):
    order = rng.permutation(len(templates))
    arrived: "queue.Queue[Sent]" = queue.Queue()
    sent = []

    def send():
        rec = Sent(template=int(order[len(sent) % len(order)]),
                   due=time.monotonic())
        sent.append(rec)
        submit(engine, templates, rec, k, on_done=arrived.put)

    for _ in range(mix["outstanding"]):
        send()
    first = engine.cfg.batch_size
    done = []
    t_open = t_close = None
    while True:
        with span("bench.wait"):
            try:
                rec = arrived.get(timeout=STALL_S)
            except queue.Empty:
                break
        done.append(rec)
        if len(done) == first:
            t_open = rec.t_done
            t_close = t_open + seconds
            tracer.arm(t_open, seconds)
        rec.in_window = (t_open is not None and len(done) > first
                         and rec.t_done <= t_close)
        if t_close is not None and time.monotonic() >= t_close:
            break
        send()
    for rec in sent:
        if t_close is None or rec.t_done is None or rec.t_done > t_close:
            rec.in_window = False
    with span("bench.drain"):
        surfaced = engine.drain()
    return Window(sent=sent, t_open=t_open, t_close=t_close,
                  surfaced=surfaced)

"""Requests answered in the window over the time from the window's opening
to the last of those answers (req/s). The window opens at a batch
boundary and the rate ends at the last answer, so whole batches do not
quantise it."""


def read(run):
    done = [s for s in run.window.measured if not s.failed]
    if not done:
        return None
    last = max(s.t_done for s in done)
    return len(done) / (last - run.window.t_open)

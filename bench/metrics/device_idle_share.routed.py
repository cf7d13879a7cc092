"""Device: share of the traced window in which no program ran, with busy
time averaged over the mesh's chips (1 - busy / window)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.programs:
        return None
    return 1.0 - t.busy_s / t.window_s

"""Seconds from process start until the engine is started and warm: the
index made on the device, every program compiled or loaded, the warm-up
batches served."""


def read(run):
    return run.setup_s

"""Admission and batching (``serve/engine.py``): the longest of Python's
garbage collections during the window (ms). A collection stops the
engine's admit and dispatch threads with the rest of the process; its
length grows with what the process keeps alive, such as every
``Completion`` the engine holds for its lifetime."""


def read(run):
    return max(run.gc_pauses) * 1e3 if run.gc_pauses else None

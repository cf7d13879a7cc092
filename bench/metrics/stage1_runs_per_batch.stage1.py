"""Stage-1: executions of the stage-1 program (``jit_stage1`` in the
trace's XLA Modules line) per ``engine.stage1`` span (one per batch that
runs stage-1), counted from the start of the traced window's first
complete span to the end of its last: 1.0 when the device runs stage-1
once per batch. A span is recorded only if it starts and ends while the
profiler runs, and an execution under way at either edge of the trace is
recorded cut short, so counting every start in the window would set the
edges' pieces against spans that were never recorded."""
from bench.trace import module_name

PROGRAM = "jit_stage1"
SPAN = "engine.stage1"


def read(run):
    t = run.trace
    if t is None:
        return None
    lo, hi = t.window
    spans = [e for e in t.host
             if e.name == SPAN and lo <= e.start and e.end <= hi]
    if not spans:
        return None
    first = min(e.start for e in spans)
    last = max(e.end for e in spans)
    runs = sum(1 for e in t.programs
               if module_name(e.name) == PROGRAM and first <= e.start < last)
    return runs / len(spans) if runs else None

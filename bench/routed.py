"""What the routed cell's device-trace readers share: the routed program's
whole executions on each chip, and the collective operations inside them.

The routed program (``retrieval/service.py``, ``make_routed_serving_step``)
runs as ``jit_step`` on every chip of the mesh: routing, the shard's own
stage-1 scan, the bandit and the scorecard merge in one ``shard_map``. An
execution under way when the trace starts or stops is recorded cut short,
so each chip's first and last recorded execution is left out.
"""
from __future__ import annotations

import re
from typing import Dict, List

from bench.trace import Event, Reduction, module_name

PROGRAM = "jit_step"
# An XLA collective by its instruction name (``%all-gather.1 = ...``), the
# -start/-done halves of an async one included (``%all-reduce-start.2``).
_COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)\b")


def whole_runs(trace: Reduction) -> List[Event]:
    """Executions of the routed program that lie inside the window, less
    each device's first and last recorded one."""
    lo, hi = trace.window
    by_device: Dict[int, List[Event]] = {}
    for e in trace.programs:
        if module_name(e.name) == PROGRAM:
            by_device.setdefault(e.device, []).append(e)
    out = []
    for runs in by_device.values():
        runs.sort(key=lambda e: e.start)
        out += [e for e in runs[1:-1] if lo <= e.start and e.end <= hi]
    return out


def is_collective(text: str) -> bool:
    return _COLLECTIVE.search(text) is not None


def collective_s(trace: Reduction, runs: List[Event]) -> List[float]:
    """Device seconds of the collective operations inside each of
    ``runs``, on the run's own device."""
    ops: Dict[int, List[Event]] = {}
    for e in trace.ops:
        if is_collective(e.name):
            ops.setdefault(e.device, []).append(e)
    return [sum(o.dur for o in ops.get(r.device, [])
                if r.start <= o.start and o.end <= r.end) / 1e9
            for r in runs]

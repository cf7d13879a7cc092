"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Three things, all over the traced window (the host span ``bench.trace``):

* device time per program (the ``XLA Modules`` line, e.g. ``jit_run``) and
  per operation (the ``XLA Ops`` line; a Pallas kernel shows as a
  ``custom-call`` whose instruction is named after the kernel, e.g.
  ``%fused_reveal.13 = (...) custom-call(...)``, with its shapes);
* device busy and idle time: busy is the union of the intervals in which a
  program runs, averaged over the devices;
* the longest idle gaps, each labelled by the benchmark's own host span
  that covers it and the host event that overlaps it most.

Device and host events share the profiler's clock (ns from its start).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace"
SPAN_PREFIX = "bench."
CONTAINERS = (" while(", " conditional(", " call(")
_OP_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns
    device: int = 0       # index of the device plane (device events)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Reduction:
    window: Tuple[float, float]
    programs: List[Event]            # every device's program executions
    ops: List[Event]                 # every device's operations
    host: List[Event]                # host events of every thread
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, e: Event) -> float:
        return max(0.0, min(e.end, self.window[1])
                   - max(e.start, self.window[0]))

    @property
    def busy_s(self) -> float:
        """Seconds in which a program ran on a device, averaged over the
        devices."""
        n = max(self.n_devices, 1)
        return sum(hi - lo for d in range(n)
                   for lo, hi in self.busy_intervals(d)) / 1e9 / n

    def busy_intervals(self, device: Optional[int] = None
                       ) -> List[Tuple[float, float]]:
        """Intervals in which a program ran on ``device`` (None: on any
        device)."""
        ivs = sorted((max(e.start, self.window[0]), min(e.end, self.window[1]))
                     for e in self.programs
                     if device is None or e.device == device)
        out: List[List[float]] = []
        for lo, hi in ivs:
            if hi <= lo:
                continue
            if out and lo <= out[-1][1]:
                out[-1][1] = max(out[-1][1], hi)
            else:
                out.append([lo, hi])
        return [(lo, hi) for lo, hi in out]

    def program_time(self, name: str) -> List[float]:
        """Device seconds of each execution of program ``name`` that lies
        wholly inside the window."""
        return [e.dur / 1e9 for e in self.programs
                if module_name(e.name) == name and e.start >= self.window[0]
                and e.end <= self.window[1]]

    def op_events(self, name: str) -> List[Event]:
        """Operations (kernels included) named ``name`` inside the window."""
        return [e for e in self.ops if op_name(e.name) == name
                and e.start >= self.window[0] and e.end <= self.window[1]]

    def gaps(self) -> List[Tuple[float, float]]:
        """Intervals of the window in which no device ran a program,
        longest first."""
        out, t = [], self.window[0]
        for lo, hi in self.busy_intervals():
            if lo > t:
                out.append((t, lo))
            t = max(t, hi)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return sorted(out, key=lambda g: g[0] - g[1])

    def label(self, lo: float, hi: float) -> str:
        """The benchmark span covering the gap's middle, and the host
        event that overlaps the gap most."""
        mid = 0.5 * (lo + hi)
        spans = [e for e in self.host if e.name.startswith(SPAN_PREFIX)
                 and e.name != WINDOW_SPAN and e.start <= mid <= e.end]
        span = min(spans, key=lambda e: e.dur).name if spans else "no-span"
        best, best_ov = None, 0.0
        for e in self.host:
            if e.name.startswith(SPAN_PREFIX):
                continue
            ov = min(e.end, hi) - max(e.start, lo)
            if ov > best_ov:
                best, best_ov = e.name, ov
        return f"{span} | {best}" if best else span

    def program_of(self, op: Event) -> Optional[str]:
        """Name of the program whose execution holds the operation."""
        if not hasattr(self, "_starts"):
            self._by_dev: Dict[int, List[Event]] = {}
            for e in sorted(self.programs, key=lambda e: e.start):
                self._by_dev.setdefault(e.device, []).append(e)
            self._starts = {d: [e.start for e in es]
                            for d, es in self._by_dev.items()}
        i = bisect.bisect_right(self._starts.get(op.device, []), op.start)
        if i and op.start < self._by_dev[op.device][i - 1].end:
            return module_name(self._by_dev[op.device][i - 1].name)
        return None

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        """The ``n`` operations (by program and instruction, e.g.
        ``jit_run/fused_reveal.13``: instruction names repeat across
        programs; loops left out, their bodies are counted) that took most
        device time, and the ``n`` longest idle gaps with their labels."""
        agg: Dict[str, float] = {}
        for e in self.ops:
            if any(c in e.name for c in CONTAINERS):
                continue
            t = self._clip(e)
            if t > 0:
                prog = self.program_of(e)
                key = (f"{prog}/{instruction(e.name)}" if prog
                       else instruction(e.name))
                agg[key] = agg.get(key, 0.0) + t
        ops = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        gaps = [[self.label(lo, hi), (hi - lo) / 1e9]
                for lo, hi in self.gaps()[:n]]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": gaps}


def op_name(text: str) -> str:
    """``%fused_reveal.13 = (...) custom-call(...)`` -> ``fused_reveal``."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def instruction(text: str) -> str:
    """``%fused_reveal.13 = (...) custom-call(...)`` -> ``fused_reveal.13``."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_run(878588376052493605)`` -> ``jit_run``."""
    return _MODULE.match(text).group(1)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def reduce_xplane(path: str) -> Optional[Reduction]:
    """The reduction of one trace file; None if it has no window span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    programs, ops, host = [], [], []
    n_devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = n_devices
            n_devices += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs += [Event(e.name, e.start_ns, e.duration_ns,
                                       dev) for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [Event(e.name, e.start_ns, e.duration_ns, dev)
                            for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.duration_ns)
                         for e in line.events]
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        return None
    w = max(spans, key=lambda e: e.dur)
    return Reduction(window=(w.start, w.end), programs=programs, ops=ops,
                     host=host, n_devices=n_devices)

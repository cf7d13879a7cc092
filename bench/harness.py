"""One run of one cell: set-up, the measured window, the comparison, the
result line.

Everything that belongs to one configuration, traffic mix or metric is a
file the harness finds by the name ``BENCHMARK.json`` gives it:

* ``<config file>``: sizes, engine settings, ``reduced``/``assumed``;
* ``bench/traffic/<traffic>.json``: a mix's parameters; its ``kind`` names
  the generator module ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.

Data files are looked up under ``root`` (the directory that holds
``BENCHMARK.json``), code files there first and then beside this file.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

from repro.serve.engine import (AsyncRetrievalEngine, Completion,  # noqa
                                EngineConfig, Request)

from bench import check, reference  # noqa: E402
from bench.corpus import Corpus, make_corpus  # noqa: E402
from bench.requests import Template, make_templates  # noqa: E402
from bench.trace import Reduction, find_xplane, reduce_xplane  # noqa: E402


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _find(items: Sequence[dict], name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise KeyError(f"no {what} named {name!r}")


def load_module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root``, else beside this file."""
    for base in (os.path.join(root, "bench"), HERE):
        path = os.path.join(base, kind, f"{name}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no bench/{kind}/{name}.py under {root} or "
                            f"{HERE}")


@dataclasses.dataclass
class Cell:
    root: str
    spec: dict              # BENCHMARK.json
    workload: dict          # its entry in ``workloads``
    config: dict            # the configuration file
    mix: dict               # the traffic file

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, per_layer: bool) -> List[dict]:
        """The metrics this cell reports: end-to-end ones listed for it (or,
        without a ``workloads`` key, for every cell), or per-layer ones
        whose ``workloads`` list it."""
        if per_layer:
            return [m for m in self.spec["per_layer"]
                    if self.name in m["workloads"]]
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def load_cell(root: str, workload: str) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = _find(spec["workloads"], workload, "workload")
    cfg_entry = _find(spec["configs"], wl["config"], "config")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(root, "bench", "traffic",
                                 f"{wl['traffic']}.json"))
    return Cell(root=root, spec=spec, workload=wl, config=config, mix=mix)


def engine_config(cfg: dict) -> EngineConfig:
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in cfg["engine"].items()}
    return EngineConfig(**kw)


# -- spans and the traced window ----------------------------------------------

def span(name: str):
    """A host span on the profiler's clock (inert while nothing traces)."""
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Traces ``seconds`` of the window from ``offset`` after it opens, on
    a thread of its own so that the load generator is not held up."""

    def __init__(self, offset: float, seconds: float):
        self.offset, self.seconds = offset, seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def arm(self, t_open: float, window_s: float) -> None:
        seconds = min(self.seconds, window_s)
        start = t_open + max(0.0, min(self.offset, window_s - seconds))
        self._thread = threading.Thread(target=self._run,
                                        args=(start, seconds),
                                        name="bench-tracer", daemon=True)
        self._thread.start()

    def _run(self, start: float, seconds: float) -> None:
        try:
            time.sleep(max(0.0, start - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with span("bench.trace"):
                    time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by reduce()
            self.error = e

    def reduce(self) -> Optional[Reduction]:
        try:
            if self._thread is None:
                return None
            self._thread.join()
            if self.error is not None:
                raise RuntimeError("tracing failed") from self.error
            return reduce_xplane(find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class NoTracer:
    def arm(self, t_open: float, window_s: float) -> None:
        pass


class GcPauses:
    """Seconds of each of Python's garbage collections while recording:
    the collector holds every thread of the process, the engine's too."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t0 = 0.0

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)


# -- the window ---------------------------------------------------------------

@dataclasses.dataclass
class Sent:
    """One request as the client saw it."""
    template: int
    due: float                            # intended send time (monotonic)
    rid: int = -1
    t_done: Optional[float] = None        # when its answer reached us
    completion: Optional[Completion] = None
    in_window: bool = True

    @property
    def failed(self) -> bool:
        return self.completion is None or self.completion.error is not None

    @property
    def latency_s(self) -> float:
        return float("inf") if self.failed else self.t_done - self.due


@dataclasses.dataclass
class Window:
    sent: List[Sent]
    t_open: float
    t_close: float
    surfaced: List[Completion]            # every completion drain() returned

    @property
    def measured(self) -> List[Sent]:
        return [s for s in self.sent if s.in_window]


def submit(engine, templates: Sequence[Template], rec: Sent, k: int,
           on_done=None) -> None:
    """Send one request and have its answer stamped when it arrives."""
    t = templates[rec.template]
    with span("bench.submit"):
        rec.rid = engine.submit(Request(query=t.query, k=k,
                                        cand_ids=t.cand_ids))

    def done(fut):
        rec.t_done = time.monotonic()
        rec.completion = fut.result()
        if on_done is not None:
            on_done(rec)

    engine.future(rec.rid).add_done_callback(done)


# -- the run ------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    setup_s: float
    window: Window
    batches: list                 # the engine's BatchRecords of the window
    templates: Sequence[Template]
    corpus_shape: tuple           # (C, L, M)
    itemsize: int
    overlaps: List[float]         # top-k overlap of each measured request
    gc_pauses: List[float]        # seconds of each collection in the window
    trace: Optional[Reduction]
    peaks: dict

    @property
    def cfg(self) -> dict:
        return self.cell.config


def device_info(chips: int) -> Dict[str, Any]:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def warm(engine, templates: Sequence[Template], batches: int, k: int) -> None:
    """Serve ``batches`` batches through every program the traffic uses,
    before the window (part of set-up)."""
    n = batches * engine.cfg.batch_size
    for j in range(n):
        t = templates[j % len(templates)]
        engine.submit(Request(query=t.query, k=k, cand_ids=t.cand_ids))
    engine.drain()


def reference_answers(cell: Cell, corpus: Corpus,
                      templates: Sequence[Template], used: Sequence[int],
                      rng: np.random.Generator):
    """Reference scores and candidate sets of the templates to check: every
    one used for a rerank mix; a seeded sample for a stage-1 mix, whose
    candidates the plain scan rebuilds."""
    cfg, mix = cell.config, cell.mix
    if mix.get("candidates") is not None:
        check_ids = list(used)
        cands = [templates[j].cand_ids for j in check_ids]
    else:
        n = min(len(used), mix["check_sample"])
        check_ids = sorted(rng.choice(list(used), size=n, replace=False))
        eng = cfg["engine"]
        cands = [reference.stage1_candidates(
            corpus.embs, corpus.mask, templates[j].query,
            kprime=eng["stage1_kprime"],
            max_candidates=eng["stage1_candidates"],
            span=cfg["reference"]["stage1_span_docs"]) for j in check_ids]
    with span("bench.reference"):
        scores = reference.maxsim_scores(
            corpus.embs, corpus.mask, [templates[j].query for j in check_ids],
            cands)
    return ({j: s for j, s in zip(check_ids, scores)},
            {j: set(c.tolist()) for j, c in zip(check_ids, cands)})


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, chips: int = 1, peaks: Optional[dict] = None,
             engine_cls=AsyncRetrievalEngine) -> Dict[str, Any]:
    """Run the cell once; returns the result line's object."""
    cfg, mix = cell.config, cell.mix
    k = cfg["k"]
    rng = np.random.default_rng(seed)
    corpus = make_corpus(cfg, seed)
    templates = make_templates(corpus, mix, rng)
    engine = engine_cls(corpus.embs, corpus.mask, engine_config(cfg))
    engine.warmup()
    warm(engine, templates, mix.get("warm_batches", 0), k)
    engine.start()
    n_warm = len(engine.metrics.batches)
    setup_s = time.monotonic() - t_start

    tracer = (Tracer(mix["trace_offset_s"], mix["trace_seconds"]) if trace
              else NoTracer())
    driver = load_module(cell.root, "traffic", mix["kind"])
    try:
        with GcPauses() as pauses:
            window = driver.drive(engine, templates, mix, seconds, rng,
                                  tracer, k=k)
    finally:
        engine.stop()
    device = device_info(chips)
    recompiles = engine.metrics.summary()["compiles_after_warmup"]
    batches = list(engine.metrics.batches[n_warm:])
    del engine
    gc.collect()
    reduction = tracer.reduce() if trace else None

    used = sorted({s.template for s in window.sent})
    ref, cand_sets = reference_answers(cell, corpus, templates, used, rng)
    answers = [((s.completion.topk_ids, s.completion.topk_scores)
                if not s.failed else (np.full((k,), -1), np.zeros((k,))))
               for s in window.sent]
    numbers = check.answer_numbers(
        answers, [ref.get(s.template) for s in window.sent],
        [cand_sets.get(s.template) for s in window.sent], k)
    rids = [c.rid for c in window.surfaced]
    numbers["lost"] = (sum(s.failed for s in window.sent)
                       + len(rids) - len(set(rids)))
    numbers["recompiles"] = recompiles
    overlaps = [o for s, o in zip(window.sent, numbers.pop("overlaps"))
                if s.in_window and o is not None]
    limits = mix["limits"]
    correct = check.judge(numbers, limits)

    run = Run(cell=cell, setup_s=setup_s, window=window, batches=batches,
              templates=templates,
              corpus_shape=tuple(corpus.embs.shape),
              itemsize=corpus.embs.dtype.itemsize, overlaps=overlaps,
              gc_pauses=pauses.pauses, trace=reduction, peaks=peaks or {})
    metrics = {}
    for m in cell.metrics(per_layer=trace):
        value = load_module(cell.root, "metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    out = {"correct": bool(correct), "attempted": len(window.sent),
           "failed": int(sum(s.failed for s in window.sent)),
           "metrics": metrics, "device": device}
    if reduction is not None:
        out["breakdown"] = reduction.breakdown()
    out["check"] = check.summary(numbers, limits)
    return out

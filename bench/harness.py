"""One run of one cell: set-up, the measured window, the comparison, the
result line.

Everything that belongs to one configuration, traffic mix or metric is a
file the harness finds by the name ``BENCHMARK.json`` gives it:

* ``<config file>``: sizes, engine settings, ``reduced``/``assumed``;
* ``bench/traffic/<traffic>.json``: a mix's parameters; its ``kind`` names
  the generator module ``bench/traffic/<kind>.py``; ``shard_failure`` fails
  one shard of a sharded index for part of the window, whatever the kind;
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
from typing import Any, Dict, List, Optional, Sequence, Tuple

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


class ShardFailure:
    """A mix's ``shard_failure: {"shard": s, "at_s": a, "for_s": d}``:
    shard ``s`` of the engine's index fails ``a`` seconds after the window
    opens and is restored ``d`` seconds later (at the latest when the
    window's traffic has been drained), on a thread of its own. Each call
    is stamped, on the engine's clock, when it starts and when it returns:
    the shard's health is known between them."""

    def __init__(self, engine, spec: dict):
        self.engine = engine
        self.shard, self.at_s, self.for_s = (int(spec["shard"]),
                                             float(spec["at_s"]),
                                             float(spec["for_s"]))
        # (start, return) of each call; None if it was never made.
        self.fail_call: Optional[Tuple[float, float]] = None
        self.restore_call: Optional[Tuple[float, float]] = None
        self.error: Optional[BaseException] = None
        self._over = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def arm(self, t_open: float, window_s: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t_open,),
                                        name="bench-shard-failure",
                                        daemon=True)
        self._thread.start()

    def _call(self, name: str, fn) -> Tuple[float, float]:
        with span(name):
            t0 = self.engine.clock()
            fn(self.shard)
            return t0, self.engine.clock()

    def _run(self, t_open: float) -> None:
        try:
            if self._over.wait(t_open + self.at_s - time.monotonic()):
                return
            self.fail_call = self._call("bench.fail", self.engine.fail_shard)
            self._over.wait(t_open + self.at_s + self.for_s
                            - time.monotonic())
            self.restore_call = self._call("bench.restore",
                                           self.engine.restore_shard)
        except BaseException as e:  # raised by run_cell
            self.error = e

    def end(self) -> None:
        """Restore the shard if it is still down, and wait for the thread."""
        self._over.set()
        if self._thread is not None:
            self._thread.join()

    def state(self, record) -> str:
        """The shard's health as the batch ``record`` read it, which it
        does while it is prepared (from ``t_release`` to ``t_prepared``):
        ``"up"`` before the failure's call started or after the restore's
        returned, ``"down"`` between them, ``"either"`` where the
        preparation overlaps a call."""
        never = (float("inf"), float("inf"))
        fail = self.fail_call or never
        restore = self.restore_call or never
        if record.t_prepared < fail[0] or record.t_release > restore[1]:
            return "up"
        if record.t_release > fail[1] and record.t_prepared < restore[0]:
            return "down"
        return "either"


class Armed:
    """What the traffic generator arms when the window opens (its
    ``tracer`` argument): the tracer and any shard failure."""

    def __init__(self, *parts):
        self.parts = parts

    def arm(self, t_open: float, window_s: float) -> None:
        for p in self.parts:
            p.arm(t_open, window_s)


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
    chips: int = 1
    # The mix's shard failure: its shard, and the (start, return) of the
    # calls that failed and restored it on the engine's clock (None if
    # never made); None without one.
    failure: Optional[Dict[str, Any]] = None
    degraded: List[Sent] = dataclasses.field(default_factory=list)
                                  # answers held to the healthy shards

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


def stage1_scope(cfg: dict) -> str:
    """Where the configuration's engine draws stage-1 candidates from, as
    ``reference.stage1_candidates`` names it: each shard's own rows for
    shard-local stage-1 (``engine.stage1 == "local"``), else the whole
    index."""
    eng = cfg["engine"]
    if eng.get("stage1_total", 0) > 0:
        raise ValueError(
            "engine.stage1_total > 0 is out of the benchmark's scope: its "
            "per-shard quotas follow the program's k-means router, which "
            "the reference cannot rebuild independently")
    return "shard" if eng.get("stage1") == "local" else "index"


def reference_answers(cell: Cell, corpus: Corpus,
                      templates: Sequence[Template], used: Sequence[int],
                      rng: np.random.Generator):
    """Reference scores and candidate sets of the templates to check: every
    one used for a rerank mix; a seeded sample for a stage-1 mix, whose
    candidates the plain scan rebuilds in the engine's scope."""
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
            span=cfg["reference"]["stage1_span_docs"],
            scope=stage1_scope(cfg)) for j in check_ids]
    with span("bench.reference"):
        scores = reference.maxsim_scores(
            corpus.embs, corpus.mask, [templates[j].query for j in check_ids],
            cands)
    return ({j: s for j, s in zip(check_ids, scores)},
            {j: set(c.tolist()) for j, c in zip(check_ids, cands)})


def expected(window: Window, batches: Sequence, ref: Dict[int, dict],
             cand_sets: Dict[int, set], failure: Optional[ShardFailure],
             docs_per_shard: int):
    """What each sent request is held to: its reference scores and
    candidates, whether it was served degraded, and whether its coverage
    misstates the failed shard's health.

    Without a shard failure every answer is held to the whole reference.
    With one, the answer's batch (``Completion.bid``) says when it read the
    shard's health (``ShardFailure.state``). An answer read with the shard
    down, or at the edge of a call and reporting partial coverage
    (``coverage < 1``), is degraded: held to the healthy shards, with the
    failed shard's documents taken out of its candidates and of the
    reference's top-k, so that one of them in the answer is foreign. Any
    other answer is held to the whole reference. Coverage misstates the
    shard's health where it is short with the shard up (before the failure,
    or after the restore: service did not recover), or full with it down
    while the reference's candidates hold one of its documents."""
    n = len(window.sent)
    if failure is None:
        return ([ref.get(s.template) for s in window.sent],
                [cand_sets.get(s.template) for s in window.sent],
                [False] * n, [False] * n)
    down = failure.shard
    on_down = lambda d: check.shard_of(d, docs_per_shard) == down
    ref_up = {j: {d: v for d, v in r.items() if not on_down(d)}
              for j, r in ref.items()}
    cand_up = {j: {d for d in c if not on_down(d)}
               for j, c in cand_sets.items()}
    off_shard = check.OffShard(down, docs_per_shard)
    record = {b.bid: b for b in batches}
    refs, cands, degraded, misstated = [], [], [], []
    for s in window.sent:
        full = (ref.get(s.template), cand_sets.get(s.template))
        if s.failed:
            state, short = "up", False
        else:
            state = failure.state(record[s.completion.bid])
            short = s.completion.coverage < 1
        worse = state == "down" or (state == "either" and short)
        if worse:
            refs.append(ref_up.get(s.template))
            cands.append(cand_up.get(s.template, off_shard))
        else:
            refs.append(full[0])
            cands.append(full[1])
        degraded.append(worse)
        misstated.append(
            (state == "up" and short)
            or (state == "down" and not short and full[1] is not None
                and any(on_down(d) for d in full[1])))
    return refs, cands, degraded, misstated


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks: Optional[dict] = None,
             engine_cls=AsyncRetrievalEngine) -> Dict[str, Any]:
    """Run the cell once on its chips; returns the result line's object."""
    cfg, mix = cell.config, cell.mix
    k, chips = cfg["k"], cell.workload["chips"]
    stage1_scope(cfg)          # refuse quota routing before any work
    rng = np.random.default_rng(seed)
    corpus = make_corpus(cfg, seed, chips)
    templates = make_templates(corpus, mix, rng)
    engine = engine_cls(corpus.embs, corpus.mask, engine_config(cfg))
    engine.warmup()
    warm(engine, templates, mix.get("warm_batches", 0), k)
    engine.start()
    n_warm = len(engine.metrics.batches)
    setup_s = time.monotonic() - t_start

    tracer = (Tracer(mix["trace_offset_s"], mix["trace_seconds"]) if trace
              else NoTracer())
    failure = (ShardFailure(engine, mix["shard_failure"])
               if "shard_failure" in mix else None)
    driver = load_module(cell.root, "traffic", mix["kind"])
    try:
        with GcPauses() as pauses:
            window = driver.drive(engine, templates, mix, seconds, rng,
                                  Armed(tracer, *filter(None, [failure])),
                                  k=k)
    finally:
        if failure is not None:
            failure.end()
        engine.stop()
    if failure is not None and failure.error is not None:
        raise RuntimeError("the shard failure failed") from failure.error
    device = device_info(chips)
    recompiles = engine.metrics.summary()["compiles_after_warmup"]
    batches = list(engine.metrics.batches[n_warm:])
    del engine
    gc.collect()
    reduction = tracer.reduce() if trace else None

    used = sorted({s.template for s in window.sent})
    ref, cand_sets = reference_answers(cell, corpus, templates, used, rng)
    refs, cands, is_degraded, misstated = expected(
        window, batches, ref, cand_sets, failure, corpus.n_docs // chips)
    answers = [((s.completion.topk_ids, s.completion.topk_scores)
                if not s.failed else (np.full((k,), -1), np.zeros((k,))))
               for s in window.sent]
    numbers = check.answer_numbers(answers, refs, cands, k)
    rids = [c.rid for c in window.surfaced]
    numbers["lost"] = (sum(s.failed for s in window.sent)
                       + len(rids) - len(set(rids)) + sum(misstated))
    numbers["recompiles"] = recompiles
    overlaps = [o for s, o in zip(window.sent, numbers.pop("overlaps"))
                if s.in_window and o is not None]
    limits = mix["limits"]
    correct = check.judge(numbers, limits)

    degraded = [s for s, d in zip(window.sent, is_degraded) if d]
    run = Run(cell=cell, setup_s=setup_s, window=window, batches=batches,
              templates=templates,
              corpus_shape=tuple(corpus.embs.shape),
              itemsize=corpus.embs.dtype.itemsize, overlaps=overlaps,
              gc_pauses=pauses.pauses, trace=reduction, peaks=peaks or {},
              chips=chips, degraded=degraded,
              failure=None if failure is None else {
                  "shard": failure.shard, "fail_call": failure.fail_call,
                  "restore_call": failure.restore_call})
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
    if failure is not None:
        out["shard_failure"] = {
            "shard": failure.shard, "degraded": len(degraded),
            "misstated": int(sum(misstated)),
            "fail_s": _since(failure.fail_call, window.t_open),
            "restore_s": _since(failure.restore_call, window.t_open)}
    out["check"] = check.summary(numbers, limits)
    return out


def _since(call: Optional[Tuple[float, float]],
           t_open: float) -> Optional[float]:
    """When ``call`` returned, in seconds from the window's opening."""
    return None if call is None else call[1] - t_open

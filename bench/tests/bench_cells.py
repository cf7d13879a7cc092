"""A small cell for CPU tests: a configuration and traffic mixes written
into a temporary directory beside a BENCHMARK.json that names them. The
harness finds them by name; no file of the benchmark is edited."""
import json
import os
import time

from bench import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


def load(path):
    with open(path) as f:
        return json.load(f)


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def small_root(tmp) -> str:
    """Root with cells ``small-poisson`` and ``small-backlog`` over the
    ``small`` configuration, a shrunk copy of ``colbert-text``."""
    root = str(tmp)
    cfg = load(os.path.join(BENCH, "configs", "colbert-text.json"))
    cfg.update(name="small", query_tokens=8, doc_tokens=16,
               min_doc_tokens=8, dim=32, corpus_docs=512)
    cfg["engine"].update(token_buckets=[8], cand_buckets=[32],
                         stage1_candidates=32, stage1_kprime=4)
    cfg["corpus"].update(chunk_docs=64, planted_queries=8)
    cfg["reference"]["stage1_span_docs"] = 128
    write(os.path.join(root, "bench", "configs", "small.json"), cfg)
    rerank = load(os.path.join(BENCH, "traffic", "text-rerank-poisson.json"))
    rerank.update(rate_qps=40, candidates=[16, 32], templates=32,
                  trace_offset_s=0.2, trace_seconds=0.3)
    write(os.path.join(root, "bench", "traffic", "small-poisson.json"),
          rerank)
    backlog = load(os.path.join(BENCH, "traffic",
                                "text-stage1-backlog.json"))
    backlog.update(templates=16, check_sample=16, trace_offset_s=0.2,
                   trace_seconds=0.3)
    write(os.path.join(root, "bench", "traffic", "small-backlog.json"),
          backlog)
    spec = load(os.path.join(CHECKOUT, "BENCHMARK.json"))
    spec["configs"] = [dict(name="small", source="small copy",
                            file="bench/configs/small.json", reduced=[],
                            why="CPU test")]
    spec["workloads"] = [
        dict(name="small-poisson", config="small", traffic="small-poisson",
             chips=1, why="CPU test"),
        dict(name="small-backlog", config="small", traffic="small-backlog",
             chips=1, why="CPU test")]
    # The text cells' metrics, listed for their small twins.
    twin = {"text-rerank-poisson": "small-poisson",
            "text-stage1-backlog": "small-backlog"}
    for key in ("end_to_end", "per_layer"):
        kept = []
        for m in spec[key]:
            if "workloads" in m:
                m["workloads"] = [twin[w] for w in m["workloads"]
                                  if w in twin]
                if not m["workloads"]:
                    continue
            kept.append(m)
        spec[key] = kept
    write(os.path.join(root, "BENCHMARK.json"), spec)
    return root


def run(root, workload, seed=3, seconds=1.0, trace=False, **kw):
    cell = harness.load_cell(root, workload)
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.monotonic(), peaks=PEAKS, **kw)


def routed_root(tmp) -> str:
    """``small_root`` plus a 4-shard routed twin of a sharded deployment:
    the ``small-routed`` configuration (4 x 256 passages, one ``data`` axis
    over 4 devices, shard-local stage-1, no quotas) under a Zipf-skewed
    closed loop, ``small-routed``, and the same with shard 1 failed for
    part of the window, ``small-routed-failover``. At this size the
    bandit's answers are exact (``miss_share`` reads 0), so the twin's
    ``miss_share`` limit is 0.05: a shard left out costs about a quarter of
    each top-k. Run it where JAX has 4 devices (``four_devices.py``)."""
    root = small_root(tmp)
    cfg = load(os.path.join(root, "bench", "configs", "small.json"))
    cfg.update(name="small-routed", corpus_docs=1024)
    cfg["engine"].update(mesh_axes=[["data", 4]], stage1="local",
                         stage1_total=0)
    write(os.path.join(root, "bench", "configs", "small-routed.json"), cfg)
    mix = load(os.path.join(root, "bench", "traffic", "small-backlog.json"))
    mix.update(topic_zipf=1.0)
    mix["limits"]["miss_share"] = 0.05
    write(os.path.join(root, "bench", "traffic", "small-routed.json"), mix)
    mix.update(shard_failure={"shard": 1, "at_s": 0.5, "for_s": 1.0})
    write(os.path.join(root, "bench", "traffic",
                       "small-routed-failover.json"), mix)
    spec = load(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append(dict(name="small-routed", source="small copy",
                                file="bench/configs/small-routed.json",
                                reduced=[], why="CPU test"))
    for name in ROUTED:
        spec["workloads"].append(dict(name=name, config="small-routed",
                                      traffic=name, chips=4,
                                      why="CPU test"))
    for m in spec["end_to_end"]:
        if m["name"] == "throughput_qps":
            m["workloads"] += ROUTED
    write(os.path.join(root, "BENCHMARK.json"), spec)
    return root


ROUTED = ["small-routed", "small-routed-failover"]

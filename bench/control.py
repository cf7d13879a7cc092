#!/usr/bin/env python3
"""The controls that the comparison of ``correct`` must fail.

  python3 bench/control.py --workload text-rerank-poisson --seeds 1,2,3

For each seed the cell's index and requests are made as a run makes them,
and the numbers of the comparison (``bench/check.py``) are read for the
controls put in the program's place, over every request template (a
stage-1 mix: over the seeded sample a run checks):

* ``bf16_query``: the reference itself with the query rounded to bf16
  (one MXU pass), the step below the configuration's f32 scores that
  would tempt a later change; its own top-k and its own scores;
* ``half_candidates``: the exact f32 top-k over a seeded half of each
  request's candidates, with exact scores: a scorer that leaves part of
  the candidates out;
* ``int8_index``: the program's int8 resident index (its own path to a
  lower precision, ``EngineConfig.corpus_format="int8"``), served through
  the engine (rerank mixes only: the engine's stage-1 needs the bf16 rows).

One JSON line per seed and control. The benchmark's own runs do not run
this; ``bench/tests/test_bench_control.py`` runs it at a small size.
"""
import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, List

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
# Before the program is imported (its import compiles): see bench/run.py.
jax.config.update("jax_compilation_cache_dir",
                  os.path.join(CHECKOUT, ".jax_cache"))

import numpy as np  # noqa: E402

from bench import check, harness, reference  # noqa: E402
from bench.corpus import make_corpus  # noqa: E402
from bench.requests import make_templates  # noqa: E402


def control_numbers(cell: harness.Cell, seed: int) -> List[Dict]:
    cfg, mix = cell.config, cell.mix
    k = cfg["k"]
    rng = np.random.default_rng(seed)
    corpus = make_corpus(cfg, seed, cell.workload["chips"])
    templates = make_templates(corpus, mix, rng)
    used = list(range(len(templates)))
    ref, cand_sets = harness.reference_answers(cell, corpus, templates, used,
                                               rng)
    ids = sorted(ref)
    cands = [np.asarray(sorted(cand_sets[j]), np.int32) for j in ids]
    refs = [ref[j] for j in ids]
    sets = [cand_sets[j] for j in ids]
    out = []

    low = reference.maxsim_scores(corpus.embs, corpus.mask,
                                  [templates[j].query for j in ids], cands,
                                  bf16_query=True)
    answers = []
    for s in low:
        top = reference.topk(s, k)
        answers.append((np.asarray(top), np.asarray([s[d] for d in top])))
    out.append(_row(seed, "bf16_query", answers, refs, sets, k))

    answers = []
    for r in refs:
        pool = np.asarray(sorted(r))
        kept = {int(d): r[int(d)]
                for d in rng.permutation(pool)[:max(k, len(pool) // 2)]}
        top = reference.topk(kept, k)
        answers.append((np.asarray(top), np.asarray([kept[d] for d in top])))
    out.append(_row(seed, "half_candidates", answers, refs, sets, k))

    if mix.get("candidates") is not None:
        ecfg = dataclasses.replace(harness.engine_config(cfg),
                                   corpus_format="int8")
        eng = harness.AsyncRetrievalEngine(corpus.embs, corpus.mask, ecfg)
        eng.warmup()
        rids = [eng.submit(harness.Request(query=templates[j].query, k=k,
                                           cand_ids=templates[j].cand_ids))
                for j in ids]
        by_rid = {c.rid: c for c in eng.drain()}
        answers = [(by_rid[r].topk_ids, by_rid[r].topk_scores) for r in rids]
        out.append(_row(seed, "int8_index", answers, refs, sets, k))
        del eng
    return out


def _row(seed, control, answers, refs, sets, k) -> Dict:
    nums = check.answer_numbers(answers, refs, sets, k)
    nums.pop("overlaps")
    return {"seed": seed, "control": control, **nums}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    args = ap.parse_args(argv)
    cell = harness.load_cell(CHECKOUT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in control_numbers(cell, seed):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

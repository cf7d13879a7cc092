"""The comparison that decides ``correct``.

Each number is compared with its limit, a number passes at or below it:

* ``lost``: requests submitted in the window and not answered exactly
  once, plus answers that carry an error; in a mix that fails a shard,
  also answers whose coverage misstates that shard's health when their
  batch read it (``harness.expected``);
* ``recompiles``: programs compiled after ``warmup()``;
* ``foreign``: answers with a doc id outside the request's candidates
  (as the reference rebuilds them), a repeated id, or fewer than k ids;
  an answer served with a shard down may hold none of that shard's docs;
* ``miss_share``: 1 - the mean top-k overlap with the reference's f32
  MaxSim top-k over the same candidates: the share of returned ids that
  the exhaustive top-k does not hold. A scorer that skips part of each
  request's candidates, or returns wrong docs with exact scores, misses
  about half;
* ``inexact_share``: the share of returned (doc, score) pairs whose score
  lies more than ``EXACT_RTOL`` (relative) from the reference's f32 MaxSim
  score of that doc. Col-Bandit returns the exact score for every doc it
  revealed in full, and those are most of its winners; a lower-precision
  scorer returns almost none exactly.

A lower precision fails ``inexact_share`` and passes ``miss_share`` (its
top-k overlaps the reference about as well as Col-Bandit's); a scorer
that skips candidates fails ``miss_share`` and passes ``inexact_share``.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

EXACT_RTOL = 1e-5


def shard_of(doc: int, docs_per_shard: int) -> int:
    """The shard that holds ``doc`` in an index placed as contiguous blocks
    of ``docs_per_shard`` rows."""
    return doc // docs_per_shard


class OffShard:
    """The doc ids that do not lie on ``shard``: the candidate set of an
    answer served with that shard down, where the reference has not
    rebuilt its candidates."""

    def __init__(self, shard: int, docs_per_shard: int):
        self.shard, self.docs_per_shard = shard, docs_per_shard

    def __contains__(self, doc: int) -> bool:
        return shard_of(doc, self.docs_per_shard) != self.shard


def answer_numbers(answers: Sequence, ref_scores: Sequence[Dict[int, float]],
                   cand_sets: Sequence[Optional[set]], k: int) -> Dict:
    """``answers[i]`` = (ids, scores) of request i; ``ref_scores[i]`` the
    reference score of each of its candidates (None: not checked against
    the reference); ``cand_sets[i]`` its candidate ids, or any container of
    the ids it may hold (None: unknown).
    ``overlaps`` holds each request's top-k overlap (None: not checked)."""
    foreign, overlaps, inexact, scored = 0, [], 0, 0
    per_request: List[Optional[float]] = []
    best_of: Dict[int, set] = {}
    for (ids, scores), ref, cand in zip(answers, ref_scores, cand_sets):
        got = [int(x) for x in ids if x >= 0]
        bad = len(got) < k or len(set(got)) != len(got)
        if cand is not None:
            bad = bad or any(d not in cand for d in got)
        foreign += int(bad)
        if ref is None:
            per_request.append(None)
            continue
        if id(ref) not in best_of:
            best_of[id(ref)] = set(sorted(ref, key=lambda d: (-ref[d], d))[:k])
        best = best_of[id(ref)]
        overlaps.append(len(set(got) & best) / len(best))
        per_request.append(overlaps[-1])
        for d, s in zip(got, scores):
            r = ref.get(d)
            scored += 1
            if r is None or abs(float(s) - r) > EXACT_RTOL * max(1.0, abs(r)):
                inexact += 1
    overlap = float(np.mean(overlaps)) if overlaps else 0.0
    return {"foreign": foreign, "overlap": overlap,
            "miss_share": 1.0 - overlap,
            "inexact_share": inexact / scored if scored else 1.0,
            "checked": len(overlaps), "overlaps": per_request}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True if every number is within its limit."""
    return all(numbers[name] <= limit for name, limit in limits.items())


def summary(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in limits.items()}


def report(check: Dict) -> None:
    """Print each compared number beside its limit on stderr."""
    for name, c in check.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)

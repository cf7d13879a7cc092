"""The comparison fails a run whose timed path is broken underneath: one
answer altered where it is produced; half of every batch left out; half
of every request's candidates left out."""
import dataclasses

import numpy as np
import pytest

from bench import harness
from bench_cells import run, small_root


class AlteredAnswer(harness.AsyncRetrievalEngine):
    def _finish_batch(self, prep, out):
        comps = super()._finish_batch(prep, out)
        if comps and self._started and not getattr(self, "_altered", False):
            self._altered = True
            c = comps[0]
            ids = c.topk_ids.copy()
            n = self.corpus_embs.shape[0]
            cand = set(np.asarray(prep.args[3][0]).tolist())
            ids[0] = next(d for d in range(n) if d not in cand)
            comps[0] = dataclasses.replace(c, topk_ids=ids)
        return comps


class HalfBatch(harness.AsyncRetrievalEngine):
    """The step computes only the first half of each batch; the rest get
    the answers of the first half."""

    def _finish_batch(self, prep, out):
        comps = super()._finish_batch(prep, out)
        half = len(comps) // 2
        if self._started and half:
            for i in range(half, 2 * half):
                src = comps[i - half]
                comps[i] = dataclasses.replace(
                    comps[i], topk_ids=src.topk_ids.copy(),
                    topk_scores=src.topk_scores.copy())
        return comps


class HalfCandidates(harness.AsyncRetrievalEngine):
    """Every request is scored over the first half of its candidates."""

    def submit(self, req, *args, **kw):
        if req.cand_ids is not None and self._started:
            req = dataclasses.replace(
                req, cand_ids=req.cand_ids[:max(req.k,
                                                len(req.cand_ids) // 2)])
        return super().submit(req, *args, **kw)


# The stage-1 twin checks every request it sends; at full size a run
# checks a seeded sample of them.
CELLS = ["small-poisson", "small-backlog"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(tmp_path, cell):
    out = run(small_root(tmp_path), cell, engine_cls=AlteredAnswer)
    assert not out["correct"]
    assert out["check"]["foreign"]["value"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_not_correct(tmp_path, cell):
    out = run(small_root(tmp_path), cell, engine_cls=HalfBatch)
    assert not out["correct"]
    assert out["check"]["foreign"]["value"] >= 1


def test_half_the_candidates_left_out_is_not_correct(tmp_path):
    out = run(small_root(tmp_path), "small-poisson",
              engine_cls=HalfCandidates)
    assert not out["correct"]
    assert out["check"]["foreign"]["value"] == 0
    assert out["check"]["miss_share"]["value"] > \
        out["check"]["miss_share"]["limit"]

"""ISSUE 5: shape-bucket kernel autotuning (repro.kernels.tuning).

Contracts:
  * resolution order — explicit block argument > tuned bucket entry >
    default, resolved at trace time;
  * pow2 bucketing — one tuned entry covers the whole shape family;
  * JSON persistence round-trips the table exactly;
  * ``autotune_op`` records a winner drawn from the candidate grid and the
    op produces identical RESULTS under every candidate (tuning is a pure
    performance knob);
  * engine integration — ``EngineConfig(autotune=True)`` tunes at warmup
    before the AOT compiles (zero-recompile contract intact), persists to
    ``tuning_table``, and a second engine reuses the table instead of
    re-timing.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, tuning
from repro.kernels.ops import autotune_op, gather_maxsim_op, maxsim_op


@pytest.fixture(autouse=True)
def _clean_table():
    tuning.clear()
    yield
    tuning.clear()


def test_bucketing_covers_shape_family():
    k1 = tuning.bucket_key("gather_maxsim", dict(B=65, L=200, M=128))
    k2 = tuning.bucket_key("gather_maxsim", dict(B=128, L=256, M=128))
    k3 = tuning.bucket_key("gather_maxsim", dict(B=129, L=256, M=128))
    assert k1 == k2 and k2 != k3


def test_lookup_merges_tuned_over_defaults():
    dims = dict(N=32, T=16, L=128, M=128)
    base = tuning.lookup("maxsim", dims)
    assert base == tuning.DEFAULTS["maxsim"]
    tuning.record("maxsim", dims, {"block_l": 64})
    got = tuning.lookup("maxsim", dims)
    assert got["block_l"] == 64
    assert got["block_n"] == tuning.DEFAULTS["maxsim"]["block_n"]


def test_maxsim_default_block_t_capped_not_full_axis(monkeypatch):
    """Satellite: the old ``block_t=0 -> bt = T`` default is retired — an
    unbucketed large-T call must tile T at the documented 128 cap (and
    pad), not grow the VMEM tile linearly in T. Pinned by parity at
    T > 128 with an odd T (the pad path is the fix's risk surface)."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    assert tuning.DEFAULTS["maxsim"]["block_t"] == 128
    rng = np.random.default_rng(0)
    N, L, M, T = 4, 32, 128, 200                   # T > 128, unaligned
    E = jnp.asarray(rng.standard_normal((N, L, M)), jnp.float32)
    mask = jnp.asarray(rng.random((N, L)) > 0.2)
    Q = jnp.asarray(rng.standard_normal((T, M)), jnp.float32)
    h = maxsim_op(E, mask, Q, block_l=32)          # default block_t
    # Relative: f32 accumulation order differs on 128-wide dots (|h| ~ 30).
    np.testing.assert_allclose(np.asarray(h),
                               np.asarray(ref.maxsim_ref(E, mask, Q)),
                               rtol=1e-6)


def test_explicit_block_argument_beats_tuned_entry(monkeypatch):
    """An explicit block argument must win over a (deliberately broken)
    tuned entry — pinned via the kernel's divisibility error: block_b=3
    with B=6 pads to 6 rows, while a tuned block_b would differ."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    rng = np.random.default_rng(1)
    N, L, M, T = 8, 32, 16, 8
    E = jnp.asarray(rng.standard_normal((N, L, M)), jnp.float32)
    mask = jnp.ones((N, L), jnp.bool_)
    Q = jnp.asarray(rng.standard_normal((T, M)), jnp.float32)
    di = jnp.asarray(rng.integers(0, N, 6), jnp.int32)
    ti = jnp.asarray(rng.integers(0, T, (6, 2)), jnp.int32)
    dims = dict(B=6, G=2, L=L, M=M, D=N, TQ=T)
    tuning.record("gather_maxsim", dims, {"block_b": 4, "block_l": 16})
    want = np.asarray(ref.gather_maxsim_ref(E, mask, Q, di, ti))
    for explicit in (None, 2):                     # tuned path, then override
        out = gather_maxsim_op(E, mask, Q, di, ti, block_b=explicit)
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


def test_save_load_roundtrip(tmp_path):
    dims = dict(B=64, G=4, L=128, M=128, D=256, TQ=256)
    tuning.record("fused_reveal", dims, {"block_b": 16, "block_l": 64})
    tuning.record("maxsim", dict(N=8, T=8, L=64, M=128), {"block_l": 64})
    path = str(tmp_path / "table.json")
    tuning.save_table(path)
    before = tuning.table()
    tuning.clear()
    assert tuning.table() == {}
    assert tuning.load_table(path) == 2
    assert tuning.table() == before
    # file is plain rows
    rows = json.load(open(path))
    assert all(set(r) == {"op", "bucket", "config"} for r in rows)


def test_autotune_op_records_winner_and_results_invariant(monkeypatch):
    """autotune_op must record a candidate-grid winner, and every candidate
    configuration must produce identical op RESULTS — block sizes are a
    pure performance knob."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    dims = dict(B=8, G=2, L=32, M=16, D=16, TQ=16)
    best, timings = autotune_op("gather_maxsim", dims, repeats=1)
    assert timings and best in tuning.candidates("gather_maxsim", dims)
    assert tuning.bucket_key("gather_maxsim", dims) in tuning.table()
    rng = np.random.default_rng(2)
    E = jnp.asarray(rng.standard_normal((16, 32, 16)), jnp.float32)
    mask = jnp.ones((16, 32), jnp.bool_)
    Q = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    di = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    ti = jnp.asarray(rng.integers(0, 16, (8, 2)), jnp.int32)
    outs = [np.asarray(gather_maxsim_op(E, mask, Q, di, ti, **cand))
            for cand in tuning.candidates("gather_maxsim", dims)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)


def test_autotune_op_ref_lane_is_a_noop(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    best, timings = autotune_op("fused_reveal",
                                dict(B=4, G=2, L=16, M=8, D=8, TQ=8))
    assert timings == {} and tuning.table() == {}
    assert best == tuning.DEFAULTS["fused_reveal"]


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("REPRO_KERNEL_IMPL") == "ref",
                    reason="block sizes are ignored by the pure-jnp "
                           "oracles; autotune is a documented no-op")
def test_engine_warmup_autotunes_and_persists(tmp_path):
    """EngineConfig(autotune=True, tuning_table=...): warmup times the
    serving buckets' kernel shapes, persists the table, keeps the
    zero-recompile contract, and a second engine reuses the table (zero
    buckets re-measured)."""
    from repro.serve.engine import EngineConfig, Request, RetrievalEngine

    rng = np.random.default_rng(3)
    C, L, M = 40, 16, 16
    embs = rng.standard_normal((C, L, M)).astype(np.float32)
    mask = np.ones((C, L), bool)
    path = str(tmp_path / "tuned.json")
    cfg = EngineConfig(batch_size=2, token_buckets=(8,), cand_buckets=(16,),
                       flavor="bandit", block_docs=4, block_tokens=4,
                       max_rounds=6, autotune=True, tuning_table=path)
    eng = RetrievalEngine(embs, mask, cfg)
    eng.warmup()
    assert eng.metrics.autotune_buckets > 0
    assert eng.metrics.autotune_s > 0
    rows = json.load(open(path))
    assert len(rows) == eng.metrics.autotune_buckets
    # serving still zero-recompile after warmup
    for _ in range(3):
        eng.submit(Request(query=rng.standard_normal((8, M)).astype(
            np.float32), k=4, cand_ids=np.arange(16)))
    done = eng.drain()
    assert len(done) == 3
    assert eng.metrics.compiles_after_warmup == 0
    summary = eng.metrics.summary()
    assert summary["autotune_buckets"] == eng.metrics.autotune_buckets

    # second engine: loads the table, re-times nothing
    eng2 = RetrievalEngine(embs, mask, cfg)
    eng2.warmup()
    assert eng2.metrics.tuning_entries_loaded == len(rows)
    assert eng2.metrics.autotune_buckets == 0

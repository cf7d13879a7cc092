"""Rehearsal of ``chip_smoke.py`` on the CPU.

The serving phases run at a tiny size with the Pallas kernels in interpret
mode (the CPU backend's default dispatch); the script itself has no CPU
option. ``main()`` must refuse to run anywhere but on a TPU with the
Pallas kernels.
"""
import json
import os
import sys

import numpy as np
import pytest

from _subproc import REPO_ROOT, run_in_subprocess

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
import chip_smoke as cs  # noqa: E402

TINY = dict(n_docs=256, n_requests=8, doc_len=16, dim=32, query_tokens=(4, 8),
            candidates=(12, 16), token_buckets=(8,), cand_buckets=(16,),
            batch_size=4, relevant=2, distractors=4, chunk_docs=64)


def test_one_chip_phase_matches_reference_on_cpu():
    out = cs.serve_one_chip(cs.Shape(**TINY), seed=0, require_kernels=False)
    for label in ("sync", "async"):
        assert out[f"{label} dense"]["dense"] >= cs.DENSE_FLOOR
        assert out[f"{label} bandit"]["bandit"] >= cs.BANDIT_FLOOR
    assert out["resident_bytes"] == 256 * 16 * 32 * 2 + 256 * 16


def test_requests_mix_candidates_and_stage1():
    dc = cs.make_corpus(cs.Shape(**TINY), 0, 256)
    reqs = cs.make_requests(dc, cs.Shape(**TINY), 0, block=1)
    carried = [r for r in reqs if r.cand_ids is not None]
    assert len(carried) == len(reqs) // 2
    for i, r in enumerate(reqs):
        assert 4 <= r.query.shape[0] <= 8
        if r.cand_ids is not None:
            assert 12 <= len(r.cand_ids) <= 16
            assert len(set(r.cand_ids.tolist())) == len(r.cand_ids)
            assert set(dc.relevant[i]) <= set(r.cand_ids.tolist())


def test_plain_stage1_matches_chunked_scan(monkeypatch):
    """The smoke's plain stage-1 (spans merged on the host) rebuilds the
    candidates of the chunked scan, with spans and chunks that do not
    divide the index."""
    shape = cs.Shape(**TINY)
    dc = cs.make_corpus(shape, 0, 256)
    shards = cs.local_shards(dc.embs, dc.mask)
    cfg = cs.engine_config(shape, flavor="dense")
    monkeypatch.setattr(cs, "PLAIN_SPAN", 48)
    for i in range(3):
        q = dc.queries[i]
        got = cs.generate_candidates(dc.embs, dc.mask, q,
                                     kprime=cfg.stage1_kprime,
                                     max_candidates=32, support=cfg.support,
                                     chunk_docs=40)
        got = np.asarray(got.doc_ids)
        want = cs.plain_stage1(shards, q, cfg, 32)
        assert sorted(want.tolist()) == sorted(got[got >= 0].tolist())


def test_main_refuses_a_cpu_device(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_main_refuses_non_pallas_kernels(monkeypatch, capsys, impl):
    monkeypatch.setattr(cs, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    assert cs.main([]) != 0
    assert "Pallas" in capsys.readouterr().err


def test_four_chip_phase_on_virtual_devices():
    out = run_in_subprocess("""
        import json
        import chip_smoke as cs
        shape = cs.Shape(n_docs=128, n_requests=24, doc_len=16, dim=32,
                         query_tokens=(4, 8), candidates=(12, 32),
                         token_buckets=(8,), cand_buckets=(16, 32),
                         batch_size=8, relevant=2, distractors=4,
                         chunk_docs=64)
        print(json.dumps(cs.serve_four_chips(shape, 0,
                                             require_kernels=False)))
    """, n_devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["routed"]["dense"] >= cs.DENSE_FLOOR
    assert res["routed"]["bandit"] >= cs.BANDIT_FLOOR
    assert res["resident_bytes_per_device"] == 128 * 16 * 32 * 2


def test_sharded_device_corpus_moves_nothing_between_devices():
    """Each device allocates and fills its own rows: no device-to-device
    copy, and the same corpus as one device makes."""
    out = run_in_subprocess("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.data.synthetic import make_device_corpus
        from repro.launch.mesh import make_mesh
        kw = dict(n_docs=256, n_queries=4, doc_len=16, min_doc_len=4,
                  query_len=8, dim=32, chunk_docs=32)
        mesh = make_mesh((4,), ("data",))
        with jax.transfer_guard_device_to_device("disallow"):
            dc = make_device_corpus(
                **kw, sharding=NamedSharding(mesh, P("data", None, None)))
            jax.block_until_ready((dc.embs, dc.mask))
        one = make_device_corpus(**kw)
        for s in dc.embs.addressable_shards:
            assert s.data.devices() == {s.device}
        same = (np.array_equal(np.asarray(dc.embs, np.float32),
                               np.asarray(one.embs, np.float32))
                and np.array_equal(np.asarray(dc.mask), np.asarray(one.mask)))
        print(len(dc.embs.addressable_shards), same)
    """, n_devices=4)
    assert out.split()[-2:] == ["4", "True"]


def test_compile_cache_lands_in_env_dir(tmp_path):
    out = run_in_subprocess(f"""
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
        import jax
        import jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()
        print(jax.config.jax_compilation_cache_dir)
    """, n_devices=1)
    assert out.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

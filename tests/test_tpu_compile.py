"""Compile the serving path's kernels for a TPU v5e, without a chip.

The TPU compiler compiles for a described (not attached) v5e, so these
tests catch what interpret mode cannot: Mosaic's block-shape rule, i1
vector reshapes, VMEM and HBM limits. Each test lowers one kernel (or one
whole bandit rerank step, or the four-chip routed step) at the
``colbert-text`` widths (d=128, passages of 128 tokens) and at the
``colbert-mm`` page length (729, padded by ``kernels.ops``), and checks
that the Pallas kernel is in the program (``tpu_custom_call``) and that
the program fits a v5e's 16 GiB of HBM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.quant import QuantTokens
from repro.retrieval.ann import STAGE1_CHUNK_DOCS, generate_candidates_batch
from repro.retrieval.service import (make_routed_serving_step,
                                     make_serving_step)

HBM_BYTES = 16 << 30
M = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < HBM_BYTES, f"program needs {total} bytes of HBM"
    return compiled


def _corpus(sds, lead, L, fmt):
    if fmt == "bf16":
        return sds((*lead, L, M), jnp.bfloat16)
    scales = sds((*lead, L), jnp.bfloat16)
    if fmt == "int8":
        return QuantTokens(sds((*lead, L, M), jnp.int8), scales)
    return QuantTokens(sds((*lead, L, M), jnp.int8), scales,
                       sds((*lead, L), jnp.int32), sds((8, M), jnp.float32))


KERNEL_CASES = [(op, L, fmt)
                for op in ("maxsim_batch", "gather_maxsim", "fused_reveal")
                for L in (128, 729) for fmt in ("bf16", "int8")]
KERNEL_CASES += [("fused_reveal", 128, "residual")]


@pytest.mark.parametrize("op,L,fmt", KERNEL_CASES)
def test_kernel_compiles_for_v5e(one_chip, monkeypatch, op, L, fmt):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if op == "maxsim_batch":        # dense step: B=8 queries x 256 cands
        B, N, T = 8, 256, 32
        _compile_for_chip(ops.maxsim_batch_op, _corpus(sds, (B, N), L, fmt),
                          sds((B, N, L), jnp.bool_), sds((B, T, M),
                                                        jnp.float32))
        return
    # bandit reveal round: 64 frontier rows x 8 tokens over the batch's
    # stacked 8 x 256 candidates and 8 x 32 query tokens
    F, G, D, TQ = 64, 8, 2048, 256
    args = [_corpus(sds, (D,), L, fmt), sds((D, L), jnp.bool_),
            sds((TQ, M), jnp.float32), sds((F,), jnp.int32),
            sds((F, G), jnp.int32)]
    if op == "gather_maxsim":
        _compile_for_chip(ops.gather_maxsim_op, *args)
    else:
        _compile_for_chip(ops.fused_reveal_op, *args, sds((F, G), jnp.bool_))


def test_bandit_rerank_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole Col-Bandit serving step the engine warms, over a resident
    bf16 colbert-text index of 131072 passages (4 GiB)."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    step = make_serving_step("bandit", topk=5, alpha_ef=0.2)

    def run(ce, cm, q, cand, a, b, seed):
        return step(ce, cm, q, cand, a, b,
                    jax.random.fold_in(jax.random.key(0), seed))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, L, B, T, N = 131072, 128, 8, 32, 256
    text = _compile_for_chip(
        run, sds((C, L, M), jnp.bfloat16), sds((C, L), jnp.bool_),
        sds((B, T, M), jnp.float32), sds((B, N), jnp.int32),
        sds((B, N, T), jnp.float32), sds((B, N, T), jnp.float32),
        sds((), jnp.int32)).as_text()
    # The benchmark finds the kernel by its instruction prefix in the device
    # trace; XProf groups the step's operations by the frontier's scopes.
    assert re.search(r"%fused_reveal\.\d+ = [^\n]*custom-call", text)
    for scope in ("frontier_init", "frontier_round"):
        assert f"/{scope}/" in text, scope


def test_stage1_chunk_topk_lowers_to_tpu_topk(one_chip):
    """The engine's stage-1 program at the text cell's shape: a resident
    bf16 index of 131072 passages, 8 queries of 32 tokens, k'=10 and 320
    candidates. Each chunk's top-k' over its STAGE1_CHUNK_DOCS * L token
    columns must be the TPU's TopK, never a sort of those columns (a
    vmapped, rank-3 top_k lowers to one, and sorting every chunk in full
    costs many times the chunk's dot)."""
    C, L, B, T = 131072, 128, 8, 32

    def stage1(ce, cm, q):
        cs = generate_candidates_batch(ce, cm, q, kprime=10,
                                       max_candidates=320)
        return cs.doc_ids, cs.a, cs.b

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(stage1).lower(
        sds((C, L, M), jnp.bfloat16), sds((C, L), jnp.bool_),
        sds((B, T, M), jnp.float32)).compile()
    text = compiled.as_text()
    width = STAGE1_CHUNK_DOCS * L
    lines = text.splitlines()
    # An instruction's shapes are what precedes its opcode.
    sorts = [ln.split(" sort(")[0] for ln in lines if " sort(" in ln]
    assert sorts, "expected the small merge and candidate sorts"
    for shapes in sorts:
        assert not re.search(rf"\[[\d,]*\b{width}\]", shapes), shapes
    topks = [re.search(r"= \(f32\[([\d,]+)\]", ln).group(1) for ln in lines
             if 'custom_call_target="TopK"' in ln]
    # Both chunk selections (the first chunk's and the scan body's) are a
    # TopK of k'=10 over all B*T query-token rows.
    assert topks.count(f"{B * T},10") == 2, topks
    # A vmapped per-query scan, which sorts, needs 1343019520 bytes of temp.
    assert compiled.memory_analysis().temp_size_in_bytes < 1343019520


def test_routed_step_compiles_for_v5e_2x2(topo, monkeypatch):
    """The four-chip routed program of ``text-routed-4chip`` over a described
    v5e 2x2 host: 4 x 131072 resident passages, shard-local stage-1 (k'=10,
    320 candidates a shard), the bandit and the merge in one shard_map. Its
    only collectives are the merge's (the scorecard all-gathers and the
    psums), under the ``routed_merge`` scope the benchmark's merge reader
    and XProf name them by."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    mesh = Mesh(topo.devices, ("data",))
    C, L, B, T, Kc, S = 4 * 131072, 128, 8, 32, 8, 4

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    step = make_routed_serving_step(mesh, "bandit", topk=5, n_local=320,
                                    n_total=0, kprime=10, alpha_ef=0.2)
    compiled = _compile_for_chip(
        step, sds((C, L, M), jnp.bfloat16, "data"),
        sds((C, L), jnp.bool_, "data"), sds((Kc, M), jnp.float32),
        sds((Kc, S), jnp.float32), sds((B, T, M), jnp.float32),
        sds((S,), jnp.int32), sds((), jnp.int32), sds((S,), jnp.bool_),
        sds((), jnp.float32), sds((), jnp.int32))
    text = compiled.as_text()
    for scope in ("routed_stage1", "routed_rerank", "routed_merge"):
        assert f"/{scope}/" in text, scope
    collectives = [ln for ln in text.splitlines() if re.search(
        r"^\s*%(all-gather|all-reduce|reduce-scatter|collective-permute|"
        r"all-to-all)", ln)]
    assert collectives
    for ln in collectives:
        assert "/routed_merge/" in ln, ln

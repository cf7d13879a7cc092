"""Operation and byte counts checked by hand."""
import pytest

from bench.kernels import fused_reveal, rerank, stage1
from bench.stats import least_time
from bench_cells import PEAKS

# One round launch as the v5e trace shows it (text cell: 8 queries x 8
# docs per round, 8 query tokens per doc, 320 candidates of 128 x 128).
ROUND = (
    "%fused_reveal.13 = (f32[64,1,8]{2,1,0:T(1,128)S(1)}, f32[64,1,8]"
    "{2,1,0:T(1,128)S(1)}) custom-call(s32[64]{0:T(128)S(1)} %reshape.1153, "
    "bf16[2560,128,128]{2,1,0:T(8,128)(2,1)} %get-tuple-element.829, "
    "s32[64,1,128]{2,1,0:T(1,128)S(1)} %fusion.206, f32[64,8,128]"
    "{2,1,0:T(8,128)S(1)} %bitcast_select_fusion.3, s32[64,1,8]"
    "{2,1,0:T(1,128)S(1)} %bitcast.205), custom_call_target=\"tpu_custom_"
    "call\", operand_layout_constraints={s32[64]{0}, bf16[2560,128,128]"
    "{2,1,0}, s32[64,1,128]{2,1,0}, f32[64,8,128]{2,1,0}, s32[64,1,8]"
    "{2,1,0}}, frontend_attributes={kernel_metadata={}}")


def test_fused_reveal_round_launch_by_hand():
    flops, nbytes = fused_reveal.cost(ROUND)
    assert flops == 2 * 64 * 8 * 128 * 128                  # 16777216
    rows = 64 * 128 * 128 * 2           # the 64 selected docs, not 2560
    idx, mask, q, new = 64 * 4, 64 * 128 * 4, 64 * 8 * 128 * 4, 64 * 8 * 4
    outs = 2 * 64 * 8 * 4
    assert nbytes == rows + idx + mask + q + new + outs     # 2398464
    t, bound = least_time(flops, nbytes, PEAKS)
    assert bound == "memory"
    assert t == pytest.approx(2398464 / 819e9)


def test_fused_reveal_ignores_other_ops():
    assert fused_reveal.cost("%sort.23 = (f32[8,320]) sort(f32[8,320] %x)") \
        is None


def test_stage1_batch_by_hand():
    flops, nbytes = stage1.cost(8, 32, 131072, 128, 128)
    assert flops == 2 * 8 * 32 * 131072 * 128 * 128        # 1.0995e12
    assert nbytes == 131072 * 128 * 128 * 2                # 4294967296
    t, bound = least_time(flops, nbytes, PEAKS)
    assert bound == "compute"
    assert t == pytest.approx(1099511627776 / 197e12)      # 5.58 ms


def test_rerank_batch_by_hand():
    flops, nbytes = rerank.cost(1536, 0.37 * 1536 * 32, 128, 128)
    assert nbytes == 1536 * 128 * 128 * 2
    assert flops == pytest.approx(2 * 0.37 * 1536 * 32 * 128 * 128)
    assert least_time(flops, nbytes, PEAKS)[1] == "memory"

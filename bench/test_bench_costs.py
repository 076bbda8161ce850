"""Each cost function against shapes worked out by hand."""
import pytest

from bench.costs import (chunked_prefill_attn, model_step, paged_decode_attn,
                         sparq_format, sparq_matmul)
from bench.observe import least_time, per_execution

# a dense model small enough to count by hand
S = {"layers": 2, "d": 8, "heads": 4, "kv_heads": 2, "head_dim": 2,
     "ff": 16, "vocab": 10, "mlp": "swiglu"}


def test_sparq_format_bytes_per_value():
    assert sparq_format.bytes_per_value(4, True, True) == 0.9375
    assert sparq_format.bytes_per_value(4, False, True) == 0.875
    assert sparq_format.bytes_per_value(trimming=False) == 1.0
    assert sparq_format.KV_BYTES_PER_VALUE == 0.9375


def test_sparq_matmul_call():
    ops, nbytes = sparq_matmul.call(3, 8, 16)
    assert ops == 2 * 3 * 8 * 16
    # int8 codes, bf16 rows, f32 product, f32 channel scales
    assert nbytes == 8 * 16 + 2 * 3 * 8 + 4 * 3 * 16 + 4 * 16


def test_sparq_matmul_layer_shapes_and_rows():
    # q 8->8, k 8->4, v 8->4, o 8->8, up 8->16, down 16->8, gate 8->16
    assert sparq_matmul.layer_shapes(S) == [
        (8, 8), (8, 4), (8, 4), (8, 8), (8, 16), (16, 8), (8, 16)]
    params = 64 + 32 + 32 + 64 + 128 + 128 + 128
    ops, _ = sparq_matmul.rows(S, 5)
    assert ops == 2 * 5 * params * 2
    gelu = dict(S, mlp="gelu")
    assert len(sparq_matmul.layer_shapes(gelu)) == 6


def test_paged_decode_attn_counts_live_context_only():
    ops, nbytes = paged_decode_attn.step(S, [3, 5])
    assert ops == 4 * 8 * 4 * 2 * 2                 # keys * H * hd * L
    kv = 8 * 2 * (2 * 2) * 0.9375                   # keys, K and V, KV*hd
    q_out = 2 * (4 * 2) * 4                         # bf16 q read, out write
    assert nbytes == pytest.approx((kv + q_out) * 2)


def test_chunked_prefill_attn():
    # one sequence: history 4 tokens in pages, 3 new tokens at 4, 5, 6
    ops, nbytes = chunked_prefill_attn.chunk(S, [4, 5, 6], [(4, 3)])
    assert ops == 4 * (5 + 6 + 7) * 4 * 2 * 2
    hist = 4 * 2 * (2 * 2) * 0.9375
    per_tok = 3 * (2 * 4 * 2 + 2 * 8 * 2)
    assert nbytes == pytest.approx((hist + per_tok) * 2)


def test_model_step_flops():
    mm = model_step.matmul_flops(S)
    assert mm == 2 * (64 + 32 + 32 + 64 + 128 + 128 + 128) * 2
    assert model_step.head_flops(S) == 2 * 8 * 10
    assert model_step.decode_step(S, [3, 5]) == \
        2 * (mm + 160) + 4 * 8 * 4 * 2 * 2
    assert model_step.chunk(S, [0, 1], 1) == \
        2 * mm + 4 * 3 * 4 * 2 * 2 + 160


def test_least_time_takes_the_binding_bound():
    # 1e12 ops at 1e12 op/s = 1 s; 1e9 bytes at 1e10 B/s = 0.1 s
    assert least_time(1e12, 1e9, 1e12, 1e10) == pytest.approx(1.0)
    assert least_time(1e9, 1e10, 1e12, 1e10) == pytest.approx(1.0)
    assert least_time(1e9, 1e9, 1e12, 1e10) == pytest.approx(0.1)


def test_per_execution_scales_host_calls_to_device_runs():
    assert per_execution([1.0, 3.0], 4) == 8.0
    assert per_execution([], 4) == 0.0
    assert per_execution([2.0], 0) == 0.0

"""Each cost function against shapes worked out by hand."""
import json
from types import SimpleNamespace

import pytest

from bench import cell, check
from bench.costs import (chunked_prefill_attn, expert_matmul, layers,
                         model_step, paged_decode_attn, paged_latent_attn,
                         sparq_format, sparq_matmul)
from bench.observe import least_time, load_module, per_execution

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


# a model with sparse experts and latent attention: one leading dense
# layer and two MoE layers, 4 routed experts (2 a token), 2 shared of
# width 4; latent rank 6, nope 3, rope 2, v 5
SM = dict(S, layers=3, dense_layers=1, heads=2, experts=4,
          experts_active=2, shared_experts=2, moe_ff=4, kv_lora_rank=6,
          qk_nope_dim=3, qk_rope_dim=2, v_head_dim=5)


def test_layer_groups():
    assert layers.groups(S) == [(2, "dense")]
    assert layers.groups(SM) == [(1, "dense"), (2, "moe")]
    assert layers.groups(dict(SM, dense_layers=0)) == [(3, "moe")]
    assert not layers.latent(S) and layers.latent(SM)


def test_sparq_matmul_layer_shapes_moe_latent():
    # wq 8 -> 2 * (3 + 2), w_dkv 8 -> 6 + 2, wo 2 * 5 -> 8
    attn = [(8, 10), (8, 8), (10, 8)]
    assert sparq_matmul.layer_shapes(SM) == attn + [(8, 16), (16, 8),
                                                    (8, 16)]
    # shared experts: one SwiGLU FFN of width 2 * 4
    assert sparq_matmul.layer_shapes(SM, "moe") == attn + [(8, 8)] * 3
    assert sparq_matmul.layer_shapes(dict(SM, shared_experts=0),
                                     "moe") == attn
    dense_layer = 80 + 64 + 80 + 128 + 128 + 128
    moe_layer = 80 + 64 + 80 + 64 + 64 + 64
    ops, _ = sparq_matmul.rows(SM, 5)
    assert ops == 2 * 5 * (dense_layer + 2 * moe_layer)


def test_model_step_moe_latent_flops():
    projections = 2 * (608 + 2 * 416)
    absorbed = 2 * 6 * 2 * (3 + 5) * 3       # w_uk and w_uv, every layer
    routed = 2 * (32 * 3) * 2 * 2            # 2 experts a token, 2 layers
    mm = projections + absorbed + routed
    assert model_step.matmul_flops(SM) == mm == 4224
    per_key = 2 * 2 * (6 + 2) + 2 * 2 * 6    # scores + latent PV
    assert model_step.decode_step(SM, [3, 5]) == \
        2 * (mm + 160) + per_key * 8 * 3
    assert model_step.chunk(SM, [0, 1], 1) == \
        2 * mm + per_key * 3 * 3 + 160


def test_expert_matmul_counts_experts_hit():
    # rows 3 and 1 on experts 0 and 2; three 8x4 matrices an expert
    ops, nbytes = expert_matmul.layer(SM, [3, 0, 1, 0])
    assert ops == 2 * (3 + 1) * (32 * 3)
    codes_scales = 3 * 32 + 4 * (4 + 8 + 4)
    rows = 2 * 8 * 2                          # bf16 in and out, per row
    assert nbytes == 2 * codes_scales + (3 + 1) * rows
    assert expert_matmul.layer(SM, [0, 0, 0, 0]) == (0.0, 0.0)


def test_paged_latent_attn_counts():
    per_key = 2 * 2 * 8 + 2 * 2 * 6
    query = 2 * (2 * 8 + 2 * 6)              # bf16 latent query and output
    ops, nbytes = paged_latent_attn.step(SM, [3, 5])
    assert ops == per_key * 8 * 3
    assert nbytes == pytest.approx((8 * 8 * 0.9375 + 2 * query) * 3)
    # one sequence: history 4 in pages, 3 new tokens at 4, 5, 6
    ops, nbytes = paged_latent_attn.chunk(SM, [4, 5, 6], [(4, 3)])
    assert ops == per_key * (5 + 6 + 7) * 3
    assert nbytes == pytest.approx(
        (4 * 8 * 0.9375 + 3 * (2 * 8 + query)) * 3)


def _matmul_least(sizes, m):
    peaks = {"int8_ops": 4e2, "hbm_bytes_per_s": 1e2}
    mod = load_module("metrics", "kern.sparq_matmul_roofline.offline")
    return mod.least(SimpleNamespace(sizes=sizes, peaks=peaks), m)


def test_matmul_roofline_costs_each_layer_kind():
    want = 0.0
    for n, kind in ((1, "dense"), (2, "moe")):
        want += n * sum(max(o / 4e2, b / 1e2) for o, b in (
            sparq_matmul.call(5, k, n_) for k, n_ in
            sparq_matmul.layer_shapes(SM, kind)))
    assert _matmul_least(SM, 5) == pytest.approx(want)


#: at the parent commit, for the sizes above and both configuration files:
#: rows(32), matmul_flops, decode_step, chunk, paged decode, chunked
#: prefill (ctx, positions and sequences of `_dense_values`)
DENSE_VALUES = {
    "S": ((73728.0, 26240.0), 2304.0, 402496.0, 7610688.0,
          (392640.0, 92281.0), (7020544.0, 29076.0)),
    "starcoder2-3b.decode-4k": (
        (184213831680.0, 3018608640.0), 5756682240.0, 26496294912.0,
        1514752966656.0, (2261606400.0, 89818560.0),
        (40438333440.0, 106556160.0)),
    "mistral-large-123b-l4.offline-chat": (
        (354334801920.0, 5613518848.0), 11072962560.0, 48719265792.0,
        2857856139264.0, (1206190080.0, 47903232.0),
        (21567111168.0, 56829952.0)),
}


def _dense_values(s):
    ctx, pos = [1000, 2047, 3071, 17], list(range(300, 556))
    return (sparq_matmul.rows(s, 32), model_step.matmul_flops(s),
            model_step.decode_step(s, ctx), model_step.chunk(s, pos, 2),
            paged_decode_attn.step(s, ctx),
            chunked_prefill_attn.chunk(s, pos, [(300, 256)]))


@pytest.mark.parametrize("name", sorted(DENSE_VALUES))
def test_dense_costs_are_unchanged(name):
    s = S if name == "S" else check.ref_sizes(cell.load_spec(name).conf)
    assert _dense_values(s) == DENSE_VALUES[name]


def test_dense_matmul_roofline_least_is_unchanged():
    """The reader's least time at v5e peaks, as at the parent commit."""
    peaks = json.loads((cell.ROOT / "bench" / "peaks.json").read_text())
    p = peaks["devices"]["TPU v5 lite"]
    mod = load_module("metrics", "kern.sparq_matmul_roofline.offline")
    got = [mod.least(SimpleNamespace(sizes=s, peaks=p), m)
           for s in (S, check.ref_sizes(cell.load_spec(
               "starcoder2-3b.decode-4k").conf), check.ref_sizes(
               cell.load_spec("mistral-large-123b-l4.offline-chat").conf))
           for m in (32, 256)]
    assert got == [3.203907203907204e-08, 2.420903540903541e-07,
                   0.003685724835164835, 0.004862012014652015,
                   0.006854113367521368, 0.007499390905982907]

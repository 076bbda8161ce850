"""Compile every Pallas kernel of the serving path for a TPU v5e, with no
chip attached.

The TPU compiler in the installed libtpu compiles for a chip that is
described and not attached (`v5e:2x2` topology). These tests
lower each kernel at TinyLlama-1.1B's published widths (d_model 2048,
32 query heads over 4 KV heads of 64 lanes, d_ff 5632, page size 16)
with `interpret=False`, so they catch what interpret mode cannot: block
shapes the TPU tiling rule refuses, operand types the MXU does not take,
and tiles that overflow VMEM. Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, and the test workers all import this
file. Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparq import SparqConfig
from repro.kernels import ops
from repro.kernels.sparq_dequant import sparq_dequant_pallas
from repro.kernels.sparq_matmul import sparq_matmul_pallas
from repro.kernels.sparq_quant import sparq_quant_pallas

# TinyLlama-1.1B (configs/tinyllama_1_1b.py) and the serving geometry
D, H, KV, HD, FF = 2048, 32, 4, 64, 5632
PS, N_PAGES, SLOTS, NB = 16, 2048, 8, 34     # 34 pages: 512 + 32 tokens
CHUNK, ALIGN = 256, 8
CODEC = SparqConfig.opt5(signed=True)        # the served `--sparq 5opt`


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_v5e(one_chip):
    """compile(fn, *shapes, sharding=one chip) -> HLO text of `fn`
    compiled for v5e, with the persistent compilation cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes, sharding=one_chip):
        args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _codec_kw(cfg):
    return dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
                vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
                enabled=cfg.enabled)


f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32


# ----------------------------------------------------------------------
# the three row/tile kernels, called directly
# ----------------------------------------------------------------------

@pytest.mark.parametrize("K,N", [(D, D), (D, KV * HD), (D, FF), (FF, D)],
                         ids=["wq_wo", "wk_wv", "w1_w3", "w2"])
@pytest.mark.parametrize("cfg", [CODEC, SparqConfig.opt5(signed=False)],
                         ids=["signed_int8", "unsigned_bf16"])
def test_sparq_matmul_compiles(compile_v5e, K, N, cfg):
    def fn(x, w, a, c):
        return sparq_matmul_pallas(x, w, a, c, interpret=False,
                                   **_codec_kw(cfg))
    hlo = compile_v5e(fn, ((128, K), f32), ((K, N), i8), ((), f32),
                      ((N,), f32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("K", [HD, D, FF])
def test_sparq_quant_compiles(compile_v5e, K):
    def fn(x, a):
        return sparq_quant_pallas(x, a, interpret=False, **_codec_kw(CODEC))
    assert "tpu_custom_call" in compile_v5e(fn, ((256, K), f32), ((), f32))


@pytest.mark.parametrize("K", [HD, D, FF])
def test_sparq_dequant_compiles(compile_v5e, K):
    def fn(s, m):
        return sparq_dequant_pallas(s, m, interpret=False)
    assert "tpu_custom_call" in compile_v5e(fn, ((256, K), i8),
                                            ((256, K), i8))


# ----------------------------------------------------------------------
# the six hot dispatchers, steered to their compiled kernels
# ----------------------------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatchers pick interpret mode from the process's backend,
    which is the CPU here; compile their TPU branch instead."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _qscale(scale):
    from repro.core.quantizer import QScale
    return QScale(scale=scale, bits=CODEC.act_bits, signed=CODEC.signed)


@pytest.mark.parametrize("M", [SLOTS, CHUNK], ids=["decode", "prefill"])
def test_quantized_matmul_compiles(compile_v5e, on_tpu, M):
    def fn(x, w, a, c):
        return ops.quantized_matmul(x, w, _qscale(a), c, CODEC,
                                    impl="pallas")
    hlo = compile_v5e(fn, ((1, M, D), f32), ((D, FF), i8), ((), f32),
                      ((FF,), f32))
    assert "tpu_custom_call" in hlo


# the benchmark's widths: Mistral-Large-2407 (d_model 12288, 8 KV heads of
# 128, d_ff 28672) at a 32-row decode step and a 256-row chunk, and
# StarCoder2-3B's MLP (d_model 3072, d_ff 12288) at 24 rows and 256
BENCH_MATMULS = [(12288, 12288, 32), (12288, 1024, 32), (12288, 28672, 32),
                 (28672, 12288, 32), (12288, 12288, 256), (12288, 1024, 256),
                 (12288, 28672, 256), (28672, 12288, 256),
                 (3072, 12288, 24), (12288, 3072, 24),
                 (3072, 12288, 256), (12288, 3072, 256)]


@pytest.mark.parametrize("K,N,M", BENCH_MATMULS,
                         ids=[f"{k}x{n}_m{m}" for k, n, m in BENCH_MATMULS])
def test_quantized_matmul_compiles_at_bench_widths(compile_v5e, on_tpu,
                                                    K, N, M):
    """The row block's codes scratch ([M, K] int8 at up to 28672 lanes)
    and the weight tiles the kernel chooses fit v5e's VMEM."""
    def fn(x, w, a, c):
        return ops.quantized_matmul(x, w, _qscale(a), c, CODEC,
                                    impl="pallas")
    hlo = compile_v5e(fn, ((M, K), f32), ((K, N), i8), ((), f32),
                      ((N,), f32))
    assert "tpu_custom_call" in hlo


def test_quantized_matmul_compiles_on_tp_mesh(compile_v5e, topo, on_tpu):
    """Under a 4-chip TP mesh the kernel must sit in a shard_map: the
    compiler refuses to partition a Mosaic kernel. Each chip computes a
    quarter of the output columns: 512 of 2048 (one 32-row block)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def fn(x, w, a, c):
        return ops.quantized_matmul(x, w, _qscale(a), c, CODEC,
                                    impl="pallas", mesh=mesh)
    hlo = compile_v5e(fn, ((SLOTS, D), f32), ((D, D), i8), ((), f32),
                      ((D,), f32), sharding=NamedSharding(mesh, P()))
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(f"f32[32,{D // 4}]" in line
                           for line in kernels), kernels


def test_sparq_quantize_compiles(compile_v5e, on_tpu):
    def fn(x, a):
        return ops.sparq_quantize(x, _qscale(a), CODEC, impl="pallas")
    assert "tpu_custom_call" in compile_v5e(
        fn, ((SLOTS, CHUNK, KV, HD), f32), ((), f32))


def test_sparq_dequantize_compiles(compile_v5e, on_tpu):
    def fn(s, m):
        return ops.sparq_dequantize(s, m, impl="pallas")
    plane = ((SLOTS, CHUNK, KV, HD), i8)
    assert "tpu_custom_call" in compile_v5e(fn, plane, plane)


@pytest.mark.parametrize("bk", [PS, 128], ids=["page_tile", "default"])
def test_sparq_decode_attention_compiles(compile_v5e, on_tpu, bk):
    Tk = NB * PS
    plane = ((SLOTS, Tk, KV, HD), i8)

    def fn(q, kd, km, ks, vd, vm, vs, kpos, cur):
        return ops.sparq_decode_attention(q, kd, km, ks, vd, vm, vs, kpos,
                                          cur, impl="pallas", bk=bk)
    assert "tpu_custom_call" in compile_v5e(
        fn, ((SLOTS, 1, H, HD), f32), plane, plane, ((), f32), plane,
        plane, ((), f32), ((SLOTS, Tk), i32), ((), i32))


POOL = ((N_PAGES + 1, PS, KV * HD), i8)      # lane-dense, + trash page


@pytest.mark.parametrize("window", [0, 256], ids=["full", "window"])
def test_sparq_paged_decode_attention_compiles(compile_v5e, on_tpu,
                                               window):
    def fn(q, kd, km, ks, vd, vm, vs, bt, cur):
        return ops.sparq_paged_decode_attention(
            q, kd, km, ks, vd, vm, vs, bt, cur, window=window,
            impl="pallas")
    assert "tpu_custom_call" in compile_v5e(
        fn, ((SLOTS, 1, H, HD), f32), POOL, POOL, ((SLOTS,), f32), POOL,
        POOL, ((SLOTS,), f32), ((SLOTS, NB), i32), ((SLOTS,), i32))


@pytest.mark.parametrize("chunk", [32, CHUNK])
def test_sparq_chunked_prefill_attention_compiles(compile_v5e, on_tpu,
                                                  chunk):
    def fn(q, kc, vc, kd, km, ks, vd, vm, vs, bt, sid, pos, hist, ts):
        return ops.sparq_chunked_prefill_attention(
            q, kc, vc, kd, km, ks, vd, vm, vs, bt, sid, pos, hist, ts,
            impl="pallas", bq=ALIGN)
    tok = ((chunk,), i32)
    assert "tpu_custom_call" in compile_v5e(
        fn, ((chunk, H, HD), f32), ((chunk, KV, HD), f32),
        ((chunk, KV, HD), f32), POOL, POOL, ((SLOTS,), f32), POOL, POOL,
        ((SLOTS,), f32), ((SLOTS, NB), i32), tok, tok, tok,
        ((chunk // ALIGN,), i32))


def test_every_hot_dispatcher_is_compiled():
    """A dispatcher added to ops.HOT_DISPATCHERS needs a compile test
    here too."""
    import sys
    here = sys.modules[__name__]
    assert all(hasattr(here, f"test_{name}_compiles")
               for name in ops.HOT_DISPATCHERS)

"""Pallas kernel validation: interpret=True vs pure-jnp oracle, shape/dtype
sweeps, and agreement with the core fake-quant semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import act_scale_from_stats, quantize_weight
from repro.core.sparq import SparqConfig, sparq_fake_quant
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.sparq_dequant import sparq_dequant_pallas
from repro.kernels.sparq_matmul import sparq_matmul_pallas
from repro.kernels.sparq_quant import sparq_quant_pallas

KEY = jax.random.PRNGKey(0)

CONFIGS = [
    SparqConfig.opt5(signed=True),
    SparqConfig.opt3(signed=True, rounding=False),
    SparqConfig.opt2(signed=True),
    SparqConfig.opt6(signed=True),
    SparqConfig.opt7(signed=True, vsparq=False),
    SparqConfig.opt5(signed=False),        # paper's unsigned mode
    SparqConfig.opt3(signed=False, vsparq=False),
    SparqConfig(enabled=False, signed=True),  # plain A8W8
]


def _mk_inputs(m, k, n, signed, dtype=jnp.float32, sparsity=0.3):
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), dtype=jnp.float32)
    if not signed:
        x = jnp.maximum(x, 0.0)
    # inject exact zeros so vSPARQ's pair path is exercised
    mask = jax.random.uniform(jax.random.PRNGKey(2), (m, k)) < sparsity
    x = jnp.where(mask, 0.0, x).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n)) / np.sqrt(k)
    w_codes, wqs = quantize_weight(w, 8)
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))) or 1.0, bits=8,
                              signed=signed)
    return x, w_codes.astype(jnp.int8), qs, wqs.scale


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.name)
def test_matmul_kernel_matches_oracle(cfg):
    m, k, n = 128, 512, 128
    x, w_codes, qs, cscale = _mk_inputs(m, k, n, cfg.signed)
    kw = dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
              vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
              enabled=cfg.enabled)
    got = sparq_matmul_pallas(x, w_codes, jnp.float32(qs.scale), cscale,
                              bm=64, bn=64, bk=128, interpret=True, **kw)
    want = kref.ref_sparq_matmul(x, w_codes, qs.scale, cscale, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(64, 256, 64), (128, 128, 256),
                                   (256, 1024, 32)])
def test_matmul_kernel_shape_sweep(shape):
    m, k, n = shape
    cfg = SparqConfig.opt3(signed=True)
    x, w_codes, qs, cscale = _mk_inputs(m, k, n, True)
    got = sparq_matmul_pallas(
        x, w_codes, jnp.float32(qs.scale), cscale, bm=64, bn=32, bk=128,
        interpret=True, bits=cfg.bits, opts_shifts=cfg.shifts,
        rounding=cfg.rounding, vsparq=cfg.vsparq, signed=True,
        max_val=cfg.max_val, enabled=True)
    want = kref.ref_sparq_matmul(
        x, w_codes, qs.scale, cscale, bits=cfg.bits, opts_shifts=cfg.shifts,
        rounding=cfg.rounding, vsparq=cfg.vsparq, signed=True,
        max_val=cfg.max_val, enabled=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_dtypes(dtype):
    cfg = SparqConfig.opt5(signed=True)
    x, w_codes, qs, cscale = _mk_inputs(64, 128, 64, True, dtype=dtype)
    got = sparq_matmul_pallas(
        x, w_codes, jnp.float32(qs.scale), cscale, bm=64, bn=64, bk=128,
        interpret=True, bits=4, opts_shifts=cfg.shifts, rounding=True,
        vsparq=True, signed=True, max_val=127, enabled=True)
    want = kref.ref_sparq_matmul(
        x.astype(jnp.float32), w_codes, qs.scale, cscale, bits=4,
        opts_shifts=cfg.shifts, rounding=True, vsparq=True, signed=True,
        max_val=127, enabled=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cfg", [SparqConfig.opt5(signed=True),
                                 SparqConfig.opt5(signed=False)],
                         ids=["signed_int8", "unsigned_bf16"])
@pytest.mark.parametrize("m", [24, 32, 200])
def test_matmul_kernel_reuses_row_block_codes(m, cfg):
    """Three column tiles over three K tiles: the first column tile
    writes each row block's codes, the next two read them back. Ragged M
    pads to 32-row blocks (200 rows: seven blocks, each its own codes)."""
    k, n = 768, 384
    x, w_codes, qs, cscale = _mk_inputs(m, k, n, cfg.signed)
    got = ops.quantized_matmul(x, w_codes, qs, cscale, cfg, impl="pallas",
                               block=(32, 128, 256))
    want = kref.ref_sparq_matmul(
        x, w_codes, qs.scale, cscale, bits=cfg.bits, opts_shifts=cfg.shifts,
        rounding=cfg.rounding, vsparq=cfg.vsparq, signed=cfg.signed,
        max_val=cfg.max_val, enabled=cfg.enabled)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# (K, N) of every quantized matmul of the benchmark's two configurations
BENCH_MATMULS = {
    "mistral_wq_wo": (12288, 12288), "mistral_wk_wv": (12288, 1024),
    "mistral_w1_w3": (12288, 28672), "mistral_w2": (28672, 12288),
    "starcoder2_wq_wo": (3072, 3072), "starcoder2_wk_wv": (3072, 256),
    "starcoder2_w1": (3072, 12288), "starcoder2_w2": (12288, 3072),
}


@pytest.mark.parametrize("kn", BENCH_MATMULS.values(), ids=BENCH_MATMULS)
def test_matmul_tiles_fit_the_benchmark_shapes(kn):
    from repro.kernels import sparq_matmul as sm
    K, N = kn
    for m in (24, 32, 256):
        for cfg in (SparqConfig.opt5(signed=True),
                    SparqConfig.opt5(signed=False)):
            bm, bn, bk = sm.choose_tiles(m, K, N, signed=cfg.signed,
                                         max_val=cfg.max_val)
            assert -(-m // 32) * 32 % bm == 0 and bm % 32 == 0
            assert K % bk == 0 and N % bn == 0
            assert bk % 2 == 0
            assert cfg.signed or bk <= 512
            need = sm.vmem_bytes(bm, bn, bk, K, signed=cfg.signed,
                                 max_val=cfg.max_val)
            assert need <= (sm.vmem_limit(need) or 16 << 20) <= 128 << 20
            # decode rows run one row block, not a 128-row pad
            assert bm == (32 if m <= 32 else 256)


def test_wrapper_pads_and_unpads():
    cfg = SparqConfig.opt5(signed=True)
    x = jax.random.normal(KEY, (10, 6, 130))  # ragged everything
    w = jax.random.normal(jax.random.PRNGKey(9), (130, 50)) * 0.1
    w_codes, wqs = quantize_weight(w, 8)
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))), bits=8, signed=True)
    got = ops.quantized_matmul(x, w_codes.astype(jnp.int8), qs, wqs.scale,
                               cfg, impl="pallas", block=(64, 64, 128))
    want = ops.quantized_matmul(x, w_codes.astype(jnp.int8), qs, wqs.scale,
                                cfg, impl="reference")
    assert got.shape == (10, 6, 50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dense_pallas_and_reference_programs_agree_bitwise():
    """Inside a model's program the scales are made next to the matmul
    (`s / qmax`, `max|w| / 127`). The oracle must not let XLA fold those
    constants into its scale chain: the two programs then give the same
    bits, as the kernel reads its scales from memory."""
    from repro.models.common import QuantCtx, dense
    cfg = SparqConfig.opt5(signed=True)
    x = jax.random.normal(KEY, (256, 256))
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 128)) / 16

    def program(impl):
        return jax.jit(lambda w, x, s: dense(w, x, "w", QuantCtx(
            mode="quantized", cfg=cfg, impl=impl, scales={"w": s})))
    s = jnp.max(jnp.abs(x))
    np.testing.assert_array_equal(np.asarray(program("pallas")(w, x, s)),
                                  np.asarray(program("reference")(w, x, s)))


@pytest.mark.parametrize("cfg", [SparqConfig.opt5(signed=True),
                                 SparqConfig.opt3(signed=True),
                                 SparqConfig.opt6(signed=True)],
                         ids=lambda c: c.name)
def test_quant_kernel_matches_oracle(cfg):
    x = jax.random.normal(KEY, (256, 128))
    x = jnp.where(jax.random.uniform(jax.random.PRNGKey(5), x.shape) < 0.4,
                  0.0, x)
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))), bits=8, signed=True)
    kw = dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
              vsparq=cfg.vsparq, signed=True, max_val=127)
    codes_k, meta_k = sparq_quant_pallas(
        x, jnp.float32(qs.scale), bm=128, interpret=True, **kw)
    codes_r, meta_r = kref.ref_sparq_quant(x, qs.scale, **kw)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))
    np.testing.assert_array_equal(np.asarray(meta_k), np.asarray(meta_r))


def test_quant_codes_match_fake_quant():
    """codes * scale == the core fake-quant reconstruction."""
    cfg = SparqConfig.opt5(signed=True)
    x = jax.random.normal(KEY, (128, 64))
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))), bits=8, signed=True)
    codes, _ = ops.sparq_quantize(x, qs, cfg, impl="reference")
    recon = codes.astype(jnp.float32) * qs.scale
    want = sparq_fake_quant(x, qs, cfg)
    np.testing.assert_allclose(np.asarray(recon), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_meta_bits_roundtrip():
    """Meta byte + data nibble reconstructs the trimmed value (storage
    format sanity: decode(q, shift) == codes when not mux'd)."""
    cfg = SparqConfig.opt5(signed=True, rounding=True)
    x = jnp.abs(jax.random.normal(KEY, (64, 32))) + 0.1  # no zeros -> no mux
    qs = act_scale_from_stats(float(jnp.max(x)), bits=8, signed=True)
    codes, meta = ops.sparq_quantize(x, qs, cfg, impl="reference")
    codes = np.asarray(codes, np.int32)
    meta = np.asarray(meta, np.int32)
    s_even, s_odd = (meta >> 3) & 7, meta & 7
    mux = (meta >> 6) & 1
    assert (mux == 0).all()
    shift = np.where(np.arange(32)[None, :] % 2 == 0, s_even, s_odd)
    assert ((np.abs(codes) >> shift) << shift == np.abs(codes)).all()
    assert (np.abs(codes) >> shift < (1 << cfg.bits)).all()


@pytest.mark.parametrize("vsparq", [True, False], ids=["vS", "no-vS"])
@pytest.mark.parametrize("signed", [True, False], ids=["signed", "unsigned"])
def test_meta_byte_unpack_reproduces_codes(vsparq, signed):
    """§5.1 storage round trip straight off the Pallas quant kernel: unpack
    [mux | shift_hi | shift_lo] from the meta byte, window the codes down to
    data nibbles, and reproduce the reconstructed codes exactly."""
    cfg = SparqConfig.opt5(signed=signed, vsparq=vsparq)
    x = jax.random.normal(KEY, (128, 32))
    if not signed:
        x = jnp.abs(x)
    # exact zeros exercise the vSPARQ mux path
    x = jnp.where(jax.random.uniform(jax.random.PRNGKey(7), x.shape) < 0.35,
                  0.0, x)
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))), bits=8,
                              signed=signed)
    codes, meta = sparq_quant_pallas(
        x, jnp.float32(qs.scale), bm=128, interpret=True, bits=cfg.bits,
        opts_shifts=cfg.shifts, rounding=cfg.rounding, vsparq=vsparq,
        signed=signed, max_val=cfg.max_val)
    # unsigned codes occupy the full 8-bit range; the int8 output is a bit
    # reinterpretation, so recover the magnitude via a uint8 view
    codes = np.asarray(codes, np.int8)
    mag = np.abs(codes.astype(np.int32)) if signed \
        else codes.view(np.uint8).astype(np.int32)
    sign = np.sign(codes.astype(np.int32)) if signed else 1
    meta = np.asarray(meta, np.int32)
    mux = (meta >> 6) & 1
    s_even, s_odd = (meta >> 3) & 7, meta & 7
    shift = np.where(np.arange(32)[None, :] % 2 == 0, s_even, s_odd)
    nibble = mag >> shift                         # the stored data field
    # decode: nibble << shift with the sign restored == reconstructed codes
    np.testing.assert_array_equal(
        (sign * (nibble << shift)).astype(np.int8), codes)
    # non-mux'd lanes fit the n-bit window; mux is only raised by vSPARQ
    assert (nibble[mux == 0] < (1 << cfg.bits)).all()
    if not vsparq:
        assert (mux == 0).all()


@pytest.mark.parametrize("cfg", [SparqConfig.opt5(signed=True),
                                 SparqConfig.opt3(signed=True,
                                                  rounding=False),
                                 SparqConfig.opt6(signed=True, vsparq=False),
                                 SparqConfig.opt5(signed=False)],
                         ids=lambda c: c.name)
def test_dequant_kernel_matches_ref(cfg):
    """sparq_dequant_pallas (interpret) is bit-exact against
    ref_sparq_dequant, and both invert sparq_pack back to the codes."""
    x = jax.random.normal(KEY, (256, 64))
    if not cfg.signed:
        x = jnp.abs(x)
    x = jnp.where(jax.random.uniform(jax.random.PRNGKey(3), x.shape) < 0.3,
                  0.0, x)
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))), bits=8,
                              signed=cfg.signed)
    codes, meta = ops.sparq_quantize(x, qs, cfg, impl="reference")
    store = ops.sparq_pack(codes, meta)
    want = kref.ref_sparq_dequant(store, meta)
    got = sparq_dequant_pallas(store, meta, bm=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(codes))


def test_dequant_wrapper_pads_and_unpads():
    cfg = SparqConfig.opt5(signed=True)
    x = jax.random.normal(KEY, (5, 7, 10))        # ragged rows
    qs = act_scale_from_stats(float(jnp.max(jnp.abs(x))), bits=8,
                              signed=True)
    codes, meta = ops.sparq_quantize(x, qs, cfg, impl="reference")
    store = ops.sparq_pack(codes, meta)
    got = ops.sparq_dequantize(store, meta, impl="pallas", bm=64)
    assert got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(codes))


# ----------------------------------------------------------------------
# fused packed-cache decode attention (§5.1 meta-decode inside the kernel)
# ----------------------------------------------------------------------

def _mk_cache_planes(cfg, B=2, Tmax=24, KV=2, hd=16, pos=13, seed=0):
    """Quantize random K/V up to `pos` into packed (data, meta, scale)
    planes via the CachedTensor write path; slots >= pos stay zeroed."""
    from repro.models.cache import CacheConfig, CacheStore
    cc = CacheConfig(layout="sparq", sparq=cfg)
    st = CacheStore.init((B, Tmax, KV, hd), cc)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(k1, (B, pos, KV, hd))
    v = jax.random.normal(k2, (B, pos, KV, hd))
    if not cfg.signed:
        k, v = jnp.abs(k), jnp.abs(v)
    st = st.update(k, v)
    q = jax.random.normal(k3, (B, 1, 2 * KV, hd))  # H=2*KV -> GQA groups
    return q, st


DECODE_CODECS = [
    SparqConfig.opt5(signed=True),                    # vsparq + signed
    SparqConfig.opt5(signed=True, vsparq=False),      # no vsparq
    SparqConfig.opt6(signed=True),                    # 3-bit window
    # unsigned magnitudes at act_bits=7 so codes (<=127) still fit int8
    SparqConfig.opt5(signed=False, act_bits=7),
    SparqConfig.opt5(signed=False, vsparq=False, act_bits=7),
    SparqConfig(enabled=False, signed=True),          # lossless int8 grid
]


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("cfg", DECODE_CODECS, ids=lambda c: c.name)
def test_decode_attn_ref_vs_pallas_vs_dequant_oracle(cfg, window):
    """Bit-exactness of the fused decode path: the tiled jnp oracle
    (ref_sparq_decode_attn) and the Pallas kernel (interpret mode) agree
    bit for bit, and both match the dequantize-then-attend oracle
    (decode_attention_dequant) to f32 rounding."""
    from repro.models.attention import decode_attention_dequant
    B, Tmax = 2, 24
    pos = 13                                          # non-multiple of bk
    q, st = _mk_cache_planes(cfg, B=B, Tmax=Tmax, pos=pos)
    kpos = jnp.broadcast_to(jnp.arange(Tmax, dtype=jnp.int32)[None],
                            (B, Tmax))
    args = (q, st.k.data, st.k.meta, st.k.scale,
            st.v.data, st.v.meta, st.v.scale, kpos, st.pos - 1)
    ref = ops.sparq_decode_attention(*args, window=window,
                                     impl="reference", bk=8)
    pal = ops.sparq_decode_attention(*args, window=window,
                                     impl="pallas", bk=8)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    oracle = decode_attention_dequant(q, st, window=window)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [1, 7, 16, 23])
def test_decode_attn_ragged_pos_and_tiles(pos):
    """Length masking from `pos` across tile boundaries: every fill level
    (including tile-straddling and full cache) matches the oracle, with a
    tile size that does NOT divide Tmax (dispatcher pads with kpos=-1)."""
    from repro.models.attention import decode_attention_dequant
    cfg = SparqConfig.opt5(signed=True)
    B, Tmax = 2, 24
    q, st = _mk_cache_planes(cfg, B=B, Tmax=Tmax, pos=pos)
    kpos = jnp.broadcast_to(jnp.arange(Tmax, dtype=jnp.int32)[None],
                            (B, Tmax))
    args = (q, st.k.data, st.k.meta, st.k.scale,
            st.v.data, st.v.meta, st.v.scale, kpos, st.pos - 1)
    ref = ops.sparq_decode_attention(*args, impl="reference", bk=7)
    pal = ops.sparq_decode_attention(*args, impl="pallas", bk=7)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    oracle = decode_attention_dequant(q, st)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


def test_decode_attn_ring_slot_positions():
    """The windowed variant with ring-ordered slot positions (kpos is the
    rotated slot_pos array, not arange) masks by absolute position."""
    from repro.models.cache import CacheConfig, CacheStore
    cfg = SparqConfig(enabled=False, signed=True)     # exact grid
    B, W, KV, hd = 2, 8, 2, 16
    window = 6
    cc = CacheConfig(layout="sparq", sparq=cfg)
    st = CacheStore.init((B, W, KV, hd), cc)
    kv = jax.random.normal(KEY, (B, W, KV, hd))
    st = st.update(kv, kv)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, 1, 2 * KV, hd))
    # ring state: slots hold absolute positions 8..15 rotated by 3
    slot_pos = jnp.broadcast_to(
        jnp.roll(jnp.arange(8, 16, dtype=jnp.int32), 3)[None], (B, W))
    cur = jnp.asarray(15, jnp.int32)
    out = ops.sparq_decode_attention(
        q, st.k.data, st.k.meta, st.k.scale,
        st.v.data, st.v.meta, st.v.scale, slot_pos, cur,
        window=window, impl="pallas", bk=4)
    # oracle: dense attention over the dequantized ring with the same mask
    kf = st.k.read()
    ok = (slot_pos <= cur) & (slot_pos > cur - window)
    G = 2
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, kf) * hd ** -0.5
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bkgs,bskh->bkgh", p, st.v.read()).reshape(
        B, 1, 2 * KV, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

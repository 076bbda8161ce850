"""Pure-jnp oracles for the Pallas kernels (single source of truth is
repro.core; these wrappers match the kernels' exact signatures/dtypes)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bsparq import bsparq_encode
from repro.core.sparq import SparqConfig, sparq_recon_int


def _as_read(*scales):
    """The oracle's scale operands as given. A kernel reads its scales
    from memory; an oracle fused with their producers (`s / qmax`,
    `max|w| / 127`) lets XLA fold those constants into its own
    arithmetic, which moves some outputs by an ulp."""
    return jax.lax.optimization_barrier(scales)


def _cfg(bits, shifts, rounding, vsparq, signed, max_val, enabled=True):
    opts = len(shifts)
    return SparqConfig(bits=bits, opts=opts, rounding=rounding, vsparq=vsparq,
                       signed=signed, enabled=enabled,
                       act_bits=8)


def ref_sparq_matmul(x, w_codes, act_scale, chan_scale, *, bits=4,
                     opts_shifts=(0, 1, 2, 3, 4), rounding=True, vsparq=True,
                     signed=False, max_val=255, enabled=True):
    """Oracle for sparq_matmul_pallas: float x, int8 weight codes."""
    act_scale, chan_scale = _as_read(act_scale, chan_scale)
    qmin = -max_val if signed else 0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / act_scale), qmin, max_val)
    q = q.astype(jnp.int32)
    cfg = _cfg(bits, opts_shifts, rounding, vsparq, signed, max_val, enabled)
    r = sparq_recon_int(q, cfg) if enabled else q
    if signed and max_val <= 127:
        # native int8 x int8 -> int32 dot (the v5e MXU path). Keeping both
        # operands int8 also keeps the FSDP weight all-gather at 1 byte —
        # int32 operands made GSPMD gather 4x the bytes (§Perf iteration 4).
        acc = jax.lax.dot_general(
            r.astype(jnp.int8), w_codes.astype(jnp.int8),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:
        acc = jax.lax.dot_general(  # exact int32 accumulation (unsigned)
            r, w_codes.astype(jnp.int32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    # one f32 scale per column, then one rounding per output: the
    # kernel's order (XLA regroups (acc * a) * c into it on some backends)
    return acc.astype(jnp.float32) * (act_scale * chan_scale)[None, :]


def ref_sparq_quant(x, act_scale, *, bits=4, opts_shifts=(0, 1, 2, 3, 4),
                    rounding=True, vsparq=True, signed=True, max_val=127,
                    enabled=True):
    """Oracle for sparq_quant_pallas: returns (codes int8, meta int8)."""
    (act_scale,) = _as_read(act_scale)
    qmin = -max_val if signed else 0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / act_scale), qmin, max_val)
    q = q.astype(jnp.int32)
    if not enabled:
        # plain int8 PTQ (paper baseline): full codes, empty meta
        return q.astype(jnp.int8), jnp.zeros_like(q, dtype=jnp.int8)
    sign = jnp.sign(q)
    mag = jnp.abs(q)
    qq, ss = bsparq_encode(mag, bits, opts_shifts, rounding, max_val)
    trimmed = jnp.left_shift(qq, ss)
    if vsparq:
        pairs = mag.reshape(*mag.shape[:-1], -1, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        partner = jnp.stack([b, a], axis=-1).reshape(mag.shape)
        full = partner == 0
        recon = jnp.where(full, mag, trimmed)
        shift_code = jnp.where(full, 0, ss)
        mux = full
    else:
        recon = trimmed
        shift_code = ss
        mux = jnp.zeros_like(mag, dtype=jnp.bool_)
    codes = (sign * recon).astype(jnp.int8)
    mux_i = mux.astype(jnp.int32).reshape(*mag.shape[:-1], -1, 2)
    s_pair = shift_code.reshape(*mag.shape[:-1], -1, 2)
    mux_any = jnp.minimum(mux_i[..., 0] + mux_i[..., 1], 1)
    meta_pair = mux_any * 64 + s_pair[..., 0] * 8 + s_pair[..., 1]
    meta = jnp.repeat(meta_pair, 2, axis=-1).astype(jnp.int8)
    return codes, meta


def meta_shifts(meta: jnp.ndarray) -> jnp.ndarray:
    """Per-lane ShiftCtrl from the packed per-pair meta byte (§5.1):
    [mux(1) | shift_even(3) | shift_odd(3)], mirrored to both lanes."""
    m = meta.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, m.ndim - 1)
    return jnp.where(lane % 2 == 0, jnp.right_shift(m, 3) & 7, m & 7)


def ref_sparq_dequant(store: jnp.ndarray, meta: jnp.ndarray) -> jnp.ndarray:
    """Oracle for sparq_dequant_pallas: int8 window codes + packed meta ->
    int8 SPARQ-reconstructed codes (codes[i] = sign * (|store[i]| << s_i))."""
    q = store.astype(jnp.int32)
    shift = meta_shifts(meta)
    return (jnp.sign(q) * jnp.left_shift(jnp.abs(q), shift)).astype(jnp.int8)


def _meta_decode32(store, meta, scale):
    """§5.1 meta-decode of one packed tile, in int32 (no int8 narrowing) —
    the exact datapath of the fused decode kernels."""
    q32 = store.astype(jnp.int32)
    shift = meta_shifts(meta)
    recon = jnp.sign(q32) * jnp.left_shift(jnp.abs(q32), shift)
    return recon.astype(jnp.float32) * scale


#: the attention oracles' f32 contractions at full f32 precision (the
#: kernels' dots are f32 too): a no-op on CPU, and on TPU it keeps XLA
#: from running them as single bf16 passes, which would make the oracle
#: a coarser computation than the kernel it checks
_F32 = jax.lax.Precision.HIGHEST


def chunk_key_tile(C: int) -> int:
    """Keys per flash update in the chunked-prefill in-chunk stage: 128
    (one MXU pass per contraction) when it divides the chunk, else the
    whole chunk."""
    return 128 if C % 128 == 0 else C


def row_sum(p: jnp.ndarray) -> jnp.ndarray:
    """Sum of p over its last axis, keepdims, as a 2-D MXU contraction
    with ones. The kernels and the oracles both take it here: on a TPU a
    lane reduction sums in an order that Mosaic and XLA choose
    differently, while a dot over at most 128 keys (one MXU pass) sums
    alike in both."""
    keys = p.shape[-1]
    ones = jnp.ones((keys, 128), jnp.float32)
    s = jax.lax.dot_general(
        p.reshape(-1, keys), ones, (((1,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)
    return s[:, :1].reshape(*p.shape[:-1], 1)


def _heads(n_kv: int, plane):
    """Lane-dense [..., KV*hd] plane (gathered pages) -> [..., KV, hd]."""
    return plane.reshape(*plane.shape[:-1], n_kv, plane.shape[-1] // n_kv)


def ref_sparq_decode_attn(q, k_data, k_meta, k_scale, v_data, v_meta,
                          v_scale, kpos, cur, *, window: int = 0,
                          bk: int = 128):
    """Tiled oracle for sparq_decode_attn_pallas: same Tk-tile loop, same
    per-tile meta-decode + online-softmax update order, expressed in jnp
    with a lax.scan over tiles — so it never materializes the dequantized
    K/V planes either, and (running the identical op sequence) matches the
    interpret-mode kernel bit for bit.

    q [B,KV,G,hd] float; k/v planes [B,Tk,KV,hd] int8; kpos [B,Tk] int32
    slot positions (-1 = empty); cur scalar int32. Returns f32 [B,KV,G,hd].
    """
    k_scale, v_scale = _as_read(k_scale, v_scale)
    B, KV, G, hd = q.shape
    Tk = k_data.shape[1]
    assert Tk % bk == 0, (Tk, bk)
    qf = q.astype(jnp.float32)
    sm_scale = hd ** -0.5

    _decode = _meta_decode32

    def tile(carry, t):
        m, l, acc = carry
        kd = jax.lax.dynamic_slice_in_dim(k_data, t * bk, bk, 1)
        km = jax.lax.dynamic_slice_in_dim(k_meta, t * bk, bk, 1)
        vd = jax.lax.dynamic_slice_in_dim(v_data, t * bk, bk, 1)
        vm = jax.lax.dynamic_slice_in_dim(v_meta, t * bk, bk, 1)
        kp = jax.lax.dynamic_slice_in_dim(kpos, t * bk, bk, 1)  # [B, bk]
        k = _decode(kd, km, k_scale)                   # [B, bk, KV, hd]
        s = jnp.einsum("bkgh,bskh->bkgs", qf, k,
                       preferred_element_type=jnp.float32,
                       precision=_F32) * sm_scale
        ok = (kp >= 0) & (kp <= cur)
        if window:
            ok &= kp > cur - window
        okb = ok[:, None, None, :]                     # [B, 1, 1, bk]
        s = jnp.where(okb, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(okb, p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + row_sum(p)
        v = _decode(vd, vm, v_scale)
        pv = jnp.einsum("bkgs,bskh->bkgh", p, v,
                        preferred_element_type=jnp.float32, precision=_F32)
        return (m_new, l_new, acc * corr + pv), None

    m0 = jnp.full((B, KV, G, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, 1), jnp.float32)
    a0 = jnp.zeros((B, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(tile, (m0, l0, a0), jnp.arange(Tk // bk))
    return acc / jnp.maximum(l, 1e-30)


def ref_sparq_chunked_prefill_attn(q, k_chunk, v_chunk, k_data, k_meta,
                                   k_scale, v_data, v_meta, v_scale,
                                   block_table, seq_id, pos, hist,
                                   tile_seq, *, window: int = 0):
    """Tiled oracle for sparq_chunked_prefill_attn_pallas: ragged chunked
    prefill over a packed token stream.

    One fixed-shape chunk of C prompt tokens (possibly from several
    sequences, possibly only a slice of a long prompt) attends to

      1. its own sequence's *already-written* §5.1 packed pages — every
         position below the token's history boundary `hist` — gathered
         through the per-slot block table and meta-decoded tile by tile
         (one page == one Tk tile, same `_meta_decode32` datapath as the
         decode kernels), and
      2. the float K/V of its own history window [hist, pos]: causal
         attention over the chunk, segment-masked by per-token sequence
         id AND bounded below by `hist`.

    `hist` is per token: the scheduler sets it to the token's *segment*
    start ((pos // seg) * seg), and packs whole segments only — so a
    prompt's float-vs-packed attention split depends only on the prompt
    and the segment quantum, never on how chunks happened to be packed
    (this is what keeps chunked prefill deterministic per request and
    requeue-replay bit-exact). Tokens in [hist, pos) are guaranteed to be
    in the same chunk; positions below hist are guaranteed already
    written (possibly by this very chunk program — writes precede reads).

    Page tiles run first (ascending kpos), the in-chunk stage last; the
    pallas kernel walks the identical stage order with the identical f32
    update arithmetic (interpret-mode agreement is exact for the in-chunk
    stage and within a couple of f32 ulps over page tiles, where XLA's
    fusion of this scanned oracle reorders the multiply-add chain).

    q           [C, KV, G, hd] float — chunk queries, GQA via grouping
    k/v_chunk   [C, KV, hd] float — the chunk's own (pre-quantization) K/V
    k/v planes  [P, ps, KV*hd] int8 — the global §5.1 page pools
                (lane-dense: KV and hd flattened into one axis)
    k/v scale   [S] f32 — per-slot site scales (frozen at first write)
    block_table [S, NB] int32 — physical page per logical block (-1 unset)
    seq_id      [C] int32 — sequence slot per stream token (-1 = padding)
    pos         [C] int32 — absolute position of each token in its prompt
    hist        [C] int32 — per-token history boundary: packed pages for
                kpos < hist, float in-chunk keys for kpos in [hist, pos]
    tile_seq    [C/bq] int32 — slot owning each aligned query tile (-1 =
                padding tile); the stream packs each sequence's run
                aligned to bq so one tile gathers one block-table row
    Returns f32 [C, KV, G, hd]; fully-masked (padding) rows are zeros.
    """
    k_scale, v_scale = _as_read(k_scale, v_scale)
    C, KV, G, hd = q.shape
    ps = k_data.shape[1]
    NB = block_table.shape[1]
    nt = tile_seq.shape[0]
    assert C % nt == 0, (C, nt)
    bq = C // nt
    qf = q.astype(jnp.float32)
    sm_scale = hd ** -0.5
    tseq = jnp.repeat(jnp.asarray(tile_seq, jnp.int32), bq)        # [C]
    s_safe = jnp.maximum(tseq, 0)
    ksc = jnp.asarray(k_scale, jnp.float32)[s_safe]                # [C]
    vsc = jnp.asarray(v_scale, jnp.float32)[s_safe]
    qhist = jnp.asarray(hist, jnp.int32)                           # [C]
    sid = jnp.asarray(seq_id, jnp.int32)
    qpos = jnp.asarray(pos, jnp.int32)
    qvalid = sid >= 0

    def upd(m, l, s, ok):
        """Shared online-softmax statistics update. Returns the new
        (m, l), the correction factor for the running accumulator, and
        the masked probabilities p (the caller contracts p @ V — the two
        stages gather V with different shapes)."""
        okb = ok[:, None, None, :]                 # [C, 1, 1, keys]
        s = jnp.where(okb, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(okb, p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + row_sum(p)
        return m_new, l_new, corr, p

    def tile(carry, t):
        m, l, acc = carry
        pages = block_table[s_safe, t]             # [C]
        pg = jnp.maximum(pages, 0)
        k = _meta_decode32(_heads(KV, k_data[pg]), _heads(KV, k_meta[pg]),
                           ksc[:, None, None, None])   # [C, ps, KV, hd]
        s = jnp.einsum("ckgh,cskh->ckgs", qf, k,
                       preferred_element_type=jnp.float32,
                       precision=_F32) * sm_scale
        kp = t * ps + jnp.arange(ps, dtype=jnp.int32)[None]    # [1, ps]
        ok = (pages >= 0)[:, None] & qvalid[:, None] & (kp < qhist[:, None])
        if window:
            ok &= kp > qpos[:, None] - window
        m, l, corr, p = upd(m, l, s, ok)
        v = _meta_decode32(_heads(KV, v_data[pg]), _heads(KV, v_meta[pg]),
                           vsc[:, None, None, None])
        pv = jnp.einsum("ckgs,cskh->ckgh", p, v,
                        preferred_element_type=jnp.float32, precision=_F32)
        return (m, l, acc * corr + pv), None

    m0 = jnp.full((C, KV, G, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((C, KV, G, 1), jnp.float32)
    a0 = jnp.zeros((C, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(tile, (m0, l0, a0), jnp.arange(NB))

    # in-chunk causal stage: float K/V, segment mask by sequence id, in
    # key tiles of chunk_key_tile(C), each one flash update. The
    # contractions run per head as 2-D dots of the kernel's shapes
    # ([C*G, hd] x [kt, hd]^T, then [C*G, kt] x [kt, hd]): XLA:CPU sums a
    # 2-D f32 dot in a blocked order but a batched dot_general as one
    # sequential FMA chain, so only matching dot shapes keeps this
    # stage bit-identical to the interpret-mode kernel; on a TPU a tile
    # of at most 128 keys is one MXU pass, which Mosaic and XLA sum
    # alike. This copies the kernel's summation structure, so the oracle
    # is not an independent formulation here: test_prefill's dense float
    # oracle is the independent witness. It is also why flash-attention
    # prefill (the batched form) and the chunk path differ by an ulp on
    # XLA:CPU, which keeps test_write_chunk_bytes_match_adopt_prefill red.
    kcf = k_chunk.astype(jnp.float32)
    vcf = v_chunk.astype(jnp.float32)
    dot = functools.partial(jax.lax.dot_general, precision=_F32,
                            preferred_element_type=jnp.float32)
    per_head = lambda f: jnp.stack([f(h) for h in range(KV)], axis=1)
    ok = (sid[None, :] == sid[:, None]) & qvalid[:, None] \
        & (qpos[None, :] <= qpos[:, None]) \
        & (qpos[None, :] >= qhist[:, None])
    if window:
        ok &= qpos[None, :] > qpos[:, None] - window
    kt = chunk_key_tile(C)
    for j in range(0, C, kt):
        keys = slice(j, j + kt)
        s = per_head(lambda h: dot(
            qf[:, h].reshape(C * G, hd), kcf[keys, h],
            (((1,), (1,)), ((), ()))).reshape(C, G, kt)) * sm_scale
        m, l, corr, p = upd(m, l, s, ok[:, keys])
        pv = per_head(lambda h: dot(
            p[:, h].reshape(C * G, kt), vcf[keys, h],
            (((1,), (0,)), ((), ()))).reshape(C, G, hd))
        acc = acc * corr + pv
    return acc / jnp.maximum(l, 1e-30)


def ref_sparq_paged_decode_attn(q, k_data, k_meta, k_scale, v_data, v_meta,
                                v_scale, block_table, cur, *,
                                window: int = 0):
    """Tiled oracle for sparq_paged_decode_attn_pallas: the block-table
    gather path over a global page pool. One Tk tile == one fixed-size page,
    fetched through the per-sequence block table; everything else (per-tile
    §5.1 meta-decode, online-softmax update order, masking arithmetic) is
    the contiguous oracle's, so with page_size == bk and identical packed
    bytes the two paths agree bit for bit.

    q           [B, KV, G, hd] float — one query token per sequence
    k/v planes  [P, ps, KV*hd] int8 — the global lane-dense page pool (any
                page the block table never names, e.g. a trash page, is
                simply dead)
    k/v scale   [B] f32 — per-sequence site scales
    block_table [B, NB] int32 — physical page per logical block (-1 = not
                allocated; masked out, gather index clamped to 0)
    cur         [B] int32 — per-sequence position of the decoded token
                (-1/-2 = inactive slot: fully masked, output 0)
    Returns f32 [B, KV, G, hd].
    """
    k_scale, v_scale = _as_read(k_scale, v_scale)
    B, KV, G, hd = q.shape
    ps = k_data.shape[1]
    NB = block_table.shape[1]
    qf = q.astype(jnp.float32)
    sm_scale = hd ** -0.5
    k_scale = jnp.asarray(k_scale, jnp.float32).reshape(B, 1, 1, 1)
    v_scale = jnp.asarray(v_scale, jnp.float32).reshape(B, 1, 1, 1)
    cur_b = jnp.asarray(cur, jnp.int32).reshape(B, 1)

    def tile(carry, t):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(block_table, t, 1, 1)[:, 0]
        safe = jnp.maximum(pages, 0)                   # [B]
        k = _meta_decode32(_heads(KV, k_data[safe]),
                           _heads(KV, k_meta[safe]), k_scale)
        s = jnp.einsum("bkgh,bskh->bkgs", qf, k,
                       preferred_element_type=jnp.float32,
                       precision=_F32) * sm_scale
        kp = t * ps + jnp.arange(ps, dtype=jnp.int32)[None]    # [1, ps]
        ok = (pages >= 0)[:, None] & (kp <= cur_b)
        if window:
            ok &= kp > cur_b - window
        okb = ok[:, None, None, :]                     # [B, 1, 1, ps]
        s = jnp.where(okb, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(okb, p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + row_sum(p)
        v = _meta_decode32(_heads(KV, v_data[safe]),
                           _heads(KV, v_meta[safe]), v_scale)
        pv = jnp.einsum("bkgs,bskh->bkgh", p, v,
                        preferred_element_type=jnp.float32, precision=_F32)
        return (m_new, l_new, acc * corr + pv), None

    m0 = jnp.full((B, KV, G, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, 1), jnp.float32)
    a0 = jnp.zeros((B, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(tile, (m0, l0, a0), jnp.arange(NB))
    return acc / jnp.maximum(l, 1e-30)

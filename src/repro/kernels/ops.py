"""Public jit'd wrappers around the SPARQ kernels.

`quantized_matmul` is what the model layers call;
`sparq_decode_attention` / `sparq_paged_decode_attention` are the fused
packed-cache decode reads (contiguous planes vs block-table-gathered
pages). Dispatch everywhere:
  impl="pallas"     — the fused TPU kernel (interpret=True off-TPU);
  impl="reference"  — pure-jnp oracle semantics via an int dot_general
                      (what the XLA int8 MXU path lowers to on TPU);
  impl="auto"       — pallas on TPU backends, reference elsewhere.

Handles padding to tile multiples (K is padded in whole pairs so vSPARQ
decisions are unchanged; M/N zero-padding is dropped from the result).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quantizer import QScale
from repro.core.sparq import SparqConfig
from repro.kernels import ref as _ref
from repro.kernels.sparq_decode_attn import (sparq_decode_attn_pallas,
                                             sparq_paged_decode_attn_pallas)
from repro.kernels.sparq_dequant import sparq_dequant_pallas
from repro.kernels.sparq_prefill_attn import sparq_chunked_prefill_attn_pallas
from repro.kernels.sparq_matmul import choose_tiles, sparq_matmul_pallas
from repro.kernels.sparq_quant import row_block, sparq_quant_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# default Tk-tile size of the fused decode-attention kernels; callers pass
# bk=None to defer here (CachedTensor.bk overrides per cache config)
DEFAULT_BK = 128

# ----------------------------------------------------------------------
# repro.analysis registration: which code is *allowed* to turn packed
# §5.1 planes back into floats, and which dispatchers the jaxpr auditor
# traces as standalone hot programs.
# ----------------------------------------------------------------------

#: source-path fragments whose int->float conversions are the blessed
#: meta-decode. Everything under repro/kernels/ qualifies: the fused
#: pallas kernels decode tile-by-tile in-loop, and the ref.py oracles
#: are their bit-exact jnp counterparts. A float cast of a packed plane
#: anywhere else is a whole-plane dequantize the format exists to avoid
#: (analysis check JX102).
META_DECODE_SOURCES = ("repro/kernels/",)

#: public dispatcher names the analysis registry audits as hot programs
#: (each is traced abstractly with engine-shaped packed planes).
HOT_DISPATCHERS = (
    "quantized_matmul",
    "sparq_quantize",
    "sparq_dequantize",
    "sparq_decode_attention",
    "sparq_chunked_prefill_attention",
    "sparq_paged_decode_attention",
)


# ----------------------------------------------------------------------
# tensor parallelism. The attention dispatchers shard along the KV-head
# axis of the packed planes (GQA head order is KV-major, so H splits at
# head-group boundaries whenever KV does): each mesh "model" shard holds
# KV/tp head groups of every page and computes its heads' attention
# locally — per-head flash accumulation never crosses heads, so shard
# outputs are bit-identical to the same head slice of the TP=1 program.
# Collectives happen only outside, at the QKV/output projections (the
# caller re-replicates before the wo matmul; see models/attention.py).
# ----------------------------------------------------------------------

TP_AXIS = "model"


def tp_size(mesh: Optional[Mesh]) -> int:
    """Model-parallel degree of `mesh` (1 = no tensor parallelism)."""
    if mesh is None or TP_AXIS not in mesh.axis_names:
        return 1
    return mesh.shape[TP_AXIS]


def _tp_guard(kv_heads: int, tp: int) -> None:
    assert kv_heads % tp == 0, (
        f"{kv_heads} KV heads do not split over tp={tp}: a head group "
        f"(one KV head + its G query heads) never splits")


def _pad_to(x: jnp.ndarray, mult: int, axis: int,
            value: float = 0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ----------------------------------------------------------------------
# §5.1 footprint accounting — the single source of truth. models/cache.py
# delegates here, so the roofline (combined figure) and the cache reports
# (data plane vs ShiftCtrl side-band) can never drift apart.
# ----------------------------------------------------------------------

def data_bytes_per_value(cfg: SparqConfig) -> float:
    """Data-plane HBM residency: n data bits per value + 1 MuxCtrl bit per
    vSPARQ pair. Plain int8 (trimming disabled) is one full byte."""
    if not cfg.enabled:
        return 1.0
    mux = 0.5 if cfg.vsparq else 0.0
    return (cfg.bits + mux) / 8.0


def ctrl_bytes_per_value(cfg: SparqConfig) -> float:
    """ShiftCtrl side-band residency: 3 bits per value when trimming."""
    return 3.0 / 8.0 if cfg.enabled else 0.0


def bytes_per_value(cfg: SparqConfig) -> float:
    """Combined HBM residency of the packed SPARQ format (paper §5.1):
    n data bits + 3-bit ShiftCtrl per value + 1 MuxCtrl bit per vSPARQ
    pair (charged only when vSPARQ is on). Used by the roofline."""
    return data_bytes_per_value(cfg) + ctrl_bytes_per_value(cfg)


def quantized_matmul(
    x: jnp.ndarray,            # (..., K) float activations
    w_codes: jnp.ndarray,      # (K, N) int8 weight codes
    act_qs: QScale,
    chan_scale: jnp.ndarray,   # (N,) f32
    cfg: SparqConfig,
    impl: str = "auto",
    block: Optional[tuple[int, int, int]] = None,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """SPARQ-quantized x @ dequant(w). Leading dims of x are flattened.

    With a tensor-parallel `mesh`, the Pallas kernel runs under a
    shard_map (the compiler cannot partition a Mosaic kernel): each
    device computes its N/tp output columns from the whole x and its
    columns of w, then all-gathers them. A column's K-summation does not
    depend on the other columns, so the result is bit-identical to TP=1.
    The result leaves replicated: a column-sharded output would let
    GSPMD split later reductions over N (an RMSNorm's sum) into partial
    sums, which changes their order. When tp does not divide N every
    device computes the whole product.

    `block` is the kernel's (bm, bn, bk); None chooses them from
    (M, K, N) and the codec (`sparq_matmul.choose_tiles`)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    tp = tp_size(mesh)
    if impl == "pallas" and tp > 1:
        split = w_codes.shape[1] % tp == 0

        def body(x, w_codes, scale, chan_scale):
            qs = QScale(scale=scale, bits=act_qs.bits, signed=act_qs.signed)
            out = quantized_matmul(x, w_codes, qs, chan_scale, cfg,
                                   impl=impl, block=block)
            return jax.lax.all_gather(out, TP_AXIS, axis=out.ndim - 1,
                                      tiled=True) if split else out
        col = TP_AXIS if split else None
        return jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(None, col), P(), P(col)),
            out_specs=P(), check_vma=False)(
            x, w_codes, jnp.asarray(act_qs.scale), chan_scale)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_codes.shape[1]
    assert K % 2 == 0, "vSPARQ pairs adjacent K lanes; K must be even"
    x2 = x.reshape(-1, K)
    kw = dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
              vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
              enabled=cfg.enabled)
    if impl == "reference":
        out = _ref.ref_sparq_matmul(x2, w_codes, act_qs.scale, chan_scale, **kw)
    elif impl == "pallas":
        M = x2.shape[0]
        bm, bn, bk = block or choose_tiles(M, K, N, signed=cfg.signed,
                                           max_val=cfg.max_val)
        xp = _pad_to(_pad_to(x2, bm, 0), bk, 1)
        wp = _pad_to(_pad_to(w_codes, bk, 0), bn, 1)
        cp = _pad_to(chan_scale, bn, 0)
        out = sparq_matmul_pallas(
            xp, wp, jnp.asarray(act_qs.scale, jnp.float32), cp,
            bm=bm, bn=bn, bk=bk, interpret=not _on_tpu(), **kw)
        out = out[:M, :N]
    else:
        raise ValueError(impl)
    return out.reshape(*lead, N)


def sparq_quantize(
    x: jnp.ndarray,           # (..., K) float
    act_qs: QScale,
    cfg: SparqConfig,
    impl: str = "auto",
    bm: Optional[int] = None,
):
    """Standalone SPARQ quantization (KV-cache write path).

    Args:
      x:      float (..., K); the last axis is the vSPARQ pairing axis
              (K even).
      act_qs: QScale whose f32 `scale` is the quantization step (already
              resolved/frozen by the cache — see CachedTensor).
      cfg:    codec; `cfg.enabled=False` is plain int8 (empty meta).
    Returns (codes int8, meta int8), both with x's shape. `codes` are the
    *reconstructed* values (window << shift, sign applied) ready for an
    int matmul; `sparq_pack` shifts them down to the §5.1 stored form."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    kw = dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
              vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
              enabled=cfg.enabled)
    if impl == "reference":
        codes, meta = _ref.ref_sparq_quant(x2, act_qs.scale, **kw)
    else:
        M = x2.shape[0]
        bm = bm or row_block(K)
        xp = _pad_to(x2, bm, 0)
        codes, meta = sparq_quant_pallas(
            xp, jnp.asarray(act_qs.scale, jnp.float32),
            bm=bm, interpret=not _on_tpu(), **kw)
        codes, meta = codes[:M], meta[:M]
    return codes.reshape(*lead, K), meta.reshape(*lead, K)


def sparq_pack(codes: jnp.ndarray, meta: jnp.ndarray) -> jnp.ndarray:
    """Reconstructed int8 codes -> stored window codes (§5.1 data nibbles).

    Inverse of the decode path: |codes| >> shift is the n-bit window value
    (or the full magnitude on mux'd lanes, whose shift is 0). Exact because
    codes were built as (window << shift). Pure jnp — runs at cache-write
    time right after `sparq_quantize`.
    """
    q = codes.astype(jnp.int32)
    shift = _ref.meta_shifts(meta)
    return (jnp.sign(q) * jnp.right_shift(jnp.abs(q), shift)).astype(jnp.int8)


def sparq_dequantize(
    store: jnp.ndarray,       # (..., K) int8 window codes
    meta: jnp.ndarray,        # (..., K) int8 packed meta bytes
    impl: str = "auto",
    bm: Optional[int] = None,
) -> jnp.ndarray:
    """Meta-decode (KV-cache read fallback): (store, meta) -> int8 codes.

    store/meta: int8 (..., K) §5.1 planes (see docs/packed_format.md).
    Returns the reconstructed int8 codes (sign * (|store| << ShiftCtrl));
    multiply by the plane's scale for floats. The decode *hot* path never
    calls this — the fused decode-attention kernels do the same decode
    tile-by-tile in-loop."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    lead = store.shape[:-1]
    K = store.shape[-1]
    s2 = store.reshape(-1, K)
    m2 = meta.reshape(-1, K)
    if impl == "reference":
        codes = _ref.ref_sparq_dequant(s2, m2)
    else:
        M = s2.shape[0]
        bm = bm or row_block(K)
        codes = sparq_dequant_pallas(
            _pad_to(s2, bm, 0), _pad_to(m2, bm, 0),
            bm=bm, interpret=not _on_tpu())[:M]
    return codes.reshape(*lead, K)


def sparq_decode_attention(
    q: jnp.ndarray,           # (B, 1, H, hd) float query, one decode token
    k_data: jnp.ndarray,      # (B, Tk, KV, hd) int8 window codes
    k_meta: jnp.ndarray,      # (B, Tk, KV, hd) int8 packed meta bytes
    k_scale: jnp.ndarray,     # scalar f32 per-site scale
    v_data: jnp.ndarray,
    v_meta: jnp.ndarray,
    v_scale: jnp.ndarray,
    kpos: jnp.ndarray,        # (B, Tk) int32 slot positions (-1 = empty)
    cur: jnp.ndarray,         # scalar int32: position of the decoded token
    window: int = 0,
    impl: str = "auto",
    bk: Optional[int] = None,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Fused flash-decode attention over the raw packed SPARQ cache planes
    (§5.1 meta-decode inside the Tk-tile loop; no full-plane dequantize).

    Serves both the linear cache (kpos = arange, masked by kpos <= cur) and
    the sliding-window ring cache (kpos = slot_pos + static `window`).

    Args:
      q:       f32/bf16 [B, 1, H, hd] — one query token per sequence.
      k_data:  int8 [B, Tk, KV, hd] window codes (§5.1 data plane).
      k_meta:  int8 [B, Tk, KV, hd] packed [mux|shift_hi|shift_lo] bytes.
      k_scale: f32 scalar per-site scale (v_* likewise for the V plane).
      kpos:    int32 [B, Tk] absolute position per cache slot (-1 = empty).
      cur:     int32 scalar — position of the token being decoded.
      window:  static sliding-window bound (0 = full causal).
      impl:    reference | pallas | auto (pallas on TPU, else reference).
      bk:      Tk-tile size (None -> DEFAULT_BK, clamped to Tk). Tile
               decomposition determines f32 summation order; match it
               (bk == page_size) when comparing against the paged path
               bit for bit.
      mesh:    optional ("data","model") Mesh — shard the head axis over
               the "model" axis via shard_map (KV % tp must be 0).
    Returns f32 [B, 1, H, hd]."""
    tp = tp_size(mesh)
    if tp > 1:
        _tp_guard(k_data.shape[2], tp)
        head = P(None, None, TP_AXIS, None)
        body = functools.partial(
            sparq_decode_attention, window=window, impl=impl, bk=bk)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(head, head, head, P(), head, head, P(), P(), P()),
            out_specs=head, check_vma=False,
        )(q, k_data, k_meta, k_scale, v_data, v_meta, v_scale, kpos, cur)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    B, Tq, H, hd = q.shape
    assert Tq == 1, f"decode attention takes one query token, got Tq={Tq}"
    Tk, KV = k_data.shape[1], k_data.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    bk = DEFAULT_BK if bk is None else bk
    assert bk >= 1, f"bk must be >= 1, got {bk}"
    bk = min(bk, Tk)
    # pad Tk to a tile multiple in the packed domain (int8 planes + the
    # kpos vector, padded with -1 so padding is masked out) — still ~7x
    # cheaper than padding a dequantized fp32 plane would be
    kd = _pad_to(k_data, bk, 1)
    km = _pad_to(k_meta, bk, 1)
    vd = _pad_to(v_data, bk, 1)
    vm = _pad_to(v_meta, bk, 1)
    kp = _pad_to(kpos.astype(jnp.int32), bk, 1, value=-1)
    cur = jnp.asarray(cur, jnp.int32)
    ks = jnp.asarray(k_scale, jnp.float32)
    vs = jnp.asarray(v_scale, jnp.float32)
    if impl == "reference":
        out = _ref.ref_sparq_decode_attn(
            qg, kd, km, ks, vd, vm, vs, kp, cur, window=window, bk=bk)
    elif impl == "pallas":
        flat = lambda x: x.reshape(B, x.shape[1], KV * hd)
        out = sparq_decode_attn_pallas(
            qg, flat(kd), flat(km), ks, flat(vd), flat(vm), vs, kp, cur,
            window=window, bk=bk, interpret=not _on_tpu())
    else:
        raise ValueError(impl)
    return out.reshape(B, 1, H, hd)


def sparq_chunked_prefill_attention(
    q: jnp.ndarray,            # (C, H, hd) float — one chunk of queries
    k_chunk: jnp.ndarray,      # (C, KV, hd) float — chunk K (pre-quant)
    v_chunk: jnp.ndarray,      # (C, KV, hd) float
    k_data: jnp.ndarray,       # (P, ps, KV*hd) int8 window-code pool
    k_meta: jnp.ndarray,       # (P, ps, KV*hd) int8 meta-byte pool
    k_scale: jnp.ndarray,      # (S,) f32 per-slot site scales
    v_data: jnp.ndarray,
    v_meta: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_table: jnp.ndarray,  # (S, NB) int32 page per block (-1 unset)
    seq_id: jnp.ndarray,       # (C,) int32 slot per stream token (-1 pad)
    pos: jnp.ndarray,          # (C,) int32 position per token
    hist: jnp.ndarray,         # (C,) int32 per-token history boundary
    tile_seq: jnp.ndarray,     # (C/bq,) int32 slot per aligned query tile
    window: int = 0,
    impl: str = "auto",
    bq: int = 8,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Ragged chunked-prefill flash attention over the §5.1 page pool.

    One fixed-shape token stream carries a chunk of ragged pending
    prompts (per-token (seq_id, pos) metadata; each sequence's run is
    packed contiguously and aligned to `bq`). Every token attends to its
    sequence's already-written packed pages for positions below its
    history boundary `hist` (block-table gather + in-loop meta-decode)
    followed by causal segment-masked attention over the chunk's float
    K/V in [hist, pos]. One compiled program serves every prompt length
    and join pattern — the point of the chunked prefill path. `hist` is
    the token's segment start, so per-prompt numerics are independent of
    stream packing (see kernels.ref.ref_sparq_chunked_prefill_attn).

    Returns f32 (C, H, hd); padding rows (seq_id < 0) are zeros.
    With `mesh`, heads/pools shard over the "model" axis (see tp_size)."""
    tp = tp_size(mesh)
    if tp > 1:
        _tp_guard(k_chunk.shape[1], tp)
        h2 = P(None, TP_AXIS, None)       # (C, H, hd) streams
        h3 = P(None, None, TP_AXIS)       # (P, ps, KV*hd) pools
        body = functools.partial(
            sparq_chunked_prefill_attention, window=window, impl=impl, bq=bq)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(h2, h2, h2, h3, h3, P(), h3, h3, P(),
                      P(), P(), P(), P(), P()),
            out_specs=h2, check_vma=False,
        )(q, k_chunk, v_chunk, k_data, k_meta, k_scale, v_data, v_meta,
          v_scale, block_table, seq_id, pos, hist, tile_seq)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    C, H, hd = q.shape
    KV = k_chunk.shape[1]
    G = H // KV
    assert C % bq == 0, (C, bq)
    qg = q.reshape(C, KV, G, hd)
    bt = block_table.astype(jnp.int32)
    S = bt.shape[0]
    ks = jnp.broadcast_to(jnp.asarray(k_scale, jnp.float32), (S,))
    vs = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32), (S,))
    args = (qg, k_chunk, v_chunk, k_data, k_meta, ks, v_data, v_meta, vs,
            bt, seq_id.astype(jnp.int32), pos.astype(jnp.int32),
            hist.astype(jnp.int32), tile_seq.astype(jnp.int32))
    if impl == "reference":
        out = _ref.ref_sparq_chunked_prefill_attn(*args, window=window)
    elif impl == "pallas":
        out = sparq_chunked_prefill_attn_pallas(
            *args, window=window, bq=bq, interpret=not _on_tpu())
    else:
        raise ValueError(impl)
    return out.reshape(C, H, hd)


def sparq_paged_decode_attention(
    q: jnp.ndarray,            # (B, 1, H, hd) float, one token per sequence
    k_data: jnp.ndarray,       # (P, ps, KV*hd) int8 window-code page pool
    k_meta: jnp.ndarray,       # (P, ps, KV*hd) int8 packed meta-byte pool
    k_scale: jnp.ndarray,      # (B,) f32 per-sequence site scale
    v_data: jnp.ndarray,
    v_meta: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_table: jnp.ndarray,  # (B, NB) int32 page per logical block (-1 =
                               # unallocated; masked out)
    cur: jnp.ndarray,          # (B,) int32 per-sequence decoded position
                               # (< 0 = inactive slot, output is zeros)
    window: int = 0,
    impl: str = "auto",
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Fused flash-decode attention over a *paged* packed SPARQ cache.

    Same §5.1 in-loop meta-decode as `sparq_decode_attention`, but the K/V
    planes live in one global pool of fixed-size pages shared by all
    sequences; each sequence reads its own pages through `block_table`
    (one Tk tile == one page, gathered by scalar-prefetched page index).
    Slot positions are computed from the logical block index, so the
    masking/GQA/window arithmetic is the contiguous kernel's — with
    page_size == bk the two paths are bit-identical on identical bytes.

    `cur` and the site scales are per-sequence: a continuous-batching step
    serves slots of different lengths (and different calibrations) in one
    traced call. No padding is needed — the pool geometry is static.
    Returns f32 (B, 1, H, hd). With `mesh`, pools and heads shard over
    the "model" axis; block table / cur / scales stay replicated."""
    tp = tp_size(mesh)
    B, Tq, H, hd = q.shape
    KV = k_data.shape[-1] // hd
    if tp > 1:
        _tp_guard(KV, tp)
        head = P(None, None, TP_AXIS, None)
        pool = P(None, None, TP_AXIS)
        body = functools.partial(
            sparq_paged_decode_attention, window=window, impl=impl)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(head, pool, pool, P(), pool, pool, P(), P(), P()),
            out_specs=head, check_vma=False,
        )(q, k_data, k_meta, k_scale, v_data, v_meta, v_scale,
          block_table, cur)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    assert Tq == 1, f"decode attention takes one query token, got Tq={Tq}"
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    bt = block_table.astype(jnp.int32)
    cur = jnp.broadcast_to(jnp.asarray(cur, jnp.int32), (B,))
    ks = jnp.broadcast_to(jnp.asarray(k_scale, jnp.float32), (B,))
    vs = jnp.broadcast_to(jnp.asarray(v_scale, jnp.float32), (B,))
    if impl == "reference":
        out = _ref.ref_sparq_paged_decode_attn(
            qg, k_data, k_meta, ks, v_data, v_meta, vs, bt, cur,
            window=window)
    elif impl == "pallas":
        out = sparq_paged_decode_attn_pallas(
            qg, k_data, k_meta, ks, v_data, v_meta, vs, bt, cur,
            window=window, interpret=not _on_tpu())
    else:
        raise ValueError(impl)
    return out.reshape(B, 1, H, hd)

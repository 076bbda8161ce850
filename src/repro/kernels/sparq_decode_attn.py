"""Fused flash-decode attention over the packed SPARQ KV cache.

This is the kernel the §5.1 footprint argument needs to be *true*: the
decode hot path streams the cache's raw storage — int8 window codes plus
the packed per-pair meta byte [mux(1) | shift_hi(3) | shift_lo(3)] — from
HBM and performs the meta-decode (|code| << ShiftCtrl, sign reapplied;
mux'd vSPARQ lanes pass through at shift 0) *inside* the Tk-tile loop,
fused with the online-softmax QK/PV accumulation. The fp32 K/V planes are
never materialized: each tile is decoded in VMEM, contracted, and dropped.
`CachedTensor.read()` (the full-plane dequantize) remains only as the
prefill/debug fallback.

Lane-dense planes. The kernels read the packed planes with the KV-head
and head_dim axes flattened into one lane axis, `[..., rows, KV*hd]`:
the same bytes in the same order as `[..., rows, KV, hd]`. A TPU block's
last two dims must be multiples of (8, 128) or the whole array dims, so
a per-head `(rows, 1, hd)` block of a `(rows, KV, hd)` plane is refused;
and XLA stores a rank-4 int8 array with small minor dims in a
page-minor HBM layout, which would put a whole-pool relayout copy in
front of every kernel call. One `(rows, KV*hd)` tile per grid step holds
every local head; the kernel loops over the heads inside, taking each
head's `hd` lanes of the tile.

Shapes and grid (contiguous kernel):
  q        [B, KV, G, hd]        one query token, GQA via head grouping
  k/v data [B, Tk, KV*hd]        int8 window codes (§5.1 data plane)
  k/v meta [B, Tk, KV*hd]        int8 packed ShiftCtrl/MuxCtrl bytes
  kpos     [B, Tk/bk, 1, bk]     absolute position per slot (-1 = empty)
  cur      scalar int32          position of the token being decoded

grid = (B, Tk/bk); the Tk axis is sequential ("arbitrary") and carries
flash statistics (m, l, acc) per head in VMEM scratch; B is parallel.
The same kernel serves the linear cache (kpos = arange, masked by
kpos <= cur) and the sliding-window ring cache (kpos = slot_pos, plus the
static `window` bound) — masking is pure position arithmetic, so ring
slot order never needs unrotating.

Within a head's `hd` lanes the lane index is the vSPARQ pairing axis of
the cache planes (hd is even, so a head's lanes start on a pair), and
ShiftCtrl extraction is a parity select on the lane index, exactly as in
`sparq_dequant._kernel`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import meta_shifts, row_sum

#: f32 contractions at full f32 precision on the MXU, as in the oracles
_F32 = jax.lax.Precision.HIGHEST


def _meta_decode_f32(store, meta, scale):
    """int8 (codes, meta) tile -> f32 values tile (lane axis = pair axis).
    Pure jnp (meta_shifts is shared with the ref oracle and sparq_pack),
    so it traces inside the Pallas kernel body unchanged."""
    q = store.astype(jnp.int32)
    recon = jnp.sign(q) * jnp.left_shift(jnp.abs(q), meta_shifts(meta))
    return recon.astype(jnp.float32) * scale


def decode_head(data_ref, meta_ref, h: int, hd: int, scale):
    """f32 [rows, hd] values of head `h` from a lane-dense packed tile
    ref [1, rows, KV*hd] (the head's lanes are [h*hd, (h+1)*hd))."""
    lanes = pl.ds(h * hd, hd)
    return _meta_decode_f32(data_ref[0, :, lanes], meta_ref[0, :, lanes],
                            scale)


def flash_update(q, k, v, ok, m_ref, l_ref, acc_ref, h: int, *,
                 sm_scale: float):
    """One online-softmax tile update of head `h`: q [R, hd] rows against
    keys k/v [keys, hd] under the allow-mask `ok` ([R, keys] or
    broadcastable). The statistics (m, l, acc) of head h persist in VMEM
    scratch row h across the sequential tile axis. Every kernel of this
    package (contiguous and paged decode, chunked prefill) runs this one
    f32 op sequence, and the tiled jnp oracles in `kernels.ref` mirror it
    op for op — that is what keeps their bit-identity honest."""
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ok, s, -jnp.inf)
    m_prev = m_ref[h]                                      # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_new = l_ref[h] * corr + row_sum(p)
    pv = jax.lax.dot_general(
        p, v, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)  # [R, hd]
    m_ref[h] = m_new
    l_ref[h] = l_new
    acc_ref[h] = acc_ref[h] * corr + pv


def init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _decode_tile(q_ref, o_ref, m_ref, l_ref, acc_ref, kd_ref, km_ref,
                 vd_ref, vm_ref, k_scale, v_scale, ok, *, sm_scale: float):
    """One Tk tile of single-token decode for every local head (grid axis
    1 is the sequential tile axis), shared by the contiguous and paged
    kernels — they differ only in how the tile is fetched and how `ok`
    ([1, bk] allow-mask over the tile's slots) is built."""
    t = pl.program_id(1)
    n_kv, _, hd = acc_ref.shape

    @pl.when(t == 0)
    def _init():
        init_stats(m_ref, l_ref, acc_ref)

    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32)                # [G, hd]
        k = decode_head(kd_ref, km_ref, h, hd, k_scale)    # [bk, hd]
        v = decode_head(vd_ref, vm_ref, h, hd, v_scale)
        flash_update(q, k, v, ok, m_ref, l_ref, acc_ref, h,
                     sm_scale=sm_scale)

    @pl.when(t == pl.num_programs(1) - 1)
    def _emit():
        for h in range(n_kv):
            o_ref[0, h] = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)


def stats_scratch(n_kv: int, rows: int, hd: int):
    """VMEM scratch of `flash_update`'s per-head statistics."""
    return [
        pltpu.VMEM((n_kv, rows, 1), jnp.float32),    # m: running max
        pltpu.VMEM((n_kv, rows, 1), jnp.float32),    # l: running denominator
        pltpu.VMEM((n_kv, rows, hd), jnp.float32),   # acc: running numerator
    ]


def _kernel(q_ref, kd_ref, km_ref, vd_ref, vm_ref, kpos_ref, cur_ref,
            kscale_ref, vscale_ref, o_ref, m_ref, l_ref, acc_ref, *,
            window: int, sm_scale: float):
    kpos = kpos_ref[0, 0]                                  # [1, bk]
    cur = cur_ref[0, 0]
    ok = (kpos >= 0) & (kpos <= cur)
    if window:
        ok &= kpos > cur - window
    _decode_tile(q_ref, o_ref, m_ref, l_ref, acc_ref, kd_ref, km_ref,
                 vd_ref, vm_ref, kscale_ref[0, 0], vscale_ref[0, 0], ok,
                 sm_scale=sm_scale)


@functools.partial(jax.jit,
                   static_argnames=("window", "bk", "interpret"))
def sparq_decode_attn_pallas(
    q: jnp.ndarray,           # (B, KV, G, hd) float
    k_data: jnp.ndarray,      # (B, Tk, KV*hd) int8 window codes
    k_meta: jnp.ndarray,      # (B, Tk, KV*hd) int8 packed meta bytes
    k_scale: jnp.ndarray,     # scalar f32 per-site scale
    v_data: jnp.ndarray,
    v_meta: jnp.ndarray,
    v_scale: jnp.ndarray,
    kpos: jnp.ndarray,        # (B, Tk) int32 slot positions (-1 empty)
    cur: jnp.ndarray,         # scalar int32 query-token position
    *,
    window: int = 0,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns f32 (B, KV, G, hd) attention output."""
    B, KV, G, hd = q.shape
    Tk = k_data.shape[1]
    assert k_data.shape == (B, Tk, KV * hd), (q.shape, k_data.shape)
    assert Tk % bk == 0 and hd % 2 == 0, (Tk, bk, hd)
    nt = Tk // bk
    kernel = functools.partial(_kernel, window=window,
                               sm_scale=hd ** -0.5)
    plane = pl.BlockSpec((1, bk, KV * hd), lambda b, t: (b, t, 0))
    smem = pl.BlockSpec((1, 1), lambda b, t: (0, 0),
                        memory_space=pltpu.MemorySpace.SMEM)
    heads = pl.BlockSpec((1, KV, G, hd), lambda b, t: (b, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, nt),
        in_specs=[
            heads, plane, plane, plane, plane,
            pl.BlockSpec((1, 1, 1, bk), lambda b, t: (b, t, 0, 0)),
            smem, smem, smem,
        ],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        scratch_shapes=stats_scratch(KV, G, hd),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k_data, k_meta, v_data, v_meta, kpos.reshape(B, nt, 1, bk),
      cur.reshape(1, 1), k_scale.reshape(1, 1), v_scale.reshape(1, 1))


# ----------------------------------------------------------------------
# paged variant: block-table gather over a global page pool
# ----------------------------------------------------------------------

def _paged_kernel(bt_ref, cur_ref, ks_ref, vs_ref,       # scalar prefetch
                  q_ref, kd_ref, km_ref, vd_ref, vm_ref,  # tensor inputs
                  o_ref, m_ref, l_ref, acc_ref, *,
                  window: int, sm_scale: float, ps: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    # logical slot positions of this page: block t covers [t*ps, (t+1)*ps)
    kpos = t * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    cur = cur_ref[b]
    ok = (bt_ref[b, t] >= 0) & (kpos <= cur)
    if window:
        ok &= kpos > cur - window
    _decode_tile(q_ref, o_ref, m_ref, l_ref, acc_ref, kd_ref, km_ref,
                 vd_ref, vm_ref, ks_ref[b], vs_ref[b], ok,
                 sm_scale=sm_scale)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def sparq_paged_decode_attn_pallas(
    q: jnp.ndarray,           # (B, KV, G, hd) float, one token per sequence
    k_data: jnp.ndarray,      # (P, ps, KV*hd) int8 window-code page pool
    k_meta: jnp.ndarray,      # (P, ps, KV*hd) int8 packed meta-byte pool
    k_scale: jnp.ndarray,     # (B,) f32 per-sequence site scales
    v_data: jnp.ndarray,
    v_meta: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_table: jnp.ndarray,  # (B, NB) int32 page per block (-1 = unset)
    cur: jnp.ndarray,         # (B,) int32 per-sequence decoded position
    *,
    window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged variant of `sparq_decode_attn_pallas`: the K/V planes live in a
    global pool of fixed-size pages and each sequence's Tk tiles are fetched
    through its block table, prefetched as scalars so the BlockSpec index
    maps can name the physical page each grid step streams from HBM. The
    Tk-tile loop runs over logical blocks (one page == one tile, all local
    heads); slot positions are computed from the block index, so
    masking/GQA/window logic is unchanged from the contiguous kernel — with
    page_size == bk the two are bit-identical on identical packed bytes.

    Per-sequence `cur` and `k/v_scale` (continuous batching: every active
    slot has its own length and its own calibration) ride along as scalar-
    prefetch arguments; unallocated block-table entries are clamped to page
    0 for the gather and masked out by `bt >= 0`. Returns f32 (B,KV,G,hd).
    """
    B, KV, G, hd = q.shape
    P, ps = k_data.shape[:2]
    NB = block_table.shape[1]
    assert k_data.shape == (P, ps, KV * hd), (q.shape, k_data.shape)
    assert hd % 2 == 0, hd
    kernel = functools.partial(_paged_kernel, window=window,
                               sm_scale=hd ** -0.5, ps=ps)
    plane = pl.BlockSpec(
        (1, ps, KV * hd),
        lambda b, t, bt, cur, ks, vs: (jnp.maximum(bt[b, t], 0), 0, 0))
    heads = pl.BlockSpec((1, KV, G, hd),
                         lambda b, t, bt, cur, ks, vs: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # block_table, cur, k_scale, v_scale
        grid=(B, NB),
        in_specs=[heads, plane, plane, plane, plane],
        out_specs=heads,
        scratch_shapes=stats_scratch(KV, G, hd),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), cur.astype(jnp.int32),
      k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
      q, k_data, k_meta, v_data, v_meta)

"""Ragged chunked-prefill flash attention over the packed SPARQ page pool.

This is the kernel behind `--prefill chunked`: admission packs ragged
pending prompts into a fixed-shape token stream (per-token (seq_id, pos)
metadata, each sequence's run aligned to the `bq` query tile), and ONE
jitted program processes every chunk — no per-prompt-length retraces.
Each chunk token attends to

  1. its sequence's already-written §5.1 packed pages — every position
     below the token's per-token history boundary `hist` — gathered
     through the per-slot block table with the same scalar-prefetch
     pattern as the paged decode kernel: one page == one Tk tile, §5.1
     meta-decode (window << ShiftCtrl, mux'd-lane passthrough, per-slot
     scale) fused inside the tile loop; and
  2. the float K/V of its history window [hist, pos] inside the chunk:
     causal attention segment-masked by sequence id (tokens of different
     prompts never see each other) and bounded below by hist.

The scheduler sets hist to the token's *segment* start ((pos // seg) *
seg) and packs whole segments only, so a prompt's float-vs-packed
attention split depends only on the prompt and the segment quantum —
never on how the stream happened to be packed. That invariance is what
keeps chunked prefill deterministic per request (and requeue-replay
resume bit-exact) under any join pattern, pool size, or preemption
schedule.

Shapes and grid:
  q          [KV, C*G, hd]   chunk queries, one row per (token, group)
  q_seq/pos/hist [C*G, 1]    per-row stream metadata (-1 = padding)
  k_seq/pos  [1, C]          per-token stream metadata of the keys
  k/v_chunk  [C, KV*hd]      the chunk's own float K/V, lane-dense
  k/v pools  [P, ps, KV*hd]  int8 §5.1 planes (global page pool)
  tile_seq   [nt]            slot owning each bq-aligned query tile

grid = (C/bq, NB + 1): stages 0..NB-1 stream the tile's sequence's
pages (ascending kpos), stage NB is the in-chunk causal stage, one
flash update per 128 chunk keys (`ref.chunk_key_tile`); the stage
axis is sequential ("arbitrary") and carries the flash statistics
(m, l, acc) in VMEM scratch, one row per (token, group) pair and one
slab per local KV head. Every block covers whole minor dims (all local
heads of a page, every group of a token), so the TPU tiling rule holds
at any head count; the head loop runs inside the kernel (see
`sparq_decode_attn` for the lane-dense plane layout). The stage order
and f32 update arithmetic (`sparq_decode_attn.flash_update`) mirror
`kernels.ref.ref_sparq_chunked_prefill_attn` op for op. Interpret-mode
outputs agree with the oracle bit for bit in the in-chunk stage, where
the oracle contracts with the kernel's per-head 2-D dot shapes, and to
within a couple of f32 ulps over page tiles, where it gathers pages per
token and contracts with batched dots (XLA:CPU sums the two dot forms
in different orders); each engine run uses one impl throughout, so the
serving-level greedy-token guarantees are unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import chunk_key_tile
from repro.kernels.sparq_decode_attn import (decode_head, flash_update,
                                             init_stats, stats_scratch)


def _kernel(tile_seq_ref, bt_ref, ks_ref, vs_ref,          # scalar pref.
            q_ref, qseq_ref, qpos_ref, qhist_ref, kseq_ref, kpos_ref,
            kc_ref, vc_ref, kd_ref, km_ref, vd_ref, vm_ref,
            o_ref, m_ref, l_ref, acc_ref, *,
            window: int, sm_scale: float, ps: int, nb: int):
    qt = pl.program_id(0)
    t = pl.program_id(1)
    n_kv, _, hd = acc_ref.shape

    @pl.when(t == 0)
    def _init():
        init_stats(m_ref, l_ref, acc_ref)

    s_tile = jnp.maximum(tile_seq_ref[qt], 0)
    qseq = qseq_ref[...]                               # [bq*G, 1]
    qpos = qpos_ref[...]
    qhist = qhist_ref[...]
    qvalid = qseq >= 0

    @pl.when(t < nb)
    def _page_stage():
        kp = t * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        ok = (bt_ref[s_tile, t] >= 0) & qvalid & (kp < qhist)  # [bq*G, ps]
        if window:
            ok &= kp > qpos - window
        for h in range(n_kv):
            k = decode_head(kd_ref, km_ref, h, hd, ks_ref[s_tile])
            v = decode_head(vd_ref, vm_ref, h, hd, vs_ref[s_tile])
            flash_update(q_ref[h].astype(jnp.float32), k, v, ok,
                         m_ref, l_ref, acc_ref, h, sm_scale=sm_scale)

    @pl.when(t == nb)
    def _chunk_stage():
        kseq = kseq_ref[...]                           # [1, C]
        kpos = kpos_ref[...]
        ok = (kseq == qseq) & qvalid & (kpos <= qpos) \
            & (kpos >= qhist)                          # [bq*G, C]
        if window:
            ok &= kpos > qpos - window
        C = kc_ref.shape[0]
        kt = chunk_key_tile(C)
        for h in range(n_kv):
            lanes = pl.ds(h * hd, hd)
            for j in range(0, C, kt):                  # key tiles
                keys = pl.ds(j, kt)
                k = kc_ref[keys, lanes].astype(jnp.float32)   # [kt, hd]
                v = vc_ref[keys, lanes].astype(jnp.float32)
                flash_update(q_ref[h].astype(jnp.float32), k, v,
                             ok[:, j:j + kt], m_ref, l_ref, acc_ref, h,
                             sm_scale=sm_scale)
            o_ref[h] = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("window", "bq", "interpret"))
def sparq_chunked_prefill_attn_pallas(
    q: jnp.ndarray,            # (C, KV, G, hd) float — chunk queries
    k_chunk: jnp.ndarray,      # (C, KV, hd) float — chunk K (pre-quant)
    v_chunk: jnp.ndarray,      # (C, KV, hd) float
    k_data: jnp.ndarray,       # (P, ps, KV*hd) int8 window-code pool
    k_meta: jnp.ndarray,       # (P, ps, KV*hd) int8 meta-byte pool
    k_scale: jnp.ndarray,      # (S,) f32 per-slot site scales
    v_data: jnp.ndarray,
    v_meta: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_table: jnp.ndarray,  # (S, NB) int32 page per block (-1 unset)
    seq_id: jnp.ndarray,       # (C,) int32 slot per token (-1 padding)
    pos: jnp.ndarray,          # (C,) int32 position per token
    hist: jnp.ndarray,         # (C,) int32 per-token history boundary
    tile_seq: jnp.ndarray,     # (C/bq,) int32 slot per query tile
    *,
    window: int = 0,
    bq: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns f32 (C, KV, G, hd) attention output (padding rows zero)."""
    C, KV, G, hd = q.shape
    P, ps = k_data.shape[:2]
    NB = block_table.shape[1]
    assert k_data.shape == (P, ps, KV * hd), (q.shape, k_data.shape)
    assert C % bq == 0 and hd % 2 == 0, (C, bq, hd)
    assert tile_seq.shape == (C // bq,), tile_seq.shape
    kernel = functools.partial(_kernel, window=window,
                               sm_scale=hd ** -0.5, ps=ps, nb=NB)
    # queries and their metadata one row per (token, group) pair, so the
    # masks build at score-row granularity with no in-kernel repeat
    rows = lambda x: jnp.repeat(x.astype(jnp.int32), G).reshape(C * G, 1)
    cols = lambda x: x.astype(jnp.int32).reshape(1, C)
    qh = q.transpose(1, 0, 2, 3).reshape(KV, C * G, hd)

    def page_idx(qt, t, ts, bt, ks, vs):
        # stage t streams the tile's sequence's page t; the chunk stage
        # (t == NB) and unallocated blocks clamp to page 0 (masked out)
        s = jnp.maximum(ts[qt], 0)
        return (jnp.maximum(bt[s, jnp.minimum(t, NB - 1)], 0), 0, 0)

    plane = pl.BlockSpec((1, ps, KV * hd), page_idx)
    row_meta = pl.BlockSpec((bq * G, 1), lambda qt, t, *s: (qt, 0))
    key_meta = pl.BlockSpec((1, C), lambda qt, t, *s: (0, 0))
    chunk_kv = pl.BlockSpec((C, KV * hd), lambda qt, t, *s: (0, 0))
    heads = pl.BlockSpec((KV, bq * G, hd), lambda qt, t, *s: (0, qt, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # tile_seq, block_table, k/v scales
        grid=(C // bq, NB + 1),
        in_specs=[heads, row_meta, row_meta, row_meta, key_meta, key_meta,
                  chunk_kv, chunk_kv, plane, plane, plane, plane],
        out_specs=heads,
        scratch_shapes=stats_scratch(KV, bq * G, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, C * G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_seq.astype(jnp.int32), block_table.astype(jnp.int32),
      k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
      qh, rows(seq_id), rows(pos), rows(hist), cols(seq_id), cols(pos),
      k_chunk.reshape(C, KV * hd), v_chunk.reshape(C, KV * hd),
      k_data, k_meta, v_data, v_meta)
    return out.reshape(KV, C, G, hd).transpose(1, 0, 2, 3)

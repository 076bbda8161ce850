"""Standalone SPARQ meta-decode Pallas kernel (KV-cache read path).

Inverse of `sparq_quant.sparq_quant_pallas` + `ops.sparq_pack`: takes the
stored int8 window codes (sign-magnitude data nibbles; full 8-bit magnitude
on vSPARQ mux'd lanes, whose ShiftCtrl is 0) and the packed per-pair meta
byte [mux(1) | shift_hi(3) | shift_lo(3)] mirrored to both lanes, and
reconstructs the SPARQ integer codes:

    codes[i] = sign(store[i]) * (|store[i]| << shift[i]),
    shift[i] = meta[i]>>3 & 7 on even lanes, meta[i] & 7 on odd lanes.

This is the §5.1 decode datapath the paper's memory-footprint argument
rests on — the cache holds (n + 3 + ½)-bit values, the MXU consumes 8-bit
reconstructions. Grid is 1-D over row tiles of `row_block(K)` rows; lane
axis is the pair axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparq_quant import row_block


def _kernel(store_ref, meta_ref, codes_ref):
    q = store_ref[...].astype(jnp.int32)
    m = meta_ref[...].astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, dimension=1)
    shift = jnp.where(lane % 2 == 0, jnp.right_shift(m, 3) & 7, m & 7)
    recon = jnp.left_shift(jnp.abs(q), shift)
    codes_ref[...] = (jnp.sign(q) * recon).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def sparq_dequant_pallas(
    store: jnp.ndarray,       # (M, K) int8 window codes
    meta: jnp.ndarray,        # (M, K) int8 packed ShiftCtrl/MuxCtrl bytes
    *,
    bm: Optional[int] = None,
    interpret: bool = False,
):
    """Returns int8 (M, K): SPARQ-reconstructed integer codes."""
    M, K = store.shape
    bm = bm or row_block(K)
    assert store.shape == meta.shape, (store.shape, meta.shape)
    assert M % bm == 0 and K % 2 == 0, (M, K, bm)
    return pl.pallas_call(
        _kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda m: (m, 0)),
            pl.BlockSpec((bm, K), lambda m: (m, 0)),
        ],
        out_specs=pl.BlockSpec((bm, K), lambda m: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, K), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(store, meta)

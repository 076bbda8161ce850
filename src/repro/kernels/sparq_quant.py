"""Standalone SPARQ quantization Pallas kernel (KV-cache / storage path).

Quantizes a float tile to SPARQ codes and emits (a) the reconstructed
integer codes as int8 ready for an integer matmul, and (b) packed metadata:
for each pair of lanes one byte holding [mux(1) | shift_hi(3) | shift_lo(3)]
— the paper's MuxCtrl + ShiftCtrl (§5.1 footprint discussion). The data
nibbles themselves would pack 2-per-byte on real hardware; we keep recon
codes unpacked int8 here because the MXU consumes 8-bit operands anyway
(the packed format only matters for HBM residency, which `bytes_per_value`
in ops.py models for the roofline analysis).

Grid is 1-D over row tiles of `row_block(K)` rows; the lane (last) axis
is the pairing axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bsparq import bsparq_encode


def row_block(K: int) -> int:
    """Row tile of the row-streaming kernels (`sparq_quant`,
    `sparq_dequant`), whose block is a whole (bm, K) row slab. Sized so
    the slab is at most 2^18 elements (1 MiB of f32 input): the quant
    kernel's int32 temporaries then fit the v5e's scoped VMEM at every K
    the main path passes, where a fixed 256 rows ran out at K = 5632 (the
    TinyLlama FFN width). A multiple of 32 rows, the int8 sublane tile."""
    return min(256, max(32, (1 << 18) // K // 32 * 32))


def _kernel(x_ref, ascale_ref, codes_ref, meta_ref, *,
            bits, shifts, rounding, vsparq, signed, max_val, enabled):
    a = ascale_ref[0, 0]
    x = x_ref[...]
    qmin = -max_val if signed else 0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / a), qmin, max_val)
    q = q.astype(jnp.int32)
    if not enabled:
        # plain int8 PTQ (paper baseline): full codes, empty meta
        codes_ref[...] = q.astype(jnp.int8)
        meta_ref[...] = jnp.zeros_like(q, dtype=jnp.int8)
        return
    sign = jnp.sign(q)
    mag = jnp.abs(q)
    qq, ss = bsparq_encode(mag, bits, shifts, rounding, max_val)
    trimmed = jnp.left_shift(qq, ss)
    if vsparq:
        sz = mag.shape[1]
        left = pltpu.roll(mag, sz - 1, axis=1)  # lane i -> holds mag[i+1]
        right = pltpu.roll(mag, 1, axis=1)      # lane i -> holds mag[i-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, mag.shape, dimension=1)
        even = lane % 2 == 0
        partner = jnp.where(even, left, right)
        full = partner == 0
        recon = jnp.where(full, mag, trimmed)
        shift_code = jnp.where(full, 0, ss)
        mux = full
    else:
        recon = trimmed
        shift_code = ss
        mux = jnp.zeros_like(mag, dtype=jnp.bool_)
    codes_ref[...] = (sign * recon).astype(jnp.int8)
    # pack per-pair meta byte: [mux_any(1) | shift_even(3) | shift_odd(3)],
    # computed on even lanes and mirrored to odd lanes (storage would keep
    # even lanes only: 7 meta bits per pair, the paper's §5.1 footprint).
    lane = jax.lax.broadcasted_iota(jnp.int32, mag.shape, dimension=1)
    even = lane % 2 == 0
    mux_i = mux.astype(jnp.int32)
    szk = mag.shape[1]
    mux_any = jnp.minimum(mux_i + pltpu.roll(mux_i, szk - 1, axis=1), 1)
    s_next = pltpu.roll(shift_code, szk - 1, axis=1)  # lane i: shift[i+1]
    meta_even = mux_any * 64 + shift_code * 8 + s_next
    meta = jnp.where(even, meta_even, pltpu.roll(meta_even, 1, axis=1))
    meta_ref[...] = meta.astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "opts_shifts", "rounding", "vsparq", "signed",
                     "max_val", "enabled", "bm", "interpret"))
def sparq_quant_pallas(
    x: jnp.ndarray,           # (M, K) float
    act_scale: jnp.ndarray,   # scalar f32
    *,
    bits: int = 4,
    opts_shifts: tuple[int, ...] = (0, 1, 2, 3, 4),
    rounding: bool = True,
    vsparq: bool = True,
    signed: bool = True,
    max_val: int = 127,
    enabled: bool = True,
    bm: Optional[int] = None,
    interpret: bool = False,
):
    """Returns (codes int8 [M,K] — SPARQ-reconstructed integer values,
    meta int8 [M,K] — per-lane packed ShiftCtrl/MuxCtrl byte)."""
    M, K = x.shape
    bm = bm or row_block(K)
    assert M % bm == 0 and K % 2 == 0, (M, K, bm)
    kernel = functools.partial(
        _kernel, bits=bits, shifts=opts_shifts, rounding=rounding,
        vsparq=vsparq, signed=signed, max_val=max_val, enabled=enabled)
    return pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda m: (m, 0)),
            pl.BlockSpec((1, 1), lambda m: (0, 0),
                         memory_space=pltpu.MemorySpace.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, K), lambda m: (m, 0)),
            pl.BlockSpec((bm, K), lambda m: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), jnp.int8),
            jax.ShapeDtypeStruct((M, K), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, act_scale.reshape(1, 1))

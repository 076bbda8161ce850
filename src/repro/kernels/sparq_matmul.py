"""Fused SPARQ quantize + matmul Pallas TPU kernel.

This is the TPU-native adaptation of the paper's PE datapath (Fig. 2,
DESIGN.md §3): the dynamic quantization chain (min-max quantize ->
vSPARQ pair test -> bSPARQ window select -> round) runs on the VPU over
VMEM-resident tiles, immediately before the MXU contraction, so the
activation tensor is read from HBM exactly once and SPARQ costs no extra
memory traffic. Products accumulate in an int32 VMEM scratch (the psum
register of the paper's PE); per-output-channel weight scales and the
per-tensor activation scale are applied once on the final K step. Signed
codes (clipped to ±127) feed the MXU as int8 operands; the paper's
unsigned mode (codes up to 255) takes an exact bf16 dot per K tile.

vSPARQ pairing is implemented with a lane roll instead of a reshape:
partner(i) = x[i+1] for even lanes, x[i-1] for odd lanes — a pure
elementwise select after `pltpu.roll`, which keeps the tile in its native
(sublane, lane) layout (no relayout between the VPU chain and the MXU).

Tile sizes default to (128, 128, 512): MXU-aligned 128s, and a K tile
chosen so x(128x512 f32) + w(512x128 int8) + acc(128x128 i32) + recon
(128x512 i32) stay well under VMEM (~16 MiB on v5e).

Semantics notes:
  * The reduction (K) axis must be even (vSPARQ pairs adjacent K lanes) and
    the K tile must be even so pairs never straddle tiles.
  * Zero padding of K is safe only in whole pairs (handled by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bsparq import bsparq_recon


def _recon_tile(q: jnp.ndarray, *, bits: int, shifts: tuple[int, ...],
                rounding: bool, vsparq: bool, signed: bool,
                max_val: int) -> jnp.ndarray:
    """SPARQ reconstruction of an int32 code tile (sublane, lane=K)."""
    if signed:
        sign = jnp.sign(q)
        mag = jnp.abs(q)
    else:
        sign, mag = None, q
    trimmed = bsparq_recon(mag, bits, shifts, rounding, max_val)
    if vsparq:
        # partner(i) = mag[i+1] on even lanes, mag[i-1] on odd lanes
        sz = mag.shape[1]
        left = pltpu.roll(mag, sz - 1, axis=1)  # lane i -> holds mag[i+1]
        right = pltpu.roll(mag, 1, axis=1)      # lane i -> holds mag[i-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, mag.shape, dimension=1)
        partner = jnp.where(lane % 2 == 0, left, right)
        recon = jnp.where(partner == 0, mag, trimmed)  # Eq. (2)
    else:
        recon = trimmed
    return recon if sign is None else sign * recon


def _kernel(x_ref, w_ref, ascale_ref, cscale_ref, o_ref, acc_ref, *,
            bits, shifts, rounding, vsparq, signed, max_val, enabled):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = ascale_ref[0, 0]
    x = x_ref[...]
    qmax = max_val
    qmin = -max_val if signed else 0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / a), qmin, qmax)
    q = q.astype(jnp.int32)
    if enabled:
        q = _recon_tile(q, bits=bits, shifts=shifts, rounding=rounding,
                        vsparq=vsparq, signed=signed, max_val=max_val)
    dims = (((1,), (0,)), ((), ()))
    if signed and max_val <= 127:
        # codes fit int8: the native int8 x int8 -> int32 MXU dot
        acc_ref[...] += jax.lax.dot_general(
            q.astype(jnp.int8), w_ref[...], dimension_numbers=dims,
            preferred_element_type=jnp.int32)
    else:
        # unsigned codes reach 255 and do not fit int8. A bf16 dot with an
        # f32 accumulator is exact inside one K tile: every code and
        # weight fits bf16's 8-bit significand, and a tile's partial sums
        # stay below 2^24 (bk * 255 * 128 < 2^24 for bk <= 512)
        acc_ref[...] += jax.lax.dot_general(
            q.astype(jnp.bfloat16), w_ref[...].astype(jnp.bfloat16),
            dimension_numbers=dims,
            preferred_element_type=jnp.float32).astype(jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _emit():
        # one combined scale per column, as in ref_sparq_matmul
        o_ref[...] = (acc_ref[...].astype(jnp.float32) *
                      (a * cscale_ref[...].astype(jnp.float32)))


@functools.partial(
    jax.jit,
    static_argnames=("bits", "opts_shifts", "rounding", "vsparq", "signed",
                     "max_val", "enabled", "bm", "bn", "bk", "interpret"))
def sparq_matmul_pallas(
    x: jnp.ndarray,            # (M, K) float32/bfloat16 activations
    w_codes: jnp.ndarray,      # (K, N) int8 weight codes
    act_scale: jnp.ndarray,    # scalar f32
    chan_scale: jnp.ndarray,   # (N,) f32 per-output-channel weight scales
    *,
    bits: int = 4,
    opts_shifts: tuple[int, ...] = (0, 1, 2, 3, 4),
    rounding: bool = True,
    vsparq: bool = True,
    signed: bool = False,
    max_val: int = 255,
    enabled: bool = True,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    M, K = x.shape
    K2, N = w_codes.shape
    assert K == K2, (K, K2)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"pad to tiles first: {(M, K, N)} vs {(bm, bk, bn)}"
    assert bk % 2 == 0, "K tile must be even (vSPARQ pairs adjacent lanes)"
    assert (signed and max_val <= 127) or bk <= 512, \
        f"unsigned codes accumulate exactly in f32 only for bk <= 512: {bk}"

    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(
        _kernel, bits=bits, shifts=opts_shifts, rounding=rounding,
        vsparq=vsparq, signed=signed, max_val=max_val, enabled=enabled)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0),
                         memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_codes, act_scale.reshape(1, 1), chan_scale.reshape(1, N))

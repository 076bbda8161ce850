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

The activation chain runs once per row block. The grid is
(M/bm, N/bn, K/bk) with the column axis outside the K axis: during the
first column tile (n == 0) each K tile of x is quantized and its codes
are kept in a VMEM scratch of the whole row block, [K/bk, bm, bk] (int8
on the signed path; bf16, exact for 0..256, on the unsigned one). Every
later column tile reads the codes back, and x's block index stays on the
tile last fetched, so the pipeline moves no more x for that row block:
those steps only stream weight codes into the MXU. The scratch carries
across column tiles, so that axis is "arbitrary" (v5e has one
TensorCore, which loses nothing).

Tiles come from the call's shape (`choose_tiles`): bm is the live rows
rounded up to the int8 sublane tile (32), up to 256 rows, so a decode
step of 24-32 rows runs one 32-row block; bk and bn are the largest
divisors of K and N (in 128-lane units) that keep a weight tile near
1 MiB, bk <= 2048 (<= 512 on the unsigned path, see the kernel's
assert), so per-step overhead stays small next to the DMA.
`vmem_bytes` reckons the buffers, the codes scratch and the chain's
temporaries, and the scoped VMEM limit is raised to that when it
passes the 16 MiB default.

Semantics notes:
  * The reduction (K) axis must be even (vSPARQ pairs adjacent K lanes) and
    the K tile must be even so pairs never straddle tiles.
  * Zero padding of K is safe only in whole pairs (handled by ops.py).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

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


#: rows of a block: a multiple of the int8 sublane tile, at most _BM_MAX
_ROW_ALIGN, _BM_MAX = 32, 256
_LANE = 128
#: largest K tile: any on the signed int8 path, 512 where unsigned codes
#: take the bf16 dot (exact only up to 512, see _kernel)
_BK_MAX, _BK_MAX_BF16 = 2048, 512
#: weight codes a grid step streams, bk * bn int8 bytes, at most
_W_TILE_BYTES = 1 << 20
#: lanes of x the SPARQ chain works on at once, which bounds its int32
#: temporaries to (bm, 512) whatever bk is
_CHAIN_LANES = 512
#: the chain's live (bm, lanes) int32 temporaries, reckoned generously
_CHAIN_TEMPS = 8
#: the compiler's scoped VMEM default on v5e, and the chip's whole VMEM
_VMEM_DEFAULT, _VMEM_MAX = 16 << 20, 128 << 20


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _largest_tile(size: int, unit: int, cap: int) -> int:
    """Largest multiple of `unit`, at most `cap`, that divides `size`
    (itself a multiple of `unit`)."""
    return max(t for t in range(unit, min(size, cap) + 1, unit)
               if size % t == 0)


def _int8_codes(signed: bool, max_val: int) -> bool:
    return signed and max_val <= 127


class Tiles(NamedTuple):
    bm: int
    bn: int
    bk: int


def choose_tiles(M: int, K: int, N: int, *, signed: bool,
                 max_val: int) -> Tiles:
    """Tiles for an (M, K) x (K, N) call. They divide M rounded up to 32
    and K and N rounded up to 128, which is what the caller pads to."""
    bm = _largest_tile(_round_up(M, _ROW_ALIGN), _ROW_ALIGN, _BM_MAX)
    bk_max = _BK_MAX if _int8_codes(signed, max_val) else _BK_MAX_BF16
    bk = _largest_tile(_round_up(K, _LANE), _LANE, bk_max)
    bn = _largest_tile(_round_up(N, _LANE), _LANE,
                       max(_LANE, _W_TILE_BYTES // bk))
    return Tiles(bm, bn, bk)


def vmem_bytes(bm: int, bn: int, bk: int, K: int, *, signed: bool,
               max_val: int, x_bytes: int = 4) -> int:
    """VMEM the kernel needs at these tiles: the row block's codes
    scratch, the double-buffered x, weight, scale and output blocks, the
    int32 accumulator, the chain's temporaries and the dot's operands."""
    int8_codes = _int8_codes(signed, max_val)
    lanes = math.gcd(bk, _CHAIN_LANES)
    codes = bm * K * (1 if int8_codes else 2)
    blocks = 2 * (bm * bk * x_bytes + bk * bn + 8 * bn * 4 + bm * bn * 4)
    acc = bm * bn * 4
    chain = _CHAIN_TEMPS * bm * lanes * 4
    dot = bm * bn * 4 + (0 if int8_codes else bk * bn * 2)
    return codes + blocks + acc + chain + dot


def vmem_limit(nbytes: int) -> Optional[int]:
    """Scoped VMEM limit for a kernel that needs `nbytes`: the compiler's
    default where that is enough, else `nbytes` rounded up to a MiB."""
    if nbytes <= _VMEM_DEFAULT:
        return None
    assert nbytes <= _VMEM_MAX, f"tiles need {nbytes} B of VMEM"
    return _round_up(nbytes, 1 << 20)


def _kernel(x_ref, w_ref, ascale_ref, cscale_ref, o_ref, codes_ref, acc_ref,
            *, bits, shifts, rounding, vsparq, signed, max_val, enabled):
    n, k = pl.program_id(1), pl.program_id(2)
    a = ascale_ref[0, 0]

    @pl.when(n == 0)
    def _quantize():
        # the first column tile quantizes this K tile of the row block,
        # in lane chunks that keep vSPARQ pairs whole; the later column
        # tiles read the codes back
        bk = x_ref.shape[1]
        lanes = math.gcd(bk, _CHAIN_LANES)
        qmin = -max_val if signed else 0
        for j in range(0, bk, lanes):
            x = x_ref[:, j:j + lanes]
            q = jnp.clip(jnp.round(x.astype(jnp.float32) / a), qmin, max_val)
            q = q.astype(jnp.int32)
            if enabled:
                q = _recon_tile(q, bits=bits, shifts=shifts,
                                rounding=rounding, vsparq=vsparq,
                                signed=signed, max_val=max_val)
            codes_ref[k, :, j:j + lanes] = q.astype(codes_ref.dtype)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = codes_ref[k]
    dims = (((1,), (0,)), ((), ()))
    if _int8_codes(signed, max_val):
        # codes fit int8: the native int8 x int8 -> int32 MXU dot
        acc_ref[...] += jax.lax.dot_general(
            q, w_ref[...], dimension_numbers=dims,
            preferred_element_type=jnp.int32)
    else:
        # unsigned codes reach 255 and do not fit int8. A bf16 dot with an
        # f32 accumulator is exact inside one K tile: every code and
        # weight fits bf16's 8-bit significand, and a tile's partial sums
        # stay below 2^24 (bk * 255 * 128 < 2^24 for bk <= 512)
        acc_ref[...] += jax.lax.dot_general(
            q, w_ref[...].astype(jnp.bfloat16), dimension_numbers=dims,
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
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tiles left None are chosen from the shape (`choose_tiles`)."""
    M, K = x.shape
    K2, N = w_codes.shape
    assert K == K2, (K, K2)
    if None in (bm, bn, bk):
        auto = choose_tiles(M, K, N, signed=signed, max_val=max_val)
        bm, bn, bk = bm or auto.bm, bn or auto.bn, bk or auto.bk
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"pad to tiles first: {(M, K, N)} vs {(bm, bk, bn)}"
    assert bk % 2 == 0, "K tile must be even (vSPARQ pairs adjacent lanes)"
    int8_codes = _int8_codes(signed, max_val)
    assert int8_codes or bk <= _BK_MAX_BF16, \
        f"unsigned codes accumulate exactly in f32 only for bk <= 512: {bk}"

    nk = K // bk
    grid = (M // bm, N // bn, nk)
    kernel = functools.partial(
        _kernel, bits=bits, shifts=opts_shifts, rounding=rounding,
        vsparq=vsparq, signed=signed, max_val=max_val, enabled=enabled)
    need = vmem_bytes(bm, bn, bk, K, signed=signed, max_val=max_val,
                      x_bytes=x.dtype.itemsize)
    if interpret:
        # a one-step grid is inlined into the caller's program, where XLA
        # would fold the scales' producers into the kernel's arithmetic;
        # compiled, the kernel reads its scales from memory
        act_scale, chan_scale = jax.lax.optimization_barrier(
            (act_scale, chan_scale))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # x moves only while the first column tile quantizes it; after
            # that its block stays on the last K tile, so no DMA is issued
            pl.BlockSpec((bm, bk),
                         lambda m, n, k: (m, jnp.where(n == 0, k, nk - 1))),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0),
                         memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nk, bm, bk),
                       jnp.int8 if int8_codes else jnp.bfloat16),
            pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
    )(x, w_codes, act_scale.reshape(1, 1), chan_scale.reshape(1, N))

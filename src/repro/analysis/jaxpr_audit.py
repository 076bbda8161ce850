"""Jaxpr-level invariant auditor for the registered hot programs.

Each hot program (decode step, paged step, prefill chunk, replay, the
`kernels/ops.py` dispatchers) is traced to a closed jaxpr with
`jax.make_jaxpr` over `ShapeDtypeStruct` arguments — zero compute, no
device state — and the jaxpr is walked recursively to enforce the
invariants the dynamic spy tests only probe at single call sites:

JX101  no host callbacks (`pure_callback` / `io_callback` /
       `debug_callback`) or explicit device<->host transfers inside a
       hot program — a callback serializes every step on a host round
       trip.
JX102  packed int8/uint8 planes are never `convert_element_type`'d to
       float outside a `pallas_call` or the registered meta-decode
       sources (`kernels.ops.META_DECODE_SOURCES`) — the static form of
       the `CachedTensor.read()` spy: decode must stream packed bytes,
       not materialize a float cache.
JX103  every Pallas block shape divides its operand's array shape —
       ragged tails would silently read OOB-masked garbage or force
       masking the kernels don't do.
JX104  in a program that declares a page size, every block of a
       lane-dense packed plane (`[..., rows, KV*hd]` int8) is one whole
       page of every local head: `block[1] == page_size` and
       `block[2] == KV*hd`. The paged kernels gather whole pages via the
       block table, so a mismatched row tile (e.g. replay forgetting
       `attn_bk = page_size`) reads across page boundaries; a tile of
       part of the lane axis splits heads, which the TPU tiling rule
       refuses for sub-128 head groups.
JX105  the VMEM footprint of a `pallas_call` stays under the budget:
       every pipelined operand tile double-buffered, plus scratch, each
       padded to the TPU's (sublane, 128-lane) tile — a (16, 64) int8
       tile occupies a (32, 128) slot.
JX106  re-tracing a program under the engine's real shape set yields
       ONE jit signature — the static generalization of the
       compile-count regression guard.

Taint rule (JX102): any int8/uint8 value — input leaf or produced
in-trace — is treated as a packed plane, and taint flows through
*integer* ops, so laundering through an int32 widen before the float
cast is still caught. Integer→float conversions inside `pallas_call` or
in code whose source file lives under a registered meta-decode path are
the blessed decode and clear the taint. Sub-jaxprs (`pjit`, `scan`,
`while`, `cond`, custom-derivative wrappers) are entered with exact
positional taint mapping so an untainted int32 (e.g. a rotary position
index) does not false-positive when cast to float.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax._src import source_info_util
from jax.experimental import pallas as pl

from repro.analysis.findings import (Finding, JX_COMPILE_CACHE, JX_HOSTCALL,
                                     JX_PACKED_CAST, JX_PAGE_TILE,
                                     JX_TILE_DIVIDE, JX_VMEM)

#: default per-kernel VMEM budget for the padded, double-buffered tiles
#: and scratch JX105 counts. The v5e's default scoped VMEM limit is
#: 16 MiB; a quarter of it leaves headroom for the kernel body's own
#: temporaries, which the estimate does not see.
DEFAULT_VMEM_BUDGET = 4 * 1024 * 1024


def vmem_tile_bytes(dims: Sequence[int], dtype) -> int:
    """Bytes one array tile occupies in TPU VMEM: the last dim padded to
    128 lanes and the second-minor to the dtype's sublane tile (8 rows of
    32-bit words: 8 f32, 16 bf16 or 32 int8 rows)."""
    itemsize = jnp.dtype(dtype).itemsize
    d = list(dims) or [1]
    d[-1] = -(-d[-1] // 128) * 128
    if len(d) >= 2:
        sub = 8 * max(1, 4 // itemsize)
        d[-2] = -(-d[-2] // sub) * sub
    return math.prod(d) * itemsize

_HOSTCALL_PRIMS = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"})
_TRANSFER_PRIMS = frozenset({"device_put"})
_PACKED_DTYPES = frozenset({"int8", "uint8"})


@dataclasses.dataclass
class ProgramSpec:
    """One registered hot program.

    `shape_set` is a list of abstract argument tuples (pytrees of
    `jax.ShapeDtypeStruct` leaves plus static values): the first entry
    drives the jaxpr walk, the full list drives the JX106 compile-cache
    audit — it should mirror the shapes the live engine actually feeds
    the program. `audit_cache=False` opts a program out of JX106 (the
    replay program legitimately retraces per recorded-token count; it is
    a cold path run once per preemption)."""
    name: str
    fn: Callable
    shape_set: Sequence[tuple]
    page_size: Optional[int] = None
    audit_cache: bool = True


def _frame(eqn) -> Tuple[str, int]:
    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return "", 0
    return fr.file_name, fr.start_line


def _dtype_of(v) -> Optional[str]:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def _is_packed(v) -> bool:
    return _dtype_of(v) in _PACKED_DTYPES


def _is_int(v) -> bool:
    dt = _dtype_of(v)
    return dt is not None and ("int" in dt or "bool" in dt)


class _Taint:
    """Per-var taint keyed by object identity (jaxpr Vars are unique
    objects; Literals are always looked up by dtype)."""

    def __init__(self):
        self._m: Dict[int, bool] = {}

    def get(self, v) -> bool:
        if _is_packed(v):
            return True
        return self._m.get(id(v), False)

    def set(self, v, t: bool) -> None:
        self._m[id(v)] = bool(t) or _is_packed(v)


def call_signature(args: tuple, kwargs: Optional[dict] = None) -> tuple:
    """The jit-cache identity of a call: pytree structure plus (shape,
    dtype) per array leaf and `repr` per static leaf. Two calls with
    equal signatures share one traced program."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append(("arr", tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append(("static", repr(leaf)))
    return (str(treedef), tuple(sig))


class _Auditor:
    def __init__(self, program: str, page_size: Optional[int],
                 vmem_budget: int, meta_decode_sources: Tuple[str, ...]):
        self.program = program
        self.page_size = page_size
        self.vmem_budget = vmem_budget
        self.meta_sources = tuple(s.replace("\\", "/")
                                  for s in meta_decode_sources)
        self.findings: List[Finding] = []

    # ------------------------------------------------------------ helpers
    def _emit(self, check: str, eqn, message: str) -> None:
        file, line = _frame(eqn)
        self.findings.append(Finding(check=check, file=file, line=line,
                                     program=self.program, message=message))

    def _in_meta_decode(self, eqn) -> bool:
        file, _ = _frame(eqn)
        file = file.replace("\\", "/")
        return any(s in file for s in self.meta_sources)

    # ------------------------------------------------------------- pallas
    def _block_dims(self, bm) -> List[Optional[int]]:
        """Block extents of one BlockMapping: `Blocked(n)` gives n, a
        squeezed dim is one element, other indexing modes are skipped."""
        dims: List[Optional[int]] = []
        for d in bm.block_shape:
            size = 1 if isinstance(d, pl.Squeezed) else d.block_size
            dims.append(size if isinstance(size, int) else None)
        return dims

    def _check_pallas(self, eqn) -> None:
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            return
        vmem = 0
        for bm in gm.block_mappings:
            shape = tuple(bm.array_aval.shape)
            dtype = str(bm.array_aval.dtype)
            dims = self._block_dims(bm)
            if getattr(bm.block_aval, "memory_space", None) is None:
                vmem += 2 * vmem_tile_bytes(         # double-buffered
                    [d if isinstance(d, int) else 1 for d in dims], dtype)
            bad = [(i, b, s) for i, (b, s) in enumerate(zip(dims, shape))
                   if isinstance(b, int) and b > 0 and s % b]
            if bad:
                i, b, s = bad[0]
                self._emit(JX_TILE_DIVIDE, eqn,
                           f"block shape {tuple(dims)} does not divide "
                           f"operand shape {shape} (dim {i}: {s} % {b} "
                           f"!= 0)")
            if (self.page_size is not None and dtype in _PACKED_DTYPES
                    and len(shape) == 3
                    and (dims[1], dims[2]) != (self.page_size, shape[2])):
                self._emit(JX_PAGE_TILE, eqn,
                           f"packed plane {shape} {dtype} tiled with "
                           f"block {tuple(dims)} but program page_size="
                           f"{self.page_size} — paged kernels must tile "
                           f"whole pages of every local head (block "
                           f"(1, page_size, KV*hd), attn_bk == page_size)")
        n_scratch = gm.num_scratch_operands
        kernel_in = eqn.params["jaxpr"].invars
        for v in kernel_in[len(kernel_in) - n_scratch:]:
            aval = getattr(v.aval, "inner_aval", v.aval)
            vmem += vmem_tile_bytes(aval.shape, aval.dtype)
        if vmem > self.vmem_budget:
            self._emit(JX_VMEM, eqn,
                       f"estimated VMEM footprint {vmem} B (padded, "
                       f"double-buffered tiles + scratch) exceeds budget "
                       f"{self.vmem_budget} B")

    # --------------------------------------------------------------- walk
    def walk(self, jaxpr, taint_in: Sequence[bool],
             const_taint: Sequence[bool], inside_pallas: bool = False
             ) -> List[bool]:
        """Walk one (open) jaxpr; returns the taint of its outvars."""
        taint = _Taint()
        for v, t in zip(jaxpr.invars, taint_in):
            taint.set(v, t)
        for v, t in zip(jaxpr.constvars, const_taint):
            taint.set(v, t)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_taint = [taint.get(v) for v in eqn.invars]

            if name in _HOSTCALL_PRIMS:
                self._emit(JX_HOSTCALL, eqn,
                           f"host callback `{name}` inside a hot program "
                           f"— every step would block on a host round "
                           f"trip")
                for v in eqn.outvars:
                    taint.set(v, False)
                continue
            if name in _TRANSFER_PRIMS and not inside_pallas:
                self._emit(JX_HOSTCALL, eqn,
                           f"device transfer `{name}` inside a hot "
                           f"program — placement belongs on the host "
                           f"side of the jit boundary")
                for v, t in zip(eqn.outvars, in_taint):
                    taint.set(v, t)
                continue

            if name == "convert_element_type":
                out = eqn.outvars[0]
                out_dt = _dtype_of(out)
                to_float = out_dt is not None and jnp.issubdtype(
                    jnp.dtype(out_dt), jnp.floating)
                if any(in_taint) and to_float:
                    if inside_pallas or self._in_meta_decode(eqn):
                        taint.set(out, False)   # blessed decode
                    else:
                        self._emit(
                            JX_PACKED_CAST, eqn,
                            f"packed plane cast "
                            f"{_dtype_of(eqn.invars[0])}->"
                            f"{_dtype_of(out)} outside pallas/meta-decode"
                            f" — decode must stream packed bytes, not "
                            f"materialize a float cache")
                        taint.set(out, False)
                else:
                    taint.set(out, any(in_taint) and _is_int(out))
                continue

            if name == "pallas_call":
                if not inside_pallas:
                    self._check_pallas(eqn)
                inner = eqn.params.get("jaxpr")
                if inner is not None:
                    n = len(inner.invars)
                    self.walk(inner, ([False] * n),
                              [False] * len(inner.constvars),
                              inside_pallas=True)
                for v in eqn.outvars:
                    taint.set(v, _is_packed(v))
                continue

            out_taint = self._sub_jaxpr(name, eqn, in_taint, inside_pallas)
            if out_taint is None:
                # generic primitive: integer outputs inherit taint so
                # int8 -> int32 -> float laundering is still caught
                out_taint = [any(in_taint) and _is_int(v)
                             for v in eqn.outvars]
            for v, t in zip(eqn.outvars, out_taint):
                taint.set(v, t)

        return [taint.get(v) for v in jaxpr.outvars]

    def _closed(self, closed, taint_in, inside_pallas) -> List[bool]:
        consts = getattr(closed, "consts", ())
        const_taint = [hasattr(c, "dtype") and str(c.dtype) in _PACKED_DTYPES
                       for c in consts]
        return self.walk(closed.jaxpr, taint_in, const_taint,
                         inside_pallas=inside_pallas)

    def _sub_jaxpr(self, name: str, eqn, in_taint: List[bool],
                   inside_pallas: bool) -> Optional[List[bool]]:
        """Recurse into call-like primitives with exact positional taint
        mapping. Returns outvar taint, or None for generic primitives."""
        p = eqn.params
        if name in ("pjit", "closed_call", "core_call", "xla_call"):
            return self._closed(p["jaxpr"], in_taint, inside_pallas)
        if name == "shard_map":
            # tensor-parallel body (jax.experimental.shard_map): the
            # inner jaxpr sees per-shard shapes but identical positional
            # structure, so taint maps through unchanged. The param is an
            # open Jaxpr on current jax; handle ClosedJaxpr too.
            j = p["jaxpr"]
            if hasattr(j, "jaxpr"):
                return self._closed(j, in_taint, inside_pallas)
            return self.walk(j, in_taint, [False] * len(j.constvars),
                             inside_pallas=inside_pallas)
        if name == "scan":
            # invars = consts ++ carry ++ xs; inner sees xs minus the
            # leading scan axis — positions are unchanged
            out = self._closed(p["jaxpr"], in_taint, inside_pallas)
            return out
        if name == "while":
            nc, nb = p["cond_nconsts"], p["body_nconsts"]
            carry = in_taint[nc + nb:]
            self._closed(p["cond_jaxpr"], in_taint[:nc] + carry,
                         inside_pallas)
            return self._closed(p["body_jaxpr"],
                                in_taint[nc:nc + nb] + carry,
                                inside_pallas)
        if name == "cond":
            ops = in_taint[1:]          # invars = [branch index] ++ operands
            outs = [self._closed(br, ops, inside_pallas)
                    for br in p["branches"]]
            return [any(ts) for ts in zip(*outs)] if outs else []
        if name in ("custom_jvp_call", "custom_vjp_call",
                    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"):
            inner = p.get("call_jaxpr") or p.get("fun_jaxpr")
            if inner is not None:
                return self._closed(inner, in_taint, inside_pallas)
        if name in ("remat", "remat2", "checkpoint"):
            return self._closed(p["jaxpr"], in_taint, inside_pallas) \
                if hasattr(p.get("jaxpr"), "jaxpr") else \
                self.walk(p["jaxpr"], in_taint, [], inside_pallas)
        return None


def audit_program(spec: ProgramSpec, *,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  meta_decode_sources: Optional[Tuple[str, ...]] = None
                  ) -> Tuple[List[Finding], int]:
    """Audit one hot program: trace, walk, and (optionally) count the
    jit signatures its real shape set produces. Returns (findings,
    n_signatures)."""
    if meta_decode_sources is None:
        from repro.kernels.ops import META_DECODE_SOURCES
        meta_decode_sources = META_DECODE_SOURCES
    if not spec.shape_set:
        raise ValueError(f"program {spec.name}: empty shape_set")

    aud = _Auditor(spec.name, spec.page_size, vmem_budget,
                   meta_decode_sources)
    closed = jax.make_jaxpr(spec.fn)(*spec.shape_set[0])
    leaves, _ = jax.tree_util.tree_flatten(spec.shape_set[0])
    taint_in = [hasattr(l, "dtype") and str(l.dtype) in _PACKED_DTYPES
                for l in leaves]
    aud._closed(closed, taint_in, inside_pallas=False)

    sigs = {call_signature(args) for args in spec.shape_set}
    if spec.audit_cache and len(sigs) > 1:
        aud.findings.append(Finding(
            check=JX_COMPILE_CACHE, file="", line=0, program=spec.name,
            message=f"{len(sigs)} distinct jit signatures across the "
                    f"engine's shape set ({len(spec.shape_set)} calls) — "
                    f"a hot program must trace exactly once"))
    return aud.findings, len(sigs)


def audit_all(specs: Sequence[ProgramSpec], *,
              vmem_budget: int = DEFAULT_VMEM_BUDGET
              ) -> Tuple[List[Finding], dict]:
    """Audit every registered program. Returns (findings, counters) where
    counters carries the compile-cache stats surfaced in BENCH blobs:
    {"programs_traced": N, "jaxprs_per_program": {name: n_sigs}}."""
    findings: List[Finding] = []
    per_program: Dict[str, int] = {}
    for spec in specs:
        fs, nsig = audit_program(spec, vmem_budget=vmem_budget)
        findings.extend(fs)
        per_program[spec.name] = nsig
    return findings, {"programs_traced": len(specs),
                      "jaxprs_per_program": per_program}

"""The registered hot programs the jaxpr auditor traces.

Everything here is *abstract*: params and cache/store pytrees are built
with `jax.eval_shape` over the real constructors, and every traced
argument is a `jax.ShapeDtypeStruct` — registering a program costs a
trace, never a FLOP or a device buffer. The geometry mirrors the serving
benchmarks (reduced tinyllama, page_size 16, chunked prefill) so the
audited jaxprs are the ones the engines actually run, with
`impl="pallas"` so the fused kernels' `pallas_call`s (grid, block
shapes, VMEM footprint) are visible to the checks.

Programs:
  decode_step.scan     DecodeEngine's jitted `lax.scan` decode loop
                       (contiguous packed cache).
  decode_step.paged    ContinuousBatchingEngine's per-token step over
                       the paged store (page_size declared: JX104).
  prefill_chunk        the PrefillScheduler's single chunk program; its
                       shape set comes from *driving the real packer*
                       over a ragged prompt mix, so JX106 asserts what
                       the compile-count regression test asserts — one
                       signature for every join pattern.
  decode_replay        requeue-resume teacher-forced replay. Registered
                       with audit_cache=False: it legitimately retraces
                       per recorded-token count (cold path, once per
                       preemption) — but it still declares page_size so
                       JX104 pins `attn_bk == page_size` on its
                       contiguous planes (replay reads must tile exactly
                       like the paged reads that produced the tokens).
  ops.*                each kernels/ops.py dispatcher standalone, with
                       engine-shaped packed planes.
  decode_step.paged_tp2 / prefill_chunk_tp2
                       tensor-parallel (tp=2 shard_map over a
                       ("data","model") mesh) variants of the paged step
                       and chunk programs; registered only when >= 2
                       devices are visible (the multidevice CI job).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_audit import ProgramSpec
from repro.core.quantizer import QScale
from repro.core.sparq import SparqConfig
from repro.models.cache import CacheConfig
from repro.models.paging import ChunkMeta

# serving geometry (mirrors benchmarks/run.py's paged scenarios)
PAGE_SIZE = 16
N_PAGES = 24
MAX_ACTIVE = 4
MAX_SEQ_LEN = 80
CHUNK = 32
ALIGN = 8


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _codec() -> SparqConfig:
    return SparqConfig.opt5(signed=True)


@functools.lru_cache(maxsize=1)
def _model():
    from repro.configs.base import get_reduced_config
    from repro.models.model import Model
    cfg = get_reduced_config("tinyllama-1.1b").replace(
        dtype=jnp.float32, remat=False)
    return Model(cfg)


def _scan_engine_specs(model, params) -> List[ProgramSpec]:
    from repro.launch.serve import DecodeEngine
    cc = CacheConfig.sparq_cache(_codec(), impl="pallas")
    eng = DecodeEngine(model, cc)
    B, L = 2, 64
    caches = jax.eval_shape(
        functools.partial(model.init_cache, B, L, cache_cfg=cc))
    args = (params, _sds((B, 1), jnp.int32), caches, _sds((), jnp.int32))
    fn = functools.partial(eng._decode_fn, steps=4)
    return [ProgramSpec("decode_step.scan", fn, [args, args])]


def _paged_engine_specs(model, params) -> List[ProgramSpec]:
    from repro.launch.serve import ContinuousBatchingEngine
    cc = dataclasses.replace(
        CacheConfig.sparq_cache(_codec(), impl="pallas"),
        attn_bk=PAGE_SIZE)
    eng = ContinuousBatchingEngine(
        model, cc, page_size=PAGE_SIZE, n_pages=N_PAGES,
        max_active=MAX_ACTIVE, max_seq_len=MAX_SEQ_LEN,
        prefill="chunked", chunk_size=CHUNK, chunk_align=ALIGN)
    stores = jax.eval_shape(eng._init_stores)
    specs: List[ProgramSpec] = []

    step_args = (params, _sds((MAX_ACTIVE, 1), jnp.int32), stores,
                 _sds((MAX_ACTIVE,), jnp.int32))
    specs.append(ProgramSpec("decode_step.paged", eng._step_fn,
                             [step_args, step_args],
                             page_size=PAGE_SIZE))

    # chunk shape set: drive the real packer over a ragged prompt mix
    # (multi-chunk prompts, mid-chunk joins, a sub-segment stub) — every
    # planned chunk must map to the same jit signature
    sched = eng._sched
    n_blocks = MAX_SEQ_LEN // PAGE_SIZE
    host_bt = np.full((MAX_ACTIVE, n_blocks), -1, np.int64)
    next_page = [0]

    def grant(slot, blocks):
        for b in blocks:
            host_bt[slot, b] = next_page[0]
            next_page[0] += 1

    for slot, n_tok in enumerate([17, 33, 46, 9]):
        sched.add(slot, slot, np.arange(n_tok, dtype=np.int64) % 7)
    chunk_set = []
    while True:
        plan = sched.plan(lambda: N_PAGES, grant, host_bt)
        if plan is None:
            break
        meta = ChunkMeta(
            seq_id=_sds(plan.seq_id.shape, jnp.int32),
            pos=_sds(plan.pos.shape, jnp.int32),
            hist=_sds(plan.hist.shape, jnp.int32),
            tile_seq=_sds(plan.tile_seq.shape, jnp.int32),
            seq_pos_after=_sds((MAX_ACTIVE,), jnp.int32))
        chunk_set.append((params, _sds((1, CHUNK), jnp.int32), stores,
                          meta, _sds((MAX_ACTIVE,), jnp.int32)))
    assert chunk_set, "packer produced no chunks — registry bug"
    specs.append(ProgramSpec("prefill_chunk", sched._chunk_fn, chunk_set,
                             page_size=PAGE_SIZE))

    # replay: shape per recorded-token count — audit_cache=False, but
    # JX104 still pins the replay tile to the page size (_cc_replay)
    replay_caches = jax.eval_shape(functools.partial(
        model.init_cache, 1, 48, cache_cfg=eng._cc_replay))
    replay_set = [(params, _sds((1, n), jnp.int32), replay_caches,
                   _sds((), jnp.int32)) for n in (4, 7)]
    specs.append(ProgramSpec("decode_replay", eng._replay_fn, replay_set,
                             page_size=PAGE_SIZE, audit_cache=False))
    return specs


def _tp_engine_specs(model, params) -> List[ProgramSpec]:
    """Tensor-parallel variants of the paged hot programs (tp=2 over a
    ("data","model") host mesh) so JX101-JX106 gate the shard_map'd
    decode step and prefill chunk too — the auditor walks into the
    shard_map body (per-shard pools: KV/tp head groups). Registered only
    when the process actually has >= 2 devices (the multidevice CI job
    forces 8 on CPU); on a single-device run the sharded programs cannot
    even build a mesh, and the plain-jit programs above still audit the
    identical kernel bodies."""
    if len(jax.devices()) < 2:
        return []
    from repro.launch.mesh import make_tp_mesh
    from repro.launch.serve import ContinuousBatchingEngine
    cc = dataclasses.replace(
        CacheConfig.sparq_cache(_codec(), impl="pallas"),
        attn_bk=PAGE_SIZE)
    eng = ContinuousBatchingEngine(
        model, cc, page_size=PAGE_SIZE, n_pages=N_PAGES,
        max_active=MAX_ACTIVE, max_seq_len=MAX_SEQ_LEN,
        prefill="chunked", chunk_size=CHUNK, chunk_align=ALIGN,
        mesh=make_tp_mesh(2))
    stores = jax.eval_shape(eng._init_stores)
    specs: List[ProgramSpec] = []

    step_args = (params, _sds((MAX_ACTIVE, 1), jnp.int32), stores,
                 _sds((MAX_ACTIVE,), jnp.int32))
    specs.append(ProgramSpec("decode_step.paged_tp2", eng._step_fn,
                             [step_args, step_args],
                             page_size=PAGE_SIZE))

    meta = ChunkMeta(
        seq_id=_sds((CHUNK,), jnp.int32), pos=_sds((CHUNK,), jnp.int32),
        hist=_sds((CHUNK,), jnp.int32),
        tile_seq=_sds((CHUNK // ALIGN,), jnp.int32),
        seq_pos_after=_sds((MAX_ACTIVE,), jnp.int32))
    chunk_args = (params, _sds((1, CHUNK), jnp.int32), stores, meta,
                  _sds((MAX_ACTIVE,), jnp.int32))
    specs.append(ProgramSpec("prefill_chunk_tp2", eng._sched._chunk_fn,
                             [chunk_args, chunk_args],
                             page_size=PAGE_SIZE))
    return specs


def _dispatcher_specs(model) -> List[ProgramSpec]:
    from repro.kernels import ops
    cfg = model.cfg
    codec = _codec()
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    H = cfg.n_heads
    P, ps, NB, S = 8, PAGE_SIZE, MAX_SEQ_LEN // PAGE_SIZE, MAX_ACTIVE
    i8, i32, f32 = jnp.int8, jnp.int32, jnp.float32
    specs: List[ProgramSpec] = []

    def qm(x, w_codes, scale, chan_scale):
        return ops.quantized_matmul(
            x, w_codes, QScale(scale=scale, bits=codec.bits, signed=True),
            chan_scale, codec, impl="pallas")

    specs.append(ProgramSpec("ops.quantized_matmul", qm, [(
        _sds((8, 64), f32), _sds((64, 32), i8), _sds((), f32),
        _sds((32,), f32))]))

    def quant(x, scale):
        return ops.sparq_quantize(
            x, QScale(scale=scale, bits=codec.bits, signed=True), codec,
            impl="pallas", bm=16)

    specs.append(ProgramSpec("ops.sparq_quantize", quant,
                             [(_sds((32, 64), f32), _sds((), f32))]))

    dequant = functools.partial(ops.sparq_dequantize, impl="pallas", bm=16)
    specs.append(ProgramSpec("ops.sparq_dequantize", dequant,
                             [(_sds((32, 64), i8), _sds((32, 64), i8))]))

    decode = functools.partial(ops.sparq_decode_attention,
                               impl="pallas", bk=PAGE_SIZE)
    plane = _sds((2, 32, KV, hd), i8)
    specs.append(ProgramSpec("ops.sparq_decode_attention", decode, [(
        _sds((2, 1, H, hd), f32), plane, plane, _sds((), f32),
        plane, plane, _sds((), f32), _sds((2, 32), i32),
        _sds((), i32))]))

    chunked = functools.partial(ops.sparq_chunked_prefill_attention,
                                impl="pallas", bq=ALIGN)
    pool = _sds((P, ps, KV * hd), i8)          # lane-dense page pool
    specs.append(ProgramSpec(
        "ops.sparq_chunked_prefill_attention", chunked,
        [(_sds((CHUNK, H, hd), f32), _sds((CHUNK, KV, hd), f32),
          _sds((CHUNK, KV, hd), f32), pool, pool, _sds((S,), f32),
          pool, pool, _sds((S,), f32), _sds((S, NB), i32),
          _sds((CHUNK,), i32), _sds((CHUNK,), i32), _sds((CHUNK,), i32),
          _sds((CHUNK // ALIGN,), i32))],
        page_size=PAGE_SIZE))

    paged = functools.partial(ops.sparq_paged_decode_attention,
                              impl="pallas")
    specs.append(ProgramSpec(
        "ops.sparq_paged_decode_attention", paged,
        [(_sds((S, 1, H, hd), f32), pool, pool, _sds((S,), f32),
          pool, pool, _sds((S,), f32), _sds((S, NB), i32),
          _sds((S,), i32))],
        page_size=PAGE_SIZE))

    audited = {s.name.split(".", 1)[1] for s in specs}
    missing = set(ops.HOT_DISPATCHERS) - audited
    assert not missing, f"dispatchers registered but not audited: {missing}"
    return specs


def default_programs() -> List[ProgramSpec]:
    """Every registered hot program, traced abstractly."""
    model = _model()
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    specs: List[ProgramSpec] = []
    specs += _scan_engine_specs(model, params)
    specs += _paged_engine_specs(model, params)
    specs += _tp_engine_specs(model, params)
    specs += _dispatcher_specs(model)
    return specs

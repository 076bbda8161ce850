"""Chunked ragged prefill: kernel grid, direct-write path, engine
equality, compile-count guard, and the preemption cost model.

What "exact" means here, layer by layer:

  kernel     ref vs pallas-interpret agree to a couple of f32 ulps
             (the oracle's page stage contracts with batched dots, which
             XLA:CPU sums in another order than the kernel's 2-D tile
             dots; the in-chunk stage, where both use the 2-D form, is
             bitwise) and both match a dense float oracle. The oracle's
             in-chunk stage copies the kernel's per-head 2-D dot shapes
             to be bitwise, so the dense oracle is the independent
             witness; the same dot-order choice is why flash prefill and
             the chunk path differ by an f32 ulp on XLA:CPU, which keeps
             test_write_chunk_bytes_match_adopt_prefill red;
             masking structure (padding rows, page bounds, windows) is
             asserted exactly.
  bytes      a prompt prefilled through one chunk writes bit-identical
             §5.1 page bytes, scales, and positions to the sequential
             contiguous-prefill + adopt_prefill path.
  tokens     greedy tokens are bit-identical between --prefill
             sequential and --prefill chunked whenever prompts fit one
             segment, for the plain-int8 grid and the 4-bit 5opt codec,
             across a ragged staggered-arrival trace; multi-segment
             prompts are *packing-invariant* (identical tokens under any
             chunk size / slot count / join pattern at a fixed segment
             quantum), which is what requeue-replay resume relies on.
  compiles   the chunk program traces exactly once across any mix of
             prompt lengths (the per-length-retrace regression guard).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparq import SparqConfig
from repro.models.cache import CacheConfig

KEY = jax.random.PRNGKey(0)
PS = 4                                  # page size for every engine test


def _cc(codec=None):
    codec = codec or SparqConfig.opt5(signed=True)
    return dataclasses.replace(
        CacheConfig.sparq_cache(codec, impl="reference"), attn_bk=PS)


@pytest.fixture(scope="module")
def tiny_lm():
    from repro.configs.base import get_reduced_config
    from repro.models.model import Model
    cfg = get_reduced_config("tinyllama-1.1b").replace(
        dtype=jnp.float32, remat=False)
    model = Model(cfg)
    params = model.init_params(KEY)
    return model, params


# ----------------------------------------------------------------------
# cost model: requeue-vs-swap crossover (SchedulerPolicy.estimate_cost)
# ----------------------------------------------------------------------

def test_cost_model_crossover_is_pinned():
    """Requeue cost grows with decode progress (sequential replay steps),
    swap cost is flat in progress (bytes only): the crossover sits where
    replay_tok_us * (generated-1) overtakes the byte cost, and --preempt
    auto must flip exactly there."""
    from repro.launch.serve import SchedulerPolicy
    pol = SchedulerPolicy(preempt="auto", prefill_tok_us=1.0,
                          replay_tok_us=100.0, swap_gb_s=10.0)
    L, swap_bytes = 50, 500_000
    # swap cost: 2 * 5e5 B / (10 GB/s) = 100 us, flat in `generated`
    req1, swap1 = pol.estimate_cost(L, 1, swap_bytes)
    reqN, swapN = pol.estimate_cost(L, 5, swap_bytes)
    assert swap1 == swapN == pytest.approx(100.0)
    assert req1 == pytest.approx(50.0) and reqN == pytest.approx(450.0)
    # crossover: requeue(g) = 50 + 100*(g-1) crosses 100 between g=1, g=2
    assert pol.resolve(L, 1, swap_bytes) == "requeue"
    assert pol.resolve(L, 2, swap_bytes) == "swap"
    # monotone in generated
    costs = [pol.estimate_cost(L, g, swap_bytes)[0] for g in range(1, 6)]
    assert costs == sorted(costs)
    # fixed modes ignore the model
    assert SchedulerPolicy(preempt="requeue").resolve(L, 99, 1) == "requeue"
    assert SchedulerPolicy(preempt="swap").resolve(L, 1, 10**9) == "swap"


# ----------------------------------------------------------------------
# kernel grid: ref vs pallas-interpret vs dense float oracle
# ----------------------------------------------------------------------

def _build_pool(rng, cfg, S, P, NB, ps, KV, hd, cached):
    """Quantize `cached[s]` float K/V through the §5.1 codec into pool
    pages (block-table rows in order), returning the packed planes, the
    per-slot scales/tables, and the dequantized float planes (what the
    meta-decode reconstructs) for the dense oracle."""
    from repro.kernels import ref as R
    from repro.kernels.ops import sparq_pack
    kw = dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
              vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
              enabled=cfg.enabled)
    planes = {n: np.zeros((P, ps, KV * hd), np.int8)   # lane-dense pool
              for n in ("kd", "km", "vd", "vm")}
    scales = {n: np.zeros(S, np.float32) for n in ("k", "v")}
    bt = -np.ones((S, NB), np.int64)
    deq = {}
    next_page = 1                       # page 0 stays dead (clamp target)
    for s, (xk, xv) in cached.items():
        n_tok = xk.shape[0]
        npages = math.ceil(n_tok / ps)
        pad = npages * ps - n_tok
        xk = np.concatenate([xk, np.zeros((pad, KV, hd), np.float32)])
        xv = np.concatenate([xv, np.zeros((pad, KV, hd), np.float32)])
        deq[s] = {}
        for name, x in (("k", xk), ("v", xv)):
            sc = max(np.abs(x).max(), 1e-8) / cfg.max_val
            scales[name][s] = sc
            codes, meta = R.ref_sparq_quant(jnp.asarray(x), sc, **kw)
            data = np.asarray(sparq_pack(codes, meta))
            meta = np.asarray(meta)
            for b in range(npages):
                pg = next_page + b
                rows = slice(b * ps, (b + 1) * ps)
                planes[name + "d"][pg] = data[rows].reshape(ps, KV * hd)
                planes[name + "m"][pg] = meta[rows].reshape(ps, KV * hd)
            deq[s][name] = (np.asarray(R.ref_sparq_dequant(
                jnp.asarray(data), jnp.asarray(meta))).astype(np.float32)
                * sc)[:n_tok]
        bt[s, :npages] = np.arange(next_page, next_page + npages)
        next_page += npages
    assert next_page <= P
    return planes, scales, bt, deq


def _dense_oracle(q, kc, vc, deq, seq_id, pos, hist, KV, G, hd, window):
    """Per-token full-softmax attention over dequantized pages below
    `hist` plus float chunk keys in [hist, pos]."""
    C = q.shape[0]
    out = np.zeros((C, KV, G, hd), np.float32)
    for i in range(C):
        s = seq_id[i]
        if s < 0:
            continue
        keys, vals, kp = [], [], []
        if s in deq:
            h = min(hist[i], deq[s]["k"].shape[0])
            keys.append(deq[s]["k"][:h])
            vals.append(deq[s]["v"][:h])
            kp.append(np.arange(h))
        m = (seq_id == s) & (pos <= pos[i]) & (pos >= hist[i])
        keys.append(kc[m])
        vals.append(vc[m])
        kp.append(pos[m])
        K = np.concatenate(keys)
        V = np.concatenate(vals)
        KP = np.concatenate(kp)
        if window:
            K, V = K[KP > pos[i] - window], V[KP > pos[i] - window]
        qi = q[i].reshape(KV, G, hd)
        s_ = np.einsum("kgh,tkh->kgt", qi, K) * hd ** -0.5
        p = np.exp(s_ - s_.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("kgt,tkh->kgh", p, V)
    return out


@pytest.mark.parametrize("vsparq", [True, False], ids=["vsparq", "plain"])
@pytest.mark.parametrize("window", [0, 5], ids=["full", "win5"])
def test_chunked_prefill_kernel_grid(vsparq, window):
    """Ragged chunk over a §5.1 page pool: sequence continuing mid-page
    (run straddles a page boundary), a second sequence resuming at a
    segment boundary, a fresh sequence, and padding — ref vs interpret
    vs the dense dequantize-everything oracle."""
    from repro.kernels.ops import sparq_chunked_prefill_attention
    rng = np.random.default_rng(0)
    S, NB, ps, KV, G, hd = 3, 4, 4, 2, 2, 8
    P, C, bq = 8, 16, 4
    cfg = dataclasses.replace(SparqConfig.opt5(signed=True), vsparq=vsparq)
    # slot 0: 7 cached tokens (page boundary straddled at 4); slot 1: 4
    cached = {0: (rng.standard_normal((7, KV, hd)).astype(np.float32),
                  rng.standard_normal((7, KV, hd)).astype(np.float32)),
              1: (rng.standard_normal((4, KV, hd)).astype(np.float32),
                  rng.standard_normal((4, KV, hd)).astype(np.float32))}
    planes, scales, bt, deq = _build_pool(
        rng, cfg, S, P, NB, ps, KV, hd, cached)
    # stream: slot 0 continues at pos 7..12 (hist 7: cached history),
    # slot 1 at 4..7 (hist 4), slot 2 fresh 0..2 (hist 0), 1 pad tile
    seq_id = np.full(C, -1, np.int64)
    pos = np.zeros(C, np.int64)
    hist = np.zeros(C, np.int64)
    tile_seq = np.array([0, 0, 1, 2], np.int64)
    seq_id[0:6], pos[0:6], hist[0:6] = 0, np.arange(7, 13), 7
    seq_id[8:12], pos[8:12], hist[8:12] = 1, np.arange(4, 8), 4
    seq_id[12:15], pos[12:15], hist[12:15] = 2, np.arange(0, 3), 0
    tile_seq = np.array([0, 0, 1, 2], np.int64)
    q = rng.standard_normal((C, KV * G, hd)).astype(np.float32)
    kc = rng.standard_normal((C, KV, hd)).astype(np.float32)
    vc = rng.standard_normal((C, KV, hd)).astype(np.float32)

    def run(impl):
        return np.asarray(sparq_chunked_prefill_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(planes["kd"]), jnp.asarray(planes["km"]),
            jnp.asarray(scales["k"]),
            jnp.asarray(planes["vd"]), jnp.asarray(planes["vm"]),
            jnp.asarray(scales["v"]),
            jnp.asarray(bt, jnp.int32), jnp.asarray(seq_id, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(hist, jnp.int32),
            jnp.asarray(tile_seq, jnp.int32), window=window, impl=impl,
            bq=bq))

    o_ref, o_pal = run("reference"), run("pallas")
    # ref and interpret-mode pallas walk the same stage order and f32
    # update arithmetic; XLA's fusion of the scanned oracle reorders the
    # multiply-add chain by at most a couple of ulps
    np.testing.assert_allclose(o_ref, o_pal, atol=5e-6, rtol=1e-5)
    dense = _dense_oracle(q, kc, vc, deq, seq_id, pos, hist,
                          KV, G, hd, window).reshape(C, KV * G, hd)
    for o in (o_ref, o_pal):
        np.testing.assert_allclose(o, dense, atol=1e-4, rtol=1e-4)
        # masking structure is exact: padding rows are exactly zero
        assert (o[seq_id < 0] == 0).all()


def test_chunked_kernel_chunk_only_bitwise():
    """With no cached pages (hist == 0 everywhere) the kernel reduces to
    segment-masked causal attention over float K/V — there ref and
    interpret-mode pallas agree bit for bit."""
    from repro.kernels.ops import sparq_chunked_prefill_attention
    rng = np.random.default_rng(1)
    S, NB, ps, KV, G, hd = 3, 4, 4, 2, 2, 8
    P, C, bq = 6, 16, 4
    z8 = jnp.zeros((P, ps, KV * hd), jnp.int8)
    sc = jnp.full((S,), 0.01, jnp.float32)
    bt = jnp.full((S, NB), -1, jnp.int32)
    seq_id = np.repeat(np.arange(4), 4)
    seq_id[seq_id == 3] = -1
    pos = np.tile(np.arange(4), 4)
    tile_seq = np.array([0, 1, 2, -1])
    q = jnp.asarray(rng.standard_normal((C, KV * G, hd)).astype(np.float32))
    kc = jnp.asarray(rng.standard_normal((C, KV, hd)).astype(np.float32))
    vc = jnp.asarray(rng.standard_normal((C, KV, hd)).astype(np.float32))

    def run(impl):
        return np.asarray(sparq_chunked_prefill_attention(
            q, kc, vc, z8, z8, sc, z8, z8, sc, bt,
            jnp.asarray(seq_id, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.zeros(C, jnp.int32), jnp.asarray(tile_seq, jnp.int32),
            impl=impl, bq=bq))

    a, b = run("reference"), run("pallas")
    np.testing.assert_array_equal(a, b)
    assert (a[seq_id < 0] == 0).all()


# ----------------------------------------------------------------------
# direct write path: one chunk == contiguous prefill + adopt, byte-level
# ----------------------------------------------------------------------

def test_write_chunk_bytes_match_adopt_prefill(tiny_lm):
    """A whole prompt through one chunk writes bit-identical page bytes,
    frozen scales, and positions to the sequential contiguous-prefill +
    adopt_prefill path, and emits the same greedy tok0 — the direct-write
    §5.1 path is a true replacement, not an approximation."""
    from repro.models import paging
    model, params = tiny_lm
    cfg = model.cfg
    cc = _cc()
    S, NPAGES, NB, L = 2, 8, 4, 11
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, (L,))
    nbp = math.ceil(L / PS)

    def stores():
        out = []
        for kind, count in model.groups_meta:
            one = paging.PagedCacheStore.init(
                S, NPAGES, PS, NB, cfg.n_kv_heads, cfg.head_dim, cc)
            out.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (count,) + x.shape).copy(),
                one))
        return out

    # sequential: contiguous prefill + page adoption
    caches_a = stores()
    tmp = model.init_cache(1, nbp * PS, cache_cfg=cc)
    logits, tmp = model.prefill(params, {"tokens": jnp.asarray(toks)[None]},
                                tmp)
    tok0_a = int(np.asarray(jnp.argmax(logits, -1))[0])
    pages = jnp.arange(nbp, dtype=jnp.int32)
    caches_a = [paging.adopt_prefill(c, t, jnp.int32(0), pages)
                for c, t in zip(caches_a, tmp)]

    # chunked: one chunk covering the prompt, written straight to pages
    C, bq = 16, 4
    stream = np.zeros(C, np.int64)
    stream[:L] = toks
    seq_id = np.full(C, -1, np.int64)
    seq_id[:L] = 0
    pos = np.zeros(C, np.int64)
    pos[:L] = np.arange(L)
    tile_seq = np.full(C // bq, -1, np.int64)
    tile_seq[:math.ceil(L / bq)] = 0
    caches_b = stores()
    bt = np.full((S, NB), -1, np.int64)
    bt[0, :nbp] = np.arange(nbp)
    bt_dev = jnp.asarray(bt, jnp.int32)
    caches_b = [dataclasses.replace(
        c, block_table=jnp.broadcast_to(bt_dev, c.block_table.shape))
        for c in caches_b]
    meta = paging.ChunkMeta(
        seq_id=jnp.asarray(seq_id, jnp.int32),
        pos=jnp.asarray(pos, jnp.int32),
        hist=jnp.zeros(C, jnp.int32),
        tile_seq=jnp.asarray(tile_seq, jnp.int32),
        seq_pos_after=jnp.asarray([L, -1], jnp.int32))
    tok0_b, caches_b = model.prefill_chunk(
        params, jnp.asarray(stream)[None], caches_b, meta,
        jnp.asarray([L - 1, -1], jnp.int32))

    assert tok0_a == int(np.asarray(tok0_b)[0])
    for ca, cb in zip(caches_a, caches_b):
        for name in ("k_data", "k_meta", "v_data", "v_meta"):
            a = np.asarray(getattr(ca, name))[:, :nbp]
            b = np.asarray(getattr(cb, name))[:, :nbp]
            # only rows < L are logical; rows past the prompt are zero
            # init on both paths
            np.testing.assert_array_equal(
                a.reshape(a.shape[0], nbp * PS, *a.shape[3:])[:, :L],
                b.reshape(b.shape[0], nbp * PS, *b.shape[3:])[:, :L],
                err_msg=name)
        for name in ("k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ca, name))[:, 0],
                np.asarray(getattr(cb, name))[:, 0], err_msg=name)
        np.testing.assert_array_equal(np.asarray(ca.seq_pos),
                                      np.asarray(cb.seq_pos))


# ----------------------------------------------------------------------
# engine: chunked == sequential tokens; packing invariance; compile guard
# ----------------------------------------------------------------------

def _trace(model, seed=7):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    lens = [5, 11, 3, 9, 14, 6]
    gens = [7, 5, 9, 6, 4, 8]
    arr = [0, 0, 2, 3, 5, 7]
    return [Request(rng.integers(0, model.cfg.vocab_size, (L,)), g,
                    arrive_at=a) for L, g, a in zip(lens, gens, arr)]


@pytest.mark.parametrize("codec", ["a8w8", "5opt"])
def test_chunked_prefill_token_equality(tiny_lm, codec):
    """Acceptance: greedy tokens bit-identical between --prefill
    sequential and --prefill chunked across a ragged staggered-arrival
    trace, for the plain-int8 grid and the 4-bit 5opt codec. Chunk size
    16 >= every prompt (single-segment regime: the guaranteed-exact
    window); runs straddle page boundaries (PS=4) throughout."""
    from repro.launch.serve import ContinuousBatchingEngine
    model, params = tiny_lm
    cc = _cc(SparqConfig(enabled=False, signed=True) if codec == "a8w8"
             else None)
    reqs = _trace(model)
    res_seq, _ = ContinuousBatchingEngine(
        model, cc, page_size=PS, n_pages=24, max_active=3,
        max_seq_len=24).run(params, reqs)
    res_ch, stats = ContinuousBatchingEngine(
        model, cc, page_size=PS, n_pages=24, max_active=3, max_seq_len=24,
        prefill="chunked", chunk_size=16, chunk_align=4).run(params, reqs)
    for rid in res_seq:
        np.testing.assert_array_equal(res_seq[rid], res_ch[rid])
    assert stats["prefill_chunks"] > 0
    assert stats["prefill_compile_count"] == 1


def test_multi_segment_prompts_are_packing_invariant(tiny_lm):
    """Prompts longer than the segment quantum attend their earlier
    segments through packed pages. Whole-segment packing makes the
    float-vs-packed split a function of (prompt, seg) only, so tokens
    must be identical under different chunk sizes, slot counts, and the
    resulting completely different stream packings."""
    from repro.launch.serve import ContinuousBatchingEngine
    model, params = tiny_lm
    reqs = _trace(model)
    outs = []
    for max_active, chunk in ((3, 16), (1, 16), (2, 24)):
        res, stats = ContinuousBatchingEngine(
            model, _cc(), page_size=PS, n_pages=24, max_active=max_active,
            max_seq_len=24, prefill="chunked", chunk_size=chunk,
            chunk_align=4, chunk_seg=8).run(params, reqs)
        assert stats["prefill_compile_count"] == 1
        outs.append(res)
    for res in outs[1:]:
        for rid in outs[0]:
            np.testing.assert_array_equal(outs[0][rid], res[rid])


def test_scale_freezes_from_first_segment_not_first_chunk(tiny_lm):
    """Regression (found in review): one 3-segment prompt, chunk sizes
    that place one / two / all three of its segments into the first
    chunk. The frozen quantization scale must come from the FIRST
    SEGMENT's dynamic range only — were it taken from whatever tokens
    share the first chunk (as an earlier draft did), the cache bytes and
    greedy tokens would differ across these packings."""
    from repro.launch.serve import ContinuousBatchingEngine, Request
    model, params = tiny_lm
    rng = np.random.default_rng(7)
    req = [Request(rng.integers(0, model.cfg.vocab_size, (12,)), 6)]
    outs = []
    for chunk in (8, 12, 16):           # 2 / 3 / 3 segments per chunk
        res, _ = ContinuousBatchingEngine(
            model, _cc(), page_size=PS, n_pages=24, max_active=2,
            max_seq_len=24, prefill="chunked", chunk_size=chunk,
            chunk_align=4, chunk_seg=4).run(params, req)
        outs.append(res[0])
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_compile_count_regression_guard(tiny_lm):
    """One jitted chunk program for every prompt-length mix. The dynamic
    smoke runs one ragged trace and checks the live jit cache; the
    second-trace sweep this test used to run is now the jaxpr auditor's
    job — it drives the real packer over a ragged mix abstractly and
    pins one signature (JX106), so the static check covers every length
    mix at a fraction of the cost. The sequential path's per-length
    retraces must never silently return."""
    from repro.launch.serve import ContinuousBatchingEngine, Request
    model, params = tiny_lm
    rng = np.random.default_rng(11)
    eng = ContinuousBatchingEngine(
        model, _cc(), page_size=PS, n_pages=24, max_active=3,
        max_seq_len=24, prefill="chunked", chunk_size=16, chunk_align=4)
    mk = lambda L, g: Request(rng.integers(0, model.cfg.vocab_size, (L,)), g)
    _, st1 = eng.run(params, [mk(3, 4), mk(7, 3), mk(11, 2), mk(5, 3)])
    assert st1["prefill_compile_count"] == 1
    # static counterpart: abstract trace of the registry's ragged mix
    from repro.analysis import audit_all
    from repro.analysis.registry import default_programs
    findings, counters = audit_all(default_programs())
    assert counters["jaxprs_per_program"]["prefill_chunk"] == 1
    assert not [f for f in findings if f.check == "JX106"], \
        "chunked prefill retraced for a new prompt-length mix"
    # the sequential path, by contrast, is shape-specialized per length:
    # its admission prefill jit accumulates one entry per unique shape
    eng_seq = ContinuousBatchingEngine(
        model, _cc(), page_size=PS, n_pages=24, max_active=3,
        max_seq_len=24)
    eng_seq.run(params, [mk(3, 2), mk(7, 2), mk(11, 2)])
    assert eng_seq._prefill._cache_size() >= 3


# ----------------------------------------------------------------------
# chunked prefill x preemption: requeue replays through the chunked path
# ----------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["requeue", "swap", "auto"])
def test_chunked_prefill_with_preemption(tiny_lm, mode):
    """Oversubscribed pool with chunked admission: victims drop or swap
    pages mid-flight (including mid-prefill and mid-replay victims, which
    force requeue) and every request still reproduces the uncontended
    contiguous tokens exactly — requeue re-prefills through the chunked
    path and replays its recorded tokens in-band through the regular
    decode steps."""
    from repro.launch.serve import (ContinuousBatchingEngine, DecodeEngine,
                                    Request, SchedulerPolicy)
    model, params = tiny_lm
    rng = np.random.default_rng(0)
    lens = [5, 7, 3, 6, 8, 4]
    gens = [12, 8, 9, 10, 6, 11]
    arr = [0, 0, 2, 3, 5, 7]
    reqs = [Request(rng.integers(0, model.cfg.vocab_size, (L,)), g,
                    arrive_at=a) for L, g, a in zip(lens, gens, arr)]
    contig = DecodeEngine(model, _cc())
    oracle = {}
    for rid, r in enumerate(reqs):
        t, _ = contig.generate(
            params, {"tokens": jnp.asarray(r.tokens)[None]}, r.gen,
            warmup=False)
        oracle[rid] = np.asarray(t)[0]
    eng = ContinuousBatchingEngine(
        model, _cc(), page_size=PS, n_pages=6, max_active=3,
        max_seq_len=24, prefill="chunked", chunk_size=16, chunk_align=4,
        chunk_seg=8, policy=SchedulerPolicy(preempt=mode))
    results, stats = eng.run(params, reqs)
    assert stats["preemptions"] > 0
    if mode == "requeue":
        assert stats["replay_steps"] > 0
        assert stats["swap_bytes_out"] == 0
    for rid in oracle:
        np.testing.assert_array_equal(results[rid], oracle[rid])

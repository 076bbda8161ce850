"""Paged SPARQ KV-cache + continuous batching.

Covers: bit-identity of the block-table gather kernel against the
contiguous fused kernel (ref and pallas-interpret, full/partial block
tables, windowed = ring-style masking), PagedCacheStore write semantics
(page/offset addressing, per-slot scale freeze, trash-page isolation),
allocator edge cases (exhaustion raises host-side before tracing, the
used-set refcount guard, watermarks, page reuse after eviction is
bit-exact), swap round-trip byte identity (preemption's swap-out/swap-in
across the vsparq x signed x window grid), and the end-to-end acceptance:
the continuous-batching engine reproduces the contiguous scan engine's
greedy tokens for ragged requests on both the int8 grid and the 5opt
codec. Scheduler-level preemption traces live in tests/test_scheduler.py.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import QScale
from repro.core.sparq import SparqConfig
from repro.kernels import ops
from repro.models.cache import CacheConfig, CacheStore
from repro.models.paging import (PageAllocator, PagedCacheStore,
                                 PoolExhausted, SwapStore, adopt_prefill,
                                 evict_slot, gather_slot_pages,
                                 modeled_pool_bytes, paged_decode_attention,
                                 restore_slot_pages)

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------------
# kernel level: block-table gather vs contiguous fused decode
# ----------------------------------------------------------------------

def _packed_planes(rng, B, Tk, KV, hd, cfg, scale=0.02):
    x = jnp.asarray(rng.normal(size=(B, Tk, KV, hd)), jnp.float32)
    qs = QScale(scale=jnp.float32(scale), bits=8, signed=True)
    codes, meta = ops.sparq_quantize(x, qs, cfg, impl="reference")
    return ops.sparq_pack(codes, meta), meta


def _scatter_pool(rng, kd, km, vd, vm, ps):
    """Move contiguous [B, Tk, ...] planes into a pool with a scrambled
    per-sequence block table. Returns (pools..., block_table)."""
    B, Tk, KV, hd = kd.shape
    NB = Tk // ps
    P = B * NB + 2
    pages = rng.permutation(P)[: B * NB].reshape(B, NB)
    pool = lambda: np.zeros((P, ps, KV * hd), np.int8)   # lane-dense
    pk, pkm, pv, pvm = pool(), pool(), pool(), pool()
    row = lambda x, b, sl: np.asarray(x[b, sl]).reshape(ps, KV * hd)
    for b in range(B):
        for t in range(NB):
            sl = slice(t * ps, (t + 1) * ps)
            pk[pages[b, t]] = row(kd, b, sl)
            pkm[pages[b, t]] = row(km, b, sl)
            pv[pages[b, t]] = row(vd, b, sl)
            pvm[pages[b, t]] = row(vm, b, sl)
    return (jnp.asarray(pk), jnp.asarray(pkm), jnp.asarray(pv),
            jnp.asarray(pvm), jnp.asarray(pages, jnp.int32))


class TestPagedKernel:
    B, KV, G, hd, ps, NB = 3, 2, 4, 16, 8, 4

    @pytest.fixture(scope="class")
    def planes(self):
        rng = np.random.default_rng(0)
        cfg = SparqConfig.opt5(signed=True)
        Tk = self.NB * self.ps
        kd, km = _packed_planes(rng, self.B, Tk, self.KV, self.hd, cfg)
        vd, vm = _packed_planes(rng, self.B, Tk, self.KV, self.hd, cfg)
        q = jnp.asarray(rng.normal(size=(self.B, 1, self.KV * self.G,
                                         self.hd)), jnp.float32)
        pool = _scatter_pool(rng, kd, km, vd, vm, self.ps)
        return q, (kd, km, vd, vm), pool

    @pytest.mark.parametrize("cur,window", [(19, 0), (31, 0), (19, 12),
                                            (30, 12)])
    @pytest.mark.parametrize("impl", ["reference", "pallas"])
    def test_bit_identical_to_contiguous(self, planes, cur, window, impl):
        """One page == one Tk tile: with page_size == bk the gather path
        reproduces the contiguous fused kernel bit for bit (the windowed
        case is the ring cache's masking arithmetic — ring + paged
        composition at the kernel level)."""
        q, (kd, km, vd, vm), (pk, pkm, pv, pvm, bt) = planes
        Tk = kd.shape[1]
        s = jnp.float32(0.02)
        kpos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None],
                                (self.B, Tk))
        want = ops.sparq_decode_attention(
            q, kd, km, s, vd, vm, s, kpos, jnp.int32(cur),
            window=window, impl="reference", bk=self.ps)
        sv = jnp.full((self.B,), s)
        got = ops.sparq_paged_decode_attention(
            q, pk, pkm, sv, pv, pvm, sv, bt,
            jnp.full((self.B,), cur, jnp.int32), window=window, impl=impl)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_partial_block_table(self, planes):
        """Blocks past a sequence's length stay unallocated (-1): identical
        to the contiguous path as long as cur never reaches them."""
        q, (kd, km, vd, vm), (pk, pkm, pv, pvm, bt) = planes
        Tk = kd.shape[1]
        s = jnp.float32(0.02)
        cur = 2 * self.ps + 3                   # block 3 never touched
        bt2 = np.asarray(bt).copy()
        bt2[:, 3] = -1
        kpos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None],
                                (self.B, Tk))
        want = ops.sparq_decode_attention(
            q, kd, km, s, vd, vm, s, kpos, jnp.int32(cur),
            impl="reference", bk=self.ps)
        sv = jnp.full((self.B,), s)
        got = ops.sparq_paged_decode_attention(
            q, pk, pkm, sv, pv, pvm, sv, jnp.asarray(bt2),
            jnp.full((self.B,), cur, jnp.int32), impl="reference")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_ragged_cur_and_inactive_slots(self, planes):
        """Per-sequence cur: each row masks at its own length; an inactive
        slot (cur < 0) is fully masked and returns zeros."""
        q, (kd, km, vd, vm), (pk, pkm, pv, pvm, bt) = planes
        Tk = kd.shape[1]
        s = jnp.float32(0.02)
        curs = [19, -2, 7]
        sv = jnp.full((self.B,), s)
        got = ops.sparq_paged_decode_attention(
            q, pk, pkm, sv, pv, pvm, sv, bt,
            jnp.asarray(curs, jnp.int32), impl="reference")
        assert np.all(np.asarray(got)[1] == 0.0)
        kpos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None],
                                (self.B, Tk))
        for b in (0, 2):                        # rows agree with per-row cur
            want = ops.sparq_decode_attention(
                q, kd, km, s, vd, vm, s, kpos, jnp.int32(curs[b]),
                impl="reference", bk=self.ps)
            np.testing.assert_array_equal(np.asarray(want)[b],
                                          np.asarray(got)[b])


# ----------------------------------------------------------------------
# store level: write addressing, scales, adoption
# ----------------------------------------------------------------------

CC5 = CacheConfig.sparq_cache(SparqConfig.opt5(signed=True),
                              impl="reference")


class TestPagedCacheStore:
    def test_update_addresses_page_and_offset(self):
        st = PagedCacheStore.init(n_seqs=2, n_pages=4, page_size=4,
                                  n_blocks=3, kv_heads=2, head_dim=8, cc=CC5)
        st = dataclasses.replace(
            st,
            block_table=jnp.asarray([[2, 0, -1], [1, -1, -1]], jnp.int32),
            seq_pos=jnp.asarray([5, 2], jnp.int32),
            k_scale=jnp.asarray([0.1, 0.1]), v_scale=jnp.asarray([0.1, 0.1]))
        k = jnp.ones((2, 1, 2, 8)) * 0.3
        st2 = st.update(k, k)
        # seq 0: pos 5 -> block 1 (page 0), row 1; seq 1: pos 2 -> page 1
        assert np.any(np.asarray(st2.k_data[0, 1]) != 0)
        assert np.any(np.asarray(st2.k_data[1, 2]) != 0)
        np.testing.assert_array_equal(np.asarray(st2.seq_pos), [6, 3])
        # everything else untouched
        assert not np.any(np.asarray(st2.k_data[3]))

    def test_inactive_slot_writes_trash_page(self):
        st = PagedCacheStore.init(n_seqs=2, n_pages=3, page_size=4,
                                  n_blocks=2, kv_heads=2, head_dim=8, cc=CC5)
        st = dataclasses.replace(
            st, block_table=jnp.asarray([[0, -1], [-1, -1]], jnp.int32),
            seq_pos=jnp.asarray([1, -1], jnp.int32),
            k_scale=jnp.asarray([0.1, 0.0]), v_scale=jnp.asarray([0.1, 0.0]))
        x = jnp.ones((2, 1, 2, 8))
        st2 = st.update(x, x)
        trash = st.n_pages                      # last page index
        assert np.any(np.asarray(st2.k_data[trash]))    # inactive -> trash
        assert np.any(np.asarray(st2.k_data[0, 1]))     # active -> its page
        np.testing.assert_array_equal(np.asarray(st2.seq_pos), [2, -1])
        assert float(st2.k_scale[1]) == 0.0     # inactive scale untouched

    def test_per_slot_scale_freeze(self):
        st = PagedCacheStore.init(n_seqs=2, n_pages=3, page_size=4,
                                  n_blocks=2, kv_heads=2, head_dim=8, cc=CC5)
        st = dataclasses.replace(
            st, block_table=jnp.asarray([[0, -1], [1, -1]], jnp.int32),
            seq_pos=jnp.asarray([0, 0], jnp.int32),
            k_scale=jnp.asarray([0.5, 0.0]))    # slot 0 calibrated
        x = jax.random.normal(KEY, (2, 1, 2, 8))
        st2 = st.update(x, x)
        assert float(st2.k_scale[0]) == 0.5     # frozen
        assert float(st2.k_scale[1]) > 0        # calibrated from this write
        st3 = st2.update(10.0 * x, 10.0 * x)
        assert float(st3.k_scale[1]) == pytest.approx(float(st2.k_scale[1]))

    def test_adopt_prefill_copies_bytes_verbatim(self):
        """Adoption moves the contiguous cache's packed planes into pages
        without requantization: gathered pool bytes == contiguous bytes."""
        ps, nbp, L = 4, 3, 2                    # L = stacked layer count
        cs = CacheStore.init((1, nbp * ps, 2, 8), CC5)
        k = jax.random.normal(KEY, (1, 10, 2, 8))
        cs = cs.update(k, k * 0.5)
        cs_stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy(), cs)
        one = PagedCacheStore.init(n_seqs=2, n_pages=6, page_size=ps,
                                   n_blocks=4, kv_heads=2, head_dim=8,
                                   cc=CC5)
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy(), one)
        pages = jnp.asarray([4, 1, 3], jnp.int32)
        st2 = adopt_prefill(st, cs_stacked, jnp.int32(1), pages)
        got = np.asarray(st2.k_data[:, pages]).reshape(L, 1, nbp * ps, 2, 8)
        np.testing.assert_array_equal(got, np.asarray(cs_stacked.k.data))
        np.testing.assert_array_equal(np.asarray(st2.block_table[:, 1, :3]),
                                      np.asarray(pages)[None].repeat(L, 0))
        np.testing.assert_array_equal(np.asarray(st2.seq_pos[:, 1]),
                                      [10] * L)
        np.testing.assert_array_equal(np.asarray(st2.k_scale[:, 1]),
                                      np.asarray(cs_stacked.k.scale))
        # evict clears the slot
        st3 = evict_slot(st2, jnp.int32(1))
        assert np.all(np.asarray(st3.block_table[:, 1]) == -1)
        assert np.all(np.asarray(st3.seq_pos[:, 1]) == -1)
        assert np.all(np.asarray(st3.k_scale[:, 1]) == 0.0)

    def test_modeled_pool_bytes(self):
        st = PagedCacheStore.init(n_seqs=2, n_pages=3, page_size=4,
                                  n_blocks=2, kv_heads=2, head_dim=8, cc=CC5)
        tally = modeled_pool_bytes(st)
        n = 2 * (3 + 1) * 4 * 2 * 8             # k+v pools incl. trash page
        assert tally["values"] == n
        assert tally["data_bytes"] == pytest.approx(n * 0.5625)
        assert tally["ctrl_bytes"] == pytest.approx(n * 0.375)

    def test_fp_layout_rejected(self):
        with pytest.raises(ValueError, match="sparq"):
            PagedCacheStore.init(1, 2, 4, 2, 2, 8, CacheConfig.fp32())


# ----------------------------------------------------------------------
# swap round trip: preemption's swap-out -> swap-in is byte-verbatim
# ----------------------------------------------------------------------

class TestSwapRoundTrip:
    """Packed data/meta/scale planes survive a host swap round trip
    byte-identically, and fused paged decode over resumed pages matches
    the never-preempted oracle — across the vsparq x signed grid and for
    full-attention and windowed (ring-style) masking."""
    L, ps, KV, hd = 2, 4, 2, 8                  # stacked layers, geometry

    def _stacked(self, tree):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (self.L,) + a.shape).copy(), tree)

    def _filled_store(self, cfg, n_tok=10, seed=0):
        """Stacked paged store with slot 1 holding an adopted prefill."""
        cc = CacheConfig(layout="sparq", sparq=cfg, impl="reference")
        nbp = 3
        cs = CacheStore.init((1, nbp * self.ps, self.KV, self.hd), cc)
        k = jax.random.normal(jax.random.PRNGKey(seed),
                              (1, n_tok, self.KV, self.hd))
        cs = cs.update(k, k * 0.5)
        st = self._stacked(PagedCacheStore.init(
            n_seqs=2, n_pages=8, page_size=self.ps, n_blocks=4,
            kv_heads=self.KV, head_dim=self.hd, cc=cc))
        pages = jnp.asarray([5, 0, 3], jnp.int32)
        return (adopt_prefill(st, self._stacked(cs), jnp.int32(1), pages),
                pages, cc, n_tok)

    @pytest.mark.parametrize("vsparq", [True, False])
    @pytest.mark.parametrize("signed", [True, False])
    @pytest.mark.parametrize("window", [0, 8])
    def test_bits_and_attention_survive_roundtrip(self, vsparq, signed,
                                                  window):
        cfg = SparqConfig.opt5(signed=signed, vsparq=vsparq)
        st, pages, cc, n_tok = self._filled_store(cfg)
        swap = SwapStore()
        planes = gather_slot_pages(st, jnp.int32(1), pages)
        nbytes = swap.put(7, [planes], pos=n_tok)
        assert nbytes == swap.bytes_out == swap.resident_bytes > 0
        # resume into a *different* slot and different pages of a fresh,
        # partly-dirty pool (restore overwrites every claimed byte)
        fresh = self._stacked(PagedCacheStore.init(
            n_seqs=2, n_pages=8, page_size=self.ps, n_blocks=4,
            kv_heads=self.KV, head_dim=self.hd, cc=cc))
        fresh = dataclasses.replace(
            fresh, k_data=fresh.k_data.at[:].set(111))
        new_pages = jnp.asarray([2, 6, 1], jnp.int32)
        (host_groups,), pos = swap.pop(7)
        assert swap.bytes_in == nbytes and swap.resident_bytes == 0
        restored = restore_slot_pages(
            fresh, {k: jnp.asarray(v) for k, v in host_groups.items()},
            jnp.int32(0), new_pages, jnp.int32(pos))
        # byte identity of every packed plane and the per-layer scales
        back = gather_slot_pages(restored, jnp.int32(0), new_pages)
        for name in ("k_data", "k_meta", "v_data", "v_meta",
                     "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(back[name]),
                                          np.asarray(planes[name]))
        np.testing.assert_array_equal(np.asarray(restored.seq_pos[:, 0]),
                                      [pos] * self.L)
        # fused paged decode over the resumed slot == never-swapped oracle
        rng = np.random.default_rng(3)
        q = jnp.broadcast_to(                   # same query for both slots
            jnp.asarray(rng.normal(size=(1, 1, self.KV * 2, self.hd)),
                        jnp.float32), (2, 1, self.KV * 2, self.hd))
        for layer in range(self.L):
            take = lambda t, l=layer: jax.tree.map(lambda a: a[l], t)
            want = paged_decode_attention(q, take(st), window=window)
            got = paged_decode_attention(q, take(restored), window=window)
            np.testing.assert_array_equal(np.asarray(want)[1],
                                          np.asarray(got)[0])

    def test_swapstore_rejects_double_put(self):
        cfg = SparqConfig.opt5(signed=True)
        st, pages, _, n_tok = self._filled_store(cfg)
        swap = SwapStore()
        swap.put(1, [gather_slot_pages(st, jnp.int32(1), pages)], n_tok)
        assert 1 in swap and len(swap) == 1
        with pytest.raises(AssertionError, match="already swapped"):
            swap.put(1, [gather_slot_pages(st, jnp.int32(1), pages)], n_tok)
        assert swap.n_pages(1) == 3 and swap.pos(1) == n_tok


# ----------------------------------------------------------------------
# allocator
# ----------------------------------------------------------------------

class TestAllocator:
    def test_alloc_free_reuse(self):
        al = PageAllocator(4)
        a = al.alloc(3)
        assert al.free_count == 1 and al.used_count == 3
        al.free(a[:2])
        b = al.alloc(3)
        assert set(b).isdisjoint({a[2]})
        assert al.free_count == 0

    def test_exhaustion_raises(self):
        al = PageAllocator(2)
        al.alloc(1)
        with pytest.raises(PoolExhausted, match="exhausted"):
            al.alloc(2)
        assert al.free_count == 1               # failed alloc takes nothing

    def test_double_free_asserts(self):
        al = PageAllocator(2)
        pages = al.alloc(1)
        al.free(pages)
        with pytest.raises(AssertionError):
            al.free(pages)

    def test_foreign_free_asserts(self):
        """The used-set refcount guard: freeing a page that was never
        handed out trips immediately (not only a duplicate free)."""
        al = PageAllocator(4)
        al.alloc(2)
        with pytest.raises(AssertionError, match="not allocated"):
            al.free([3])
        al.assert_consistent()

    def test_peak_watermark(self):
        al = PageAllocator(4)
        a = al.alloc(3)
        al.free(a)
        al.alloc(1)
        assert al.peak_used == 3                # high watermark persists
        assert al.free_count == 3 and al.used_count == 1
        assert set(al.free_pages).isdisjoint(al.refcounts)


# ----------------------------------------------------------------------
# engine level: continuous batching end to end
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    from repro.configs.base import get_reduced_config
    from repro.models.model import Model
    cfg = get_reduced_config("tinyllama-1.1b").replace(
        dtype=jnp.float32, remat=False)
    model = Model(cfg)
    params = model.init_params(KEY)
    return model, params


def _engine(model, cc, **kw):
    from repro.launch.serve import ContinuousBatchingEngine
    kw.setdefault("page_size", 8)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_active", 2)
    kw.setdefault("max_seq_len", 64)
    return ContinuousBatchingEngine(model, cc, **kw)


def _reqs(model, lens, gens, seed=3):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, model.cfg.vocab_size, (L,)), g)
            for L, g in zip(lens, gens)]


@pytest.mark.parametrize("codec", [SparqConfig(enabled=False, signed=True),
                                   SparqConfig.opt5(signed=True)],
                         ids=["int8", "5opt"])
def test_paged_engine_matches_contiguous_greedy(tiny_lm, codec):
    """Acceptance: ragged continuous batching (queueing, staggered
    completions, multi-page sequences, page reuse) emits exactly the
    greedy tokens of the contiguous scan engine serving each request
    alone — int8 grid and the full 4-bit 5opt codec. attn_bk aligns the
    contiguous kernel's Tk tiles with the page size, so even the f32
    summation order matches (bit-identical logits, not just argmax)."""
    from repro.launch.serve import DecodeEngine
    model, params = tiny_lm
    ps = 8
    cc = dataclasses.replace(
        CacheConfig.sparq_cache(codec, impl="reference"), attn_bk=ps)
    eng = _engine(model, cc, page_size=ps, n_pages=14)
    reqs = _reqs(model, lens=[12, 9, 20, 9], gens=[10, 5, 7, 12])
    results, stats = eng.run(params, reqs)
    assert stats["decode_steps"] > 0
    contiguous = DecodeEngine(model, cc)
    for rid, req in enumerate(reqs):
        toks, _ = contiguous.generate(
            params, {"tokens": jnp.asarray(req.tokens)[None]}, req.gen,
            warmup=False)
        np.testing.assert_array_equal(results[rid], np.asarray(toks)[0])


def test_page_reuse_after_eviction_is_exact(tiny_lm):
    """One slot, a pool just big enough for one sequence: the second
    (identical) request recycles the first one's pages and must produce
    identical tokens — adoption rewrites every byte of a claimed page."""
    model, params = tiny_lm
    cc = CacheConfig.sparq_cache(SparqConfig.opt5(signed=True),
                                 impl="reference")
    eng = _engine(model, cc, page_size=8, n_pages=4, max_active=1,
                  max_seq_len=32)
    req = _reqs(model, lens=[14], gens=[12])[0]
    results, stats = eng.run(params, [req, req, req])
    assert stats["peak_pages_used"] <= 4
    np.testing.assert_array_equal(results[0], results[1])
    np.testing.assert_array_equal(results[0], results[2])


def test_pool_exhaustion_raises_before_tracing(tiny_lm):
    """Admission or decode growth beyond the pool raises host-side
    (PoolExhausted/ValueError), mirroring the contiguous engine's
    host-side capacity check — never a silent traced clamp."""
    model, params = tiny_lm
    cc = CacheConfig.sparq_cache(SparqConfig.opt5(signed=True),
                                 impl="reference")
    # request that can never fit the pool: rejected up front
    eng = _engine(model, cc, page_size=8, n_pages=2, max_active=1,
                  max_seq_len=64)
    big = _reqs(model, lens=[40], gens=[2])
    with pytest.raises(ValueError, match="pages"):
        eng.run(params, big)
    # each request alone fits (4 pages of 4 total) but two growing
    # concurrently drain the free list: without a SchedulerPolicy,
    # decode-time allocation raises host-side, before the step is traced
    # (tests/test_scheduler.py covers the preemption path)
    eng2 = _engine(model, cc, page_size=8, n_pages=4, max_active=2,
                   max_seq_len=32)
    from repro.models.paging import PoolExhausted as PE
    with pytest.raises(PE, match="exhausted"):
        eng2.run(params, _reqs(model, lens=[8, 8], gens=[18, 18]))


def test_paged_engine_rejects_unsupported(tiny_lm):
    """fp layouts and non-standard-KV families keep the scan engine."""
    from repro.configs.base import get_reduced_config
    from repro.launch.serve import ContinuousBatchingEngine
    from repro.models.model import Model
    model, _ = tiny_lm
    with pytest.raises(ValueError, match="sparq"):
        _engine(model, CacheConfig.fp32())
    mla = Model(get_reduced_config("deepseek-v2-lite-16b"))
    cc = CacheConfig.sparq_cache(SparqConfig.opt5(signed=True))
    with pytest.raises(ValueError, match="standard-KV"):
        _engine(mla, cc)


def test_ring_and_paged_masking_agree():
    """Ring + paged composition: the sliding-window ring cache (arbitrary
    slot order, kpos = slot_pos) and the paged pool (logical order through
    a block table) express the same attention set; outputs agree to fp
    tolerance (summation order differs with slot order)."""
    rng = np.random.default_rng(5)
    B, KV, G, hd, W, ps = 2, 2, 2, 8, 8, 4
    cfg = SparqConfig.opt5(signed=True)
    Tk = 16                                     # logical positions 0..15
    kd, km = _packed_planes(rng, B, Tk, KV, hd, cfg)
    vd, vm = _packed_planes(rng, B, Tk, KV, hd, cfg)
    q = jnp.asarray(rng.normal(size=(B, 1, KV * G, hd)), jnp.float32)
    s = jnp.float32(0.02)
    cur = 14
    # ring: keep the last W tokens in rotated slots, kpos = absolute pos
    slots = [(p % W) for p in range(cur + 1)]   # position p -> slot p%W
    ring_kd = np.zeros((B, W, KV, hd), np.int8)
    ring_km, ring_vd, ring_vm = (np.zeros_like(ring_kd) for _ in range(3))
    ring_pos = np.full((B, W), -1, np.int32)
    for p in range(cur + 1):
        ring_kd[:, slots[p]] = np.asarray(kd[:, p])
        ring_km[:, slots[p]] = np.asarray(km[:, p])
        ring_vd[:, slots[p]] = np.asarray(vd[:, p])
        ring_vm[:, slots[p]] = np.asarray(vm[:, p])
        ring_pos[:, slots[p]] = p
    want = ops.sparq_decode_attention(
        q, jnp.asarray(ring_kd), jnp.asarray(ring_km), s,
        jnp.asarray(ring_vd), jnp.asarray(ring_vm), s,
        jnp.asarray(ring_pos), jnp.int32(cur), window=W, impl="reference")
    pk, pkm, pv, pvm, bt = _scatter_pool(rng, kd, km, vd, vm, ps)
    sv = jnp.full((B,), s)
    got = ops.sparq_paged_decode_attention(
        q, pk, pkm, sv, pv, pvm, sv, bt,
        jnp.full((B,), cur, jnp.int32), window=W, impl="reference")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-6)


def test_stats_shape(tiny_lm):
    model, params = tiny_lm
    cc = CacheConfig.sparq_cache(SparqConfig(enabled=False, signed=True),
                                 impl="reference")
    eng = _engine(model, cc)
    results, stats = eng.run(params, _reqs(model, lens=[9], gens=[4]))
    assert results[0].shape == (4,)
    for key in ("decode_tok_s", "pool_slots", "peak_pages_used",
                "peak_pool_utilization", "cache_total_bytes"):
        assert key in stats
    assert stats["pool_slots"] == 16 * 8

"""Paged SPARQ KV-cache: one global pool of fixed-size packed pages.

The contiguous `CacheStore` gives every sequence `max_len` slots up front,
so short sequences strand capacity long ones need. `PagedCacheStore`
instead owns one pool of fixed-size pages per layer, each page holding
`page_size` slots of the raw §5.1 packed planes — int8 window codes, the
packed `[mux|shift_hi|shift_lo]` meta byte, and per-*sequence* site scales.
A sequence's cache is a *block table*: `block_table[s, b]` names the
physical page that backs logical slots `[b*page_size, (b+1)*page_size)` of
sequence-slot `s`. Because the fused decode kernel (PR 2) masks by slot
*position*, not slot order, attention over paged storage is the same
kernel with a gather: `kernels.ops.sparq_paged_decode_attention` prefetches
the block table as scalars and streams each sequence's pages straight from
the pool — the pool stores only packed bytes and a dequantized copy is
never materialized.

Division of labor:

  PagedCacheStore   device state (pools, scales, block tables, positions);
                    jit/scan-transparent pytree, one per attention layer
                    (stacked along layer 0 by the engine). `update()` is
                    the traced per-token write; attention reads go through
                    `paged_decode_attention`.
  PageAllocator     host-side free list. Allocation and eviction are
                    scheduling decisions, so they live with the engine
                    (`launch.serve.ContinuousBatchingEngine`) and happen
                    *between* traced steps; exhaustion raises here, before
                    any tracing, mirroring the contiguous engine's
                    host-side capacity check.
  adopt_prefill /   engine-level transitions: copy a freshly prefill'd
  evict_slot        contiguous sparq cache's packed planes into pool pages
                    (no re-quantization — the bytes and the calibrated
                    scale transfer verbatim), and clear a finished slot.

Pool geometry: every layer's pool has `n_pages` usable pages plus one
*trash page* at index `n_pages`, the write target for inactive sequence
slots — their (masked, garbage) decode writes land there instead of
corrupting live pages, keeping the traced step free of conditionals.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparq import SparqConfig
from repro.models.cache import CacheConfig, CacheStore

# host/device topology for the static analyzer (repro.analysis.host_lint;
# see docs/analysis.md). Pure literal — parsed with ast.literal_eval.
__analysis__ = {
    "traced": (
        "PagedCacheStore.update",
        "PagedCacheStore.write_chunk",
        "PagedCacheStore._resolve_scale",
        "PagedCacheStore._resolve_chunk_scale",
        "PagedCacheStore._encode",
        "paged_decode_attention",
        "chunked_prefill_attention",
        "adopt_prefill",
        "copy_page",
        "adopt_prefix_scales",
        "evict_slot",
        "gather_slot_pages",
        "restore_slot_pages",
    ),
    "host_loop": ("SwapStore.put", "SwapStore._to_host", "SwapStore.pop"),
    "device_returning": (),
    "device_params": ("SwapStore.put.groups", "SwapStore._to_host.groups"),
    # repro.obs metric handles: host-side floats only
    "host_objects": ("registry",),
}


class PoolExhausted(RuntimeError):
    """Raised host-side (before tracing) when the page pool runs dry."""


class ChunkMeta(NamedTuple):
    """Per-chunk metadata for the chunked ragged prefill path.

    A chunk is one fixed-shape slice of the packed token stream the
    `PrefillScheduler` (launch.prefill) builds from ragged pending
    prompts: every stream token carries its sequence slot and absolute
    position, sequence runs are contiguous and aligned to the kernel's
    query-tile size (derivable as C // tile_seq.shape[0]), and padding
    tokens are seq_id == -1. All fields are device arrays (the ChunkMeta
    is a pytree leaf-carrier traced through the jitted chunk program).

      seq_id        [C] int32 — sequence slot per token (-1 = padding)
      pos           [C] int32 — absolute prompt position per token
      hist          [C] int32 — per-token history boundary (the token's
                    segment start): attention reads packed pages for
                    kpos < hist and the chunk's float K/V for
                    kpos in [hist, pos]. Segment-granular packing makes
                    this split — and hence every prompt's numerics —
                    independent of how chunks were packed.
      tile_seq      [C/bq] int32 — slot owning each query tile (-1 pad)
      seq_pos_after [S] int32 — device seq_pos to install after the
                    chunk's writes: the prompt length for slots whose
                    prefill completes here, -1 for slots still mid-
                    prefill (keeps them inactive for interleaved decode
                    steps), and the current position for everyone else.
    """
    seq_id: jnp.ndarray
    pos: jnp.ndarray
    hist: jnp.ndarray
    tile_seq: jnp.ndarray
    seq_pos_after: jnp.ndarray


class PageAllocator:
    """Host-side refcounted free-list allocator for the shared page pool.

    Page ids are shared across layers: allocating page `p` for a sequence
    reserves physical page `p` in every layer's pool (the block table is
    one table, not per-layer). All methods are plain-Python and run between
    traced steps; `alloc` raises `PoolExhausted` *before* any tracing when
    the request cannot be satisfied.

    Pages carry **refcounts** so immutable full pages can back several
    sequences at once (shared-prefix reuse): `alloc` hands out pages at
    refcount 1, `share` adds a reference to an already-allocated page, and
    `release` drops one — a page returns to the free list only when its
    count reaches zero (`release` reports exactly which pages did, so the
    caller can invalidate any prefix-index entries naming them).
    `free` is strict release: it asserts every page was exclusively owned,
    which preserves the old guard semantics (double frees, frees of
    foreign pages, and frees of shared pages all trip it).

    `alloc` is atomic: a failing call takes nothing off the free list, so
    an exhausted multi-page request never leaks pages.
    `assert_consistent` re-checks free/refcount conservation after every
    mutation. `peak_used` is the pool's high watermark (distinct pages).
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages))
        self._ref: Dict[int, int] = {}          # page -> reference count
        self.peak_used = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Distinct allocated pages (each counted once however shared)."""
        return len(self._ref)

    @property
    def shared_count(self) -> int:
        """Allocated pages with more than one reference."""
        return sum(1 for c in self._ref.values() if c > 1)

    @property
    def total_refs(self) -> int:
        """Sum of all refcounts (== block-table references held)."""
        return sum(self._ref.values())

    @property
    def free_pages(self) -> Tuple[int, ...]:
        """Snapshot of the free list (copy; safe to hold across mutations)."""
        return tuple(self._free)

    def refcount(self, page: int) -> int:
        """Current reference count (0 = free / never allocated)."""
        return self._ref.get(page, 0)

    def reset_peak(self) -> None:
        """Restart the high watermark at the *current* residency — the
        warmup/measure boundary (engine.reset_stats): the peak reported
        afterwards reflects only allocations from now on."""
        self.peak_used = len(self._ref)

    @property
    def refcounts(self) -> Dict[int, int]:
        """Snapshot of page -> refcount (copy; for invariant checks)."""
        return dict(self._ref)

    def alloc(self, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise PoolExhausted(
                f"page pool exhausted: need {n} page(s), {len(self._free)} "
                f"of {self.n_pages} free ({self.used_count} resident, of "
                f"which {self.shared_count} shared across "
                f"{self.total_refs} references) — grow --n-pages, shrink "
                f"the admitted batch, enable --preempt, or wait for "
                f"evictions")
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._ref[p] = 1
        self.peak_used = max(self.peak_used, len(self._ref))
        self.assert_consistent()
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Add one reference to each (already-allocated) page — the
        shared-prefix adoption path: the new sequence's block table now
        also names these pages."""
        for p in pages:
            assert self._ref.get(p, 0) > 0, \
                f"page {p} shared while not allocated"
            self._ref[p] += 1
        self.assert_consistent()

    def release(self, pages: Sequence[int]) -> List[int]:
        """Drop one reference per page; pages reaching zero return to the
        free list. Returns the pages actually freed (refcount hit zero) so
        the caller can invalidate prefix-index entries naming them."""
        freed: List[int] = []
        for p in pages:
            assert 0 <= p < self.n_pages, f"page {p} outside the pool"
            assert p in self._ref, \
                f"page {p} released while not allocated (double free / " \
                f"foreign)"
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)
                freed.append(p)
        self.assert_consistent()
        return freed

    def free(self, pages: Sequence[int]) -> None:
        """Strict release: every page must have been exclusively owned
        (refcount exactly 1). Shared pages must go through `release`."""
        pages = list(pages)
        for p in pages:
            assert self._ref.get(p, 0) <= 1, \
                f"page {p} freed while shared (refcount " \
                f"{self._ref.get(p, 0)}) — use release()"
        freed = self.release(pages)
        assert len(freed) == len(pages)

    def assert_consistent(self) -> None:
        """Refcount conservation: every page is free xor allocated with a
        positive refcount, exactly once. O(n_pages); cheap next to a
        traced decode step."""
        assert len(self._free) == len(set(self._free)), \
            "duplicate pages on the free list"
        assert not set(self._ref).intersection(self._free), \
            "page simultaneously free and allocated"
        assert all(c > 0 for c in self._ref.values()), \
            "allocated page with non-positive refcount"
        assert len(self._free) + len(self._ref) == self.n_pages, \
            "pages leaked: free + used != pool size"


# ----------------------------------------------------------------------
# shared-prefix index (host-side, non-owning)
# ----------------------------------------------------------------------

_HASH_MOD = (1 << 61) - 1       # Mersenne prime: cheap mod, no collisions
_HASH_BASE = 1_000_003          # > any token id we hash


def _segment_hash(tokens) -> int:
    """Rolling polynomial hash of one token segment (child-bucket key in
    the radix index; exact token comparison guards collisions)."""
    h = 0
    for t in tokens:
        h = (h * _HASH_BASE + int(t) + 1) % _HASH_MOD
    return h


class _PrefixNode:
    """One radix-tree node: a `quantum`-token prompt segment and the full
    pages that hold its packed K/V. Children bucket by segment hash."""
    __slots__ = ("tokens", "pages", "scales", "children", "parent", "key")

    def __init__(self, tokens, pages, scales, parent, key):
        self.tokens = tokens        # np.ndarray [quantum] token ids
        self.pages = pages          # tuple[int] physical pages, block order
        self.scales = scales        # per cache group: (k_scale, v_scale)
        self.children: Dict[int, List["_PrefixNode"]] = {}
        self.parent = parent        # None once dropped from the tree
        self.key = key              # _segment_hash(tokens)


class PrefixIndex:
    """Radix tree over prompt prefixes -> full-page runs (shared-prefix
    reuse, host-side).

    Nodes are `quantum`-token segments — `quantum = lcm(page_size,
    chunk_seg)`, so every node covers whole pages *and* whole prefill
    segments: page-whole because only fully-written, never-again-written
    pages are shareable; segment-whole because the chunked prefill packer
    resumes a tail only at a segment boundary. Children are bucketed by a
    rolling hash of the segment with exact token comparison on lookup, so
    hash collisions cost a compare, never a false match.

    The index does **not** own page references — entries are valid only
    while some sequence still holds the pages (PR 5's scheduling
    invariance makes the bytes a pure function of the prompt prefix, so
    any holder's pages are interchangeable). The engine must call
    `invalidate(freed)` with every page whose refcount reached zero
    (`PageAllocator.release`'s return value): the node naming it — and
    its whole subtree, whose prefixes include the dead pages — drop out.

    Each node also carries the donor's frozen per-layer scales: the
    §5.1 scale is frozen from the prompt's *first segment* (contained in
    every node's prefix), so every donor on a match path froze the same
    scale and a borrower adopting it decodes the shared pages
    bit-identically.
    """

    def __init__(self, quantum: int, page_size: int):
        assert quantum > 0 and quantum % page_size == 0, \
            f"quantum {quantum} must cover whole pages of {page_size}"
        self.quantum = quantum
        self.page_size = page_size
        self._root = _PrefixNode(None, (), None, None, None)
        self._by_page: Dict[int, List[_PrefixNode]] = {}

    # ----------------------------------------------------------- lookup
    @staticmethod
    def _find(node: _PrefixNode, seg: np.ndarray) -> Optional[_PrefixNode]:
        for child in node.children.get(_segment_hash(seg), ()):
            if np.array_equal(child.tokens, seg):
                return child
        return None

    def match(self, tokens) -> Tuple[int, List[int], Optional[list]]:
        """Longest indexed prefix of `tokens`, in whole quanta.

        Returns (n_matched_tokens, pages, scales): the pages backing
        prompt positions [0, n) in block order and the deepest matched
        node's frozen scales (None on a miss). n is always a multiple of
        `quantum`; 0 means no match."""
        tokens = np.asarray(tokens)
        q = self.quantum
        node, pages, scales, n = self._root, [], None, 0
        for d in range(len(tokens) // q):
            child = self._find(node, tokens[d * q:(d + 1) * q])
            if child is None:
                break
            node = child
            pages.extend(child.pages)
            scales = child.scales
            n += q
        return n, pages, scales

    # ----------------------------------------------------------- insert
    def insert(self, tokens, pages: Sequence[int], scales) -> int:
        """Index the whole-quantum prefix of a freshly prefilled prompt.

        `pages`: the sequence's pages in block order (at least the blocks
        covering the indexed prefix); `scales`: its frozen per-layer
        scales, per cache group. Segments already present keep their
        existing pages (first donor wins — both copies are bit-identical
        by scheduling invariance, and the existing entry may already be
        shared). Returns the number of tokens indexed."""
        tokens = np.asarray(tokens)
        q, ps = self.quantum, self.page_size
        ppn = q // ps                       # pages per node
        depth = len(tokens) // q
        assert len(pages) >= depth * ppn, "pages do not cover the prefix"
        node = self._root
        for d in range(depth):
            seg = tokens[d * q:(d + 1) * q]
            child = self._find(node, seg)
            if child is None:
                child = _PrefixNode(
                    np.array(seg), tuple(int(p) for p in
                                         pages[d * ppn:(d + 1) * ppn]),
                    scales, node, _segment_hash(seg))
                node.children.setdefault(child.key, []).append(child)
                for p in child.pages:
                    self._by_page.setdefault(p, []).append(child)
            node = child
        return depth * q

    # ------------------------------------------------------- invalidate
    def invalidate(self, pages: Sequence[int]) -> int:
        """Drop every entry naming any of `pages` (they were released to
        zero and may be reallocated with different bytes), including
        subtrees — a deeper node's prefix contains its ancestors' pages.
        Returns the number of nodes dropped."""
        dropped = 0
        for p in pages:
            for node in list(self._by_page.get(p, ())):
                dropped += self._drop(node)
        return dropped

    def _drop(self, node: _PrefixNode) -> int:
        if node.parent is None:             # root, or already dropped
            return 0
        bucket = node.parent.children.get(node.key)
        if bucket is not None and node in bucket:
            bucket.remove(node)
            if not bucket:
                del node.parent.children[node.key]
        node.parent = None
        for p in node.pages:
            b = self._by_page.get(p)
            if b is not None and node in b:
                b.remove(node)
                if not b:
                    del self._by_page[p]
        dropped = 1
        for bucket in list(node.children.values()):
            for child in list(bucket):
                dropped += self._drop(child)
        node.children = {}
        return dropped

    # ------------------------------------------------------------ stats
    @property
    def n_nodes(self) -> int:
        count, stack = 0, [self._root]
        while stack:
            n = stack.pop()
            for bucket in n.children.values():
                count += len(bucket)
                stack.extend(bucket)
        return count

    @property
    def indexed_pages(self) -> Tuple[int, ...]:
        """Distinct pages currently named by some entry (sorted)."""
        return tuple(sorted(self._by_page))


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k_data", "k_meta", "v_data", "v_meta",
                                "k_scale", "v_scale", "block_table",
                                "seq_pos"),
                   meta_fields=("codec", "impl", "mesh"))
@dataclasses.dataclass
class PagedCacheStore:
    """Paged KV cache for one attention layer (sparq layout only).

    Shapes (S = sequence slots, P = n_pages + 1 trash, ps = page_size,
    NB = max logical blocks per sequence):

      k/v_data, k/v_meta  int8  [P, ps, KV*hd]  packed §5.1 page pools,
                                                  lane-dense: the bytes of
                                                  [P, ps, KV, hd] with the
                                                  head axes flattened (the
                                                  TPU layout the kernels
                                                  read; see
                                                  kernels.sparq_decode_attn)
      k/v_scale           f32   [S]               per-sequence site scales
                                                  (0 = uncalibrated; set by
                                                  adopt_prefill, frozen for
                                                  decode writes)
      block_table         int32 [S, NB]           physical page per logical
                                                  block (-1 = unallocated)
      seq_pos             int32 [S]               tokens written per slot
                                                  (-1 = inactive slot)
    """
    k_data: jnp.ndarray
    k_meta: jnp.ndarray
    v_data: jnp.ndarray
    v_meta: jnp.ndarray
    k_scale: jnp.ndarray
    v_scale: jnp.ndarray
    block_table: jnp.ndarray
    seq_pos: jnp.ndarray
    codec: Optional[SparqConfig] = None
    impl: str = "auto"
    #: optional ("data","model") jax Mesh. When set, attention reads run
    #: tensor-parallel via shard_map over the "model" axis (pools shard
    #: along the KV-head axis; see kernels.ops.tp_size) and the engine
    #: places the pool planes with a matching NamedSharding.
    mesh: Optional[jax.sharding.Mesh] = None

    # -------------------------------------------------------------- init
    @staticmethod
    def init(n_seqs: int, n_pages: int, page_size: int, n_blocks: int,
             kv_heads: int, head_dim: int, cc: CacheConfig,
             mesh: Optional[jax.sharding.Mesh] = None
             ) -> "PagedCacheStore":
        if cc.layout != "sparq":
            raise ValueError(
                "PagedCacheStore stores the packed §5.1 planes; use "
                "--kv-cache sparq (fp paging would just be fp paging — the "
                "point of the pool is that the hot loop reads packed bytes)")
        assert head_dim % 2 == 0, \
            f"sparq pairs adjacent lanes; head_dim must be even: {head_dim}"
        shp = (n_pages + 1, page_size, kv_heads * head_dim)  # +1: trash
        return PagedCacheStore(
            k_data=jnp.zeros(shp, jnp.int8),
            k_meta=jnp.zeros(shp, jnp.int8),
            v_data=jnp.zeros(shp, jnp.int8),
            v_meta=jnp.zeros(shp, jnp.int8),
            k_scale=jnp.zeros((n_seqs,), jnp.float32),
            v_scale=jnp.zeros((n_seqs,), jnp.float32),
            block_table=jnp.full((n_seqs, n_blocks), -1, jnp.int32),
            seq_pos=jnp.full((n_seqs,), -1, jnp.int32),
            codec=cc.sparq, impl=cc.impl, mesh=mesh)

    # --------------------------------------------------------- geometry
    @property
    def n_seqs(self) -> int:
        return self.seq_pos.shape[-1]

    @property
    def page_size(self) -> int:
        return self.k_data.shape[-2]

    @property
    def n_pages(self) -> int:        # usable pages (excludes the trash page)
        return self.k_data.shape[-3] - 1

    @property
    def n_blocks(self) -> int:
        return self.block_table.shape[-1]

    # ------------------------------------------------------------- write
    def _resolve_scale(self, stored: jnp.ndarray, x: jnp.ndarray
                       ) -> jnp.ndarray:
        """Per-sequence scale: frozen once calibrated (> 0), else set from
        this write's dynamic range — same policy as CachedTensor, per slot."""
        dyn = jnp.maximum(
            jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(1, 2, 3)), 1e-8) \
            / self.codec.max_val
        return jnp.where(stored > 0, stored, dyn)

    def _encode(self, x: jnp.ndarray, scale: jnp.ndarray):
        """float [S, KV, hd] -> (§5.1 window codes, meta bytes), int8
        [S, KV*hd] (lane-dense rows of the pool; hd is even, so flattening
        never splits a vSPARQ pair).

        Same codec semantics as CachedTensor._encode but with a per-slot
        scale vector; the reference quantizer is elementwise over leading
        axes, so codes match the contiguous path's (scalar-scale) codes
        bit for bit slot-by-slot. Decode writes are S*KV*hd values — noise
        next to the attention reads, so no Pallas dispatch here.
        """
        from repro.kernels import ref as _ref
        from repro.kernels.ops import sparq_pack
        cfg = self.codec
        x = x.reshape(x.shape[0], -1)
        codes, meta = _ref.ref_sparq_quant(
            x.astype(jnp.float32), scale[:, None],
            bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
            vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
            enabled=cfg.enabled)
        return sparq_pack(codes, meta), meta

    def _pin_pools(self, store: "PagedCacheStore") -> "PagedCacheStore":
        """Re-assert the KV-head NamedSharding on freshly written pool
        planes. The scatter of a (replicated) token write into a sharded
        pool is exact per shard, but without the constraint GSPMD may
        pick a different output sharding — which would both break the
        jitted step's donation (in/out shardings must match) and force a
        reshard. No-op without a mesh."""
        if self.mesh is None:
            return store
        from repro.distributed.sharding import pool_plane_sharding
        sh = pool_plane_sharding(self.mesh, store.k_data.ndim)
        pin = lambda x: jax.lax.with_sharding_constraint(x, sh)
        return dataclasses.replace(
            store, k_data=pin(store.k_data), k_meta=pin(store.k_meta),
            v_data=pin(store.v_data), v_meta=pin(store.v_meta))

    def update(self, k_new: jnp.ndarray, v_new: jnp.ndarray
               ) -> "PagedCacheStore":
        """Write one decode token per sequence slot and advance positions.

        k_new/v_new: float [S, 1, KV, hd]. Slot `s` writes its token at
        logical position seq_pos[s] — physical page
        block_table[s, pos // ps], row pos % ps. Inactive slots (seq_pos
        < 0) and unallocated blocks write to the trash page, so the traced
        step needs no host-side masking; the engine guarantees active
        sequences always have their current block allocated.
        """
        S, T = k_new.shape[:2]
        assert T == 1, f"paged decode writes one token per step, got {T}"
        ps = self.page_size
        trash = self.k_data.shape[0] - 1
        pos = self.seq_pos
        active = pos >= 0
        eff = jnp.maximum(pos, 0)
        blk = jnp.minimum(eff // ps, self.n_blocks - 1)
        page = self.block_table[jnp.arange(S), blk]
        page = jnp.where(active & (page >= 0), page, trash)
        off = eff % ps

        k_scale = self._resolve_scale(self.k_scale, k_new)
        v_scale = self._resolve_scale(self.v_scale, v_new)
        kd, km = self._encode(k_new[:, 0], k_scale)
        vd, vm = self._encode(v_new[:, 0], v_scale)
        return self._pin_pools(dataclasses.replace(
            self,
            k_data=self.k_data.at[page, off].set(kd),
            k_meta=self.k_meta.at[page, off].set(km),
            v_data=self.v_data.at[page, off].set(vd),
            v_meta=self.v_meta.at[page, off].set(vm),
            k_scale=jnp.where(active, k_scale, self.k_scale),
            v_scale=jnp.where(active, v_scale, self.v_scale),
            seq_pos=jnp.where(active, pos + 1, pos)))

    def _resolve_chunk_scale(self, stored: jnp.ndarray, x: jnp.ndarray,
                             s_safe: jnp.ndarray,
                             first_seg: jnp.ndarray) -> jnp.ndarray:
        """Per-sequence scale for a chunk write: frozen once calibrated
        (> 0), else set from the dynamic range of this sequence's
        *first-segment* tokens (`first_seg`: valid tokens with hist == 0)
        — never from whatever later segments happened to share the
        chunk, so the frozen scale is a function of (prompt, seg) alone
        and identical under every stream packing (the §5.1
        scale-freeze-at-first-write policy, applied at the segment
        boundary). For a prompt that fits one segment this is exactly
        the contiguous prefill's whole-prompt range — bit-identical
        scale, hence bit-identical bytes. A sequence's first segment is
        always its first chunk appearance (jobs advance in order), so a
        chunk carrying only later segments finds `stored` already
        frozen; slots with no first-segment tokens are untouched."""
        tok_max = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(1, 2))
        tok_max = jnp.where(first_seg, tok_max, 0.0)
        S = stored.shape[0]
        seq_max = jnp.zeros((S,), jnp.float32).at[s_safe].max(tok_max)
        dyn = jnp.maximum(seq_max, 1e-8) / self.codec.max_val
        has = jnp.zeros((S,), bool).at[s_safe].max(first_seg)
        return jnp.where(stored > 0, stored, jnp.where(has, dyn, stored))

    def write_chunk(self, k_new: jnp.ndarray, v_new: jnp.ndarray,
                    meta: "ChunkMeta") -> "PagedCacheStore":
        """Scatter one prefill chunk's K/V directly into the page pool.

        k_new/v_new: float [C, KV, hd] — the chunk's freshly projected
        K/V in stream order. Token i quantizes with its sequence's scale
        (resolved per `_resolve_chunk_scale`) through the same §5.1 codec
        as every other write path and lands at physical page
        block_table[seq_id[i], pos[i] // ps], row pos[i] % ps — no
        contiguous staging cache and no adopt_prefill copy. Padding
        tokens and unallocated blocks write to the trash page. seq_pos is
        replaced wholesale by meta.seq_pos_after (the engine computes it
        host-side; mid-prefill slots stay at -1 so interleaved decode
        steps treat them as inactive)."""
        ps = self.page_size
        trash = self.k_data.shape[0] - 1
        sid = meta.seq_id
        valid = sid >= 0
        s_safe = jnp.maximum(sid, 0)
        first_seg = valid & (meta.hist == 0)
        k_scale = self._resolve_chunk_scale(self.k_scale, k_new,
                                            s_safe, first_seg)
        v_scale = self._resolve_chunk_scale(self.v_scale, v_new,
                                            s_safe, first_seg)
        kd, km = self._encode(k_new, k_scale[s_safe])
        vd, vm = self._encode(v_new, v_scale[s_safe])
        eff = jnp.maximum(meta.pos, 0)
        blk = jnp.minimum(eff // ps, self.n_blocks - 1)
        page = self.block_table[s_safe, blk]
        page = jnp.where(valid & (page >= 0), page, trash)
        off = eff % ps
        return self._pin_pools(dataclasses.replace(
            self,
            k_data=self.k_data.at[page, off].set(kd),
            k_meta=self.k_meta.at[page, off].set(km),
            v_data=self.v_data.at[page, off].set(vd),
            v_meta=self.v_meta.at[page, off].set(vm),
            k_scale=k_scale, v_scale=v_scale,
            seq_pos=meta.seq_pos_after))


# ----------------------------------------------------------------------
# attention read path
# ----------------------------------------------------------------------

def paged_decode_attention(q: jnp.ndarray, store: PagedCacheStore, *,
                           window: int = 0) -> jnp.ndarray:
    """Fused flash-decode over the page pool. q [S, 1, H, hd].

    Per-sequence `cur` comes from the store's positions (the token written
    by the preceding `update`), per-sequence scales from its calibration —
    one traced call serves slots of ragged lengths. Inactive slots are
    fully masked and return zeros."""
    from repro.kernels.ops import sparq_paged_decode_attention
    out = sparq_paged_decode_attention(
        q, store.k_data, store.k_meta, store.k_scale,
        store.v_data, store.v_meta, store.v_scale,
        store.block_table, store.seq_pos - 1, window=window,
        impl=store.impl, mesh=store.mesh)
    return out.astype(q.dtype)


def chunked_prefill_attention(q: jnp.ndarray, k_chunk: jnp.ndarray,
                              v_chunk: jnp.ndarray, store: PagedCacheStore,
                              meta: ChunkMeta, *,
                              window: int = 0) -> jnp.ndarray:
    """Ragged chunked-prefill attention for one layer. q [1, C, H, hd];
    k_chunk/v_chunk [C, KV, hd] float (this chunk's own projections,
    pre-quantization). Each stream token attends to its sequence's
    already-written packed pages for kpos < meta.hist (its per-token
    history boundary) plus the causally/segment-masked float window
    [hist, pos] of the chunk itself — so calling this on the
    post-`write_chunk` store is correct, and required: a token's earlier
    *segments* may have been written by this very chunk program. Padding
    rows return zeros."""
    from repro.kernels.ops import sparq_chunked_prefill_attention
    nt = meta.tile_seq.shape[0]
    C = q.shape[1]
    out = sparq_chunked_prefill_attention(
        q[0], k_chunk, v_chunk,
        store.k_data, store.k_meta, store.k_scale,
        store.v_data, store.v_meta, store.v_scale,
        store.block_table, meta.seq_id, meta.pos, meta.hist,
        meta.tile_seq, window=window, impl=store.impl, bq=C // nt,
        mesh=store.mesh)
    return out[None].astype(q.dtype)


# ----------------------------------------------------------------------
# engine-level transitions (operate on the layer-stacked store: every
# array leaf carries a leading layer axis, scales/pos one per layer)
# ----------------------------------------------------------------------

def adopt_prefill(store: PagedCacheStore, cs: CacheStore,
                  slot: jnp.ndarray, pages: jnp.ndarray) -> PagedCacheStore:
    """Move a prefill'd sequence into the pool at `slot`, backed by `pages`.

    `cs` is the layer-stacked contiguous sparq cache the model's prefill
    just filled for this one sequence (batch 1, capacity == len(pages) *
    page_size). Its packed planes are copied page-by-page into the pools
    and its calibrated per-layer scales become the slot's scales — no
    re-quantization, so the pool bytes are bit-identical to the contiguous
    cache's. Rows past the prompt are the contiguous cache's zero
    initialization; they are masked (position > cur) until decode writes
    overwrite them, which also makes page *reuse* after eviction exact:
    adoption rewrites every byte of every page it claims.

    slot: int32 scalar sequence-slot index; pages: int32 [n_blocks_prompt].
    """
    nbp = pages.shape[0]
    L = store.k_data.shape[0]
    ps = store.page_size

    def put(pool, plane):        # plane [L, 1, nbp*ps, KV, hd]
        blocks = plane.reshape(L, nbp, ps, pool.shape[-1])
        return pool.at[:, pages].set(blocks)

    bt_row = jnp.full((store.block_table.shape[-1],), -1,
                      jnp.int32).at[:nbp].set(pages)
    return dataclasses.replace(
        store,
        k_data=put(store.k_data, cs.k.data),
        k_meta=put(store.k_meta, cs.k.meta),
        v_data=put(store.v_data, cs.v.data),
        v_meta=put(store.v_meta, cs.v.meta),
        k_scale=store.k_scale.at[:, slot].set(cs.k.scale),
        v_scale=store.v_scale.at[:, slot].set(cs.v.scale),
        block_table=store.block_table.at[:, slot].set(bt_row),
        seq_pos=store.seq_pos.at[:, slot].set(cs.pos))


def copy_page(store: PagedCacheStore, src: jnp.ndarray,
              dst: jnp.ndarray) -> PagedCacheStore:
    """Copy one physical page's packed planes to another (layer-stacked
    store) — the copy-on-write step of shared-prefix admission: when a
    new sequence's unshared tail begins mid-page, the partially-covered
    boundary page is duplicated so the tail prefill rewrites a private
    copy and never a page another sequence reads (refcount > 1 pages are
    write-never). A raw byte copy of all four §5.1 planes: rows below
    the tail boundary stay bit-identical to the shared original; rows at
    and above it are stale bytes the tail chunk overwrites."""
    upd = {name: getattr(store, name).at[:, dst].set(
        getattr(store, name)[:, src]) for name in _SWAP_PLANES}
    return dataclasses.replace(store, **upd)


def adopt_prefix_scales(store: PagedCacheStore, slot: jnp.ndarray,
                        k_scale: jnp.ndarray, v_scale: jnp.ndarray
                        ) -> PagedCacheStore:
    """Install a donor's frozen per-layer scales on `slot` (layer-stacked
    store; k_scale/v_scale [L] f32). Shared-prefix admission must do this
    *before* the tail prefill runs: the slot's scale would otherwise
    still be 0 (uncalibrated) — the tail carries no first-segment tokens
    to freeze it from — and §5.1 decode of the shared pages needs exactly
    the scale their bytes were encoded with. The donor froze its scale
    from the prompt's first segment, which is inside the shared prefix,
    so the adopted scale equals the scale the borrower would have frozen
    itself: adoption changes nothing numerically, it only short-circuits
    recomputation."""
    return dataclasses.replace(
        store,
        k_scale=store.k_scale.at[:, slot].set(k_scale),
        v_scale=store.v_scale.at[:, slot].set(v_scale))


def evict_slot(store: PagedCacheStore, slot: jnp.ndarray) -> PagedCacheStore:
    """Clear a finished sequence slot (layer-stacked store).

    Drops the block-table row, deactivates the position, and zeroes the
    scales so the next occupant recalibrates. The pages themselves are
    returned to the free list by the engine (host side); their stale bytes
    are fully overwritten on next adoption."""
    return dataclasses.replace(
        store,
        block_table=store.block_table.at[:, slot].set(-1),
        seq_pos=store.seq_pos.at[:, slot].set(-1),
        k_scale=store.k_scale.at[:, slot].set(0.0),
        v_scale=store.v_scale.at[:, slot].set(0.0))


# ----------------------------------------------------------------------
# swap-out / swap-in (preemption support; operate on layer-stacked stores)
# ----------------------------------------------------------------------

_SWAP_PLANES = ("k_data", "k_meta", "v_data", "v_meta")


def gather_slot_pages(store: PagedCacheStore, slot: jnp.ndarray,
                      pages: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Collect the packed planes and scales backing one sequence slot.

    `store` is layer-stacked; `pages` ([nbp] int32) are the physical pages
    the slot owns, in block order. Returns a dict of device arrays — each
    pool plane gathered at `pages` ([L, nbp, ps, KV*hd] int8) plus the
    per-layer scales ([L] f32). A pure gather of the raw §5.1 bytes: no
    dequantization, no requantization — what leaves the pool is exactly
    what `restore_slot_pages` puts back, so a swap round trip is
    byte-verbatim by construction.
    """
    out = {name: getattr(store, name)[:, pages] for name in _SWAP_PLANES}
    out["k_scale"] = store.k_scale[:, slot]
    out["v_scale"] = store.v_scale[:, slot]
    return out


def restore_slot_pages(store: PagedCacheStore, planes: Dict[str, jnp.ndarray],
                       slot: jnp.ndarray, pages: jnp.ndarray,
                       pos: jnp.ndarray) -> PagedCacheStore:
    """Inverse of `gather_slot_pages`: scatter swapped planes back into the
    pool (any pages — swap-in need not land on the pages swapped out of),
    rebind the slot's block table, scales, and position. Every byte of
    every claimed page is overwritten, so swap-in onto recycled pages is
    exact for the same reason prefill adoption is."""
    upd = {name: getattr(store, name).at[:, pages].set(planes[name])
           for name in _SWAP_PLANES}
    nbp = pages.shape[0]
    bt_row = jnp.full((store.block_table.shape[-1],), -1,
                      jnp.int32).at[:nbp].set(pages)
    return dataclasses.replace(
        store, **upd,
        k_scale=store.k_scale.at[:, slot].set(planes["k_scale"]),
        v_scale=store.v_scale.at[:, slot].set(planes["v_scale"]),
        block_table=store.block_table.at[:, slot].set(bt_row),
        seq_pos=store.seq_pos.at[:, slot].set(pos))


class SwapStore:
    """Host-side swap space for preempted sequences' packed pages.

    One entry per preempted request: the verbatim §5.1 packed byte planes
    (data + meta for K and V) of every page the sequence owned, its
    per-layer calibrated scales, and its position — one dict per cache
    group (the engine serves a list of layer-stacked stores). `put`
    fetches the gathered device planes to numpy (the modeled §5.1
    traffic is 0.5625 B/value data + 0.375 B/value ctrl = 0.9375 B/value
    — ~4.3x less than swapping fp32 planes) and `pop` hands them back for
    `restore_slot_pages`. Byte counters track the swap traffic and
    residency so schedulers and benchmarks can report it.
    """

    def __init__(self, registry=None):
        """`registry`, when given, is a repro.obs MetricsRegistry the
        byte counters mirror into (`swap_bytes_total{dir=out|in}`,
        `swap_resident_bytes` / `swap_peak_bytes` gauges). The plain
        attributes below stay authoritative; the engine's reset_stats
        pairs registry.reset() with reset_counters() so the two views
        never diverge."""
        self._entries: Dict[int, dict] = {}
        self.bytes_out = 0          # cumulative device -> host
        self.bytes_in = 0           # cumulative host -> device
        self.peak_bytes = 0         # peak host residency
        self._c_out = self._c_in = None
        self._g_res = self._g_peak = None
        if registry is not None:
            c = registry.counter("swap_bytes_total",
                                 "packed swap traffic by direction",
                                 unit="bytes", labelnames=("dir",))
            self._c_out = c.series(dir="out")
            self._c_in = c.series(dir="in")
            self._g_res = registry.gauge(
                "swap_resident_bytes",
                "packed bytes parked host-side", unit="bytes").series()
            self._g_peak = registry.gauge(
                "swap_peak_bytes",
                "peak host-side swap residency", unit="bytes").series()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    @property
    def resident_bytes(self) -> int:
        return sum(e["nbytes"] for e in self._entries.values())

    @staticmethod
    def _to_host(groups) -> Tuple[List[dict], int]:
        # one explicit fetch of the whole pytree — per-plane np.asarray
        # is an implicit sync per plane on the scheduler path (HL202)
        host = jax.device_get([dict(planes) for planes in groups])
        nbytes = sum(int(a.nbytes) for hp in host for a in hp.values())
        return host, nbytes

    def put(self, key: int, groups: Sequence[dict], pos: int) -> int:
        """Swap a sequence out. `groups`: one gather_slot_pages dict per
        cache group (device arrays); `pos` its seq position. Returns the
        bytes moved to host."""
        assert key not in self._entries, f"request {key} already swapped"
        host, nbytes = self._to_host(groups)
        self._entries[key] = {"groups": host, "pos": int(pos),
                              "nbytes": nbytes}
        self.bytes_out += nbytes
        self.peak_bytes = max(self.peak_bytes, self.resident_bytes)
        if self._c_out is not None:
            self._c_out.inc(nbytes)
            self._g_res.set(self.resident_bytes)
            self._g_peak.set_max(self.peak_bytes)
        return nbytes

    def pos(self, key: int) -> int:
        return self._entries[key]["pos"]

    def n_pages(self, key: int) -> int:
        return int(self._entries[key]["groups"][0]["k_data"].shape[1])

    def pop(self, key: int) -> Tuple[List[dict], int]:
        """Swap a sequence back in: returns (host plane dicts per group,
        pos) and drops the entry."""
        entry = self._entries.pop(key)
        self.bytes_in += entry["nbytes"]
        if self._c_in is not None:
            self._c_in.inc(entry["nbytes"])
            self._g_res.set(self.resident_bytes)
        return entry["groups"], entry["pos"]

    def discard(self, key: int) -> int:
        """Drop a parked entry without restoring it (a cancelled
        request): the planes are simply forgotten, so no swap-in traffic
        is charged — `bytes_in` counts bytes that actually crossed back.
        Returns the bytes released from host residency."""
        nbytes = int(self._entries.pop(key)["nbytes"])
        if self._g_res is not None:
            self._g_res.set(self.resident_bytes)
        return nbytes

    def reset_counters(self) -> None:
        """Zero the traffic counters and restart the residency peak at
        the current footprint — the warmup/measure boundary
        (engine.reset_stats)."""
        self.bytes_out = 0
        self.bytes_in = 0
        self.peak_bytes = self.resident_bytes
        if self._g_res is not None:
            self._g_res.set(self.resident_bytes)
            self._g_peak.set(self.peak_bytes)


# ----------------------------------------------------------------------
# footprint accounting
# ----------------------------------------------------------------------

def modeled_pool_bytes(stores) -> dict:
    """Model the §5.1 HBM residency of the page pools.

    Walks a pytree of PagedCacheStore (stacked or not); the packed pools
    are charged the `kernels.ops` data/ctrl figures (one meta plane models
    the ShiftCtrl side-band + MuxCtrl already folded into the data-plane
    figure, so we charge values once), bookkeeping arrays (block tables,
    positions, scales) at their actual dtype sizes."""
    from repro.kernels.ops import ctrl_bytes_per_value, data_bytes_per_value
    tally = {"data_bytes": 0.0, "ctrl_bytes": 0.0, "values": 0,
             "other_bytes": 0.0}

    def visit(st):
        n = st.k_data.size + st.v_data.size
        tally["data_bytes"] += n * data_bytes_per_value(st.codec)
        tally["ctrl_bytes"] += n * ctrl_bytes_per_value(st.codec)
        tally["values"] += n
        for extra in (st.k_scale, st.v_scale, st.block_table, st.seq_pos):
            tally["other_bytes"] += extra.size * extra.dtype.itemsize
        return st

    jax.tree.map(visit, stores,
                 is_leaf=lambda n: isinstance(n, PagedCacheStore))
    tally["total_bytes"] = (tally["data_bytes"] + tally["ctrl_bytes"] +
                            tally["other_bytes"])
    return tally


def modeled_pool_bytes_per_device(stores) -> dict:
    """Per-device share of `modeled_pool_bytes` under tensor parallelism.

    The packed pool planes (and their ShiftCtrl side-band) shard along
    the KV-head axis over the mesh's "model" axis, so each device holds
    exactly 1/tp of the data+ctrl bytes; bookkeeping (block tables,
    positions, per-sequence scales) is replicated and charged in full.
    With no mesh (tp=1) this equals `modeled_pool_bytes`."""
    from repro.kernels.ops import tp_size
    meshes = set()

    def visit(st):
        meshes.add(st.mesh)
        return st

    jax.tree.map(visit, stores,
                 is_leaf=lambda n: isinstance(n, PagedCacheStore))
    assert len(meshes) == 1, f"stores disagree on mesh: {meshes}"
    tp = tp_size(next(iter(meshes)))
    tally = modeled_pool_bytes(stores)
    out = dict(tally)
    out["tp"] = tp
    out["data_bytes"] = tally["data_bytes"] / tp
    out["ctrl_bytes"] = tally["ctrl_bytes"] / tp
    out["total_bytes"] = (out["data_bytes"] + out["ctrl_bytes"] +
                          tally["other_bytes"])
    return out

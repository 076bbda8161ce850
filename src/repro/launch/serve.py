"""Serving drivers: scan-based batch decode and paged continuous batching,
both with SPARQ quantization at the matmuls (the paper's compute path) and
the KV cache (the §5.1 packed storage path — the memory-bound workload).

Two engines share the model and the fused packed-cache decode kernels:

  DecodeEngine (`--engine scan`, default)
      Uniform batch, contiguous per-sequence cache. Generation is one
      traced `jax.lax.scan` inside one jitted program — no per-step Python
      dispatch — so tok/s measures the model, not the host loop.

  ContinuousBatchingEngine (`--engine paged`)
      Ragged requests over a *paged* cache (models.paging): one global pool
      of fixed-size packed pages per layer, per-sequence block tables, a
      host-side free-list allocator. The host loop only schedules —
      admission (prefill + page adoption), page allocation on write, and
      page free on eviction happen *between* steps; the inner decode step
      stays a single traced function over all sequence slots, reading
      pages through the block-table variant of the fused kernel. With a
      `SchedulerPolicy` (`--preempt requeue|swap`) the pool may be
      oversubscribed: decode-time exhaustion preempts victim sequences
      (requeue-and-replay, or packed-page swap to a host `SwapStore`) and
      resumes them bit-exactly ahead of new admissions. The loop also
      accepts live traffic: `submit()`/`cancel()` mailboxes drained once
      per iteration, per-token `emit` streaming, and a wall-clock mode
      (`clock_mode="wall"`, `drain=False`) that `launch.frontend`'s
      asyncio front-end drives for latency-SLO serving.

`--kv-cache {fp32,bf16,sparq}` selects the cache layout (the paged engine
requires sparq — packed pages are its point); `--impl` picks the kernel
implementation (reference / Pallas / auto) for the quantized matmuls, the
cache codec, and the fused decode-attention kernels.

Local demos:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --batch 4 --prompt-len 64 --gen 32 --sparq 5opt \
      --kv-cache sparq
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --engine paged --batch 4 --prompt-len 64 --gen 32 \
      --sparq 5opt --kv-cache sparq --page-size 16 --n-pages 64
"""
from __future__ import annotations

import argparse
import dataclasses
import heapq
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, get_reduced_config
from repro.core.sparq import SparqConfig
from repro.data.pipeline import Batcher, DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cache as cache_mod
from repro.models import paging
from repro.models.cache import CacheConfig
from repro.models.common import QuantCtx
from repro.models.model import Model
from repro.obs import Telemetry

# host/device topology for the static analyzer (repro.analysis.host_lint;
# see docs/analysis.md). Pure literal — parsed with ast.literal_eval.
__analysis__ = {
    "traced": (
        "DecodeEngine._prefill_fn",
        "DecodeEngine._decode_fn",
        "ContinuousBatchingEngine._prefill_fn",
        "ContinuousBatchingEngine._step_fn",
        "ContinuousBatchingEngine._replay_fn",
        "paging.adopt_prefill",
        "paging.evict_slot",
        "paging.gather_slot_pages",
        "paging.restore_slot_pages",
        "paging.copy_page",
        "paging.adopt_prefix_scales",
    ),
    "host_loop": ("ContinuousBatchingEngine.run",
                  "ContinuousBatchingEngine._run_impl"),
    # both spellings: the loop aliases `sched = self._sched` up front
    "device_returning": ("sched.run", "_sched.run"),
    "device_params": (),
    # host scheduling objects — taint never attaches to these names
    # (tel/reg/sp are the repro.obs telemetry handles: pure host-side
    # counters and span buffers, never device values — see
    # docs/observability.md)
    "host_objects": ("sched", "index", "allocator", "swap",
                     "tel", "reg", "sp", "telemetry"),
}

SPARQ_PRESETS = {
    "off": None,
    "a8w8": SparqConfig(enabled=False, signed=True),
    "5opt": SparqConfig.opt5(signed=True),
    "3opt": SparqConfig.opt3(signed=True),
    "2opt": SparqConfig.opt2(signed=True),
    "6opt": SparqConfig.opt6(signed=True),
    "7opt": SparqConfig.opt7(signed=True),
}


def make_cache_config(layout: str, sparq: Optional[SparqConfig],
                      impl: str = "auto") -> CacheConfig:
    """`--kv-cache` flag -> CacheConfig. The sparq layout reuses the active
    SPARQ preset as its codec (signed; falls back to plain int8 when the
    preset is off/a8w8)."""
    if layout == "fp32":
        return CacheConfig.fp32()
    if layout == "bf16":
        return CacheConfig.bf16()
    if layout == "sparq":
        if sparq is None:   # preset off -> plain int8 storage, no trimming
            return CacheConfig(layout="sparq", impl=impl)
        return CacheConfig.sparq_cache(sparq, impl=impl)
    raise ValueError(layout)


class DecodeEngine:
    """Greedy batched generation as one traced program per phase:
    a jitted prefill and a jitted `lax.scan` over decode steps (the scan
    carries (token, caches, pos)). With the sparq layout the traced step
    quantizes on write and attends through the fused packed-cache decode
    kernel on read — the packed planes are streamed directly; no full-plane
    dequantize inside the decode loop."""

    def __init__(self, model: Model, cache_cfg: Optional[CacheConfig] = None,
                 ctx: Optional[QuantCtx] = None, scales_groups=None):
        self.model = model
        self.cache_cfg = cache_cfg or CacheConfig.fp32()
        self.ctx = ctx
        self.scales_groups = scales_groups
        self._prefill = jax.jit(self._prefill_fn)
        self._decode = jax.jit(self._decode_fn, static_argnames=("steps",))

    # ------------------------------------------------------------ traced
    def _prefill_fn(self, params, batch, caches):
        logits, caches = self.model.prefill(
            params, batch, caches, ctx=self.ctx,
            scales_groups=self.scales_groups)
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32), caches

    def _decode_fn(self, params, tok0, caches, pos0, *, steps: int):
        def step(carry, _):
            tok, caches, pos = carry
            logits, caches = self.model.decode_step(
                params, tok, caches, pos, ctx=self.ctx,
                scales_groups=self.scales_groups)
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            return (nxt, caches, pos + 1), nxt[:, 0]

        (_, caches, _), toks = jax.lax.scan(
            step, (tok0, caches, jnp.asarray(pos0, jnp.int32)), None,
            length=steps)
        return toks.swapaxes(0, 1), caches  # [B, steps]

    # ------------------------------------------------------------ public
    def init_cache(self, batch: int, max_len: int):
        return self.model.init_cache(batch, max_len,
                                     cache_cfg=self.cache_cfg)

    def generate(self, params, batch, gen: int, pad: int = 8,
                 max_len: Optional[int] = None, warmup: bool = True):
        """Returns (tokens [B, gen], stats).

        `max_len` caps the cache capacity (default: prompt + gen + pad
        slots). The capacity check runs host-side *before* tracing: the
        traced write path (`dynamic_update_slice_in_dim`) silently clamps
        its start index, so an overflowing decode would quietly overwrite
        the newest cache slots instead of erroring.

        `warmup` runs prefill + decode once untimed first, so prefill_s /
        decode_tok_s measure steady-state execution rather than XLA
        compilation; the first (compiling) pass is reported as compile_s.
        """
        B, prompt_len = batch["tokens"].shape
        pos0 = prompt_len + (self.model.cfg.frontend_len
                             if self.model.cfg.family == "vlm" else 0)
        max_len = max_len if max_len is not None else pos0 + gen + pad
        if pos0 + gen > max_len:
            raise ValueError(
                f"KV-cache overflow: prompt ({pos0} slots) + generation "
                f"({gen}) needs {pos0 + gen} cache slots but capacity is "
                f"{max_len}; the traced write path would silently clamp "
                f"and overwrite the newest entries")
        caches = self.init_cache(B, max_len)

        compile_s = 0.0
        if warmup:
            t0 = time.perf_counter()
            tok_w, caches_w = self._prefill(params, batch, caches)
            if gen > 1:
                rest_w, _ = self._decode(params, tok_w, caches_w, pos0,
                                         steps=gen - 1)
                jax.block_until_ready(rest_w)
            else:
                jax.block_until_ready(tok_w)
            compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        tok0, caches = self._prefill(params, batch, caches)
        jax.block_until_ready(tok0)
        t_prefill = time.perf_counter() - t0

        t0 = time.perf_counter()
        if gen > 1:
            rest, caches = self._decode(params, tok0, caches, pos0,
                                        steps=gen - 1)
            jax.block_until_ready(rest)
            toks = jnp.concatenate([tok0, rest], axis=1)
        else:
            toks = tok0
        t_decode = time.perf_counter() - t0

        tally = cache_mod.modeled_cache_bytes(caches)
        stats = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "compile_s": compile_s,
            "decode_tok_s": (B * (gen - 1) / max(t_decode, 1e-9))
                            if gen > 1 else 0.0,
            "cache_bytes_per_value":
                cache_mod.bytes_per_value(self.cache_cfg),
            "cache_ctrl_bytes_per_value":
                cache_mod.ctrl_bytes_per_value(self.cache_cfg),
            "cache_data_bytes": tally["data_bytes"],
            "cache_total_bytes": tally["total_bytes"],
        }
        return toks, stats


def serve(model: Model, params, batch, gen: int,
          ctx: QuantCtx | None, scales_groups=None,
          cache_cfg: Optional[CacheConfig] = None, warmup: bool = True):
    """Greedy batched generation. Returns (tokens [B, gen], stats)."""
    engine = DecodeEngine(model, cache_cfg, ctx, scales_groups)
    return engine.generate(params, batch, gen, warmup=warmup)


# ----------------------------------------------------------------------
# continuous batching over the paged cache
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request: a prompt and a total token budget.

    `gen` counts like DecodeEngine's: total greedy tokens to return,
    including the one the prefill emits. `arrive_at` delays admission
    until the engine clock reaches it (0 = available at start): under
    the default `clock_mode="step"` the clock counts decode steps (plus
    idle fast-forwards); under `clock_mode="wall"` it is monotonic
    seconds since the run started (`time.perf_counter` based), so an
    arrival trace replays at real wall times. Either way it changes
    *when* a request is served, never its tokens."""
    tokens: np.ndarray          # [L] int prompt token ids
    gen: int
    arrive_at: float = 0.0      # engine-clock time at which it arrives

    def __post_init__(self):
        self.tokens = np.asarray(self.tokens)
        assert self.tokens.ndim == 1 and self.tokens.size >= 1
        assert self.gen >= 1
        assert self.arrive_at >= 0


@dataclasses.dataclass(frozen=True)
class SchedulerPolicy:
    """What to do when decode-time page allocation finds the pool dry.

    preempt  "requeue": drop the victim's pages and rebuild its cache
             later by re-running prefill plus a teacher-forced replay of
             its already-emitted tokens through the decode path — zero
             host traffic, recompute cost on resume. Exact because both
             passes are the deterministic programs that produced the
             original bytes.
             "swap": copy the victim's packed pages verbatim to a host
             SwapStore (§5.1 bytes: 0.9375 B/value modeled, ~4.3x less
             traffic than fp32 planes) and scatter them back when pages
             free up — no recompute, host bandwidth cost. Bit-exact by
             construction.
             "auto": pick per victim from the cost model below.
    victim   "last_joined": preempt the most recently admitted sequence
             first (oldest work is closest to completion).
             "fewest_pages": preempt the sequence owning the fewest pages
             (cheapest to rebuild/swap); ties broken last-joined-first.

    Either way resumed sequences take strict priority over new admissions
    (resume-before-admit), so preempted work cannot starve.

    Cost model (`--preempt auto`, `estimate_cost`): a requeue pays
    recompute — the prompt re-prefills in parallel (cheap per token) but
    every already-emitted token replays through the *sequential* decode
    path (one latency-bound step each), so its cost grows with decode
    progress. A swap pays bytes — the §5.1 packed pages cross the host
    link twice (out + in), so its cost grows with resident pages but is
    flat in decode progress. Early-life victims requeue, long-running
    victims swap; the crossover is pinned by a unit test. The knobs are
    modeled microseconds, not measurements — tune per deployment.
    """
    preempt: str = "requeue"        # requeue | swap | auto
    victim: str = "last_joined"     # last_joined | fewest_pages
    prefill_tok_us: float = 2.0     # re-prefill, parallel over the prompt
    replay_tok_us: float = 60.0     # teacher-forced decode replay, per step
    swap_gb_s: float = 8.0          # host<->device link bandwidth

    def __post_init__(self):
        if self.preempt not in ("requeue", "swap", "auto"):
            raise ValueError(f"unknown preempt mode {self.preempt!r}")
        if self.victim not in ("last_joined", "fewest_pages"):
            raise ValueError(f"unknown victim rule {self.victim!r}")

    def estimate_cost(self, prompt_len: int, generated: int,
                      swap_bytes: int) -> Tuple[float, float]:
        """Modeled (requeue_us, swap_us) for evicting + resuming one
        victim with `prompt_len` prompt tokens, `generated` tokens
        emitted so far, and `swap_bytes` §5.1 bytes resident in its
        pages (both directions are charged — gather out, scatter in)."""
        requeue = self.prefill_tok_us * prompt_len \
            + self.replay_tok_us * max(generated - 1, 0)
        swap = 2.0 * swap_bytes / (self.swap_gb_s * 1e3)   # bytes -> us
        return requeue, swap

    def resolve(self, prompt_len: int, generated: int,
                swap_bytes: int) -> str:
        """The concrete mode for one victim ("requeue" or "swap")."""
        if self.preempt != "auto":
            return self.preempt
        requeue, swap = self.estimate_cost(prompt_len, generated,
                                           swap_bytes)
        return "requeue" if requeue <= swap else "swap"


@dataclasses.dataclass
class _Slot:
    """Host-side state of one active sequence slot."""
    rid: int                    # request index
    target: int                 # total tokens to emit (== Request.gen)
    generated: int              # tokens emitted so far (tok0 counts)
    pages: List[int]            # physical pages owned by this sequence
    joined: int = 0             # admission sequence number (victim order)
    replay: List[int] = dataclasses.field(default_factory=list)
    # ^ chunked-mode requeue resume: already-emitted tokens still to be
    #   fed (teacher-forced) through the regular decode steps once the
    #   chunked re-prefill completes; outputs of those steps are
    #   discarded (the tokens are already recorded), their cache writes
    #   are the point. Empty for every other slot.


@dataclasses.dataclass
class _Preempted:
    """A preempted request waiting on the resume queue."""
    rid: int
    req: Request
    toks: List[int]             # greedy tokens emitted before preemption
    swapped: bool               # True: packed pages parked in the SwapStore


class ContinuousBatchingEngine:
    """Greedy generation over ragged requests with a paged SPARQ cache.

    The engine owns `max_active` sequence slots and one page pool
    (`n_pages` pages of `page_size` slots, shared page ids across layers).
    Requests queue for admission; a free slot admits the next request by
    prefilling it alone through the ordinary contiguous path (which also
    calibrates its per-sequence scales), then adopting the packed planes
    into freshly allocated pages — bit-identical bytes, no requantization.
    Every decode step is one jitted call over all S slots (inactive slots
    are masked inside the kernel); between steps the host only does
    scheduling: evict finished sequences (pages back to the free list),
    resume preempted sequences then admit from the queue, and allocate a
    page when a sequence's next token crosses into an unallocated block.

    With `policy=None` decode-time pool exhaustion raises `PoolExhausted`
    host-side, before any tracing. With a `SchedulerPolicy` the pool may
    be *oversubscribed*: exhaustion instead preempts victim sequences —
    requeueing them (drop pages, rebuild by prefill + teacher-forced
    replay on resume) or swapping their packed pages to a host
    `SwapStore` — and resumes them bit-exactly, ahead of new admissions,
    once pages free up. Greedy tokens are identical with and without
    preemption (tested for the int8 grid and the 4-bit 5opt codec under
    both policies); `PoolExhausted` then only fires when no victim
    remains to preempt.

    Restrictions: standard-KV attention families only (dense / MoE-GQA);
    MLA latent caches, recurrent state, and encoder-decoder cross caches
    keep the contiguous engine. The cache layout must be sparq.
    """

    def __init__(self, model: Model, cache_cfg: CacheConfig,
                 ctx: Optional[QuantCtx] = None, scales_groups=None, *,
                 page_size: int = 16, n_pages: int = 64,
                 max_active: int = 4, max_seq_len: int = 512,
                 policy: Optional[SchedulerPolicy] = None,
                 prefill: str = "sequential", chunk_size: int = 32,
                 chunk_align: int = 8, chunk_seg: Optional[int] = None,
                 prefix_cache: bool = False, prefix_min_pages: int = 1,
                 prefill_priority: float = 1.0, mesh=None,
                 telemetry: Optional[Telemetry] = None):
        if cache_cfg.layout != "sparq":
            raise ValueError("the paged engine stores packed §5.1 pages; "
                             "use --kv-cache sparq")
        bad = [k for k in model.kinds if k not in ("dense", "moe")]
        if bad or model.cfg.family == "vlm":
            raise ValueError(
                f"paged serving supports standard-KV attention stacks only "
                f"(got kinds {sorted(set(bad))or model.cfg.family}); use the "
                f"scan engine for MLA/recurrent/enc-dec/VLM architectures")
        if max_seq_len % page_size:
            raise ValueError(f"max_seq_len {max_seq_len} must be a multiple "
                             f"of page_size {page_size}")
        if prefill not in ("sequential", "chunked"):
            raise ValueError(f"unknown prefill mode {prefill!r}")
        if prefill_priority <= 0:
            raise ValueError("--prefill-priority must be > 0: it is the "
                             "mean prefill chunks run per scheduler "
                             "iteration (1.0 = one chunk per decode step)")
        if prefill_priority != 1.0 and prefill != "chunked":
            raise ValueError("--prefill-priority only meters the chunked "
                             "prefill stream; add --prefill chunked")
        if prefix_cache and prefill != "chunked":
            raise ValueError(
                "--prefix-cache requires --prefill chunked: only the "
                "chunked path's segment-granular scale freezing makes "
                "packed prefill bytes a pure function of (prompt, seg) — "
                "sequential admission freezes scales from the whole "
                "prompt's range, so equal prefixes of different prompts "
                "would not share bytes")
        # tensor parallelism: a ("data","model") jax Mesh shards the page
        # pools and attention heads over the "model" axis (head groups
        # never split, so n_kv_heads must divide). The host-side
        # allocator / prefix index / scheduler stay global — every device
        # sees the same block tables, and swap/requeue move each device's
        # local planes. See docs/sharding.md.
        from repro.kernels.ops import tp_size
        self.mesh = mesh
        self.tp = tp_size(mesh)
        self._rep_sharding = None if mesh is None else \
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        if self.tp > 1 and model.cfg.n_kv_heads % self.tp:
            raise ValueError(
                f"--tp {self.tp} must divide n_kv_heads="
                f"{model.cfg.n_kv_heads}: the packed (data, meta) planes "
                f"shard by whole GQA head groups")
        if mesh is not None and ctx is not None:
            ctx = dataclasses.replace(ctx, mesh=mesh)
        self.model = model
        self.cc = cache_cfg
        self.ctx = ctx
        self.scales_groups = scales_groups
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_active = max_active
        self.n_blocks = max_seq_len // page_size
        self.policy = policy
        self.prefill_mode = prefill
        # host bytes one resident page actually moves on a swap round
        # trip (for SchedulerPolicy "auto"): four int8 planes per layer
        # (K/V x data/meta) — the same figure SwapStore's bytes_out/in
        # counters measure, so the cost model and the reported stats
        # agree. (On §5.1 hardware the packed planes would move
        # kernels.ops.bytes_per_value instead, ~2.1x less for 5opt —
        # fold that into swap_gb_s when modeling such a link.)
        cfgm = model.cfg
        n_layers = sum(count for _, count in model.groups_meta)
        self._page_bytes = int(4 * n_layers * page_size * cfgm.n_kv_heads
                               * cfgm.head_dim)
        # telemetry: always-on metrics registry (one float add per
        # event); span tracing and per-step phase histograms only when
        # the caller attaches them (Telemetry.tracing() /
        # .metrics_only()). Every stats-dict entry is sourced from this
        # registry — see docs/observability.md for the catalog.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._sched = None
        if prefill == "chunked":
            from repro.launch.prefill import PrefillScheduler
            self._sched = PrefillScheduler(
                model, ctx, scales_groups, chunk_size=chunk_size,
                align=chunk_align, page_size=page_size,
                n_slots=max_active, seg=chunk_seg, mesh=mesh,
                telemetry=self.telemetry)
        self.prefix_cache = prefix_cache
        self.prefix_min_pages = max(1, prefix_min_pages)
        # prefix-match granularity: whole pages (only fully-written,
        # never-rewritten pages are shareable) AND whole prefill segments
        # (the tail job must resume at a segment boundary, and the
        # adopted scale is only the borrower's own would-be scale when
        # the shared prefix covers the first segment)
        self._quantum = math.lcm(page_size, self._sched.seg) \
            if prefix_cache else 0
        # requeue resume replays decode steps through a temporary
        # *contiguous* cache; pinning its fused-kernel tile to the page
        # size makes the replay reads bit-identical to the paged reads
        # that produced the original tokens (one page == one Tk tile)
        self._cc_replay = dataclasses.replace(cache_cfg, attn_bk=page_size)
        self._debug_state: dict = {}     # last run's allocator/slots (tests)
        self.prefill_priority = float(prefill_priority)
        # live-traffic mailboxes: submit()/cancel() may be called from any
        # thread while run() is looping; the loop drains both under the
        # lock exactly once per iteration, so everything inside the loop
        # stays single-threaded. `_wake` shortens idle sleeps when traffic
        # lands; `_run_live` gates submissions to a running loop.
        self._mbox_lock = threading.Lock()
        self._inbox: List[Tuple[int, Request, Optional[float]]] = []
        self._cancel_box: set = set()
        self._wake = threading.Event()
        self._run_live = threading.Event()
        self._stop_flag = False
        self._next_rid = 0
        self._t_origin: Optional[float] = None   # wall t0 of the live run
        self._live: Optional[dict] = None        # reset_stats() target
        self._prefill = jax.jit(self._prefill_fn)
        self._replay = jax.jit(self._replay_fn)
        # donate the cache buffers: the pools are the dominant state and
        # every step rewrites them in place — without donation XLA would
        # copy all packed planes each token, doubling the traffic the
        # packed format exists to shrink. run() rebinds `caches` on every
        # update and derives pos_dev as a fresh slice, so donation is
        # safe; `tok` is NOT donated (history keeps each step's tokens
        # alive until final assembly).
        self._step = jax.jit(self._step_fn, donate_argnums=(2,))
        self._adopt = jax.jit(paging.adopt_prefill, donate_argnums=(0,))
        self._evict = jax.jit(paging.evict_slot, donate_argnums=(0,))
        # swap-out gathers copy out of the pool (no donation); swap-in
        # scatters rewrite it in place (donated like adoption)
        self._gather = jax.jit(paging.gather_slot_pages)
        self._restore = jax.jit(paging.restore_slot_pages,
                                donate_argnums=(0,))
        # shared-prefix admission: copy-on-write page duplication and
        # donor-scale adoption (both rewrite the store in place)
        self._copy_page = jax.jit(paging.copy_page, donate_argnums=(0,))
        self._adopt_scales = jax.jit(paging.adopt_prefix_scales,
                                     donate_argnums=(0,))

    # ------------------------------------------------------------ traced
    def _prefill_fn(self, params, batch, caches):
        logits, caches = self.model.prefill(
            params, batch, caches, ctx=self.ctx,
            scales_groups=self.scales_groups)
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32), caches

    def _step_fn(self, params, tok, caches, pos):
        logits, caches = self.model.decode_step(
            params, tok, caches, pos, ctx=self.ctx,
            scales_groups=self.scales_groups)
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32), caches

    def _replay_fn(self, params, toks, caches, pos0):
        """Teacher-forced decode replay for requeue resume: feed the
        recorded greedy tokens `toks` [1, n] through the contiguous decode
        path, writing their K/V at positions pos0..pos0+n-1. The logits
        are discarded (the tokens are already known), so XLA drops the
        head matmul; what remains is exactly the cache-write path that
        produced the original bytes — replayed bytes are bit-identical."""
        def step(carry, tok_t):
            caches, pos = carry
            _, caches = self.model.decode_step(
                params, tok_t[:, None], caches, pos, ctx=self.ctx,
                scales_groups=self.scales_groups)
            return (caches, pos + 1), ()

        (caches, _), _ = jax.lax.scan(
            step, (caches, jnp.asarray(pos0, jnp.int32)),
            toks.swapaxes(0, 1))
        return caches

    # ------------------------------------------------------------ device
    def _init_stores(self) -> list:
        cfg = self.model.cfg
        stores = []
        for kind, count in self.model.groups_meta:
            one = paging.PagedCacheStore.init(
                self.max_active, self.n_pages, self.page_size,
                self.n_blocks, cfg.n_kv_heads, cfg.head_dim, self.cc,
                mesh=self.mesh)
            stacked = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (count,) + x.shape).copy(),
                one)
            if self.mesh is not None:
                # place the pools physically: packed planes sharded along
                # the KV-head axis, bookkeeping replicated (the host
                # scheduler is global, so every device needs the tables)
                from repro.distributed.sharding import paged_pool_shardings
                stacked = jax.device_put(
                    stacked, paged_pool_shardings(stacked, self.mesh))
            stores.append(stacked)
        return stores

    def _replicated(self, x):
        """Host->device placement for per-step scalars/tables under TP:
        one explicit replicated device_put (the blessed transfer) instead
        of letting the jitted step reshard a single-device array."""
        if self._rep_sharding is None:
            return x
        return jax.device_put(x, self._rep_sharding)

    # ------------------------------------------------------------ trace
    @staticmethod
    def _snapshot(n_steps, allocator, slots, host_bt, host_pos, caches,
                  queue, resume_q, swap, prefilling=(),
                  replaying=(), prefix=None) -> dict:
        """Scheduler-state snapshot handed to `run(trace_hook=...)` before
        each traced decode step. Host fields are copies (safe to keep);
        `caches` is the live device state for deep cross-checks.
        `prefilling` lists slots mid-chunked-prefill (their device
        seq_pos is the -1 inactive sentinel while host `pos` counts the
        prompt tokens already written); `replaying` lists slots replaying
        recorded tokens after a chunked requeue resume."""
        return {
            "step": n_steps,
            "n_pages": allocator.n_pages,
            "free_pages": allocator.free_pages,
            "peak_pages": allocator.peak_used,
            "slots": {s: {"rid": st.rid, "pages": list(st.pages),
                          "pos": int(host_pos[s]),
                          "generated": st.generated, "target": st.target,
                          "joined": st.joined}
                      for s, st in enumerate(slots) if st is not None},
            "host_bt": host_bt.copy(),
            "queued": [rid for _, rid, _ in sorted(queue)],
            "resume_rids": [rec.rid for rec in resume_q],
            "swapped_rids": sorted(
                rec.rid for rec in resume_q if rec.swapped),
            "swap_resident_bytes": swap.resident_bytes,
            "prefilling": tuple(prefilling),
            "replaying": tuple(replaying),
            "page_refcounts": allocator.refcounts,
            "prefix": dict(prefix) if prefix is not None else None,
            "caches": caches,
        }

    # ------------------------------------------------------ live traffic
    def _validate_request(self, req: Request, label="request") -> None:
        need = len(req.tokens) + req.gen - 1
        ps = self.page_size
        if need > self.n_blocks * ps or math.ceil(need / ps) > self.n_pages:
            raise ValueError(
                f"{label} needs {need} slots "
                f"({math.ceil(need / ps)} pages) but the engine serves "
                f"at most {self.n_blocks * ps} slots/sequence from "
                f"{self.n_pages} pages — raise max_seq_len/n_pages")

    def submit(self, req, at: Optional[float] = None) -> int:
        """Hand a new request to a *running* `run()` loop; thread-safe.

        Returns the request id the results/stream will use. `at` is the
        engine-clock arrival time (see Request.arrive_at); None stamps
        the request with the clock value at mailbox drain — i.e. "it
        arrived now". The request's own `arrive_at` field is ignored on
        this path (`at` is authoritative). Raises RuntimeError when no
        run loop is live to serve it."""
        # duck-typed: `python -m repro.launch.serve` loads this module as
        # __main__, so an isinstance against Request would reject Request
        # objects built by importers of repro.launch.serve
        req = req if hasattr(req, "tokens") else Request(*req)
        self._validate_request(req, label="submitted request")
        if not self._run_live.wait(timeout=5.0):
            raise RuntimeError(
                "submit() requires a live run() loop — start the engine "
                "(e.g. through launch.frontend.AsyncFrontend) first")
        with self._mbox_lock:
            rid = self._next_rid
            self._next_rid += 1
            self._inbox.append((rid, req, None if at is None else float(at)))
        self._wake.set()
        return rid

    def cancel(self, rid: int) -> None:
        """Cancel a request by id; thread-safe, idempotent, best-effort
        (a request that already finished is left untouched). Queued
        requests are dropped; a mid-prefill request drops its
        PrefillScheduler job and grants; an active or preempted one is
        evicted — shared prefix pages refcount-released, swapped planes
        discarded without a swap-in. Partial tokens stay in the result."""
        with self._mbox_lock:
            self._cancel_box.add(rid)
        self._wake.set()

    def request_stop(self) -> None:
        """Ask a `drain=False` (serve-forever) run loop to exit at the
        next iteration; thread-safe. In-flight requests are abandoned
        with partial results. Draining runs ignore it."""
        self._stop_flag = True
        self._wake.set()

    def reset_stats(self) -> None:
        """Zero the live run's measurement counters in place — the
        warmup/measure boundary. After a warmup workload has compiled
        every program and warmed the PrefixIndex, calling this makes the
        subsequently reported stats (prefix hits, preemptions, peak
        pages/swap watermarks, timings, tok/s) reflect only the traffic
        that follows, instead of inheriting the warmup's. Call it from
        the engine thread (a trace_hook) or while the loop is idle."""
        lv = self._live
        if lv is None:
            return
        reg = self.telemetry.registry
        reg.reset()
        # gauges restart from the *current* occupancy, exactly as the
        # old acc["peak_pages"] restarted from allocator.used_count
        reg.gauge("pool_pages_in_use").set(lv["allocator"].used_count)
        reg.gauge("pool_pages_peak").set(lv["allocator"].used_count)
        lv["acc"].update(t0=time.perf_counter())
        lv["allocator"].reset_peak()
        lv["swap"].reset_counters()
        if lv["sched"] is not None:
            lv["sched"].chunks_run = 0

    # ------------------------------------------------------------ public
    def run(self, params, requests: Sequence[Request],
            progress: bool = False, trace_hook=None, emit=None,
            clock_mode: str = "step", drain: bool = True
            ) -> Tuple[Dict[int, np.ndarray], dict]:
        """Serve every request to completion; greedy tokens per request.

        Returns ({request_index: int32 [gen] tokens}, stats). Each run
        starts from a fresh pool and fresh (uncalibrated) scales, so a run
        is reproducible and re-entrant; jitted programs are reused across
        runs (call once to warm up, again to time steady state).

        `trace_hook`, if given, is called with a scheduler-state snapshot
        dict immediately before every traced decode step (see `_snapshot`)
        — the randomized-trace test harness asserts per-step invariants
        there. Page accounting invariants (free-list conservation, no
        double-use, block-table/position consistency) are additionally
        asserted internally every iteration regardless of the hook.

        `emit`, if given, streams tokens: `emit(rid, token, final, t)` is
        called from the engine thread with each host-int greedy token the
        moment its step's device fetch lands (`t` = perf_counter stamp;
        one batched `jax.device_get` per decode step, never per token).
        Streaming runs skip the device-side history (results come from
        the emitted host ints), so a serve-forever loop holds no
        per-token device garbage.

        `clock_mode` selects the arrival clock `Request.arrive_at` is
        compared against: "step" (default) counts decode steps and
        fast-forwards over idle gaps — deterministic, for tests and
        throughput benchmarks; "wall" reads monotonic seconds since run
        start, and idle waits sleep in real time — the latency-SLO mode.

        `drain=False` (requires "wall") keeps the loop alive when queue
        and slots are empty, serving `submit()` traffic until
        `request_stop()` — the asyncio front-end's serve-forever mode.
        Completion asserts are skipped for requests still in flight at
        stop; their partial token streams are returned as-is.
        """
        if clock_mode not in ("step", "wall"):
            raise ValueError(f"unknown clock_mode {clock_mode!r}")
        if not drain and clock_mode != "wall":
            raise ValueError("drain=False (serve-forever) needs "
                             "clock_mode='wall': a step clock cannot "
                             "sleep for traffic")
        try:
            return self._run_impl(params, requests, progress, trace_hook,
                                  emit, clock_mode, drain)
        finally:
            # a finished (or dead) loop must stop accepting traffic:
            # late submit()/reset_stats() calls fail fast / no-op instead
            # of landing in state nobody is serving
            self._run_live.clear()
            self._live = None
            # a dead loop never reached run_end: drop the tracer's
            # process-global hooks (gc callbacks, the compile listener)
            self.telemetry.spans.runtime_off()

    def _run_impl(self, params, requests, progress, trace_hook,
                  emit, clock_mode, drain):
        wall = clock_mode == "wall"
        requests = {i: (r if hasattr(r, "tokens") else Request(*r))
                    for i, r in enumerate(requests)}
        ps, NB = self.page_size, self.n_blocks
        sched = self._sched
        if sched is not None:
            sched.reset()
        for i, r in requests.items():
            self._validate_request(r, label=f"request {i}")

        # ---- telemetry: the registry is the single store for every
        # scheduling counter and timing this run reports (the stats dict
        # below is assembled from registry reads). Series handles are
        # pre-bound here so the hot loop pays one float add per event.
        # reg.reset() gives each run fresh stats, matching the fresh
        # pool/scales semantics of run() itself.
        tel = self.telemetry
        reg = tel.registry
        sp = tel.spans
        reg.reset()
        c_preempt = reg.counter("engine_preemptions_total",
                                "sequences preempted, by resolved mode",
                                labelnames=("mode",))
        c_pre_req = c_preempt.series(mode="requeue")
        c_pre_swap = c_preempt.series(mode="swap")
        c_resumes = reg.counter("engine_resumes_total",
                                "preempted sequences rebuilt").series()
        c_replay = reg.counter("engine_replay_steps_total",
                               "teacher-forced replay decode steps"
                               ).series()
        c_cancel = reg.counter("engine_cancelled_total",
                               "requests cancelled mid-flight").series()
        c_steps = reg.counter("engine_decode_steps_total",
                              "jitted decode steps executed").series()
        c_tokens = reg.counter("engine_decode_tokens_total",
                               "greedy tokens emitted by decode steps"
                               ).series()
        c_chunks = reg.counter("engine_prefill_chunks_total",
                               "chunked-prefill chunk programs run"
                               ).series()
        c_t_prefill = reg.counter("engine_prefill_seconds_total",
                                  "time admitting prompts (prefill)",
                                  unit="seconds").series()
        c_t_resume = reg.counter("engine_resume_seconds_total",
                                 "time rebuilding preempted sequences",
                                 unit="seconds").series()
        c_phit = reg.counter("prefix_cache_hits_total",
                             "admissions adopting cached prefix pages"
                             ).series()
        c_pmiss = reg.counter("prefix_cache_misses_total",
                              "admissions with no usable cached prefix"
                              ).series()
        c_ptok = reg.counter("prefix_cache_hit_tokens_total",
                             "prompt tokens served from cached pages"
                             ).series()
        c_pshared = reg.counter("prefix_cache_shared_pages_total",
                                "whole pages adopted from the cache"
                                ).series()
        c_cow = reg.counter("prefix_cache_cow_copies_total",
                            "copy-on-write boundary-page duplications"
                            ).series()
        c_refuse = reg.counter("engine_swap_refusals_total",
                               "swap preemptions demoted to requeue "
                               "(victim held shared pages)").series()
        g_pages = reg.gauge("pool_pages_in_use",
                            "pages currently allocated", unit="pages"
                            ).series()
        g_peak = reg.gauge("pool_pages_peak",
                           "high-water allocated pages", unit="pages"
                           ).series()
        g_active = reg.gauge("engine_active_slots",
                             "slots decoding this step").series()
        g_queued = reg.gauge("engine_queue_depth",
                             "requests waiting for admission").series()
        h_phase = reg.histogram("engine_step_phase_seconds",
                                "scheduler-iteration phase durations",
                                unit="seconds", labelnames=("phase",))
        h_retire = h_phase.series(phase="retire")
        h_admit = h_phase.series(phase="admit")
        h_prefill = h_phase.series(phase="prefill")
        h_decode = h_phase.series(phase="decode")
        timed = tel.step_timing or sp.on

        def prefix_stats():
            """pstats-shaped dict from registry reads (trace snapshots
            and the stats assembly below)."""
            return {"prefix_hits": int(c_phit.value()),
                    "prefix_misses": int(c_pmiss.value()),
                    "prefix_hit_tokens": int(c_ptok.value()),
                    "prefix_shared_pages": int(c_pshared.value()),
                    "cow_copies": int(c_cow.value()),
                    "swap_refusals": int(c_refuse.value())}

        allocator = paging.PageAllocator(self.n_pages)
        # fresh prefix index per run (the pool is fresh too): non-owning,
        # invalidated page-by-page as refcounts fall to zero
        index = paging.PrefixIndex(self._quantum, ps) \
            if self.prefix_cache else None
        caches = self._init_stores()
        S = self.max_active
        # under TP, pin params and the token vector replicated over the
        # mesh once, up front — every jitted program then sees committed,
        # consistently-placed inputs (no per-step implicit resharding)
        params = self._replicated(params)
        tok = self._replicated(jnp.zeros((S, 1), jnp.int32))
        slots: List[Optional[_Slot]] = [None] * S
        host_bt = np.full((S, NB), -1, np.int64)
        host_pos = np.full((S,), -1, np.int64)
        # admission order: arrival time, then request id (FIFO) — a heap,
        # because submit() pushes mid-run and the idle fast-forward must
        # always see the *earliest* pending arrival at queue[0]
        queue = [(float(r.arrive_at), rid, r) for rid, r in requests.items()]
        heapq.heapify(queue)
        cancelled: set = set()      # rids cancelled; heap entries lazy-skip
        resume_q: List[_Preempted] = []
        swap = paging.SwapStore(registry=reg)
        first_tok: Dict[int, jnp.ndarray] = {}
        emitted: Dict[int, List[int]] = {}   # emit mode: host token copies
        history: List[Tuple[tuple, jnp.ndarray]] = []
        # replay-divergence self-checks, verified after the loop in one
        # batched fetch — reading each scalar inline would sync the
        # decode pipeline at every resume / chunk completion (HL202).
        # Device scalars and host expectations ride in parallel lists so
        # the post-loop compare touches no device values.
        deferred_checks: List[jnp.ndarray] = []
        deferred_expect: List[Tuple[int, str]] = []
        join_seq = 0
        # every measurement counter lives in the registry (reset_stats
        # delegates to reg.reset()); only the run-start wall stamp stays
        # in a plain dict so reset_stats can restamp it mid-run. n_steps
        # stays a plain local — it sequences trace snapshots, never stats
        acc = {"t0": 0.0}
        n_steps = 0                 # decode steps actually executed
        clock = 0.0                 # arrival clock: steps (or wall seconds)
        chunk_credit = 0.0          # fractional prefill chunks banked
        # expose the live scheduling state for post-mortem tests: after a
        # PoolExhausted escapes, page accounting must still be consistent
        self._debug_state = {"allocator": allocator, "slots": slots,
                             "swap": swap, "prefix_index": index}
        self._live = {"acc": acc, "allocator": allocator, "swap": swap,
                      "sched": sched}
        with self._mbox_lock:
            self._next_rid = len(requests)
            self._inbox.clear()
            self._cancel_box.clear()
        self._stop_flag = False

        # ---------------- preemption machinery (closures over run state)
        def emitted_toks(rid: int) -> List[int]:
            """Host copies of every greedy token rid has emitted, in
            order, across all of its slot residencies — one batched
            device fetch per call (preemptions are rare; per-step
            fetches would sync the decode pipeline every token). In
            emit mode the per-step streaming fetch already landed every
            token on the host, so this is a pure host read."""
            if emit is not None:
                return list(emitted[rid])
            out = [int(jax.device_get(first_tok[rid]))]
            hits = [(i, s_h) for i, (act, _) in enumerate(history)
                    for s_h, r in act if r == rid]
            if hits:
                toks_np = jax.device_get(
                    jnp.concatenate([t for _, t in history], axis=1))
                out.extend(int(toks_np[s_h, i]) for i, s_h in hits)
            return out

        def drop_pages(pages: List[int]):
            """Release one reference per page; prefix-index entries naming
            any page that reached zero are invalidated (the page may be
            reallocated with different bytes). Shared pages survive — the
            other holders' references keep them resident and indexed."""
            freed = allocator.release(pages)
            if index is not None and freed:
                index.invalidate(freed)

        def evict(s: int):
            """Drop a slot's page references and clear it. Pages shared
            with other sequences stay allocated (their refcount is still
            positive); exclusively-owned ones return to the free list."""
            nonlocal caches
            drop_pages(slots[s].pages)
            caches = [self._evict(c, jnp.int32(s)) for c in caches]
            host_bt[s] = -1
            host_pos[s] = -1
            slots[s] = None

        def drain_mailboxes():
            """Fold submit()/cancel() traffic into the run state — called
            once per loop iteration, so everything else in the loop stays
            single-threaded. Arrivals stamped `at=None` arrive "now" (the
            current clock); cancellations release whatever the request
            holds: queue entry (lazy — the rid is skipped at pop), live
            slot (evicted; shared prefix pages refcount-released),
            mid-prefill job (PrefillScheduler entry + granted pages
            dropped), or resume-queue record (swapped planes discarded
            without charging a swap-in)."""
            with self._mbox_lock:
                arrivals, self._inbox = self._inbox, []
                cxl = self._cancel_box
                self._cancel_box = set()
            self._wake.clear()
            for rid, req, at in arrivals:
                requests[rid] = req
                heapq.heappush(
                    queue, (clock if at is None else float(at), rid, req))
                sp.submitted(rid)
            for rid in cxl:
                if rid in cancelled:
                    continue
                hit = any(q_rid == rid for _, q_rid, _ in queue)
                s = next((i for i, st in enumerate(slots)
                          if st is not None and st.rid == rid), None)
                if s is not None:
                    if sched is not None and sched.has(s):
                        sched.cancel(s)
                    evict(s)
                    hit = True
                rec = next((r for r in resume_q if r.rid == rid), None)
                if rec is not None:
                    resume_q.remove(rec)
                    if rec.swapped:
                        swap.discard(rid)
                    hit = True
                if hit:
                    cancelled.add(rid)
                    c_cancel.inc()
                    sp.cancelled(rid)

        def finished_slot() -> Optional[int]:
            return next((s for s, st in enumerate(slots)
                         if st is not None and st.generated >= st.target),
                        None)

        def select_victim(exclude=()):
            cands = [(s, st) for s, st in enumerate(slots)
                     if st is not None and s not in exclude]
            if not cands or self.policy is None:
                return None
            if self.policy.victim == "fewest_pages":
                key = lambda c: (len(c[1].pages), -c[1].joined)
            else:                               # last_joined
                key = lambda c: (-c[1].joined,)
            return min(cands, key=key)[0]

        def preempt(s: int):
            nonlocal caches
            st = slots[s]
            mid_prefill = sched is not None and sched.has(s)
            toks = emitted_toks(st.rid) if st.rid in first_tok else []
            assert mid_prefill or len(toks) == st.generated, \
                (st.rid, len(toks))
            # swap needs the victim's pages to hold its *complete* cache:
            # a slot mid-chunked-prefill or mid-replay has partial pages
            # only, so it always requeues (nothing but prompt recompute
            # is lost); otherwise the policy decides — "auto" from the
            # modeled recompute-vs-bytes crossover per victim.
            if mid_prefill or st.replay or not toks:
                mode = "requeue"
            else:
                mode = self.policy.resolve(
                    len(requests[st.rid].tokens), st.generated,
                    len(st.pages) * self._page_bytes)
            if mode == "swap" and any(allocator.refcount(p) > 1
                                      for p in st.pages):
                # the swap path refuses to park pages it does not
                # exclusively own: parked planes must restore verbatim
                # onto *fresh* pages later, but a shared page's other
                # holders keep it live in the pool — parking it would
                # fork the bytes (and freeing it would tear it out from
                # under them). Requeue instead: release the references
                # and rebuild by re-prefill, which may even re-match the
                # still-resident shared prefix.
                mode = "requeue"
                c_refuse.inc()
            if mid_prefill:
                sched.cancel(s)
            rec = _Preempted(rid=st.rid, req=requests[st.rid], toks=toks,
                             swapped=mode == "swap")
            if rec.swapped:
                t_sw0 = time.perf_counter() if sp.on else 0.0
                pages_dev = jnp.asarray(st.pages, jnp.int32)
                planes = [self._gather(c, jnp.int32(s), pages_dev)
                          for c in caches]
                nbytes = swap.put(st.rid, planes, int(host_pos[s]))
                if sp.on:
                    sp.swap(st.rid, t_sw0, time.perf_counter(), "out",
                            nbytes)
            caches = [self._evict(c, jnp.int32(s)) for c in caches]
            drop_pages(st.pages)
            host_bt[s] = -1
            host_pos[s] = -1
            slots[s] = None
            resume_q.append(rec)
            (c_pre_swap if rec.swapped else c_pre_req).inc()
            sp.preempted(st.rid, mode=mode)
            if progress:
                how = "swap" if rec.swapped else "requeue"
                print(f"[preempt] rid={st.rid} slot={s} mode={how} "
                      f"done={st.generated}/{st.target}")

        def bind_slot(s: int, rid: int, req: Request, pages: List[int],
                      pos: int, generated: int, last_tok):
            nonlocal tok, join_seq
            tok = tok.at[s, 0].set(last_tok)
            slots[s] = _Slot(rid=rid, target=req.gen, generated=generated,
                             pages=list(pages), joined=join_seq)
            join_seq += 1
            host_bt[s] = -1
            host_bt[s, :len(pages)] = pages
            host_pos[s] = pos

        def bind_prefilling(s: int, rid: int, req: Request, *,
                            recorded=(), start: int = 0, pages=()):
            """Bind a slot whose prompt will stream through the chunked
            prefill path: no pages yet (granted chunk by chunk), host
            position 0 (prompt tokens written so far), device seq_pos
            stays -1 so interleaved decode steps treat it as inactive.
            `recorded` (requeue resume) is the victim's already-emitted
            token list: the chunk program's tok0 is asserted against
            recorded[0] and the rest replays teacher-forced through the
            ordinary decode steps once the prompt completes.
            `start`/`pages` (shared-prefix admission): prompt positions
            [0, start) are already backed by `pages` — the adopted shared
            run plus, when start is mid-page, its private copy-on-write
            boundary page — so the prefill job begins at `start` and only
            the tail streams through the chunk program."""
            nonlocal join_seq
            recorded = list(recorded)
            pages = list(pages)
            slots[s] = _Slot(rid=rid, target=req.gen,
                             generated=len(recorded), pages=pages,
                             joined=join_seq, replay=recorded[1:])
            join_seq += 1
            host_bt[s] = -1
            host_bt[s, :len(pages)] = pages
            host_pos[s] = start
            sched.add(s, rid, req.tokens,
                      expect_tok0=recorded[0] if recorded else None,
                      start=start)

        def resume(s: int, rec: _Preempted):
            """Rebuild a preempted sequence in slot s. Caller guarantees
            the allocator holds enough pages (incl. the growth page when
            pos sits on a block boundary)."""
            nonlocal caches
            t0 = time.perf_counter()
            c_resumes.inc()
            if rec.swapped:
                nbp = swap.n_pages(rec.rid)
                pages = allocator.alloc(nbp)
                planes_np, pos = swap.pop(rec.rid)
                pages_dev = jnp.asarray(pages, jnp.int32)
                caches = [self._restore(
                    c, {k: self._replicated(jnp.asarray(v))
                        for k, v in pl.items()},
                    jnp.int32(s), pages_dev, jnp.int32(pos))
                    for c, pl in zip(caches, planes_np)]
                jax.block_until_ready(caches[0].seq_pos)
            elif sched is not None:
                # chunked requeue: the prompt re-prefills through the
                # chunked path (pages granted chunk by chunk, interleaved
                # with decode) and the emitted tokens replay teacher-
                # forced through the regular decode steps — same traced
                # programs that produced the original bytes, so the
                # rebuilt cache is bit-identical, with no per-length
                # retrace and no contiguous staging cache.
                bind_prefilling(s, rec.rid, rec.req, recorded=rec.toks)
                c_t_resume.inc(time.perf_counter() - t0)
                sp.resumed(rec.rid, phase="prefill")
                if progress:
                    print(f"[resume] rid={rec.rid} slot={s} chunked "
                          f"re-prefill queued ({len(rec.toks)} recorded)")
                return
            else:                               # requeue: recompute
                L, done = len(rec.req.tokens), len(rec.toks)
                pos = L + done - 1
                nbp = math.ceil(pos / ps)
                pages = allocator.alloc(nbp)
                tmp = self.model.init_cache(1, nbp * ps,
                                            cache_cfg=self._cc_replay)
                tok0, tmp = self._prefill(
                    params, {"tokens": jnp.asarray(rec.req.tokens)[None]},
                    tmp)
                deferred_checks.append(tok0[0, 0])
                deferred_expect.append((
                    rec.toks[0],
                    "requeue replay diverged at prefill — greedy decode "
                    "is no longer deterministic"))
                if done > 1:
                    tmp = self._replay(
                        params, jnp.asarray(rec.toks[:-1], jnp.int32)[None],
                        tmp, jnp.int32(L))
                    c_replay.inc(done - 1)
                pages_dev = jnp.asarray(pages, jnp.int32)
                caches = [self._adopt(c, t_g, jnp.int32(s), pages_dev)
                          for c, t_g in zip(caches, tmp)]
            bind_slot(s, rec.rid, rec.req, pages, pos,
                      generated=len(rec.toks), last_tok=rec.toks[-1])
            t1 = time.perf_counter()
            c_t_resume.inc(t1 - t0)
            if sp.on:
                sp.resume_work(rec.rid, t0, t1,
                               mode="swap" if rec.swapped else "replay")
                sp.resumed(rec.rid, phase="decode", t=t1)
            if progress:
                print(f"[resume] rid={rec.rid} slot={s} pos={pos} "
                      f"pages={pages}")

        def growth_debt() -> int:
            """Pages the *running* sequences need before the next step —
            the admission watermark. Joining may not drain the free list
            below this debt: a resume or admission that stole a running
            sequence's growth page would force a preemption in the very
            same iteration (and, worst case, thrash the sequence that
            just resumed)."""
            debt = 0
            for s in range(S):
                st = slots[s]
                if st is None or st.generated >= st.target:
                    continue
                if sched is not None and sched.has(s):
                    continue        # mid-prefill: pages granted per chunk
                if host_bt[s, host_pos[s] // ps] < 0:
                    debt += 1
            return debt

        def prefill_debt() -> int:
            """Pages the partially-prefilled sequences still need to
            finish their prompts — plus, as at sequential admission, the
            first boundary-growth page of any whose prompt ends exactly
            on a block boundary (its first decode write needs a fresh
            page the moment prefill completes). Charged by the admission
            watermark so a burst of new admissions cannot starve
            in-flight prefills or thrash them into preemption at their
            very first decode step (the chunked counterpart of reserving
            prompt pages up front)."""
            if sched is None:
                return 0
            debt = 0
            for j in sched.jobs:
                debt += sched.pages_outstanding(j.slot, host_bt)
                if slots[j.slot].target > 1 and len(j.tokens) % ps == 0:
                    debt += 1
            return debt

        def resume_need(rec: _Preempted) -> int:
            """Pages a resume must find free: the restored pages plus the
            growth page when the next write crosses into a new block —
            reserving it up front keeps a fresh resume from being
            immediately re-preempted by its own growth. (In chunked mode
            a requeue resume allocates lazily, chunk by chunk; the same
            figure then acts as the admission watermark so the resume
            cannot start into guaranteed starvation.)"""
            if rec.swapped:
                nbp, pos = swap.n_pages(rec.rid), swap.pos(rec.rid)
            elif not rec.toks:          # mid-prefill victim: whole prompt
                L = len(rec.req.tokens)
                return math.ceil(L / ps) + (
                    1 if rec.req.gen > 1 and L % ps == 0 else 0)
            else:
                pos = len(rec.req.tokens) + len(rec.toks) - 1
                nbp = math.ceil(pos / ps)
            return nbp + (1 if pos // ps >= nbp else 0)

        def match_prefix(tokens):
            """Longest usable cached prefix for a prompt. Returns None
            (miss) or (T, shared, cow_src, scales): prompt positions
            [0, T) come from the cache (T a segment boundary, so the
            tail job resumes legally at T), `shared` are the whole pages
            adopted for blocks [0, len(shared)), and `cow_src` names the
            donor page to copy-on-write for the next block when T is
            mid-page (a full-prompt match: at least the last segment
            re-runs to produce the first output token, and its writes
            must land in a private copy, never a shared page)."""
            L = len(tokens)
            M, pages, scales = index.match(tokens)
            if M <= 0:
                return None
            # a full-prompt match still needs logits at position L-1:
            # re-run the last segment (the packer resumes at segment
            # boundaries only), attending to the cached pages below it
            T = ((L - 1) // sched.seg) * sched.seg if M >= L else M
            K = T // ps                 # whole shared pages adopted
            if K < self.prefix_min_pages:
                return None
            cow_src = pages[K] if T % ps else None
            return T, list(pages[:K]), cow_src, scales

        def register_prefix(s: int, rid: int):
            """Index a freshly prefilled prompt's whole-quantum prefix.
            Called at chunked-prefill completion: every page below the
            registered boundary is fully written and never written again
            (decode writes land at positions >= the prompt length), and
            the slot's scales are frozen. Re-registration after a resume
            or of a shared prefix is a no-op for segments already
            indexed (first donor wins)."""
            toks = requests[rid].tokens
            reg = (len(toks) // self._quantum) * self._quantum
            if reg <= 0:
                return
            pages_reg = [int(p) for p in host_bt[s, :reg // ps]]
            scales_reg = [(c.k_scale[:, s], c.v_scale[:, s])
                          for c in caches]
            index.insert(toks[:reg], pages_reg, scales_reg)

        def check_page_accounting():
            owned = [p for st in slots if st is not None for p in st.pages]
            mult: Dict[int, int] = {}
            for p in owned:
                mult[p] = mult.get(p, 0) + 1
            assert mult == allocator.refcounts, \
                "page refcounts disagree with block-table references"
            assert allocator.free_count + len(mult) == self.n_pages, \
                "free-list conservation violated (pages leaked)"
            allocator.assert_consistent()
            for s, st in enumerate(slots):
                if st is None:
                    continue
                row = host_bt[s][host_bt[s] >= 0]
                assert list(row) == st.pages, \
                    f"slot {s}: block table disagrees with owned pages"
                assert 0 <= host_pos[s] <= len(st.pages) * ps, \
                    f"slot {s}: position outside its allocated blocks"
                # a sequence never writes into a shared page: its next
                # write position, when it lands mid-page, must target an
                # exclusively-owned page (block boundaries target a page
                # not yet allocated or freshly allocated at refcount 1)
                blk = host_pos[s] // ps
                if host_pos[s] % ps and blk < NB and host_bt[s, blk] >= 0:
                    assert allocator.refcount(int(host_bt[s, blk])) == 1, \
                        f"slot {s}: next write targets shared page " \
                        f"{int(host_bt[s, blk])}"

        def q_peek():
            """Earliest pending (arrive_at, rid, req) by heap order,
            dropping lazily-cancelled entries; None when empty."""
            while queue and queue[0][1] in cancelled:
                heapq.heappop(queue)
            return queue[0] if queue else None

        def arrived():
            head = q_peek()
            return head is not None and head[0] <= clock

        t_run0 = time.perf_counter()
        acc["t0"] = t_run0
        self._t_origin = t_run0
        sp.run_begin(t_run0)
        if sp.on:
            for rid in sorted(requests):
                sp.submitted(rid, t_run0)
        self._run_live.set()
        while True:
            if wall:
                clock = time.perf_counter() - t_run0
            it_t0 = time.perf_counter() if timed else 0.0
            drain_mailboxes()
            # ---- evict finished sequences: pages back to the free list
            # (before the stop check: a shutdown right after a final
            # token must still release that sequence's pages)
            while (fin := finished_slot()) is not None:
                sp.finished(slots[fin].rid)
                evict(fin)
            if self._stop_flag and not drain:
                break                           # serve-forever shutdown
            t_admit0 = time.perf_counter() if timed else 0.0

            # ---- resume preempted sequences, then admit new arrivals.
            # Strict resume-before-admit: while a preempted sequence
            # waits, nothing younger is admitted past it.
            while None in slots and (resume_q or arrived()):
                s = slots.index(None)
                if resume_q:
                    rec = resume_q[0]
                    if allocator.free_count < resume_need(rec) \
                            + growth_debt() + prefill_debt():
                        break                   # wait for evictions
                    resume_q.pop(0)
                    resume(s, rec)
                    continue
                _, rid, req = q_peek()
                L = len(req.tokens)
                nbp = math.ceil(L / ps)
                # shared-prefix match (chunked + --prefix-cache): blocks
                # covered by adopted pages need no fresh allocation, so
                # the watermark charges only the unshared tail. Matching
                # takes no references — safe to re-match next iteration
                # if the watermark defers admission.
                hit = match_prefix(req.tokens) if index is not None \
                    else None
                nbp_fresh = nbp - (len(hit[1]) if hit is not None else 0)
                # watermark: fresh prompt pages, plus this request's own
                # first growth page when its prompt ends on a block
                # boundary, plus the running sequences' growth debt, plus
                # the pages partially-prefilled sequences still need
                own = 1 if (req.gen > 1 and L % ps == 0) else 0
                if allocator.free_count < nbp_fresh + own + growth_debt() \
                        + prefill_debt():
                    if not any(slots):
                        allocator.alloc(nbp_fresh + own)  # PoolExhausted
                    break                       # wait for evictions
                heapq.heappop(queue)
                if sched is not None:
                    # chunked admission is a host-side bind only: pages
                    # are granted chunk by chunk and the prompt streams
                    # through the shared chunk program interleaved with
                    # decode steps — a long prompt no longer stalls the
                    # loop for its whole length
                    if hit is not None:
                        T, shared, cow_src, sc = hit
                        allocator.share(shared)
                        hit_pages = list(shared)
                        if cow_src is not None:
                            # the tail resumes mid-page: duplicate the
                            # donor's boundary page so the tail chunk
                            # rewrites a private copy (rows below T stay
                            # bit-identical; rows at/above are overwritten)
                            (pg,) = allocator.alloc(1)
                            caches = [self._copy_page(
                                c, jnp.int32(cow_src), jnp.int32(pg))
                                for c in caches]
                            hit_pages.append(pg)
                            c_cow.inc()
                        # donor scales must be installed before the tail
                        # chunk runs: the tail carries no first-segment
                        # tokens, so nothing else would calibrate them
                        caches = [self._adopt_scales(
                            c, jnp.int32(s), k_sc, v_sc)
                            for c, (k_sc, v_sc) in zip(caches, sc)]
                        bind_prefilling(s, rid, req, start=T,
                                        pages=hit_pages)
                        c_phit.inc()
                        c_ptok.inc(T)
                        c_pshared.inc(len(shared))
                        sp.admitted(rid, mode="chunked")
                        if progress:
                            print(f"[admit] rid={rid} slot={s} prompt={L} "
                                  f"prefix hit: {T} tokens / "
                                  f"{len(shared)} shared pages"
                                  + (" + CoW" if cow_src is not None
                                     else ""))
                        continue
                    if index is not None:
                        c_pmiss.inc()
                    bind_prefilling(s, rid, req)
                    sp.admitted(rid, mode="chunked")
                    if progress:
                        print(f"[admit] rid={rid} slot={s} prompt={L} "
                              f"(chunked prefill queued)")
                    continue
                t0 = time.perf_counter()
                sp.admitted(rid, t0, mode="sequential")
                pages = allocator.alloc(nbp)
                tmp = self.model.init_cache(1, nbp * ps, cache_cfg=self.cc)
                tok0, tmp = self._prefill(
                    params, {"tokens": jnp.asarray(req.tokens)[None]}, tmp)
                pages_dev = jnp.asarray(pages, jnp.int32)
                caches = [self._adopt(c, t_g, jnp.int32(s), pages_dev)
                          for c, t_g in zip(caches, tmp)]
                first_tok[rid] = tok0[0, 0]
                bind_slot(s, rid, req, pages, pos=len(req.tokens),
                          generated=1, last_tok=tok0[0, 0])
                # drain the async prefill dispatch before reading the
                # clock, so its device time lands in t_prefill rather
                # than decode_s (the contiguous engine blocks the same
                # way before timing). Blocking on tok0 — not on the
                # adopted caches — keeps pending decode steps of *other*
                # slots out of t_prefill; the adoption copies themselves
                # are small and stay with decode_s.
                jax.block_until_ready(tok0)
                t1 = time.perf_counter()
                c_t_prefill.inc(t1 - t0)
                sp.first_token(rid, t1)
                if emit is not None:
                    tk0 = int(jax.device_get(tok0[0, 0]))
                    emitted[rid] = [tk0]
                    emit(rid, tk0, req.gen <= 1, time.perf_counter())
                if progress:
                    print(f"[admit] rid={rid} slot={s} prompt="
                          f"{len(req.tokens)} pages={pages}")
            g_pages.set(allocator.used_count)
            g_peak.set_max(allocator.used_count)
            t_prefill0 = time.perf_counter() if timed else 0.0

            # ---- chunked prefill: run fixed-shape chunks of the packed
            # prompt stream (if any prompts are pending), then fall
            # through to the decode step — admission cost is amortized
            # across the decode loop instead of blocking it. The
            # chunks:steps ratio is metered by `prefill_priority` as a
            # credit accumulator: each iteration banks that many chunk
            # credits (capped at max(priority, 1) so idle iterations
            # cannot stockpile a burst) and each whole credit runs one
            # chunk. 1.0 keeps the one-chunk-per-step cadence; 2.0 runs
            # two chunks per decode step (faster TTFT, slower ITL); 0.5
            # runs one chunk every other step (decode-favouring).
            chunk_ran = False
            chunk_gated = False
            if sched is not None and sched.pending:
                def prefill_budget() -> int:
                    """Pages prefill may take right now: the free count
                    minus the decode growth-debt watermark — a prefill
                    chunk may not take the page a running sequence needs
                    for its very next write (that would force a
                    preemption in the same iteration)."""
                    return max(allocator.free_count - growth_debt(), 0)

                def grant(slot_want: int, blocks: List[int]) -> None:
                    """Allocate pages for `blocks` (ascending logical
                    blocks) of a mid-prefill slot; the scheduler sized
                    the request to the budget, so it always succeeds."""
                    for b in blocks:
                        (pg,) = allocator.alloc(1)
                        slots[slot_want].pages.append(pg)
                        host_bt[slot_want, b] = pg

                chunk_credit = min(chunk_credit + self.prefill_priority,
                                   max(self.prefill_priority, 1.0))
                chunk_gated = chunk_credit < 1.0
                while chunk_credit >= 1.0 and sched.pending:
                    # hand-off stamps (timed runs): plan | pages.table |
                    # dispatch | wait | emit, one contiguous stretch
                    t_plan = time.perf_counter() if timed else 0.0
                    plan = sched.plan(prefill_budget, grant, host_bt)
                    if plan is None:
                        if timed:
                            sp.handoff("chunk.plan", t_plan,
                                       time.perf_counter())
                        break
                    chunk_credit -= 1.0
                    spa = np.full((S,), -1, np.int64)
                    for s2 in range(S):
                        if slots[s2] is not None and not sched.has(s2):
                            spa[s2] = host_pos[s2]
                    for s2, _, _ in plan.completed:
                        spa[s2] = host_pos[s2] + plan.advanced[s2]
                    t_bt = time.perf_counter() if timed else 0.0
                    bt_dev = self._replicated(jnp.asarray(host_bt, jnp.int32))
                    caches = [dataclasses.replace(
                        c, block_table=jnp.broadcast_to(
                            bt_dev, c.block_table.shape))
                        for c in caches]
                    t0 = time.perf_counter()
                    am, caches = sched.run(params, caches, plan, spa)
                    t_run = time.perf_counter() if timed else 0.0
                    jax.block_until_ready(am)
                    t1 = time.perf_counter()
                    c_t_prefill.inc(t1 - t0)
                    c_chunks.inc()
                    chunk_ran = True
                    if sp.on:
                        for s2, n in plan.advanced.items():
                            sp.chunk(slots[s2].rid, t0, t1, tokens=n)
                    am_np = jax.device_get(am) if emit is not None else None
                    t_am = time.perf_counter()
                    for s2, n in plan.advanced.items():
                        host_pos[s2] += n
                    for s2, rid2, expect in plan.completed:
                        t_c = am[s2]
                        if expect is not None:
                            deferred_checks.append(t_c)
                            deferred_expect.append((
                                expect,
                                "chunked re-prefill diverged from the "
                                "recorded first token — greedy decode "
                                "is no longer deterministic"))
                            sp.decoding(rid2, t_am)
                        else:
                            first_tok[rid2] = t_c
                            slots[s2].generated = 1
                            sp.first_token(rid2, t_am)
                            if emit is not None:
                                tk0 = int(am_np[s2])
                                emitted[rid2] = [tk0]
                                emit(rid2, tk0,
                                     slots[s2].target <= 1, t_am)
                        tok = tok.at[s2, 0].set(t_c)
                        if index is not None:
                            register_prefix(s2, rid2)
                        if progress:
                            print(f"[prefill] rid={rid2} slot={s2} "
                                  f"complete at pos {host_pos[s2]}")
                    g_pages.set(allocator.used_count)
                    g_peak.set_max(allocator.used_count)
                    if sp.on:
                        sp.handoff("chunk.plan", t_plan, t_bt)
                        sp.handoff("pages.table", t_bt, t0)
                        sp.handoff("chunk.dispatch", t0, t_run,
                                   tokens=int((plan.seq_id >= 0).sum()),
                                   seqs=plan.seqs(),
                                   completed=len(plan.completed))
                        sp.handoff("chunk.wait", t_run, t1)
                        sp.handoff("chunk.emit", t1, time.perf_counter())

            if not any(slots):
                if resume_q or arrived():
                    continue                    # a resume/admit now fits
                head = q_peek()
                if head is not None:
                    # idle until the *earliest* pending arrival (the heap
                    # head) — never past it, so staggered arrivals admit
                    # in (arrive_at, rid) order even when a later-indexed
                    # request carries the earlier timestamp
                    if wall:
                        self._wake.wait(timeout=max(head[0] - clock, 0.0))
                    else:
                        clock = max(clock, head[0])
                    continue
                if not drain:
                    # serve-forever: sleep until traffic or stop. The
                    # timeout bounds the wait so a stop that raced the
                    # wake-clear above is still honoured promptly.
                    self._wake.wait(timeout=0.05)
                    continue
                break                           # drained
            t_decode0 = time.perf_counter() if timed else 0.0

            # ---- allocate the page the next token will be written into
            # (finished slots were evicted above and never reach here).
            # Allocation is transactional per page: a page leaves the
            # free list only together with its slot-ownership record, so
            # a PoolExhausted mid-step (no victim left) cannot strand
            # pages — asserted by check_page_accounting every iteration.
            dirty = False
            grown = 0
            for s in range(S):
                if slots[s] is None or slots[s].generated >= slots[s].target:
                    continue
                if sched is not None and sched.has(s):
                    continue        # mid-prefill: pages granted per chunk
                blk = host_pos[s] // ps
                if host_bt[s, blk] >= 0:
                    continue
                while allocator.free_count < 1:
                    # a finished slot is a free win: evict it instead of
                    # paying a swap round trip / replay for work that
                    # will emit nothing. (The admission watermark keeps
                    # this branch from triggering today — admissions may
                    # not drain the pool below the growth debt — but the
                    # ordering "reclaim finished, then preempt" is a
                    # liveness guarantee, not an optimization.)
                    fin = finished_slot()
                    if fin is not None:
                        sp.finished(slots[fin].rid)
                        evict(fin)
                        dirty = True
                        continue
                    victim = select_victim(exclude=(s,))
                    if victim is None:
                        check_page_accounting()
                        raise paging.PoolExhausted(
                            f"page pool exhausted growing slot {s} and no "
                            f"victim left to preempt — grow --n-pages or "
                            f"enable --preempt requeue|swap"
                            if self.policy is None else
                            f"page pool exhausted growing slot {s}: every "
                            f"other sequence is already preempted")
                    preempt(victim)
                    dirty = True
                (pg,) = allocator.alloc(1)
                slots[s].pages.append(pg)
                host_bt[s, blk] = pg
                grown += 1
            g_pages.set(allocator.used_count)
            g_peak.set_max(allocator.used_count)
            dirty = dirty or grown > 0
            t_grow = time.perf_counter() if timed else 0.0
            if dirty:
                bt_dev = self._replicated(jnp.asarray(host_bt, jnp.int32))
                caches = [dataclasses.replace(
                    c, block_table=jnp.broadcast_to(
                        bt_dev, c.block_table.shape))
                    for c in caches]
            t_table = time.perf_counter() if timed else 0.0
            check_page_accounting()
            t_check = time.perf_counter() if timed else 0.0

            # ---- one traced decode step over every slot. Slots that just
            # hit their target still ride along (their masked write lands
            # in their own pages, freed at eviction) but emit no token.
            # Mid-prefill slots ride along inactive (device seq_pos -1:
            # trash write, masked attention, no advance); replaying slots
            # (chunked requeue resume) consume their recorded tokens
            # teacher-forced — the step writes their K/V, the emitted
            # token is discarded (it is already recorded).
            prefilling = tuple(s for s in range(S)
                               if sched is not None and sched.has(s))
            replaying = tuple(s for s in range(S)
                              if slots[s] is not None and slots[s].replay
                              and s not in prefilling)
            active = tuple((s, slots[s].rid) for s in range(S)
                           if slots[s] is not None
                           and slots[s].generated < slots[s].target
                           and s not in prefilling and s not in replaying)
            if not active and not replaying:
                if chunk_gated and sched is not None and sched.pending:
                    # nothing to decode and the only pending work is a
                    # credit-gated prefill chunk: skipping it would spin
                    # forever (and the stalled-prefill branch below would
                    # wrongly preempt). Force a whole credit — priority
                    # metering trades prefill against *decode* work, and
                    # there is none to favour.
                    chunk_credit = 1.0
                    continue
                if sched is not None and sched.pending and not chunk_ran:
                    # every live slot is a stalled prefill: no decode
                    # step can run and no chunk could take a page.
                    # Reclaim by preempting a victim (policy permitting)
                    # so the oldest job progresses next iteration.
                    first_slot = sched.jobs[0].slot
                    victim = select_victim(exclude=(first_slot,))
                    if victim is None:
                        check_page_accounting()
                        raise paging.PoolExhausted(
                            f"page pool exhausted mid-prefill of slot "
                            f"{first_slot} and no victim left to preempt "
                            f"— grow --n-pages or enable --preempt "
                            f"requeue|swap")
                    preempt(victim)
                continue                        # every slot done: evict
            if trace_hook is not None or sp.on:
                t_snap = time.perf_counter() if timed else 0.0
                snap = self._snapshot(
                    n_steps, allocator, slots, host_bt, host_pos, caches,
                    [e for e in queue if e[1] not in cancelled],
                    resume_q, swap, prefilling=prefilling,
                    replaying=replaying,
                    prefix=prefix_stats() if index is not None else None)
                if trace_hook is not None:
                    trace_hook(snap)
                # the tracer's counter tracks ride the same snapshot
                # point (pool occupancy + load, rendered as Perfetto
                # counter lanes)
                sp.snapshot({"pages_in_use": allocator.used_count,
                             "free_pages": allocator.free_count,
                             "active": len(active),
                             "queued": len(snap["queued"]),
                             "swapped": len(snap["swapped_rids"])})
            if sp.on:
                step_args = {"rows": len(active),
                             "ctx": [int(host_pos[s]) + 1 for s, _ in active]}
            t_disp = time.perf_counter() if timed else 0.0
            pos_dev = caches[0].seq_pos[0]      # [S]; host_pos for active
            tok, caches = self._step(params, tok, caches, pos_dev)
            t_sent = time.perf_counter() if timed else 0.0
            n_steps += 1
            c_steps.inc()
            c_tokens.inc(len(active))
            if not wall:
                clock += 1
            if emit is None:
                # batch mode: keep the device token columns alive; the
                # post-loop assembly fetches them all in one device_get
                history.append((active, tok))
                toks_np = None
            else:
                # streaming mode: one batched fetch per step (the only
                # per-step sync), fanned out host-side — no history, so
                # a serve-forever loop accumulates no device garbage
                toks_np = jax.device_get(tok)
            t_step = time.perf_counter()
            for s, _ in active:
                slots[s].generated += 1
                host_pos[s] += 1
            if emit is not None:
                for s, rid_a in active:
                    tk = int(toks_np[s, 0])
                    emitted[rid_a].append(tk)
                    emit(rid_a, tk,
                         slots[s].generated >= slots[s].target, t_step)
            for s in replaying:
                host_pos[s] += 1
                tok = tok.at[s, 0].set(slots[s].replay.pop(0))
                c_replay.inc()
            if sp.on and emit is not None:
                # per-token instants ride the streaming path's existing
                # host stamp (one batched device_get per step — reading
                # token values for batch-mode instants would add a sync)
                for _, rid_a in active:
                    sp.token(rid_a, t_step)
            if timed:
                t_it1 = time.perf_counter()
                g_active.set(len(active))
                g_queued.set(sum(1 for e in queue
                                 if e[1] not in cancelled))
                h_retire.observe(t_admit0 - it_t0)
                h_admit.observe(t_prefill0 - t_admit0)
                h_prefill.observe(t_decode0 - t_prefill0)
                h_decode.observe(t_it1 - t_decode0)
                if sp.on:
                    sp.handoff("pages.grow", t_decode0, t_grow, pages=grown)
                    if dirty:
                        sp.handoff("pages.table", t_grow, t_table)
                    sp.handoff("pages.check", t_table, t_check)
                    sp.handoff("trace.snapshot", t_snap, t_disp)
                    sp.handoff("step.dispatch", t_disp, t_sent, **step_args)
                    if emit is not None:
                        sp.handoff("step.fetch", t_sent, t_step)
                    sp.handoff("step.emit", t_step, t_it1)
                sp.step(it_t0, t_it1,
                        phases=(("retire", it_t0, t_admit0),
                                ("admit", t_admit0, t_prefill0),
                                ("prefill", t_prefill0, t_decode0),
                                ("decode", t_decode0, t_it1)),
                        active=len(active))

        jax.block_until_ready(tok)
        t_total = time.perf_counter() - acc["t0"]
        sp.run_end()

        # ---- verify the deferred replay-divergence checks (one fetch)
        if deferred_checks:
            got = jax.device_get(jnp.stack(deferred_checks))
            for g, (want, msg) in zip(got.tolist(), deferred_expect):
                assert g == want, msg

        # ---- assemble per-request token streams (single device fetch;
        # a streaming run already holds every token host-side)
        if emit is not None:
            results = {rid: np.asarray(t, np.int32)
                       for rid, t in emitted.items()}
        else:
            outputs: Dict[int, List[int]] = {
                rid: [int(jax.device_get(t))]
                for rid, t in first_tok.items()}
            if history:
                toks_np = jax.device_get(
                    jnp.concatenate([t for _, t in history], axis=1))
                for i_h, (act_h, _) in enumerate(history):
                    for s_h, rid_h in act_h:
                        outputs[rid_h].append(int(toks_np[s_h, i_h]))
            results = {rid: np.asarray(t, np.int32)
                       for rid, t in outputs.items()}
        if drain:
            # every non-cancelled request ran to completion (a stopped
            # serve-forever loop legitimately returns partial streams)
            for rid, req in requests.items():
                if rid in cancelled:
                    continue
                assert len(results[rid]) == req.gen, \
                    (rid, len(results[rid]))

        # every stats entry below is a registry read (or a pure config
        # echo) — the back-compat parity test in tests/test_obs.py
        # asserts this key set and value equality against the registry
        prefill_s = c_t_prefill.value()
        resume_s = c_t_resume.value()
        decode_s = max(t_total - prefill_s - resume_s, 1e-9)
        pool_slots = self.n_pages * ps
        total_tokens = sum(len(r.tokens) + r.gen - 1
                           for r in requests.values())
        pstats = prefix_stats()
        c_swap_bytes = reg.counter("swap_bytes_total",
                                   labelnames=("dir",))
        stats = {
            "prefill_s": prefill_s,
            "prefill_mode": self.prefill_mode,
            "prefill_priority": self.prefill_priority,
            "prefill_chunks": int(c_chunks.value()),
            "prefill_compile_count":
                sched.compile_count if sched is not None else None,
            "run_s": t_total,
            "resume_s": resume_s,
            "decode_s": decode_s,
            "decode_steps": int(c_steps.value()),
            "decode_tok_s": c_tokens.value() / decode_s,
            "clock_mode": clock_mode,
            "pool_pages": self.n_pages,
            "page_size": ps,
            "pool_slots": pool_slots,
            "peak_pages_used": int(g_peak.value()),
            "peak_pool_utilization":
                g_peak.value() / max(self.n_pages, 1),
            "total_tokens_served": total_tokens,
            "cancelled": int(c_cancel.value()),
            "preemptions": int(c_pre_req.value() + c_pre_swap.value()),
            "preempt_requeue": int(c_pre_req.value()),
            "preempt_swap": int(c_pre_swap.value()),
            "resumes": int(c_resumes.value()),
            "replay_steps": int(c_replay.value()),
            "swap_bytes_out": int(c_swap_bytes.value(dir="out")),
            "swap_bytes_in": int(c_swap_bytes.value(dir="in")),
            "swap_peak_bytes":
                int(reg.gauge("swap_peak_bytes").value()),
            "prefix_cache": self.prefix_cache,
            "prefix_hits": pstats["prefix_hits"],
            "prefix_misses": pstats["prefix_misses"],
            "prefix_hit_rate": pstats["prefix_hits"] / max(
                pstats["prefix_hits"] + pstats["prefix_misses"], 1),
            "prefix_hit_tokens": pstats["prefix_hit_tokens"],
            "prefix_shared_pages": pstats["prefix_shared_pages"],
            "cow_copies": pstats["cow_copies"],
            "swap_refusals": pstats["swap_refusals"],
            "cache_bytes_per_value":
                cache_mod.bytes_per_value(self.cc),
            "cache_total_bytes":
                paging.modeled_pool_bytes(caches)["total_bytes"],
            "tp": self.tp,
            "pool_bytes_per_device":
                paging.modeled_pool_bytes_per_device(caches)["total_bytes"],
        }
        return results, stats


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--sparq", choices=list(SPARQ_PRESETS), default="5opt")
    ap.add_argument("--kv-cache", choices=("fp32", "bf16", "sparq"),
                    default="fp32", help="KV-cache storage layout")
    ap.add_argument("--impl", choices=("reference", "pallas", "auto"),
                    default="reference",
                    help="kernel impl for quantized matmuls + cache codec")
    ap.add_argument("--engine", choices=("scan", "paged"), default="scan",
                    help="scan: one traced lax.scan over a uniform batch; "
                         "paged: continuous batching over the page pool")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged engine: cache slots per page")
    ap.add_argument("--n-pages", type=int, default=64,
                    help="paged engine: pages in the shared pool")
    ap.add_argument("--max-active", type=int, default=0,
                    help="paged engine: concurrent sequence slots "
                         "(default: --batch)")
    ap.add_argument("--prefill", choices=("sequential", "chunked"),
                    default="sequential",
                    help="paged engine admission: sequential (one prompt "
                         "at a time, shape-specialized jit per length) or "
                         "chunked (ragged prompts packed into a fixed-"
                         "shape token stream, one jitted chunk program "
                         "for every length, §5.1 pages written directly)")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="chunked prefill: stream tokens per chunk")
    ap.add_argument("--chunk-align", type=int, default=8,
                    help="chunked prefill: query-tile alignment of each "
                         "sequence's run inside the stream")
    ap.add_argument("--chunk-seg", type=int, default=0,
                    help="chunked prefill: segment quantum (prompt split "
                         "granularity; 0 = chunk size). Prompts up to one "
                         "segment admit bit-identically to sequential; "
                         "longer prompts attend earlier segments through "
                         "their packed pages")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged engine: reuse packed pages across requests "
                         "with a shared prompt prefix (radix index over "
                         "whole-page, whole-segment prefixes; refcounted "
                         "pages; copy-on-write at the tail boundary). "
                         "Requires --prefill chunked; greedy tokens are "
                         "bit-identical with the flag off")
    ap.add_argument("--prefix-min-pages", type=int, default=1,
                    help="prefix cache: minimum whole shared pages an "
                         "admission must match to take the hit path "
                         "(shorter matches prefill from scratch)")
    ap.add_argument("--prefill-priority", type=float, default=1.0,
                    help="paged engine, chunked prefill: prefill chunks "
                         "admitted per decode-loop iteration (fractional "
                         "< 1 throttles prefill to favor decode ITL; "
                         "> 1 lets several chunks run back-to-back to "
                         "favor TTFT)")
    ap.add_argument("--serve", choices=("sync", "async"), default="sync",
                    help="sync: one blocking engine.run over the batch; "
                         "async: the asyncio streaming front-end "
                         "(launch.frontend) replays a timed arrival "
                         "trace through a serve-forever engine loop and "
                         "reports TTFT/ITL percentiles (paged engine "
                         "only)")
    ap.add_argument("--arrival-trace", choices=("none", "poisson", "bursty"),
                    default="none",
                    help="async serving: arrival process for the replay "
                         "(none: every request arrives at t=0)")
    ap.add_argument("--arrival-rate", type=float, default=16.0,
                    help="async serving: offered load in requests/s for "
                         "--arrival-trace poisson|bursty")
    ap.add_argument("--preempt", choices=("off", "requeue", "swap", "auto"),
                    default="off",
                    help="paged engine: on decode-time pool exhaustion, "
                         "preempt victims — requeue (drop pages, replay on "
                         "resume), swap (packed pages to host, verbatim "
                         "restore), or auto (per-victim cost model: replay "
                         "FLOPs vs swap bytes); off raises PoolExhausted")
    ap.add_argument("--victim", choices=("last_joined", "fewest_pages"),
                    default="last_joined",
                    help="paged engine: preemption victim selection")
    ap.add_argument("--tp", type=int, default=1,
                    help="paged engine: tensor-parallel degree over a "
                         "(\"data\",\"model\") host mesh (launch.mesh."
                         "make_tp_mesh). Pools and attention heads shard "
                         "by GQA head group; greedy tokens are "
                         "bit-identical to --tp 1. Needs tp | n_kv_heads "
                         "and tp | device count (on CPU, force devices "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--oversubscribe", type=float, default=0.0,
                    metavar="FRAC",
                    help="paged engine: shrink the pool to FRAC of the "
                         "batch's uncontended working set (forces "
                         "preemption; requires --preempt requeue|swap, "
                         "overrides --n-pages)")
    ap.add_argument("--metrics-dump", metavar="PATH", default=None,
                    help="paged engine: write the telemetry registry as "
                         "a Prometheus text-exposition dump after the "
                         "run (docs/observability.md)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="paged engine: enable full span tracing and "
                         "write Chrome trace-event JSON after the run "
                         "(load in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="async serving: serve GET /metrics (Prometheus "
                         "text exposition) from the event loop on this "
                         "port while the trace plays (0 = ephemeral)")
    ap.add_argument("--calibrate", type=int, default=2,
                    help="calibration batches (0 = dynamic scales)")
    ap.add_argument("--prequantize", action="store_true",
                    help="deploy int8 weight codes (offline quantization)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the untimed warmup pass (timings then "
                         "include XLA compilation)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def load_model(args):
    """The model the flags name, with seeded weights (nothing is
    downloaded), its prompt batcher, and the calibrated activation scales
    of the `--sparq` preset. Returns (model, params, data, scales)."""
    cfg = get_reduced_config(args.arch) if args.reduced \
        else get_config(args.arch)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    data = Batcher(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
        global_batch=args.batch, seed=args.seed, frontend=cfg.frontend,
        frontend_len=cfg.frontend_len, d_model=cfg.d_model))
    scfg = SPARQ_PRESETS[args.sparq]
    scales = None
    if scfg is not None:
        scales = model.calibrate(params, data.calib_batches(args.calibrate)) \
            if args.calibrate else None
        if args.prequantize:
            from repro.models.quantize import quantize_params
            params = quantize_params(params, scfg.weight_bits)
    return model, params, data, scales


def quant_ctx(args) -> Optional[QuantCtx]:
    """The quantized-matmul context of `--sparq` / `--impl`."""
    scfg = SPARQ_PRESETS[args.sparq]
    return None if scfg is None else QuantCtx(mode="quantized", cfg=scfg,
                                              impl=args.impl)


def paged_engine(args, model: Model, scales) -> "ContinuousBatchingEngine":
    """The `--engine paged` engine the flags describe: pool sized for
    `--prompt-len + --gen` per sequence, preemption policy, TP mesh and
    telemetry level."""
    need = args.prompt_len + args.gen - 1
    max_seq = -(-need // args.page_size) * args.page_size
    pages_per_seq = max_seq // args.page_size
    n_pages = args.n_pages
    if args.oversubscribe:
        n_pages = max(pages_per_seq,
                      math.ceil(args.oversubscribe * args.batch
                                * pages_per_seq))
    policy = None if args.preempt == "off" else SchedulerPolicy(
        preempt=args.preempt, victim=args.victim)
    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(args.tp)
    telemetry = Telemetry.tracing() if args.trace_out else Telemetry()
    cache_cfg = make_cache_config(args.kv_cache, SPARQ_PRESETS[args.sparq],
                                  args.impl)
    return ContinuousBatchingEngine(
        model, cache_cfg, quant_ctx(args), scales,
        page_size=args.page_size, n_pages=n_pages,
        max_active=args.max_active or args.batch,
        max_seq_len=max_seq, policy=policy,
        prefill=args.prefill, chunk_size=args.chunk_size,
        chunk_align=args.chunk_align,
        chunk_seg=args.chunk_seg or None,
        prefix_cache=args.prefix_cache,
        prefix_min_pages=args.prefix_min_pages,
        prefill_priority=args.prefill_priority,
        mesh=mesh, telemetry=telemetry)


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.serve == "async" and args.engine != "paged":
        ap.error("--serve async streams from the paged engine's decode "
                 "loop; add --engine paged")
    if (args.metrics_dump or args.trace_out or args.metrics_port
            is not None) and args.engine != "paged":
        ap.error("--metrics-dump/--trace-out/--metrics-port read the "
                 "paged engine's telemetry registry; add --engine paged")
    if args.metrics_port is not None and args.serve != "async":
        ap.error("--metrics-port scrapes from the asyncio front-end; "
                 "add --serve async")
    if args.arrival_trace != "none" and args.serve != "async":
        ap.error("--arrival-trace replays through the async front-end; "
                 "add --serve async")
    if args.engine == "paged":
        if args.prefix_cache and args.prefill != "chunked":
            ap.error("--prefix-cache relies on the chunked path's "
                     "scheduling-invariant packed bytes; add "
                     "--prefill chunked")
        if args.oversubscribe and args.preempt == "off":
            ap.error("--oversubscribe deliberately undersizes the "
                     "pool; pick --preempt requeue|swap so the engine "
                     "can evict victims instead of raising")
    enable_compile_cache()
    model, params, data, scales = load_model(args)
    batch = data.global_batch(0)
    batch.pop("labels", None)
    print(f"arch={model.cfg.name} sparq={args.sparq} "
          f"kv-cache={args.kv_cache} impl={args.impl} engine={args.engine} "
          f"batch={args.batch} prompt={args.prompt_len} gen={args.gen}")

    if args.engine == "paged":
        engine = paged_engine(args, model, scales)

        def dump_telemetry():
            from repro.obs import export as obs_export
            if args.metrics_dump:
                obs_export.write_prometheus(engine.telemetry.registry,
                                            args.metrics_dump)
                print(f"metrics dump: {args.metrics_dump}")
            if args.trace_out:
                obs_export.write_trace(engine.telemetry.tracer,
                                       args.trace_out)
                print(f"trace (Perfetto/chrome://tracing): "
                      f"{args.trace_out}")

        reqs = [Request(np.asarray(batch["tokens"][b]), args.gen)
                for b in range(args.batch)]
        if args.serve == "async":
            from repro.launch import frontend
            ats = [0.0] * args.batch if args.arrival_trace == "none" \
                else frontend.arrival_times(
                    args.arrival_trace, args.batch, args.arrival_rate,
                    rng=np.random.default_rng(args.seed))
            trace = [(r.tokens, r.gen, at) for r, at in zip(reqs, ats)]
            warm = None if args.no_warmup else [(r.tokens, r.gen)
                                                for r in reqs]
            results, slo, stats = frontend.play_trace(
                engine, params, trace, warmup=warm,
                metrics_port=args.metrics_port)
            stats["slo"] = slo
            dump_telemetry()
            print(f"async {args.arrival_trace or 'none'} trace "
                  f"({len(trace)} requests): "
                  f"ttft p50 {slo['ttft']['p50_ms']:.1f} ms / "
                  f"p99 {slo['ttft']['p99_ms']:.1f} ms | "
                  f"itl p50 {slo['itl']['p50_ms']:.2f} ms / "
                  f"p99 {slo['itl']['p99_ms']:.2f} ms | decode "
                  f"{stats['decode_tok_s']:.1f} tok/s")
            print("sample:", results[0][:16])
            return stats
        if not args.no_warmup:
            engine.run(params, reqs)            # compile pass, untimed
        results, stats = engine.run(params, reqs)
        dump_telemetry()
        print(f"prefill {stats['prefill_s']*1e3:.0f} ms | decode "
              f"{stats['decode_tok_s']:.1f} tok/s | pool "
              f"{stats['peak_pages_used']}/{stats['pool_pages']} pages "
              f"({stats['page_size']} slots) peak, "
              f"{stats['cache_total_bytes']/1e6:.2f} MB modeled")
        if stats["tp"] > 1:
            print(f"tp={stats['tp']}: "
                  f"{stats['pool_bytes_per_device']/1e6:.2f} MB "
                  f"modeled pool per device")
        if args.prefix_cache:
            print(f"prefix-cache: {stats['prefix_hits']} hits / "
                  f"{stats['prefix_misses']} misses "
                  f"({stats['prefix_hit_rate']:.0%}), "
                  f"{stats['prefix_hit_tokens']} prompt tokens from "
                  f"cache, {stats['prefix_shared_pages']} pages shared, "
                  f"{stats['cow_copies']} CoW copies")
        if engine.policy is not None:
            print(f"preempt={args.preempt} victim={args.victim}: "
                  f"{stats['preemptions']} preemptions, "
                  f"{stats['resumes']} resumes, "
                  f"{stats['replay_steps']} replay steps, "
                  f"swap {stats['swap_bytes_out']/1e6:.2f} MB out / "
                  f"{stats['swap_bytes_in']/1e6:.2f} MB in")
        print("sample:", results[0][:16])
        return stats

    cache_cfg = make_cache_config(args.kv_cache, SPARQ_PRESETS[args.sparq],
                                  args.impl)
    toks, stats = serve(model, params, batch, args.gen, quant_ctx(args),
                        scales, cache_cfg, warmup=not args.no_warmup)
    print(f"compile {stats['compile_s']:.1f} s | "
          f"prefill {stats['prefill_s']*1e3:.0f} ms | decode "
          f"{stats['decode_tok_s']:.1f} tok/s | cache "
          f"{stats['cache_bytes_per_value']:.4f} B/value data "
          f"(+{stats['cache_ctrl_bytes_per_value']:.4f} ctrl), "
          f"{stats['cache_total_bytes']/1e6:.2f} MB modeled")
    print("sample:", np.asarray(toks[0, :16]))
    return stats


if __name__ == "__main__":
    main()

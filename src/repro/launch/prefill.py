"""Chunked ragged prefill: one jitted program for every prompt.

The sequential admission path prefills one sequence at a time through a
shape-specialized jit — every unique prompt length triggers an XLA
retrace, and a long prompt stalls the decode loop for its whole length.
Under continuous batching this is the dominant admission cost at heavy
join rates (ROADMAP: "Batched ragged prefill").

`PrefillScheduler` amortizes it: pending prompts are packed into a
fixed-shape token *stream* with per-token (seq_id, pos) metadata and
processed in fixed-size chunks interleaved with decode steps. One jitted
chunk program (`Model.prefill_chunk`) covers every prompt length and
join pattern — the compile-count regression test pins its jit cache at
exactly one entry — and each chunk's K/V quantizes straight into
`PagedCacheStore` pages (`write_chunk`): no contiguous staging cache, no
`adopt_prefill` copy on the hot path.

Stream layout (C = chunk_size tokens, bq = query-tile alignment):

      tokens   [ p0 p1 p2 p3 | p4 p5 .. .. | q0 q1 q2 q3 | .. .. .. .. ]
      seq_id   [  2  2  2  2 |  2  2 -1 -1 |  0  0  0  0 | -1 -1 -1 -1 ]
      pos      [  8  9 10 11 | 12 13  0  0 |  0  1  2  3 |  0  0  0  0 ]
      tile_seq [      2      |      2      |      0      |     -1      ]

Each sequence's run is contiguous and padded to a bq boundary so one
query tile gathers exactly one block-table row (the Pallas kernel
scalar-prefetches `tile_seq`); -1 tokens/tiles are padding and fully
masked. A prompt longer than one chunk continues across chunks, and
`seq_pos_after` keeps the slot's device position at -1 (inactive for the
interleaved decode steps) until the last prompt token lands.

Prompts are split at fixed *segment* boundaries (`seg` tokens, default
the chunk size) and the packer only ever places whole segments (the
ragged final segment included) — never a partial one. Attention inside a
segment reads float K/V; attention across segments reads the already-
written packed pages (per-token `hist` boundary). The consequence is
the scheduling-invariance property the engine's exactness guarantees
lean on: a prompt's cache bytes and greedy tokens depend only on
(prompt, seg), not on join order, pool pressure, chunk packing, or
preemption — so a requeue-replay resume re-prefills to bit-identical
bytes, and prompts of at most `seg` tokens are bit-identical to the
sequential (whole-prompt, float-attention) admission path.

Page allocation stays with the engine (the scheduler's `plan` calls
back into an engine-provided `grant`), mirroring the division of labor
in models/paging.py: scheduling decisions happen host-side between
traced steps; the traced chunk only consumes an already-consistent
block table.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.paging import ChunkMeta

# host/device topology for the static analyzer (repro.analysis.host_lint;
# see docs/analysis.md). Pure literal — parsed with ast.literal_eval.
__analysis__ = {
    "traced": ("PrefillScheduler._chunk_fn",),
    "host_loop": ("PrefillScheduler.plan", "PrefillScheduler.run"),
    "device_returning": (),
    "device_params": ("PrefillScheduler.run.caches",),
}


@dataclasses.dataclass
class _Job:
    """One pending prompt: admitted to a slot, not yet fully prefilled."""
    slot: int
    rid: int
    tokens: np.ndarray
    done: int = 0                       # prompt tokens already written
    expect_tok0: Optional[int] = None   # resume: recorded first token

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.done


class ChunkPlan(NamedTuple):
    """Host-side description of one packed chunk (see module docstring)."""
    tokens: np.ndarray      # [C] int32 stream token ids (0 = padding)
    seq_id: np.ndarray      # [C] slot per token (-1 = padding)
    pos: np.ndarray         # [C] absolute position per token
    hist: np.ndarray        # [C] per-token history boundary (segment
                            #     start; packed pages below, float above)
    tile_seq: np.ndarray    # [C/bq] slot per query tile (-1 = padding)
    last_rows: np.ndarray   # [S] stream row of the slot's final prompt
                            #     token (-1: prefill incomplete)
    completed: List[Tuple[int, int, Optional[int]]]  # (slot, rid, expect)
    advanced: Dict[int, int]            # slot -> prompt tokens written

    def seqs(self) -> List[List[int]]:
        """Per slot in the chunk, by slot: [history boundary, tokens]."""
        live = self.seq_id >= 0
        return [[int(self.hist[m].min()), int(m.sum())]
                for m in (self.seq_id == s
                          for s in np.unique(self.seq_id[live]))]


class PrefillScheduler:
    """Packs ragged pending prompts into fixed-shape chunks and runs them
    through one jitted chunk program.

    The engine admits a request by binding a slot and calling `add`; each
    engine iteration then calls `plan` (packing + page negotiation via
    the engine's `grant` callback) and `run` (the traced chunk). A job
    whose next page cannot be granted simply stalls until evictions or
    preemptions free pages — the engine handles liveness.
    """

    def __init__(self, model, ctx=None, scales_groups=None, *,
                 chunk_size: int = 32, align: int = 8, page_size: int,
                 n_slots: int, seg: Optional[int] = None, mesh=None,
                 telemetry=None):
        if chunk_size % align:
            raise ValueError(f"chunk_size {chunk_size} must be a multiple "
                             f"of the query-tile alignment {align}")
        seg = chunk_size if seg is None else seg
        if not 0 < seg <= chunk_size:
            raise ValueError(f"segment quantum {seg} must be in "
                             f"(0, chunk_size={chunk_size}] — a whole "
                             f"segment must fit one chunk")
        self.model = model
        self.ctx = ctx
        self.scales_groups = scales_groups
        self.C = chunk_size
        self.bq = align
        self.seg = seg
        self.ps = page_size
        self.S = n_slots
        # tensor parallelism: chunk metadata and the token stream are
        # global control state — placed replicated over the mesh so the
        # chunk program (whose pools are head-sharded) sees committed,
        # consistently-placed inputs (see docs/sharding.md). The
        # NamedSharding is built once here, outside the host loop.
        self.mesh = mesh
        self._rep_sharding = None if mesh is None else \
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        self.jobs: List[_Job] = []          # FIFO
        self.chunks_run = 0
        # chunk-stream utilization (repro.obs): non-pad fraction of each
        # chunk's C stream rows — low fill means admission is paying a
        # whole fixed-shape chunk for a sliver of prompt
        self._h_fill = None
        if telemetry is not None:
            self._h_fill = telemetry.registry.histogram(
                "prefill_chunk_fill_ratio",
                "non-pad fraction of each chunk's token stream",
                buckets=tuple(i / 10 for i in range(1, 11))).series()
        # ONE jitted program serves every chunk: all shapes are fixed by
        # (chunk_size, n_slots, pool geometry), so the jit cache holds a
        # single entry regardless of prompt lengths/join patterns —
        # asserted by the compile-count regression test via compile_count.
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(2,))

    # ------------------------------------------------------------ traced
    def _chunk_fn(self, params, toks, caches, meta, last_rows):
        return self.model.prefill_chunk(
            params, toks, caches, meta, last_rows,
            ctx=self.ctx, scales_groups=self.scales_groups)

    # ------------------------------------------------------------- state
    def reset(self) -> None:
        """Drop all jobs (a fresh engine run); keeps the jitted program."""
        self.jobs = []
        self.chunks_run = 0

    def add(self, slot: int, rid: int, tokens: np.ndarray,
            expect_tok0: Optional[int] = None, start: int = 0) -> None:
        """Queue a prompt for chunked prefill. `start` > 0 (shared-prefix
        admission) skips the prompt's first `start` tokens: their packed
        pages are already in the slot's block table (adopted from the
        prefix cache) and only the tail streams through the chunk
        program. `start` must sit on a segment boundary — the packer's
        history arithmetic (`hist = (pos // seg) * seg`) and the
        segment-atomic placement rule both assume `done` always is."""
        assert not self.has(slot), f"slot {slot} already mid-prefill"
        assert start % self.seg == 0, \
            f"prefill start {start} must be a multiple of seg {self.seg}"
        assert 0 <= start < len(tokens), (start, len(tokens))
        self.jobs.append(_Job(slot=slot, rid=rid,
                              tokens=np.asarray(tokens), done=start,
                              expect_tok0=expect_tok0))

    def has(self, slot: int) -> bool:
        return any(j.slot == slot for j in self.jobs)

    def job(self, slot: int) -> _Job:
        return next(j for j in self.jobs if j.slot == slot)

    def cancel(self, slot: int) -> None:
        """Drop a mid-prefill job (its slot was preempted)."""
        self.jobs = [j for j in self.jobs if j.slot != slot]

    @property
    def pending(self) -> bool:
        return bool(self.jobs)

    @property
    def pending_tokens(self) -> int:
        """Prompt tokens still to stream across every queued job — the
        prefill backlog depth (SLO snapshots and admission telemetry)."""
        return sum(j.remaining for j in self.jobs)

    @property
    def compile_count(self) -> int:
        """Number of traced chunk programs (the retrace regression guard)."""
        return self._chunk._cache_size()

    def pages_outstanding(self, slot: int, host_bt: np.ndarray) -> int:
        """Pages this mid-prefill slot still needs to finish its prompt —
        the engine's admission watermark charges these so new admissions
        cannot starve an in-flight prefill."""
        job = self.job(slot)
        last_blk = (len(job.tokens) - 1) // self.ps
        row = host_bt[slot]
        return sum(1 for b in range(last_blk + 1) if row[b] < 0)

    # -------------------------------------------------------------- plan
    def _seg_floor(self, job: _Job, n: int) -> int:
        """Largest segment-atomic token count <= n from job's position:
        whole segments, or everything that remains (the ragged final
        segment rides with the last whole one). job.done is always a
        segment boundary, so atomicity is per-job-local arithmetic."""
        if n >= job.remaining:
            return job.remaining
        return (n // self.seg) * self.seg

    def plan(self, budget: Callable[[], int],
             grant: Callable[[int, List[int]], None],
             host_bt: np.ndarray) -> Optional[ChunkPlan]:
        """Pack the next chunk, FIFO over pending jobs.

        `budget()` reports how many pages prefill may take right now (the
        engine's free count minus the decode growth-debt watermark);
        `grant(slot, blocks)` then allocates physical pages for exactly
        those (ascending) logical blocks of `slot` and updates the host
        block table. The run is shrunk segment-atomically to the budget
        *before* granting, so every granted page receives tokens in this
        very chunk — a page shortage can stall a job but never strand an
        allocated page. Mutates job progress (`done`) and removes
        completed jobs; returns None when nothing could be packed."""
        C, bq, ps = self.C, self.bq, self.ps
        used = 0
        runs: List[Tuple[_Job, int, int]] = []       # (job, n, at)
        for job in list(self.jobs):
            if used >= C:
                break
            n = self._seg_floor(job, C - used)
            first_blk = job.done // ps

            def missing(n_tok):
                last_blk = (job.done + n_tok - 1) // ps
                return [b for b in range(first_blk, last_blk + 1)
                        if host_bt[job.slot, b] < 0]

            while n > 0:
                need = missing(n)
                if len(need) <= budget():
                    break
                # shrink to the positions the affordable page prefix
                # covers, keeping whole segments only; need[budget()] is
                # the first block we cannot take
                n = self._seg_floor(job, need[budget()] * ps - job.done)
            if n <= 0:
                continue                             # stalled: no page
            need = missing(n)
            grant(job.slot, need)
            runs.append((job, n, used))
            used += -(-n // bq) * bq                 # align run to bq
        if not runs:
            return None

        tokens = np.zeros(C, np.int64)
        seq_id = np.full(C, -1, np.int64)
        pos = np.zeros(C, np.int64)
        hist = np.zeros(C, np.int64)
        tile_seq = np.full(C // bq, -1, np.int64)
        last_rows = np.full(self.S, -1, np.int64)
        completed: List[Tuple[int, int, Optional[int]]] = []
        advanced: Dict[int, int] = {}
        for job, n, at in runs:
            tokens[at:at + n] = job.tokens[job.done:job.done + n]
            seq_id[at:at + n] = job.slot
            p = np.arange(job.done, job.done + n)
            pos[at:at + n] = p
            hist[at:at + n] = (p // self.seg) * self.seg
            tile_seq[at // bq: (at + n + bq - 1) // bq] = job.slot
            advanced[job.slot] = n
            job.done += n
            if job.remaining == 0:
                last_rows[job.slot] = at + n - 1
                completed.append((job.slot, job.rid, job.expect_tok0))
                self.jobs.remove(job)
        return ChunkPlan(tokens=tokens, seq_id=seq_id, pos=pos, hist=hist,
                         tile_seq=tile_seq, last_rows=last_rows,
                         completed=completed, advanced=advanced)

    # --------------------------------------------------------------- run
    def run(self, params, caches, plan: ChunkPlan,
            seq_pos_after: np.ndarray):
        """Execute one planned chunk. Returns (tok0 [S] int32 device
        array — greedy token at each completing slot's last prompt row —
        , caches). The caches argument is donated (the pools are
        rewritten in place, like the engine's decode step)."""
        if self._rep_sharding is None:
            rep = lambda x: x
        else:
            rep = lambda x: jax.device_put(x, self._rep_sharding)
        meta = ChunkMeta(
            seq_id=rep(jnp.asarray(plan.seq_id, jnp.int32)),
            pos=rep(jnp.asarray(plan.pos, jnp.int32)),
            hist=rep(jnp.asarray(plan.hist, jnp.int32)),
            tile_seq=rep(jnp.asarray(plan.tile_seq, jnp.int32)),
            seq_pos_after=rep(jnp.asarray(seq_pos_after, jnp.int32)))
        self.chunks_run += 1
        if self._h_fill is not None:
            # plan arrays are host numpy: a pure host-side observation
            self._h_fill.observe(int((plan.seq_id >= 0).sum()) / self.C)
        return self._chunk(params,
                           rep(jnp.asarray(plan.tokens, jnp.int32)[None]),
                           caches, meta,
                           rep(jnp.asarray(plan.last_rows, jnp.int32)))

"""What a traced run records on the host, and the view the per-layer
metric readers get of it.

Recording happens only with `--trace 1`, from the benchmark's side of
the program's interfaces: the engine's `trace_hook` (called before each
decode step with the scheduler's snapshot) gives the active slots and
their context lengths; a wrapper around the chunked-prefill scheduler's
`run` gives each chunk's plan (tokens, positions, history boundaries);
the telemetry registry and span tracer give per-iteration host phases
and the scheduler's spans.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import time
from typing import Any, Dict, List, Tuple

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Step:
    t: float                    # perf_counter just before the dispatch
    ctx: List[int]              # per active slot: keys its token attends


@dataclasses.dataclass
class Chunk:
    t: float
    pos: np.ndarray             # positions of the live tokens
    seqs: List[Tuple[int, int]]  # per slot in the chunk: (history, tokens)
    completed: int              # prompts whose last token is in the chunk


class Recorder:
    """Host records of one traced run (engine thread appends)."""

    def __init__(self):
        self.steps: List[Step] = []
        self.chunks: List[Chunk] = []

    def hook(self, snap: dict) -> None:
        busy = set(snap["prefilling"]) | set(snap["replaying"])
        ctx = [s["pos"] + 1 for k, s in snap["slots"].items()
               if k not in busy and s["generated"] < s["target"]]
        self.steps.append(Step(time.perf_counter(), ctx))

    def wrap_chunks(self, sched) -> None:
        run = sched.run

        def recorded(params, caches, plan, *a, **kw):
            live = plan.seq_id >= 0
            seqs = []
            for s in np.unique(plan.seq_id[live]):
                m = plan.seq_id == s
                seqs.append((int(plan.hist[m].min()), int(m.sum())))
            self.chunks.append(Chunk(time.perf_counter(),
                                     np.asarray(plan.pos[live]), seqs,
                                     len(plan.completed)))
            return run(params, caches, plan, *a, **kw)
        sched.run = recorded


@dataclasses.dataclass
class Observation:
    """Everything a per-layer reader may use."""
    sizes: Dict[str, Any]       # reference.sizes() of the configuration
    settings: Dict[str, Any]    # the cell's engine settings
    peaks: Dict[str, float]     # this device's row of peaks.json
    trace: Any                  # trace.Reduced of the traced window
    recorder: Recorder
    window: Tuple[float, float]           # traced window, perf_counter
    phases: Dict[str, List[float]]        # engine phase samples in window
    spans: List[dict]                     # tracer events (ts in us)
    span_origin: float                    # perf_counter of tracer ts 0

    def steps_in(self, window=None) -> List[Step]:
        a, b = window or self.window
        return [s for s in self.recorder.steps if a <= s.t < b]

    def chunks_in(self, window=None) -> List[Chunk]:
        a, b = window or self.window
        return [c for c in self.recorder.chunks if a <= c.t < b]

    def span_time(self, ts_us: float) -> float:
        return self.span_origin + ts_us * 1e-6


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> float:
    """The least time the chip needs for a call: the larger of its
    operations over the compute peak and its bytes over HBM bandwidth."""
    return max(ops / peak_ops, nbytes / peak_bw)


def per_execution(values: List[float], executions: int) -> float:
    """Work of `executions` device runs of a program, from the host's
    per-call figures of the calls dispatched in the same window."""
    if not values or executions <= 0:
        return 0.0
    return float(np.mean(values)) * executions

"""The engine's spans on the host clock, grouped by iteration: what the
readers and tools of the hand-off spans (`chunk.*`, `pages.*`,
`trace.snapshot`, `step.*`, nested in the phases of each `step[i]`) and
of the runtime track (`gc`, `compile`) share. A program without those
spans gives them nothing to read."""
from __future__ import annotations

from typing import Dict, List, NamedTuple

SCHED_TID = 0
RUNTIME = ("gc", "compile")


class Span(NamedTuple):
    start: float                # perf_counter seconds
    end: float
    name: str
    args: dict


def _track(obs, keep) -> List[Span]:
    out = []
    for ev in obs.spans:
        if ev.get("ph") == "X" and keep(ev):
            s = obs.span_time(ev["ts"])
            out.append(Span(s, s + ev["dur"] * 1e-6, ev["name"],
                            ev.get("args", {})))
    return sorted(out, key=lambda sp: (sp.start, sp.end))


def scheduler(obs) -> List[Span]:
    """Every complete span of the scheduler track, by start."""
    return _track(obs, lambda ev: ev.get("tid") == SCHED_TID)


def runtime(obs) -> List[Span]:
    """The runtime track's spans that overlap the window, by start."""
    a, b = obs.window
    return [s for s in _track(obs, lambda ev: ev["name"] in RUNTIME)
            if s.start < b and s.end > a]


def iterations(spans: List[Span], window) -> List[Span]:
    """The `step[i]` spans wholly inside `window`."""
    a, b = window
    return [s for s in spans if s.name.startswith("step[")
            and a <= s.start and s.end <= b]


def within(span: Span, iters: List[Span]) -> bool:
    return any(i.start <= span.start < i.end for i in iters)


def host_ms_per_iter(spans: List[Span], iters: List[Span]) -> Dict[str, float]:
    """Each span name's host time inside `iters`, in ms per iteration."""
    out: Dict[str, float] = {}
    for s in spans:
        if not s.name.startswith("step[") and within(s, iters):
            out[s.name] = out.get(s.name, 0.0) + 1e3 * (s.end - s.start)
    return {k: v / len(iters) for k, v in out.items()} if iters else {}


def runtime_rows(obs) -> list:
    """[name, start in the window (s), ms, args] of each runtime span."""
    return [[s.name, s.start - obs.window[0], 1e3 * (s.end - s.start),
             s.args] for s in runtime(obs)]

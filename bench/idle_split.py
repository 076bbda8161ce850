"""The device's idle time in a traced run, split by the engine's spans.

    python3 bench/idle_split.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `bench/run.py --trace 1` does (the same arguments, the
same result line on standard output), then prints one more JSON line.
Each idle gap of the profiled window goes, by its midpoint on the host
clock, to the innermost span that holds it: a runtime span (`gc`,
`compile`) first, else the shortest scheduler span (a hand-off span, a
phase, `step[i]`), else `none`. The line gives, per name, the idle time
in ms per iteration and its share of all idle time; the longest gaps;
the runtime spans in the window; each scheduler span's host time in ms
per iteration; each per-layer metric in ms per iteration as a share of
the window, beside the idle share; and how many of the device's op
events carry an op-name path (`jit(<fn>)/...`) in a stat.
"""
from __future__ import annotations

import json
import pathlib
import re
import sys
from typing import Dict, Tuple

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import hostspans, observe, run, trace, tracing  # noqa: E402
from bench.trace import Reduced  # noqa: E402

OP_PATH = re.compile(r"jit\([^)]*\)/")


def innermost(sched, rt, t: float) -> str:
    """The runtime span holding host time `t`, else the shortest
    scheduler span holding it, else `none`."""
    for pool in (rt, sched):
        hit = [s for s in pool if s.start <= t < s.end]
        if hit:
            return min(hit, key=lambda s: s.end - s.start).name
    return "none"


def split(red: Reduced, obs, metrics=None) -> dict:
    """The idle split of one traced window (`obs.window`, host clock)."""
    sched, rt = hostspans.scheduler(obs), hostspans.runtime(obs)
    iters = hostspans.iterations(sched, obs.window)
    by: Dict[str, float] = {}
    named = []
    for s, e in red.gaps:
        name = innermost(sched, rt, red.to_host((s + e) / 2))
        by[name] = by.get(name, 0.0) + (e - s) * 1e-9
        named.append((name, (e - s) * 1e-9))
    idle = sum(by.values())
    per = max(len(iters), 1)
    out = {
        "iterations": len(iters), "window_s": red.window_s, "idle_s": idle,
        "idle_share": 100.0 * idle / red.window_s if red.window_s else None,
        "by_span": [[n, 1e3 * v / per, 100.0 * v / idle if idle else 0.0]
                    for n, v in sorted(by.items(), key=lambda kv: -kv[1])],
        "longest": [[n, 1e3 * v] for n, v in
                    sorted(named, key=lambda x: -x[1])[:10]],
        "runtime": hostspans.runtime_rows(obs),
        "host_ms_per_iter": hostspans.host_ms_per_iter(sched, iters),
    }
    if red.window_s:
        out["ms_per_iter_share"] = {
            k: 100.0 * m["value"] * 1e-3 * len(iters) / red.window_s
            for k, m in (metrics or {}).items() if m["unit"] == "ms"}
    return out


def op_paths(xplane: str) -> Tuple[int, int]:
    """(op events with an op-name path in a string stat, op events)."""
    from jax.profiler import ProfileData
    hit = n = 0
    for p in ProfileData.from_file(xplane).planes:
        if not re.fullmatch(r"/device:TPU:\d+", p.name):
            continue
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                n += 1
                hit += any(OP_PATH.search(s) for s in trace._stats(ev))
    return hit, n


def main(argv=None) -> int:
    kept: dict = {}
    reduce, readings = trace.reduce, tracing.Tracing.readings

    def keep_reduced(*a, **kw):
        kept["red"] = reduce(*a, **kw)
        return kept["red"]

    def keep_spans(self, *a, **kw):
        out = readings(self, *a, **kw)
        tracer = self.engine.telemetry.tracer
        kept["obs"] = observe.Observation(
            sizes={}, settings={}, peaks={}, trace=None,
            recorder=observe.Recorder(), window=self.window, phases={},
            spans=tracer.events(), span_origin=tracer._origin or 0.0)
        kept.update(metrics=out["metrics"], op_paths=op_paths(self.xplane()))
        return out

    trace.reduce = keep_reduced
    tracing.Tracing.readings = keep_spans
    args = list(sys.argv[1:] if argv is None else argv)
    rc = run.main(args + ["--trace", "1"])
    if rc or "obs" not in kept:
        return rc or 1
    res = split(kept["red"], kept["obs"], kept["metrics"])
    res["op_paths"] = list(kept["op_paths"])
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

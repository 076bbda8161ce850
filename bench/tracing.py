"""The traced run (`--trace 1`): a profiler capture of part of the
measured window, host records of what the engine dispatched, and the
per-layer readings and breakdown taken from both.

The engine runs with full telemetry (`Telemetry.tracing()`: spans and
per-iteration phase timings). The profiler captures `duration` seconds
starting `lead` seconds into the window; a `bench.clock_sync` host
annotation, opened at a known perf_counter stamp, puts the host's
records and the device's events on one clock.
"""
from __future__ import annotations

import asyncio
import glob
import os
import time
from typing import Dict, List, Optional, Tuple

from bench import observe
from bench import trace as trace_mod


class Tracing:
    def __init__(self, engine, out_dir: str, duration: float, lead: float):
        self.engine = engine
        self.dir = out_dir
        self.duration = duration
        self.lead = lead
        self.recorder = observe.Recorder()
        if engine._sched is not None:
            self.recorder.wrap_chunks(engine._sched)
        self.window: Tuple[float, float] = (0.0, 0.0)
        self.t_sync: Optional[float] = None
        self.phase0: Dict[str, int] = {}
        self.phase1: Dict[str, int] = {}

    # ------------------------------------------------------------ window
    def _phase_raw(self, phase: str) -> List[float]:
        m = self.engine.telemetry.registry.get("engine_step_phase_seconds")
        return [] if m is None else m.series(phase=phase).raw

    def _mark(self) -> Dict[str, int]:
        return {p: len(self._phase_raw(p)) for p in ("retire", "admit")}

    async def run(self, t0: float, t1: float) -> None:
        """Called at the window's start: capture the profile."""
        import jax
        self.phase0 = self._mark()
        await asyncio.sleep(max(t0 + self.lead - time.perf_counter(), 0.0))
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(trace_mod.SYNC):
            self.t_sync = time.perf_counter()
        a = time.perf_counter()
        await asyncio.sleep(self.duration)
        b = time.perf_counter()
        jax.profiler.stop_trace()
        self.window = (a, b)

    def close(self) -> None:
        """Called at the window's end."""
        self.phase1 = self._mark()

    # ---------------------------------------------------------- readings
    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[-1]

    def readings(self, spec, sizes: dict, peaks: dict) -> dict:
        red = trace_mod.reduce(self.xplane(), self.t_sync, self.window)
        tel = self.engine.telemetry
        phases = {p: self._phase_raw(p)[self.phase0.get(p, 0):
                                        self.phase1.get(p, 0)]
                  for p in ("retire", "admit")}
        spans = list(tel.tracer.events())
        obs = observe.Observation(
            sizes=sizes, settings=spec.settings, peaks=peaks, trace=red,
            recorder=self.recorder, window=self.window, phases=phases,
            spans=spans, span_origin=tel.tracer._origin or 0.0)
        metrics = {}
        for m in spec.per_layer:
            value = observe.load_module("metrics", m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return {"metrics": metrics, "busy_s": red.busy_s,
                "window_s": red.window_s, "breakdown": breakdown(red, obs)}


def breakdown(red: trace_mod.Reduced, obs: observe.Observation) -> dict:
    """The ten device ops that took most time, and the ten longest idle
    gaps named by what the engine's host loop was doing then."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:10]
    phases = []                      # (start, end, name) on the host clock
    for ev in obs.spans:
        if ev.get("ph") == "X" and ev.get("tid") == 0 and \
                ev["name"] in ("retire", "admit", "prefill", "decode"):
            s = obs.span_time(ev["ts"])
            phases.append((s, s + ev["dur"] * 1e-6, ev["name"]))
    phases.sort()
    gaps = sorted(red.gaps, key=lambda g: g[0] - g[1])[:10]
    named = []
    for s, e in gaps:
        what = "no scheduler iteration"
        if red.host_offset_ns is not None:
            mid = red.to_host((s + e) / 2)
            hit = [n for a, b, n in phases if a <= mid < b]
            if hit:
                what = f"host {hit[0]} phase"
        named.append([what, (e - s) * 1e-9])
    return {"device_ops": [[k, v * 1e-9 / red.devices] for k, v in ops],
            "idle_gaps": named}

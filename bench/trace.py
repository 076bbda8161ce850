"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics
read: device busy time, per-op and per-kernel device time, the compiled
programs (XLA modules) that ran, and the device's idle gaps.

Device planes are those named `/device:TPU:<n>`. On each, the line
"XLA Ops" holds one event per executed op and "XLA Modules" one per
program execution. A Pallas kernel is found by the jitted wrapper the
program gave it, `jit(<name>_pallas)`, in any string stat of its op
event (its op name), as `chip_smoke.mosaic_kernels` finds it in HLO.

Every interval is clipped to the traced window [t0, t1] (profile clock,
nanoseconds); `sync_ns` maps the host's perf_counter onto that clock.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

#: a Pallas kernel's op: its HLO instruction takes the name of the jitted
#: wrapper (`%sparq_matmul_pallas.46 = f32[...] custom-call(...)`), and
#: its op name holds `jit(<name>_pallas)`
KERNEL_RE = re.compile(r"(?:^%|jit\()(\w+?)_pallas\b")
#: ops whose interval holds other ops' (a layer loop's `while`)
CONTAINERS = ("while", "conditional", "call")
SYNC = "bench.clock_sync"


@dataclasses.dataclass
class Module:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Reduced:
    devices: int
    t0: int                       # traced window, profile clock (ns)
    t1: int
    busy_ns: float                # mean over devices of the op union
    op_ns: Dict[str, float]       # op group -> ns (summed over devices)
    kernel_ns: Dict[Tuple[str, str], float]   # (module kind, kernel) -> ns
    modules: List[Module]         # device 0's program executions
    gaps: List[Tuple[int, int]]   # device 0's idle intervals
    host_offset_ns: Optional[float]   # profile ns = perf_counter*1e9 + this

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module_kind_ns(self, kind: str) -> float:
        return sum(m.end - m.start for m in self.modules
                   if module_kind(m.name) == kind)

    def module_count(self, kind: str) -> int:
        return sum(1 for m in self.modules if module_kind(m.name) == kind)

    def to_profile(self, t_host: float) -> float:
        return t_host * 1e9 + self.host_offset_ns

    def to_host(self, t_prof: float) -> float:
        return (t_prof - self.host_offset_ns) * 1e-9


def module_kind(name: str) -> str:
    """The engine's program a module belongs to: its decode step, its
    chunked-prefill program, or anything else."""
    if "_step_fn" in name:
        return "step"
    if "_chunk_fn" in name:
        return "chunk"
    return "other"


def _stats(ev) -> List[str]:
    out = []
    for st in ev.stats:
        v = st[1] if isinstance(st, tuple) else getattr(st, "value", None)
        if isinstance(v, str):
            out.append(v)
    return out


def kernel_of(ev) -> Optional[str]:
    for s in [ev.name] + _stats(ev):
        m = KERNEL_RE.search(s)
        if m:
            return m.group(1)
    return None


def op_group(name: str) -> str:
    """An op's instruction name without its instance number: the trace
    names an op by its HLO text, `%fusion.12 = bf16[...] fusion(...)`
    -> fusion."""
    m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?: =|$)", name)
    return m.group(1) if m else name


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _device_planes(pd):
    planes = [p for p in pd.planes
              if re.fullmatch(r"/device:TPU:\d+", p.name)]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def host_offset(pd, t_sync_host: Optional[float]) -> Optional[float]:
    if t_sync_host is None:
        return None
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == SYNC:
                    return ev.start_ns - t_sync_host * 1e9
    return None


def reduce(path: str, t_sync_host: Optional[float] = None,
           window_host: Optional[Tuple[float, float]] = None) -> Reduced:
    """Reduce the trace at `path`. `t_sync_host` is the perf_counter
    stamp taken as the `bench.clock_sync` annotation opened;
    `window_host` the traced window in perf_counter seconds (without
    either, the window is the span of the device's events)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    off = host_offset(pd, t_sync_host)
    devs = _device_planes(pd)
    if not devs:
        raise ValueError(f"{path}: no /device:TPU:<n> plane in the trace")
    per_dev = []
    lo, hi = None, None
    for p in devs:
        ops, mods = [], []
        for line in p.lines:
            if line.name == "XLA Ops":
                ops = [(ev, int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns))
                       for ev in line.events]
            elif line.name == "XLA Modules":
                mods = [Module(ev.name, int(ev.start_ns),
                               int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        per_dev.append((ops, mods))
        for s, e in [(s, e) for _, s, e in ops] + \
                [(m.start, m.end) for m in mods]:
            lo = s if lo is None else min(lo, s)
            hi = e if hi is None else max(hi, e)
    if window_host is not None and off is not None:
        t0 = int(window_host[0] * 1e9 + off)
        t1 = int(window_host[1] * 1e9 + off)
    else:
        t0, t1 = int(lo or 0), int(hi or 0)

    busy = 0.0
    op_ns: Dict[str, float] = {}
    kernel_ns: Dict[Tuple[str, str], float] = {}
    gaps: List[Tuple[int, int]] = []
    modules: List[Module] = []
    for d, (ops, mods) in enumerate(per_dev):
        mods_sorted = sorted(mods, key=lambda m: m.start)
        spans = []
        mi = 0
        for ev, s, e in sorted(ops, key=lambda o: o[1]):
            cs, ce = max(s, t0), min(e, t1)
            if ce <= cs:
                continue
            spans.append((cs, ce))
            while mi < len(mods_sorted) and mods_sorted[mi].end < s:
                mi += 1
            owner = next((m for m in mods_sorted[mi:mi + 2]
                          if m.start <= s and e <= m.end), None)
            kind = module_kind(owner.name) if owner else "other"
            k = kernel_of(ev)
            group = k + "_pallas" if k else op_group(ev.name)
            if group not in CONTAINERS:
                key = f"{kind}:{group}"
                op_ns[key] = op_ns.get(key, 0.0) + (ce - cs)
            # kernel time only of programs wholly inside the window, which
            # are the executions the cost functions count
            if k and owner is not None and owner.start >= t0 \
                    and owner.end <= t1:
                kernel_ns[(kind, k)] = kernel_ns.get((kind, k), 0.0) + (e - s)
        u = union(spans)
        busy += sum(e - s for s, e in u)
        if d == 0:
            modules = [m for m in mods_sorted if m.start >= t0 and m.end <= t1]
            prev = t0
            for s, e in u:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
            if t1 > prev:
                gaps.append((prev, t1))
    return Reduced(len(devs), t0, t1, busy / len(devs), op_ns, kernel_ns,
                   modules, gaps, off)


def describe(path: str, per_line: int = 3) -> str:
    """Planes, lines and a few events of a trace, with their stats: what
    to look at before writing code against a new device's traces."""
    from jax.profiler import ProfileData
    out = []
    for p in ProfileData.from_file(path).planes:
        out.append(f"plane {p.name}")
        for line in p.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                out.append(f"    {ev.name!r} {ev.start_ns} +{ev.duration_ns}"
                           f" {[(s[0], s[1]) for s in ev.stats][:8]}")
    return "\n".join(out)


def _q(v: str) -> str:
    return '"' + v.replace("\\", "\\\\").replace('"', '\\"') + '"'


def excerpt(path: str, t_from: int, t_to: int) -> str:
    """The device planes' events that start in [t_from, t_to) (profile
    ns), and the host's clock-sync event, as an XSpace text proto:
    `ProfileData.text_proto_to_serialized_xspace` makes it a small
    `.xplane.pb` again (the tests' recorded trace is made so)."""
    from jax.profiler import ProfileData
    planes = []
    for pid, p in enumerate(ProfileData.from_file(path).planes):
        keep_host = p.name.startswith("/host:")
        if not (re.fullmatch(r"/device:TPU:\d+", p.name) or keep_host):
            continue
        ev_ids: Dict[str, int] = {}
        st_ids: Dict[str, int] = {}
        lines = []
        for lid, line in enumerate(p.lines):
            evs = [ev for ev in line.events
                   if (ev.name == SYNC if keep_host
                       else t_from <= ev.start_ns < t_to)]
            if not evs:
                continue
            out = []
            base = min(int(ev.start_ns) for ev in evs)
            for ev in evs:
                eid = ev_ids.setdefault(ev.name, len(ev_ids) + 1)
                stats = []
                for name, v in ev.stats:
                    sid = st_ids.setdefault(name, len(st_ids) + 1)
                    if isinstance(v, str):
                        val = f"str_value: {_q(v)}"
                    elif isinstance(v, float):
                        val = f"double_value: {v!r}"
                    else:
                        val = f"int64_value: {int(v)}"
                    stats.append(f"stats {{ metadata_id: {sid} {val} }}")
                off = int(round((ev.start_ns - base) * 1000))
                out.append(f"events {{ metadata_id: {eid} offset_ps: {off} "
                           f"duration_ps: {int(round(ev.duration_ns * 1000))}"
                           f" {' '.join(stats)} }}")
            lines.append(f"lines {{ id: {lid} name: {_q(line.name)} "
                         f"timestamp_ns: {base} {' '.join(out)} }}")
        if not lines:
            continue
        entry = "{kind} {{ key: {i} value {{ id: {i} name: {name} }} }}"
        meta = [entry.format(kind="event_metadata", i=i, name=_q(n))
                for n, i in ev_ids.items()]
        meta += [entry.format(kind="stat_metadata", i=i, name=_q(n))
                 for n, i in st_ids.items()]
        planes.append(f"planes {{ id: {pid} name: {_q(p.name)}\n"
                      + "\n".join(lines + meta) + "\n}")
    return "\n".join(planes) + "\n"


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))

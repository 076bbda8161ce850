"""A run with the engine's spans on and the profiler off.

    python3 bench/spans_only.py --workload <cell> --seed <n> --seconds <s>

The same run as `bench/run.py --trace 0`, and the same result line (the
end-to-end metrics), but the engine records its spans
(`Telemetry.tracing()`) and no profile is taken: the profiler's Python
tracer, on in a `--trace 1` run, slows every line of the host loop. One
more JSON line follows, over the measured window: each per-layer metric
that reads the program's spans alone, each scheduler span's host time in
ms per iteration, and the runtime spans (`gc`, `compile`). Against a
`--trace 0` run of the same seed, it gives the cost of the spans.
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import cell, hostspans, observe, run  # noqa: E402


def readings(spec, events, origin: float, window) -> dict:
    obs = observe.Observation(
        sizes={}, settings=spec.settings, peaks={}, trace=None,
        recorder=observe.Recorder(), window=window, phases={},
        spans=events, span_origin=origin)
    metrics = {}
    for m in spec.per_layer:
        if m["source"] != "program_span":
            continue
        value = observe.load_module("metrics", m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    spans = hostspans.scheduler(obs)
    iters = hostspans.iterations(spans, window)
    return {"window_s": window[1] - window[0], "iterations": len(iters),
            "metrics": metrics,
            "host_ms_per_iter": hostspans.host_ms_per_iter(spans, iters),
            "runtime": hostspans.runtime_rows(obs)}


def main(argv=None) -> int:
    from repro.obs import Telemetry
    kept: dict = {}
    run_cell = cell.run

    def with_spans(spec, *a, **kw):
        def hook(engine):
            engine.telemetry = kept["tel"] = Telemetry.tracing()
        out = run_cell(spec, *a, engine_hook=hook, **kw)
        kept.update(spec=spec, window=(out["rec"]["t0"], out["rec"]["t1"]))
        return out

    cell.run = with_spans
    args = list(sys.argv[1:] if argv is None else argv)
    rc = run.main(args + ["--trace", "0"])
    if rc or "window" not in kept:
        return rc or 1
    tracer = kept["tel"].tracer
    print(json.dumps(readings(kept["spec"], tracer.events(),
                              tracer._origin or 0.0, kept["window"])),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark entry point: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's `workloads`: a configuration
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<traffic>.json`), with its engine settings and check
limit in `bench/cells/<cell>.json`. With `--trace 0` the run reports the
cell's end-to-end metrics; with `--trace 1` its per-layer metrics, read
by `bench/metrics/<metric>.py` from a profile of the window (but its
first and last second) and the engine's spans.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program or the cell's files cannot
be found. Otherwise the last line of standard output is one JSON object:
correct, attempted, failed, metrics, device, (breakdown,) compared.
The numbers the check compared, each beside its limit, are the last
lines of standard error and the last key of that object.

`--keep-trace DIR` keeps the profiler's files of a traced run there.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
# import the benchmark as the `bench` package, never its files as
# top-level modules (bench/trace.py would shadow the standard library's)
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR")
    return ap.parse_args(argv)


def compile_cache() -> None:
    """JAX's persistent compilation cache at <checkout>/.jax_cache, a
    fixed path, for every program however short its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][kind]


def result_line(spec, out: dict, trace: bool) -> dict:
    dev = out["device"]
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["peak"]}
    metrics = {}
    if trace:
        layer = out["layer"]
        metrics = layer["metrics"]
        device["busy_s"] = layer["busy_s"]
        device["window_s"] = layer["window_s"]
    else:
        readings = dict(out["e2e"], setup_s=out["setup_s"])
        for m in spec.end_to_end:
            v = readings.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    from bench import check
    numbers = dict(out["check"]["numbers"])
    numbers["failed_requests"] = (out["failed"], 0)
    res = {"correct": check.verdict(out["check"]["numbers"], out["failed"]),
           "attempted": out["attempted"], "failed": out["failed"],
           "metrics": metrics, "device": device}
    if trace:
        res["breakdown"] = out["layer"]["breakdown"]
    res["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    return res


def main(argv=None) -> int:
    args = parse(argv)
    compile_cache()
    try:
        import jax
        devices = jax.devices()
    except Exception as e:          # no backend at all
        log(f"no accelerator: {e}")
        return 2
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX sees {len(devices)} {devices[0].platform} "
            f"device(s)")
        return 2
    try:
        from bench import cell
        spec = cell.load_spec(args.workload)
        peaks = peaks_for(devices[0].device_kind)
        import repro  # noqa: F401  (the program under test)
    except (ImportError, KeyError, FileNotFoundError) as e:
        log(f"cannot run {args.workload!r}: {e}")
        return 2
    if len(devices) < spec.chips:
        log(f"{args.workload} needs {spec.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    try:
        out = cell.run(spec, args.seed, args.seconds, bool(args.trace),
                       T_PROCESS, peaks=peaks, keep_trace=args.keep_trace,
                       log=log)
    except Exception:
        log(traceback.format_exc())
        return 1
    res = result_line(spec, out, bool(args.trace))
    for name, v in res["compared"].items():
        log(f"compared: {name} {v['value']} limit {v['limit']}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, the measured window, the check.

Set-up draws the configuration's weights on the device, calibrates the
activation scales on one batch, builds the paged engine with the
deployment flags, and warms every program the cell's traffic uses. The
window then drives the async front-end (`launch/frontend.py`) over that
engine with the cell's traffic for `--seconds`. Afterwards the program's
state is freed and the plain reference checks a sample of what the
window served.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import os
import pathlib
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import loadgen

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
FLAGS = ["--engine", "paged", "--kv-cache", "sparq", "--sparq", "5opt",
         "--impl", "pallas", "--prefill", "chunked", "--prefix-cache",
         "--prequantize"]
TRACE_EDGE_S = 1.0          # the profile leaves out the window's first and
                            # last second (starting and stopping it)


@dataclasses.dataclass
class Spec:
    """A cell as BENCHMARK.json and its files name it."""
    name: str
    conf: Dict[str, Any]        # bench/configs/<config>.json
    mix: Dict[str, Any]         # bench/traffic/<traffic>.json
    settings: Dict[str, Any]    # bench/cells/<cell>.json
    end_to_end: List[dict]      # the cell's end-to-end metrics
    per_layer: List[dict]       # the cell's per-layer metrics
    chips: int


def load_spec(name: str, root: pathlib.Path = ROOT) -> Spec:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    confs = {c["name"]: c for c in manifest["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = loadgen.load_mix(w["traffic"], root / "bench")
    settings = json.loads((root / "bench" / "cells" / f"{name}.json")
                          .read_text())
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if name in m.get("workloads", [name]) and m["moves"] in moved]
    return Spec(name, conf, mix, settings, e2e, per_layer, int(w["chips"]))


#: Published config.json keys beyond the dense ones, and the program's
#: ModelConfig field each sets. A key is applied only where the file
#: states it (a null states nothing); a stated key whose field the
#: program lacks raises, and so does a field of this table that the
#: registry entry sets and the file leaves unstated.
MAPPED = {
    "n_routed_experts": "n_experts",
    "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "moe_d_ff",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "rope_scaling": "rope_scaling",
}


def _mapped(conf: dict, base) -> dict:
    """The MAPPED fields a configuration file sets."""
    fields = {f.name: f.default for f in dataclasses.fields(base)}
    out: Dict[str, Any] = {}
    for key, field in MAPPED.items():
        if conf.get(key) is None:
            continue
        if field not in fields:
            raise ValueError(f"{conf['name']}: {key} = {conf[key]!r} has no "
                             f"place: the program's ModelConfig has no "
                             f"field {field!r}")
        if field in out and out[field] != conf[key]:
            raise ValueError(f"{conf['name']}: two keys set {field} to "
                             f"{out[field]!r} and {conf[key]!r}")
        out[field] = conf[key]
    for field in sorted(set(MAPPED.values()) & set(fields)):
        if field not in out and getattr(base, field) != fields[field]:
            keys = [k for k, f in MAPPED.items() if f == field]
            raise ValueError(f"{conf['name']}: registry entry "
                             f"{conf['registry']!r} has {field} = "
                             f"{getattr(base, field)!r}; the file must "
                             f"state it ({' or '.join(keys)})")
    return out


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry with every size the file states (the dense keys, and those of
    MAPPED that it holds)."""
    from repro.configs.base import get_config
    base = get_config(conf["registry"])
    cfg = base.replace(
        name=conf["name"], n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        mlp_type="swiglu" if conf["hidden_act"] == "silu" else "gelu",
        norm_type=conf["norm"], rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        norm_eps=float(conf.get("rms_norm_eps",
                                conf.get("norm_epsilon", 1e-5))),
        **_mapped(conf, base))
    if "head_dim" in conf and cfg.head_dim != conf["head_dim"]:
        raise ValueError(f"{conf['name']}: head_dim {conf['head_dim']} "
                         f"but the program derives {cfg.head_dim}")
    return cfg


def engine_args(settings: dict, trace_out: Optional[str]):
    from repro.launch import serve
    s = settings
    argv = FLAGS + ["--chunk-size", str(s["chunk"]),
                    "--page-size", str(s["page_size"]),
                    "--n-pages", str(s["pages"]),
                    "--max-active", str(s["slots"]),
                    "--batch", str(s["slots"]),
                    "--prompt-len", str(s["max_seq"]), "--gen", "1"]
    if trace_out:
        argv += ["--trace-out", trace_out]
    return serve.build_parser().parse_args(argv)


class Compiles:
    """Counts XLA compilations (backend compiles, cache hits or not)."""

    def __init__(self):
        import jax
        self.events: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(time.perf_counter())

    def between(self, a, b) -> int:
        return sum(1 for t in self.events if a <= t < b)


@dataclasses.dataclass
class Sent:
    req: loadgen.Req
    handle: Any                 # the front-end's stream, until `freeze`
    rid: int = -1
    events: list = dataclasses.field(default_factory=list)

    def freeze(self) -> None:
        """Keep what the stream delivered and let go of the stream: it
        holds the front-end, and the front-end the engine and weights,
        which must be freed before the reference runs."""
        self.rid, self.events = self.handle.rid, list(self.handle.events)
        self.handle = None

    @property
    def finished(self) -> bool:
        return bool(self.events) and self.events[-1].final

    @property
    def tokens(self):
        return np.asarray([e.token for e in self.events], np.int32)


async def _first_tokens(handles, timeout: float):
    t_end = time.perf_counter() + timeout
    while any(not h.events and not h.done for h in handles):
        if time.perf_counter() > t_end:
            raise TimeoutError("set-up requests got no first token")
        await asyncio.sleep(0.005)


async def _drive(fe, traffic: loadgen.Traffic, seconds: float, log,
                 tracer=None) -> dict:
    """Warm up, ramp, measure. Returns the window's records."""
    t_warm = time.perf_counter()
    warm = []
    for wave in traffic.warmup:
        hs = [fe.submit(r.tokens, r.gen) for r in wave]
        await _first_tokens(hs, 600.0)
        warm += hs
    for h in warm:
        await h.drain()
    await asyncio.sleep(0.05)
    log(f"[setup] warm-up traffic: {time.perf_counter() - t_warm:.1f} s")
    sent: List[Sent] = []
    stop = asyncio.Event()
    late: List[float] = []
    failed: List[Sent] = []
    t_ready = time.perf_counter()
    pending = iter(traffic.requests)

    async def client(first: loadgen.Req):
        r = first
        t_free = None
        while not stop.is_set():
            t = time.perf_counter()
            if t_free is not None:
                late.append(t - t_free)
            s = Sent(r, fe.submit(r.tokens, r.gen))
            sent.append(s)
            try:
                await s.handle.drain()
            except Exception:
                if not stop.is_set():
                    failed.append(s)
                return
            t_free = time.perf_counter()
            r = next(pending, None)
            if r is None:
                raise RuntimeError("the mix drew too few requests")
    tasks = [asyncio.ensure_future(client(f)) for f in traffic.first]
    while len(sent) < len(traffic.first):
        await asyncio.sleep(0.005)
    await _first_tokens([s.handle for s in sent[:len(traffic.first)]],
                        1200.0)
    t0 = time.perf_counter()
    log(f"[setup] ramp (every client's first request prefilled): "
        f"{t0 - t_ready:.1f} s")
    t1 = t0 + seconds
    if tracer is not None:
        await tracer.run(t0, t1)
    await asyncio.sleep(max(t1 - time.perf_counter(), 0.0))
    t1 = time.perf_counter()
    stop.set()
    if tracer is not None:
        tracer.close()
    await fe.stop()
    for t in tasks:
        t.cancel()
    for s in sent:
        s.freeze()
    return {"sent": sent, "t0": t0, "t1": t1, "late": late,
            "failed": len(failed)}


def _pctl(xs, p) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), p)) if len(xs) \
        else None


def end_to_end(rec: dict) -> dict:
    """The end-to-end readings of one window (host clock)."""
    t0, t1 = rec["t0"], rec["t1"]
    toks, gaps = 0, []
    for s in rec["sent"]:
        ev = s.events
        toks += sum(1 for e in ev if t0 <= e.t < t1)
        gaps += [b.t - a.t for a, b in zip(ev, ev[1:]) if t0 <= b.t < t1]
    return {"output_tok_s": toks / (t1 - t0),
            "itl_p95_ms": None if not gaps else 1e3 * _pctl(gaps, 95),
            "itl_samples": len(gaps)}


@dataclasses.dataclass
class Session:
    """The program as set-up leaves it: model, served weights, scales and
    the engine, ready for a window."""
    spec: Spec
    model: Any
    params: Any
    engine: Any
    tracer: Any = None
    trace_dir: Optional[str] = None


def setup(spec: Spec, *, trace: bool = False, seconds: float = 0.0,
          keep_trace: Optional[str] = None, log=print,
          engine_hook=None) -> Session:
    """Draw and quantize the weights, calibrate on one batch, build the
    engine with the deployment flags (and, traced, its recorders)."""
    import jax
    from repro.launch import serve
    from repro.models.model import Model
    from bench import weights
    model = Model(model_config(spec.conf))
    t = time.perf_counter()
    params = weights.served_params(model, spec.conf["weight_seed"])
    jax.block_until_ready(params)
    log(f"[setup] weights drawn and quantized: "
        f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    cal = spec.conf["calibration"]
    rng = np.random.default_rng(spec.conf["weight_seed"])
    calib = {"tokens": jax.numpy.asarray(rng.integers(
        0, model.cfg.vocab_size, (cal["batch"], cal["tokens"])),
        jax.numpy.int32)}
    scales = model.calibrate(params, [calib])
    log(f"[setup] calibrated on one batch: {time.perf_counter() - t:.1f} s")
    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
    engine = serve.paged_engine(
        engine_args(spec.settings, os.path.join(trace_dir, "spans.json")
                    if trace else None), model, scales)
    if engine_hook is not None:
        engine_hook(engine)
    tracer = None
    if trace:
        from bench.tracing import Tracing
        edge = min(TRACE_EDGE_S, seconds / 4)
        tracer = Tracing(engine, trace_dir, seconds - 2 * edge, edge)
    return Session(spec, model, params, engine, tracer, trace_dir)


def window(sess: Session, traffic: loadgen.Traffic, seconds: float,
           log=print) -> dict:
    """Warm-up, ramp and the measured window through the async front-end."""
    from repro.launch import frontend
    fe = frontend.AsyncFrontend(sess.engine, sess.params, trace_hook=(
        sess.tracer.recorder.hook if sess.tracer else None))

    async def main():
        await fe.start()
        return await _drive(fe, traffic, seconds, log, sess.tracer)
    return asyncio.run(main())


def run(spec: Spec, seed: int, seconds: float, trace: bool, t_proc: float,
        *, peaks: dict, keep_trace: Optional[str] = None, log=print,
        engine_hook=None, control: bool = False) -> dict:
    """One run of `spec`: set-up, window, readings, check. The JAX
    platform is whatever the caller has checked."""
    import jax
    from bench import check
    compiles = Compiles()
    sizes = check.ref_sizes(spec.conf)
    traffic = loadgen.generate(spec.mix, seed, spec.conf["vocab_size"])
    if traffic.max_seq > spec.settings["max_seq"]:
        raise ValueError(f"traffic needs {traffic.max_seq} positions, the "
                         f"cell allows {spec.settings['max_seq']}")
    log(f"[setup] {loadgen.describe(traffic)}")
    sess = setup(spec, trace=trace, seconds=seconds, keep_trace=keep_trace,
                 log=log, engine_hook=engine_hook)
    rec = window(sess, traffic, seconds, log)
    setup_s = rec["t0"] - t_proc
    n_compiles = compiles.between(rec["t0"], rec["t1"])
    e2e = end_to_end(rec)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    late = rec["late"]
    log(f"[window] {rec['t1'] - rec['t0']:.2f} s, {len(rec['sent'])} "
        f"requests sent, {sum(1 for s in rec['sent'] if s.finished)} "
        f"finished; compiles in window: {n_compiles}; generator lateness "
        f"p50 {1e3 * (_pctl(late, 50) or 0):.3f} ms, max "
        f"{1e3 * (max(late) if late else 0):.3f} ms; peak device memory "
        f"{peak} bytes; {json.dumps(e2e)}")
    layer = None
    if sess.tracer is not None:
        layer = sess.tracer.readings(spec, sizes, peaks)
        if not keep_trace:
            shutil.rmtree(sess.trace_dir, ignore_errors=True)
    # free the program's state before the reference runs
    del sess
    gc.collect()
    verdict = check.run(spec, seed, rec, sizes, control=control, log=log)
    return {"rec": rec, "e2e": e2e, "setup_s": setup_s, "peak": peak,
            "compiles_in_window": n_compiles, "attempted": len(rec["sent"]),
            "failed": rec["failed"], "check": verdict, "layer": layer,
            "device": dev}

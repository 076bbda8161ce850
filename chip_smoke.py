"""Smoke run of the SPARQ serving path on a TPU.

Drives the paged continuous-batching engine that `python -m
repro.launch.serve --engine paged` builds, at TinyLlama-1.1B's published
widths (22 layers, d_model 2048, 32 query / 4 KV heads, d_ff 5632,
vocab 32000) with weights drawn from a seed: nothing is downloaded.

    python chip_smoke.py            # one chip: phases A, B and C
    python chip_smoke.py --tp 4     # four chips: the TP=4 check alone

Phase A  synchronous run, `--kv-cache sparq --sparq 5opt --impl pallas
         --prefill chunked --prefix-cache`: 8 ragged requests of 64-512
         prompt tokens, four of them sharing a 288-token prefix, 32
         generated tokens each. The same requests then run with
         `--impl reference` on the same chip; the first generated token
         of every request must match, and the greedy-match rate over
         all tokens is printed.
Phase B  16 requests through the async streaming front-end on a Poisson
         arrival trace; every request must stream all of its tokens.
Phase C  the compiled decode step must hold the paged-decode attention
         and quantized-matmul Mosaic kernels (`tpu_custom_call`), and
         the chunked-prefill program the chunked-prefill attention and
         quantized-matmul ones, each found by its name: no interpret
         mode, no reference fallback.
--tp 4   the TP=4 paged engine against the TP=1 engine on the same
         requests, in this one process that holds all four chips: greedy
         tokens must be bit-identical, and the page pools must be spread
         over all four devices.

Everything runs in this one process: a chip belongs to the process that
first touches it. Exits non-zero, with no result line, when JAX finds no
TPU or a check fails. The last line of standard output is the result:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
The timings printed on the way are one run's observations, not metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
GEN = 32
PREFIX = 288            # shared-prefix length: one 256-token segment+


def _flags(impl: str, tp: int = 1) -> list:
    return ["--arch", "tinyllama-1.1b", "--engine", "paged",
            "--kv-cache", "sparq", "--sparq", "5opt", "--impl", impl,
            "--prefill", "chunked", "--prefix-cache", "--chunk-size", "256",
            "--page-size", "16", "--n-pages", "2048", "--batch", "8",
            "--max-active", "4", "--prompt-len", "512", "--gen", str(GEN),
            "--calibrate", "1", "--seed", "0", "--tp", str(tp)]


def _requests(vocab: int, seed: int = 0):
    """8 ragged prompts of 64-512 tokens; four share a PREFIX-token
    prefix (one admits in the first wave, three after it, so the prefix
    cache has pages to hand out)."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    tok = lambda n: rng.integers(0, vocab, n)
    shared = tok(PREFIX)
    with_prefix = lambda n: np.concatenate([shared, tok(n - PREFIX)])
    prompts = [with_prefix(320), tok(64), tok(200), tok(512),
               with_prefix(384), with_prefix(448), tok(130),
               with_prefix(512)]
    return [Request(p, GEN) for p in prompts]


def _timed(device, label, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    print(f"  {label}: {time.perf_counter() - t0:.1f} s on {device}",
          flush=True)
    return out


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")


def _tokens(results: dict) -> list:
    return [np.asarray(results[i]) for i in range(len(results))]


def phase_a(serve, model, params, scales, reqs, device):
    print(f"phase A: sync paged run on {device}", flush=True)
    eng = serve.paged_engine(serve.build_parser().parse_args(
        _flags("pallas")), model, scales)
    res, stats = _timed(device, "pallas run (compiles included)", eng.run,
                        params, reqs)
    print(f"  prefix cache: {stats['prefix_hits']} hits, "
          f"{stats['prefix_hit_tokens']} prompt tokens from cache; "
          f"peak pages {stats['peak_pages_used']}/{stats['pool_pages']}")
    ref_eng = serve.paged_engine(serve.build_parser().parse_args(
        _flags("reference")), model, scales)
    ref, _ = _timed(device, "reference run (compiles included)",
                    ref_eng.run, params, reqs)
    got, want = _tokens(res), _tokens(ref)
    _check(all(len(g) == GEN for g in got), "pallas run short of tokens")
    first = [int(g[0]) == int(w[0]) for g, w in zip(got, want)]
    match = np.mean(np.concatenate(got) == np.concatenate(want))
    print(f"  first tokens equal pallas vs reference: {sum(first)}/"
          f"{len(first)}; greedy-match rate over all tokens: "
          f"{match:.4f}")
    _check(all(first), "first generated token differs from the reference")
    _, warm = _timed(device, "pallas run again (warm)", eng.run, params,
                     reqs)
    print(f"  warm run on {device}: prefill {warm['prefill_s'] * 1e3:.0f} "
          f"ms, decode {warm['decode_tok_s']:.1f} tok/s (one run, not a "
          f"metric)")
    return eng


def phase_b(eng, params, vocab, device):
    from repro.launch import frontend
    print("phase B: async front-end, Poisson arrivals", flush=True)
    rng = np.random.default_rng(1)
    lens = rng.integers(64, 513, 16)
    gens = rng.integers(8, GEN + 1, 16)
    ats = frontend.arrival_times("poisson", 16, 4.0, rng=rng)
    trace = [(rng.integers(0, vocab, n), int(g), at)
             for n, g, at in zip(lens, gens, ats)]
    results, slo, _ = _timed(device, "trace", frontend.play_trace, eng,
                             params, trace)
    _check(sorted(results) == list(range(len(trace))),
           "a request produced no stream")
    short = [i for i, (_, g, _) in enumerate(trace)
             if len(results[i]) != g]
    _check(not short, f"requests {short} streamed too few tokens")
    print(f"  16/16 requests streamed every token; ttft p50 "
          f"{slo['ttft']['p50_ms']:.1f} ms, itl p50 "
          f"{slo['itl']['p50_ms']:.2f} ms on {device} (one run, not a "
          f"metric)")


# the Pallas wrappers each compiled program must hold as Mosaic kernels
KERNELS = {"decode step": ("sparq_paged_decode_attn_pallas",
                           "sparq_matmul_pallas"),
           "chunk program": ("sparq_chunked_prefill_attn_pallas",
                             "sparq_matmul_pallas")}


def mosaic_kernels(hlo: str) -> dict:
    """{Pallas wrapper: number of its Mosaic kernels} in compiled HLO text.
    Each `tpu_custom_call` carries its jitted wrapper in its op_name, as
    in `.../jit(sparq_matmul_pallas)/pallas_call`."""
    found: dict = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            for name in set(re.findall(r"jit\((\w+_pallas)\)", line)):
                found[name] = found.get(name, 0) + 1
    return found


def phase_c(eng, params):
    import jax
    import jax.numpy as jnp
    from repro.models.paging import ChunkMeta
    print("phase C: kernels in the compiled programs", flush=True)
    stores = jax.eval_shape(eng._init_stores)
    S, sched = eng.max_active, eng._sched
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    step = eng._step.lower(params, i32(S, 1), stores, i32(S)).compile()
    meta = ChunkMeta(seq_id=i32(sched.C), pos=i32(sched.C),
                     hist=i32(sched.C), tile_seq=i32(sched.C // sched.bq),
                     seq_pos_after=i32(S))
    chunk = sched._chunk.lower(params, i32(1, sched.C), stores, meta,
                               i32(S)).compile()
    for name, prog in (("decode step", step), ("chunk program", chunk)):
        found = mosaic_kernels(prog.as_text())
        print(f"  {name}: Mosaic kernels {found}")
        missing = [k for k in KERNELS[name] if k not in found]
        _check(not missing, f"{missing} not compiled into the {name}")


def phase_tp(serve, model, params, scales, reqs, device, n_tp=4):
    print(f"TP={n_tp} against TP=1 on {device}", flush=True)
    outs, seen = {}, []

    def hook(snap):                 # the live pools, once, mid-run
        if not seen:
            plane = snap["caches"][0].k_data
            seen.append((len(plane.sharding.device_set),
                         {s.data.shape[-1]
                          for s in plane.addressable_shards}))
    for tp in (n_tp, 1):
        eng = serve.paged_engine(serve.build_parser().parse_args(
            _flags("pallas", tp)), model, scales)
        res, _ = _timed(device, f"tp={tp} run (compiles included)", eng.run,
                        params, reqs, trace_hook=hook if tp > 1 else None)
        outs[tp] = _tokens(res)
    n_dev, lanes = seen[0]
    print(f"  tp={n_tp} pools on {n_dev} devices, {sorted(lanes)} lanes "
          f"each")
    lane_width = model.cfg.n_kv_heads * model.cfg.head_dim // n_tp
    _check(n_dev == n_tp and lanes == {lane_width},
           f"tp={n_tp} pools are not spread over {n_tp} devices")
    same = all(np.array_equal(a, b) for a, b in zip(outs[n_tp], outs[1]))
    print(f"  tokens bit-identical tp={n_tp} vs tp=1: {same}")
    _check(same, f"tp={n_tp} tokens differ from tp=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip tensor-parallel check")
    args = ap.parse_args(argv)

    # Inside a fusion XLA may keep an f32 value where the model rounds to
    # bf16, and which roundings it drops depends on the fusion plan: the
    # Pallas and reference programs (and TP=4 against TP=1) would then
    # round differently and part after a few tokens. The comparisons
    # below are token for token, so every declared rounding is kept.
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), "--xla_allow_excess_precision=false")))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.tp:
        print(f"--tp {args.tp} needs {args.tp} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"run from a checkout of the repository: {e}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    dev = devices[0]
    device = f"{dev.device_kind} x{len(devices)}"

    t0 = time.perf_counter()
    model, params, _, scales = _timed(
        device, "seeded weights + calibration", serve.load_model,
        serve.build_parser().parse_args(_flags("pallas")))
    reqs = _requests(model.cfg.vocab_size)
    if args.tp > 1:
        phase_tp(serve, model, params, scales, reqs, device)
    else:
        eng = phase_a(serve, model, params, scales, reqs, device)
        phase_b(eng, params, model.cfg.vocab_size, device)
        phase_c(eng, params)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory on {dev.device_kind}: "
              f"{stats['peak_bytes_in_use']} bytes")
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

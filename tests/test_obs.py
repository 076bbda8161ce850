"""Unified telemetry subsystem (repro.obs).

What is proven here:
  * registry semantics — counter monotonicity, gauge set/set_max,
    histogram bucketing + exact raw-reservoir percentiles, label-series
    isolation, one-meaning-per-name registration errors, and `reset()`
    zeroing values while keeping metric objects and pre-bound series
    handles alive (the engine's warmup/measure boundary contract);
  * Prometheus text exposition — a golden rendering (HELP/TYPE headers,
    labeled samples, cumulative `_bucket{le}` / `_sum` / `_count`) and a
    parse round-trip, plus a live `GET /metrics` scrape through the
    asyncio `MetricsServer`;
  * trace-event schema — spans balance (every B has its E, per tid),
    X events carry non-negative durations, chunk ordinals count up, and
    `run_end` closes stragglers so a trace always loads in Perfetto;
  * engine integration — a traced run's request-span tid set matches
    the emitted results exactly, every request shows first_token and
    finished marks, scheduler step spans carry the four phase children,
    each hand-off span nests in its phase, an iteration's hand-offs do
    not overlap and every dispatch is followed by its sync, the runtime
    track records collections and compiles and its hooks are gone after
    the run, the untraced loop reads the clock no more than it did,
    and the stats dict the engine returns is value-identical to direct
    registry reads (back-compat: the old `counters`/`pstats` keys now
    have exactly one source of truth);
  * purity — greedy tokens are BIT-identical with tracing on vs
    telemetry off, under both requeue and swap preemption on a tight
    pool, and back-to-back runs of one engine report fresh per-run
    stats (the registry reset at run start works).
"""
import asyncio
import dataclasses
import gc
import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparq import SparqConfig
from repro.launch import frontend, serve
from repro.launch.serve import (ContinuousBatchingEngine, Request,
                                SchedulerPolicy)
from repro.models.cache import CacheConfig
from repro.obs import (EngineSpans, MetricsRegistry, Telemetry, Tracer,
                       export, summary_ms)
from repro.obs.tracing import RUNTIME_TID, SCHED_TID

KEY = jax.random.PRNGKey(0)
PS = 4
MAX_SEQ_LEN = 24


# ----------------------------------------------------------------------
# registry semantics (pure host, no engine)
# ----------------------------------------------------------------------

def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", help="h")
    s = c.series()
    s.inc()
    s.inc(2.5)
    assert s.value() == 3.5
    with pytest.raises(ValueError):
        s.inc(-1)
    lc = reg.counter("tok_total", labelnames=("kind",))
    lc.inc(3, kind="a")
    lc.inc(4, kind="b")
    assert lc.value(kind="a") == 3 and lc.value(kind="b") == 4
    assert lc.total() == 7


def test_gauge_semantics():
    g = MetricsRegistry().gauge("pages").series()
    g.set(5)
    g.set_max(3)            # no-op: below current
    assert g.value() == 5
    g.set_max(9)
    assert g.value() == 9
    g.inc(2)
    g.dec(1)
    assert g.value() == 10


def test_histogram_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    s = h.series()
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        s.observe(v)
    assert s.counts == [1, 2, 1, 1]          # per-bucket (+Inf last)
    assert s.cumulative_counts() == [1, 3, 4, 5]
    assert s.count == 5 and s.sum == pytest.approx(56.05)
    raw = [0.05, 0.5, 0.5, 5.0, 50.0]
    assert s.percentile(50) == float(np.percentile(np.asarray(raw), 50))
    assert s.mean() == pytest.approx(np.mean(raw))
    assert s.max() == 50.0
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 0.5))


def test_label_isolation_and_registration_errors():
    reg = MetricsRegistry()
    h = reg.histogram("phase_s", labelnames=("phase",))
    h.series(phase="admit").observe(1.0)
    assert h.series(phase="decode").count == 0
    with pytest.raises(ValueError):                 # wrong label set
        h.series(stage="admit")
    assert reg.histogram("phase_s", labelnames=("phase",)) is h
    with pytest.raises(TypeError):                  # kind mismatch
        reg.counter("phase_s")
    with pytest.raises(ValueError):                 # labelnames mismatch
        reg.histogram("phase_s", labelnames=("other",))


def test_reset_keeps_series_handles_alive():
    """The engine pre-binds series once and holds them across
    `reset_stats()`; reset must zero values without replacing objects."""
    reg = MetricsRegistry()
    c = reg.counter("c").series()
    g = reg.gauge("g").series()
    h = reg.histogram("h").series()
    c.inc(3)
    g.set(7)
    h.observe(0.5)
    reg.reset()
    assert c.value() == 0 and g.value() == 0
    assert h.count == 0 and h.raw == [] and sum(h.counts) == 0
    assert reg.counter("c").series() is c       # same objects survive
    c.inc()                                     # old handle still live
    assert reg.counter("c").value() == 1


def test_summary_ms_matches_legacy_pctl():
    """BENCH_slo percentiles must not move across the refactor: the
    histogram-backed summary is the same numpy math as the front-end's
    legacy `_pctl` over the same samples."""
    xs = [0.011, 0.002, 0.5, 0.033, 0.07]
    s = MetricsRegistry().histogram("ttft").series()
    for v in xs:
        s.observe(v)
    assert summary_ms(s) == frontend._pctl(xs)
    empty = MetricsRegistry().histogram("e").series()
    assert summary_ms(empty) == frontend._pctl([])


# ----------------------------------------------------------------------
# Prometheus exposition
# ----------------------------------------------------------------------

def test_prometheus_golden():
    reg = MetricsRegistry()
    reg.counter("requests_total", help="requests served").series().inc(3)
    c = reg.counter("tokens_total", help="tokens", labelnames=("kind",))
    c.inc(5, kind="prefill")
    c.inc(2, kind="decode")
    reg.gauge("pool_pages", help="pages in use").series().set(7)
    h = reg.histogram("latency_seconds", help="lat", buckets=(0.1, 1.0))
    s = h.series()
    for v in (0.05, 0.5, 5.0):
        s.observe(v)
    assert export.prometheus_text(reg) == (
        "# HELP requests_total requests served\n"
        "# TYPE requests_total counter\n"
        "requests_total 3\n"
        "# HELP tokens_total tokens\n"
        "# TYPE tokens_total counter\n"
        'tokens_total{kind="prefill"} 5\n'
        'tokens_total{kind="decode"} 2\n'
        "# HELP pool_pages pages in use\n"
        "# TYPE pool_pages gauge\n"
        "pool_pages 7\n"
        "# HELP latency_seconds lat\n"
        "# TYPE latency_seconds histogram\n"
        'latency_seconds_bucket{le="0.1"} 1\n'
        'latency_seconds_bucket{le="1"} 2\n'
        'latency_seconds_bucket{le="+Inf"} 3\n'
        "latency_seconds_sum 5.55\n"
        "latency_seconds_count 3\n")


def test_prometheus_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter("a_total", labelnames=("x",)).inc(2, x="v")
    reg.gauge("b").series().set(1.5)
    h = reg.histogram("c_seconds", buckets=(1.0,)).series()
    h.observe(0.5)
    h.observe(2.0)
    parsed = export.parse_prometheus(export.prometheus_text(reg))
    assert parsed[("a_total", 'x="v"')] == 2
    assert parsed[("b", "")] == 1.5
    assert parsed[("c_seconds_bucket", 'le="1"')] == 1
    assert parsed[("c_seconds_bucket", 'le="+Inf"')] == 2
    assert parsed[("c_seconds_sum", "")] == 2.5
    assert parsed[("c_seconds_count", "")] == 2


def test_metrics_server_scrape():
    reg = MetricsRegistry()
    reg.counter("scraped_total", help="h").series().inc(3)

    async def go():
        srv = await export.MetricsServer(reg).start()
        try:
            async def fetch(path):
                r, w = await asyncio.open_connection("127.0.0.1", srv.port)
                w.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
                await w.drain()
                data = await r.read()
                w.close()
                return data
            return await fetch("/metrics"), await fetch("/other")
        finally:
            await srv.stop()

    ok, notfound = asyncio.run(go())
    head, _, body = ok.partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"version=0.0.4" in head
    assert export.parse_prometheus(body.decode())[("scraped_total", "")] == 3
    assert b"404" in notfound


# ----------------------------------------------------------------------
# trace-event schema (driven by hand)
# ----------------------------------------------------------------------

def _check_balanced(events):
    open_spans = {}
    for e in events:
        assert e["ph"] in ("B", "E", "X", "i", "C", "M")
        if e["ph"] != "M":
            assert e["ts"] >= 0
        if e["ph"] == "B":
            open_spans.setdefault(e["tid"], []).append(e["name"])
        elif e["ph"] == "E":
            assert open_spans.get(e["tid"]), "E without matching B"
            open_spans[e["tid"]].pop()
        elif e["ph"] == "X":
            assert e["dur"] >= 0
    leftovers = {k: v for k, v in open_spans.items() if v}
    assert not leftovers, f"unclosed spans: {leftovers}"


def test_span_lifecycle_balances():
    tr = Tracer()
    sp = EngineSpans(tr)
    assert sp.on
    sp.run_begin(0.0)
    sp.submitted(1, 0.001)
    sp.admitted(1, 0.002, mode="chunked")
    sp.chunk(1, 0.002, 0.003, tokens=16)
    sp.chunk(1, 0.003, 0.004, tokens=8)
    sp.first_token(1, 0.005)
    sp.preempted(1, 0.006, mode="swap")
    sp.swap(1, 0.006, 0.0065, "out", nbytes=1024)
    sp.resume_work(1, 0.007, 0.008, mode="swap")
    sp.resumed(1, 0.008)
    sp.token(1, 0.009)
    sp.finished(1, 0.010)
    sp.step(0.0, 0.01, phases=(("retire", 0.0, 0.001),
                               ("decode", 0.001, 0.01)), active=1)
    sp.snapshot({"pages_in_use": 3, "free_pages": 7,
                 "active": 1, "queued": 0, "swapped": 0}, 0.01)
    sp.run_end(0.011)
    evs = tr.events()
    json.dumps(evs)                     # serializable
    _check_balanced(evs)
    x_names = [e["name"] for e in evs if e["ph"] == "X"]
    assert "prefill_chunk[0]" in x_names and "prefill_chunk[1]" in x_names
    assert "swap_out" in x_names and "resume" in x_names
    inames = [e["name"] for e in evs if e["ph"] == "i"]
    assert inames.count("first_token") == 1 and "finished" in inames
    assert {e["name"] for e in evs if e["ph"] == "C"} == {"pool", "load"}


def test_run_end_closes_stragglers():
    tr = Tracer()
    sp = EngineSpans(tr)
    sp.run_begin(0.0)
    sp.submitted(0, 0.001)
    sp.admitted(1, 0.002)               # two requests left open
    sp.run_end(0.01)
    _check_balanced(tr.events())


def test_spans_noop_without_tracer():
    sp = EngineSpans(None)
    assert not sp.on
    sp.run_begin()
    sp.submitted(0)
    sp.chunk(0, 0.0, 1.0)
    sp.step(0.0, 1.0)
    sp.finished(0)
    sp.run_end()                        # nothing raises, nothing recorded


# ----------------------------------------------------------------------
# engine integration: one traced + one plain run per preemption mode
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    from repro.configs.base import get_reduced_config
    from repro.models.model import Model
    cfg = get_reduced_config("tinyllama-1.1b").replace(
        dtype=jnp.float32, remat=False)
    model = Model(cfg)
    params = model.init_params(KEY)
    return model, params


def _mk_reqs(model, seed=7, shared=True):
    """Ragged requests that preempt under a tight pool x 3 slots. With
    `shared`, an 8-token preamble gives prefix hits and CoW; without it
    every page is exclusively owned, so swap-policy preemptions really
    swap (the swap path refuses victims holding shared pages)."""
    rng = np.random.default_rng(seed)
    vocab = model.cfg.vocab_size
    pre = rng.integers(0, vocab, (8,))
    reqs = []
    for _ in range(5):
        tail = rng.integers(0, vocab, (int(rng.integers(2, 6)),))
        toks = np.concatenate([pre, tail]) if shared \
            else rng.integers(0, vocab, (8 + tail.size,))
        reqs.append(Request(toks.astype(np.int32),
                            int(rng.integers(6, 11))))
    return reqs


def _engine(model, mode, tel):
    cc = dataclasses.replace(
        CacheConfig.sparq_cache(SparqConfig.opt5(signed=True),
                                impl="reference"), attn_bk=PS)
    # 8 pages starves the swap workload enough to actually swap; the
    # shared-preamble requeue workload preempts at 10
    return ContinuousBatchingEngine(
        model, cc, page_size=PS, n_pages=10 if mode == "requeue" else 8,
        max_active=3, max_seq_len=MAX_SEQ_LEN,
        policy=SchedulerPolicy(preempt=mode, victim="last_joined"),
        prefill="chunked", chunk_size=16, chunk_align=4, chunk_seg=2,
        prefix_cache=True, telemetry=tel)


@pytest.fixture(scope="module")
def runs(tiny_lm):
    """Per preemption mode: a traced engine run twice (second run checks
    per-run stat freshness + tracer reset) and a telemetry-off run."""
    model, params = tiny_lm
    out = {}
    for mode in ("requeue", "swap"):
        reqs = _mk_reqs(model, shared=(mode == "requeue"))
        tel = Telemetry.tracing()
        eng = _engine(model, mode, tel)
        _, stats_first = eng.run(params, reqs)
        res, stats = eng.run(params, reqs)
        res0, stats0 = _engine(model, mode, None).run(params, reqs)
        out[mode] = dict(tel=tel, res=res, stats=stats,
                         stats_first=stats_first, res0=res0, stats0=stats0)
    return out


def test_bit_identity_on_vs_off(runs):
    for mode, r in runs.items():
        assert set(r["res"]) == set(r["res0"])
        for rid in r["res"]:
            np.testing.assert_array_equal(r["res"][rid], r["res0"][rid])
        assert r["stats"]["preemptions"] >= 1, \
            f"{mode}: workload must actually preempt"
        if mode == "swap":
            assert r["stats"]["preempt_swap"] >= 1


def test_stats_keys_and_values_match_registry(runs):
    for mode, r in runs.items():
        stats, stats0 = r["stats"], r["stats0"]
        # on/off runs expose the identical stats surface
        assert set(stats) == set(stats0)
        # the keys benchmarks consume are all still there
        assert {"decode_tok_s", "decode_steps", "prefill_chunks",
                "prefill_s", "resume_s", "preemptions", "preempt_requeue",
                "preempt_swap", "resumes", "replay_steps", "cancelled",
                "swap_bytes_out", "swap_bytes_in", "swap_peak_bytes",
                "peak_pages_used", "peak_pool_utilization", "pool_slots",
                "prefix_hits", "prefix_misses",
                "prefix_hit_tokens"} <= set(stats)
        # one source of truth: stats values ARE registry reads
        reg = r["tel"].registry
        assert stats["decode_steps"] == \
            reg.get("engine_decode_steps_total").total()
        assert stats["prefill_chunks"] == \
            reg.get("engine_prefill_chunks_total").total()
        assert stats["preempt_requeue"] == \
            reg.get("engine_preemptions_total").value(mode="requeue")
        assert stats["preempt_swap"] == \
            reg.get("engine_preemptions_total").value(mode="swap")
        assert stats["resumes"] == reg.get("engine_resumes_total").total()
        assert stats["replay_steps"] == \
            reg.get("engine_replay_steps_total").total()
        assert stats["cancelled"] == \
            reg.get("engine_cancelled_total").total()
        assert stats["swap_bytes_out"] == \
            reg.get("swap_bytes_total").value(dir="out")
        assert stats["swap_bytes_in"] == \
            reg.get("swap_bytes_total").value(dir="in")
        assert stats["peak_pages_used"] == \
            reg.get("pool_pages_peak").value()
        assert stats["prefix_hits"] == \
            reg.get("prefix_cache_hits_total").total()
        # chunked prefill observed its fill-ratio histogram per chunk
        fill = reg.get("prefill_chunk_fill_ratio").series()
        assert fill.count == stats["prefill_chunks"]
        assert all(0 < v <= 1.0 for v in fill.raw)


def test_second_run_reports_fresh_stats(runs):
    """The registry resets at run start: back-to-back runs of one warm
    engine must report per-run counts, not accumulate."""
    for r in runs.values():
        for k in ("decode_steps", "prefill_chunks", "preemptions",
                  "resumes", "swap_bytes_out", "total_tokens_served"):
            assert r["stats"][k] == r["stats_first"][k], k


def test_engine_trace_schema(runs):
    for r in runs.values():
        tel = r["tel"]
        blob = json.loads(json.dumps(export.trace_json(tel.tracer)))
        assert set(blob) == {"traceEvents", "displayTimeUnit"}
        evs = blob["traceEvents"]
        _check_balanced(evs)
        # the tracer reset at run start: exactly one run in the buffer
        run_marks = [e["name"] for e in evs if e["ph"] == "i"
                     and e["tid"] == 0 and e["name"].startswith("run_")]
        assert run_marks.count("run_begin") == 1
        assert run_marks.count("run_end") == 1
        steps = [e for e in evs if e["ph"] == "X" and e["tid"] == 0
                 and e["name"].startswith("step[")]
        assert steps and steps[0]["name"] == "step[0]"
        phase_names = {e["name"] for e in evs
                       if e["ph"] == "X" and e["tid"] == 0
                       and not e["name"].startswith("step[")}
        assert {"retire", "admit", "prefill", "decode"} <= phase_names
        # request span set == emitted requests, each with a full arc
        rid_tids = {e["tid"] for e in evs
                    if e["ph"] in ("B", "E", "X", "i")
                    and e["tid"] not in (SCHED_TID, RUNTIME_TID)}
        assert rid_tids == {rid + 1 for rid in r["res"]}
        for rid in r["res"]:
            names = [e.get("name") for e in evs if e["tid"] == rid + 1]
            assert "queued" in names and "first_token" in names
            assert "finished" in names


def test_engine_prometheus_dump(runs):
    for r in runs.items():
        mode, r = r
        reg = r["tel"].registry
        parsed = export.parse_prometheus(export.prometheus_text(reg))
        assert parsed[("engine_decode_steps_total", "")] == \
            r["stats"]["decode_steps"]
        assert parsed[("engine_step_phase_seconds_count",
                       'phase="decode"')] > 0
        if mode == "swap":
            assert parsed[("swap_bytes_total", 'dir="out"')] == \
                r["stats"]["swap_bytes_out"]


# ----------------------------------------------------------------------
# hand-off spans and the runtime track: one streaming (emit) run with
# chunked prefill and preemption, traced, and the same run untraced
# ----------------------------------------------------------------------

#: the phase span each hand-off nests in
HANDOFF_PHASE = {"chunk.plan": ("prefill",), "chunk.dispatch": ("prefill",),
                 "chunk.wait": ("prefill",), "chunk.emit": ("prefill",),
                 "pages.table": ("prefill", "decode"),
                 "pages.grow": ("decode",), "pages.check": ("decode",),
                 "trace.snapshot": ("decode",), "step.dispatch": ("decode",),
                 "step.fetch": ("decode",), "step.emit": ("decode",)}
EPS_US = 1e-3                   # float rounding of the relative stamps


def _emit_collecting(first):
    """An emit callback that forces one full collection at the first
    token, so the run's runtime track holds a gc span."""
    def emit(rid, tok, final, t):
        if not first:
            first.append(rid)
            gc.collect()
    return emit


@pytest.fixture(scope="module")
def streamed(tiny_lm):
    model, params = tiny_lm
    reqs = _mk_reqs(model, shared=True)
    tel = Telemetry.tracing()
    eng = _engine(model, "requeue", tel)
    res, stats = eng.run(params, reqs, emit=_emit_collecting([]))
    return dict(tel=tel, res=res, stats=stats, evs=tel.tracer.events(),
                model=model, params=params, reqs=reqs)


def _iterations(evs):
    """Per scheduler step: (step event, {phase: event}, [hand-offs])."""
    sched = sorted((e for e in evs if e["ph"] == "X"
                    and e["tid"] == SCHED_TID), key=lambda e: e["ts"])
    steps = [e for e in sched if e["name"].startswith("step[")]
    out = []
    for st in steps:
        a, b = st["ts"] - EPS_US, st["ts"] + st["dur"] + EPS_US
        inner = [e for e in sched if e is not st
                 and a <= e["ts"] and e["ts"] + e["dur"] <= b]
        phases = {e["name"]: e for e in inner
                  if e["name"] in ("retire", "admit", "prefill", "decode")}
        hand = [e for e in inner if e["name"] in EngineSpans.HANDOFFS]
        out.append((st, phases, hand))
    return out


def test_streamed_run_chunks_and_preempts(streamed):
    st = streamed["stats"]
    assert st["prefill_chunks"] >= 2 and st["preemptions"] >= 1
    names = {e["name"] for e in streamed["evs"] if e["ph"] == "X"}
    assert set(EngineSpans.HANDOFFS) <= names


def test_handoff_spans_nest_in_their_phase(streamed):
    evs = streamed["evs"]
    n = sum(1 for e in evs if e["ph"] == "X"
            and e["name"] in EngineSpans.HANDOFFS)
    seen = 0
    for _, phases, hand in _iterations(evs):
        for h in hand:
            ok = [p for p in HANDOFF_PHASE[h["name"]] if p in phases
                  and phases[p]["ts"] - EPS_US <= h["ts"]
                  and h["ts"] + h["dur"]
                  <= phases[p]["ts"] + phases[p]["dur"] + EPS_US]
            assert ok, (h, phases)
            seen += 1
    assert seen == n            # every hand-off lies in some iteration


def test_handoff_spans_of_an_iteration_do_not_overlap(streamed):
    for _, _, hand in _iterations(streamed["evs"]):
        hand = sorted(hand, key=lambda e: e["ts"])
        for a, b in zip(hand, hand[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + EPS_US, (a, b)


def test_each_dispatch_is_followed_by_its_sync(streamed):
    sync = {"chunk.dispatch": "chunk.wait", "step.dispatch": "step.fetch"}
    n = 0
    for _, _, hand in _iterations(streamed["evs"]):
        hand = sorted(hand, key=lambda e: e["ts"])
        for a, b in zip(hand, hand[1:] + [None]):
            if a["name"] in sync:
                assert b is not None and b["name"] == sync[a["name"]]
                assert abs(a["ts"] + a["dur"] - b["ts"]) <= EPS_US
                n += 1
    assert n > 0


def test_dispatch_args(streamed):
    for _, _, hand in _iterations(streamed["evs"]):
        for h in hand:
            if h["name"] == "chunk.dispatch":
                args = h["args"]
                assert args["tokens"] == sum(t for _, t in args["seqs"])
                assert 0 <= args["completed"] <= len(args["seqs"])
            elif h["name"] == "step.dispatch":
                assert h["args"]["rows"] == len(h["args"]["ctx"])
            elif h["name"] == "pages.grow":
                assert h["args"]["pages"] >= 0


def test_streamed_trace_balances(streamed):
    evs = json.loads(json.dumps(streamed["evs"]))
    _check_balanced(evs)
    rt = [e for e in evs if e["tid"] == RUNTIME_TID]
    assert {"name": "thread_name", "ph": "M", "pid": 0, "tid": RUNTIME_TID,
            "args": {"name": "runtime"}} in rt
    gcs = [e for e in rt if e["name"] == "gc"]
    assert any(e["args"]["generation"] == 2 for e in gcs)
    assert all(set(e["args"]) == {"generation", "collected"} for e in gcs)
    # the engine's first run compiles its programs
    assert any(e["name"] == "compile" and e["ph"] == "X" for e in rt)


def test_runtime_hooks_gone_after_run_end(streamed):
    sp = streamed["tel"].spans
    assert sp._on_gc not in gc.callbacks
    n = len(streamed["tel"].tracer)
    gc.collect()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    assert len(streamed["tel"].tracer) == n
    with pytest.raises(AssertionError):     # not registered any more
        jax.monitoring.unregister_event_duration_listener(sp._on_duration)


def test_runtime_hooks_gone_after_a_failed_run(tiny_lm):
    model, params = tiny_lm
    tel = Telemetry.tracing()
    eng = _engine(model, "requeue", tel)
    n_cb = len(gc.callbacks)

    def boom(*a):
        raise RuntimeError("emit failed")
    with pytest.raises(RuntimeError):
        eng.run(params, _mk_reqs(model), emit=boom)
    assert len(gc.callbacks) == n_cb
    assert tel.spans._on_gc not in gc.callbacks


def test_untraced_loop_reads_the_clock_as_before(streamed, monkeypatch):
    """At the default level the loop takes no new clock reads: per run
    2, per decode step 1, per chunk 3, per chunked resume 2 (the count
    the loop had before the hand-off spans)."""
    reads = []
    shim = types.SimpleNamespace(
        **{k: getattr(time, k) for k in dir(time) if not k.startswith("_")})
    shim.perf_counter = lambda: reads.append(1) or time.perf_counter()
    eng = _engine(streamed["model"], "requeue", Telemetry())
    monkeypatch.setattr(serve, "time", shim)
    res, st = eng.run(streamed["params"], streamed["reqs"],
                      emit=lambda *a: None)
    assert st["prefill_chunks"] >= 2 and st["resumes"] >= 1
    assert len(reads) == 2 + st["decode_steps"] + 3 * st["prefill_chunks"] \
        + 2 * st["resumes"]
    for rid in res:
        np.testing.assert_array_equal(res[rid], streamed["res"][rid])

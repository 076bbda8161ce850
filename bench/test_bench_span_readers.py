"""The readers of the engine's hand-off spans, on a hand-made observation
and on a CPU-size engine run, and the idle split of `bench/idle_split.py`."""
import numpy as np
import pytest

from bench import cell, idle_split, loadgen, observe, tiny, trace

LAUNCH = "sched.launch_gap_ms_per_iter.offline"
CACHE = "cache.host_ms_per_iter.offline"


def _x(name, a, b, **args):
    """A scheduler span from host seconds (span origin 1.0)."""
    ev = {"ph": "X", "pid": 0, "tid": 0, "name": name,
          "ts": (a - 1.0) * 1e6, "dur": (b - a) * 1e6}
    if args:
        ev["args"] = args
    return ev


def _iteration(i, a, b, chunk=None, step=(), fetch=True):
    """step[i] over [a, b): a prefill phase with an optional chunk
    (plan, table, dispatch, wait, emit stamps), then a decode phase with
    page growth, accounting, and the step (dispatch, fetch, emit)."""
    g0, g1, c1, d0, d1, f1 = step
    evs = [_x(f"step[{i}]", a, b), _x("retire", a, a), _x("admit", a, a),
           _x("prefill", a, g0), _x("decode", g0, b)]
    if chunk:
        p0, t0, c0, w0, w1, e1 = chunk
        evs += [_x("chunk.plan", p0, t0), _x("pages.table", t0, c0),
                _x("chunk.dispatch", c0, w0), _x("chunk.wait", w0, w1),
                _x("chunk.emit", w1, e1)]
    evs += [_x("pages.grow", g0, g1), _x("pages.table", g1, c1),
            _x("pages.check", c1, d0), _x("step.dispatch", d0, d1)]
    if fetch:
        evs += [_x("step.fetch", d1, f1)]
    return evs + [_x("step.emit", f1, b)]


def _spans():
    # before the window: its fetch ends at 0.95
    evs = _iteration(0, 0.5, 1.0, step=(0.6, 0.6, 0.6, 0.61, 0.62, 0.95))
    # in the window, with a chunk: launch gaps 1.13 - 1.00 (since the
    # last fetch) and 1.38 - 1.30 (since the chunk's wait); pages 0.03
    evs += _iteration(1, 1.05, 1.50,
                      chunk=(1.05, 1.10, 1.11, 1.13, 1.30, 1.31),
                      step=(1.31, 1.32, 1.33, 1.35, 1.38, 1.46))
    # in the window, no chunk: launch gap 1.60 - 1.46; pages 0.05
    evs += _iteration(2, 1.50, 1.90, step=(1.50, 1.52, 1.54, 1.55, 1.60,
                                           1.85))
    # straddles the window's end (3.0): not counted
    evs += _iteration(3, 2.9, 3.3, chunk=(2.9, 2.92, 2.93, 2.95, 3.1, 3.12),
                      step=(3.12, 3.13, 3.14, 3.15, 3.16, 3.2))
    return evs


def _obs(spans):
    red = trace.Reduced(1, 0, int(2e9), 1.5e9, {}, {}, [], [], 0.0)
    return observe.Observation(
        sizes={}, settings={}, peaks={}, trace=red,
        recorder=observe.Recorder(), window=(1.0, 3.0), phases={},
        spans=spans, span_origin=1.0)


def _read(name, spans):
    return observe.load_module("metrics", name).read(_obs(spans))


def test_launch_gap_per_iteration():
    gaps = (1.13 - 0.95) + (1.38 - 1.30) + (1.60 - 1.46)
    assert _read(LAUNCH, _spans()) == pytest.approx(1e3 * gaps / 2)


def test_launch_gap_counts_no_dispatch_with_work_still_queued():
    """Batch mode: no fetch after a step, so the next dispatch finds the
    device with the step still queued and adds nothing."""
    evs = _iteration(0, 1.05, 1.50, chunk=(1.05, 1.10, 1.11, 1.13, 1.30,
                                           1.31),
                     step=(1.31, 1.32, 1.33, 1.35, 1.38, 1.38), fetch=False)
    evs += _iteration(1, 1.50, 1.90, step=(1.50, 1.52, 1.54, 1.55, 1.60,
                                           1.60), fetch=False)
    assert _read(LAUNCH, evs) == pytest.approx(1e3 * (1.38 - 1.30) / 2)


def test_cache_host_time_per_iteration():
    pages = (1.35 - 1.31) + (1.11 - 1.10) + (1.55 - 1.50)
    assert _read(CACHE, _spans()) == pytest.approx(1e3 * pages / 2)


def test_readers_without_handoff_spans_give_nothing():
    phases_only = [e for e in _spans()
                   if e["name"].startswith("step[") or e["name"] in
                   ("retire", "admit", "prefill", "decode")]
    straddling = _iteration(0, 2.9, 3.3, step=(3.0, 3.1, 3.1, 3.1, 3.2,
                                               3.25))
    for name in (LAUNCH, CACHE):
        assert _read(name, phases_only) is None, name
        assert _read(name, []) is None, name
        assert _read(name, straddling) is None, name


def test_idle_split_names_each_gap_by_its_innermost_span():
    evs = _spans() + [{"ph": "X", "pid": 0, "tid": 2 ** 31 - 1,
                       "name": "gc", "ts": 0.7e6, "dur": 0.02e6,
                       "args": {"generation": 2, "collected": 0}}]
    ns = 1e9          # profile ns = host seconds * 1e9 (offset 0)
    gaps = [(int(1.31 * ns), int(1.36 * ns)),     # in pages.* / dispatch
            (int(1.46 * ns), int(1.60 * ns)),     # mid 1.53: pages.table
            (int(1.69 * ns), int(1.73 * ns)),     # mid 1.71: in the gc
            (int(2.0 * ns), int(2.4 * ns))]       # between iterations
    red = trace.Reduced(1, int(1.0 * ns), int(3.0 * ns), 1.4e9, {}, {}, [],
                        gaps, 0.0)
    obs = _obs(evs)
    out = idle_split.split(red, obs, {LAUNCH: {"value": 10.0, "unit": "ms"},
                                      "x": {"value": 1.0, "unit": "%"}})
    by = {n: ms for n, ms, _ in out["by_span"]}
    assert out["iterations"] == 2
    assert by == pytest.approx({"pages.check": 25.0, "pages.table": 70.0,
                                "gc": 20.0, "none": 200.0})
    assert sum(share for _, _, share in out["by_span"]) == \
        pytest.approx(100.0)
    assert out["by_span"][0][0] == "none"
    assert out["runtime"] == [["gc", pytest.approx(0.7), pytest.approx(20.0),
                               {"generation": 2, "collected": 0}]]
    assert out["ms_per_iter_share"] == {LAUNCH: pytest.approx(1.0)}
    host = out["host_ms_per_iter"]
    assert host["pages.table"] == pytest.approx(1e3 * (0.01 + 0.01 + 0.02)
                                                / 2)
    assert "step[1]" not in host and "gc" not in host


def test_dispatch_args_match_the_recorder():
    """On a CPU-size engine: each `step.dispatch` carries the context
    lengths, and each `chunk.dispatch` the (history, tokens) runs, that
    the benchmark's recorder takes for the same dispatch."""
    spec = tiny.spec("starcoder2-3b.decode-4k")
    sess = cell.setup(spec, trace=True, seconds=4.0, log=lambda m: None)
    try:
        from repro.launch.serve import Request
        traffic = loadgen.generate(spec.mix, 2 ** 31 + 5,
                                   spec.conf["vocab_size"])
        reqs = [Request(np.asarray(r.tokens, np.int32), r.gen)
                for r in traffic.requests[:8]]
        rec = sess.tracer.recorder
        sess.engine.run(sess.params, reqs, trace_hook=rec.hook,
                        emit=lambda *a: None)
        evs = sess.engine.telemetry.tracer.events()
        origin = sess.engine.telemetry.tracer._origin
    finally:
        import shutil
        shutil.rmtree(sess.trace_dir, ignore_errors=True)

    def spans(name):
        return [(origin + e["ts"] * 1e-6,
                 origin + (e["ts"] + e["dur"]) * 1e-6, e["args"])
                for e in evs if e["ph"] == "X" and e["name"] == name]
    steps = spans("step.dispatch")
    assert len(steps) == len(rec.steps) > 0
    for (a, _, args), st in zip(steps, rec.steps):
        assert st.t <= a and args["ctx"] == st.ctx
        assert args["rows"] == len(st.ctx)
    chunks = spans("chunk.dispatch")
    assert chunks
    for a, b, args in chunks:
        mine = [c for c in rec.chunks if a <= c.t <= b]
        assert len(mine) == 1
        assert args["seqs"] == [list(s) for s in mine[0].seqs]
        assert args["tokens"] == len(mine[0].pos)
        assert args["completed"] == mine[0].completed

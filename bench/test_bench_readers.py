"""Each per-layer reader on a hand-made observation."""
import numpy as np
import pytest

from bench import observe, trace
from bench.costs import chunked_prefill_attn, model_step, paged_decode_attn
from bench.costs import sparq_matmul

S = {"layers": 2, "d": 8, "heads": 4, "kv_heads": 2, "head_dim": 2,
     "ff": 16, "vocab": 10, "mlp": "swiglu"}
PEAKS = {"bf16_flops": 1e6, "int8_ops": 2e6, "hbm_bytes_per_s": 1e5,
         "hbm_bytes": 1e9}


def _obs(**kw):
    red = trace.Reduced(
        devices=1, t0=0, t1=int(2e9), busy_ns=1.5e9, op_ns={},
        kernel_ns={("step", "sparq_matmul"): 0.2e9,
                   ("chunk", "sparq_matmul"): 0.1e9,
                   ("step", "sparq_paged_decode_attn"): 0.05e9,
                   ("chunk", "sparq_chunked_prefill_attn"): 0.04e9},
        modules=[trace.Module("jit__step_fn", 0, 10),
                 trace.Module("jit__step_fn", 20, 30),
                 trace.Module("jit__chunk_fn", 40, 100)],
        gaps=[], host_offset_ns=0.0)
    rec = observe.Recorder()
    rec.steps = [observe.Step(1.0, [3, 5]), observe.Step(1.5, [4, 6]),
                 observe.Step(9.0, [1])]          # the last lies outside
    rec.chunks = [observe.Chunk(1.2, np.array([4, 5, 6]), [(4, 3)], 1)]
    spans = [{"ph": "X", "tid": 0, "name": "admit", "ts": 0.0,
              "dur": 2000.0}]
    base = dict(sizes=S, settings={}, peaks=PEAKS, trace=red, recorder=rec,
                window=(1.0, 3.0),
                phases={"retire": [0.001, 0.003], "admit": [0.002, 0.002]},
                spans=spans, span_origin=1.0)
    base.update(kw)
    return observe.Observation(**base)


def _read(name, obs):
    return observe.load_module("metrics", name).read(obs)


def test_idle_share():
    assert _read("device.idle_share.offline", _obs()) == pytest.approx(25.0)


def test_mfu_offline():
    flops = model_step.decode_step(S, [3, 5]) + \
        model_step.decode_step(S, [4, 6]) + \
        model_step.chunk(S, [4, 5, 6], 1)
    assert _read("mfu_int8.offline", _obs()) == \
        pytest.approx(100 * flops / (2.0 * 2e6))


def test_sparq_matmul_roofline():
    def least(m):
        t = 0.0
        for k, n in sparq_matmul.layer_shapes(S):
            o, b = sparq_matmul.call(m, k, n)
            t += max(o / 2e6, b / 1e5)
        return t * 2
    # two step modules from two host steps of 2 rows; one chunk of 3 rows
    need = 2 * least(2) + least(3)
    assert _read("kern.sparq_matmul_roofline.offline", _obs()) == \
        pytest.approx(100 * need / 0.3)


def test_paged_decode_attn_roofline():
    per = []
    for ctx in ([3, 5], [4, 6]):
        o, b = paged_decode_attn.step(S, ctx)
        per.append(2 * max(o / 2 / 1e6, b / 2 / 1e5))
    assert _read("kern.paged_decode_attn_roofline.offline", _obs()) == \
        pytest.approx(100 * np.mean(per) * 2 / 0.05)


def test_chunked_prefill_attn_roofline():
    o, b = chunked_prefill_attn.chunk(S, [4, 5, 6], [(4, 3)])
    least = 2 * max(o / 2 / 1e6, b / 2 / 1e5)
    assert _read("kern.chunked_prefill_attn_roofline.offline", _obs()) == \
        pytest.approx(100 * least / 0.04)


def test_host_ms_per_iteration():
    assert _read("sched.host_ms_per_iter.offline", _obs()) == \
        pytest.approx(4.0)


def test_nothing_to_read_gives_nothing():
    red = trace.Reduced(1, 0, 0, 0.0, {}, {}, [], [], 0.0)
    empty = _obs(trace=red, recorder=observe.Recorder(), spans=[],
                 phases={})
    for name in ("device.idle_share.offline", "mfu_int8.offline",
                 "kern.sparq_matmul_roofline.offline",
                 "kern.paged_decode_attn_roofline.offline",
                 "sched.host_ms_per_iter.offline",
                 "kern.chunked_prefill_attn_roofline.offline"):
        assert _read(name, empty) is None, name

"""The trace reduction, on a hand-made trace whose numbers are known and
on a small excerpt recorded on a TPU v5e."""
import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "testdata"


def _ev(meta, off_ns, dur_ns, stat=None):
    s = f" stats {{ metadata_id: 1 str_value: \"{stat}\" }}" if stat else ""
    return (f"events {{ metadata_id: {meta} offset_ps: {off_ns * 1000} "
            f"duration_ps: {dur_ns * 1000}{s} }}")


def _hand_made(tmp_path):
    ops = [_ev(1, 100, 50, "jit(_step_fn)/jit(sparq_matmul_pallas)/pallas"),
           _ev(2, 160, 30), _ev(3, 300, 100,
                                "jit(sparq_chunked_prefill_attn_pallas)/p"),
           _ev(2, 420, 20), _ev(2, 900, 50)]
    mods = [_ev(4, 100, 100), _ev(5, 300, 150)]
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                   f"\"{n}\" }} }} " for i, n in
                   [(1, "custom-call.1"), (2, "fusion.12"),
                    (3, "custom-call.7"), (4, "jit__step_fn(3)"),
                    (5, "jit__chunk_fn(9)")])
    text = (
        "planes { id: 1 name: \"/device:TPU:0\" "
        f"lines {{ id: 1 name: \"XLA Ops\" timestamp_ns: 0 {' '.join(ops)} }} "
        f"lines {{ id: 2 name: \"XLA Modules\" timestamp_ns: 0 "
        f"{' '.join(mods)} }} {meta}"
        "stat_metadata { key: 1 value { id: 1 name: \"tf_op\" } } } "
        "planes { id: 2 name: \"/host:CPU\" lines { id: 1 name: \"python\" "
        "timestamp_ns: 0 events { metadata_id: 1 offset_ps: 50000 "
        "duration_ps: 1000 } } event_metadata { key: 1 value { id: 1 "
        f"name: \"{trace.SYNC}\" }} }} }}")
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_hand_made_trace(tmp_path):
    path = _hand_made(tmp_path)
    # host clock: the sync annotation opened at perf_counter 10.0 s, which
    # is 50 ns on the profile clock; the window is [10 s, 10 s + 800 ns)
    red = trace.reduce(str(path), 10.0, (10.0, 10.0 + 800e-9))
    assert red.t0 == 50 and red.t1 == 850
    assert red.devices == 1
    # ops inside: [100,150] [160,190] [300,400] [420,440]; the op at 900
    # lies past the window
    assert red.busy_ns == 50 + 30 + 100 + 20
    assert red.window_s == pytest.approx(800e-9)
    assert red.kernel_ns == {("step", "sparq_matmul"): 50.0,
                             ("chunk", "sparq_chunked_prefill_attn"): 100.0}
    assert red.module_count("step") == 1 and red.module_count("chunk") == 1
    assert red.module_kind_ns("chunk") == 150
    assert red.op_ns["step:sparq_matmul_pallas"] == 50
    assert red.op_ns["chunk:fusion"] == 20
    assert red.gaps == [(50, 100), (150, 160), (190, 300), (400, 420),
                        (440, 850)]
    assert red.to_host(50) == pytest.approx(10.0)


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_module_kinds_and_op_groups():
    assert trace.module_kind("jit__step_fn(12)") == "step"
    assert trace.module_kind("jit__chunk_fn") == "chunk"
    assert trace.module_kind("jit_evict_slot") == "other"
    assert trace.op_group("fusion.123") == "fusion"
    assert trace.op_group("copy") == "copy"
    assert trace.op_group("%copy-start.1 = (bf16[2]) copy-start(x)") == \
        "copy-start"
    assert trace.op_group("%dynamic-slice_bitcast_fusion.30 = s8[3] "
                          "fusion(y)") == "dynamic-slice_bitcast_fusion"


def _raw(path):
    """The excerpt's device ops and modules, read without bench.trace."""
    ops, mods = [], []
    for p in ProfileData.from_file(str(path)).planes:
        if p.name != "/device:TPU:0":
            continue
        for line in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if line.name == "XLA Ops":
                ops = evs
            elif line.name == "XLA Modules":
                mods = evs
    return ops, mods


def test_recorded_v5e_decode_step():
    """One decode step of `starcoder2-3b.decode-4k` (32 slots, 30 layers)
    recorded on a TPU v5e: the step program, its 30 paged-attention and
    180 quantized-matmul kernel calls, and the device's busy time."""
    path = DATA / "v5e_decode_step.xplane.pb"
    red = trace.reduce(str(path))
    ops, mods = _raw(path)
    assert len(mods) == 1 and "_step_fn" in mods[0][0]
    assert red.module_count("step") == 1
    assert red.module_kind_ns("step") == pytest.approx(mods[0][2] -
                                                        mods[0][1])
    for kernel, calls in (("sparq_paged_decode_attn", 30),
                          ("sparq_matmul", 180)):
        mine = [(s, e) for n, s, e in ops
                if n.startswith(f"%{kernel}_pallas")]
        assert len(mine) == calls
        assert red.kernel_ns[("step", kernel)] == \
            pytest.approx(sum(e - s for s, e in mine))
    # busy: the union of every op interval, by a plain sweep
    iv = sorted((s, e) for _, s, e in ops)
    busy, end = 0.0, None
    for s, e in iv:
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert red.busy_ns == pytest.approx(busy)
    assert 0.9 < red.busy_s / red.window_s <= 1.0
    # the capture's clock-sync annotation is found on the host plane
    assert trace.host_offset(ProfileData.from_file(str(path)), 1.0) \
        is not None

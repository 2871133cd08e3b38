"""The reduction from a profiler trace to busy time, idle gaps and kernel
times, on a hand-made trace and on a small trace recorded on the chip."""

import json

import pytest

from chipbench_fixtures import CHIP

import tracing  # noqa: E402

DATA = CHIP / "tests" / "data"


def test_union():
    assert tracing.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_op_and_kernel_names_from_hlo_text():
    assert tracing.op_name("%fusion.190 = bf16[16,64]{1,0} fusion(x)") \
        == "fusion"
    text = ("%nm_spmm_decode.47 = f32[32,2816]{1,0:T(8,128)S(1)} "
            "custom-call(bf16[32,1024]{1,0} %fusion.220)")
    assert tracing.kernel_name(text, []) == "nm_spmm_decode"
    alloc = ("%custom-call.18 = bf16[24,16]{1,0} custom-call(), "
             "custom_call_target=\"AllocateBuffer\"")
    assert tracing.kernel_name(alloc, []) is None


def test_kernel_name_from_op_metadata():
    stats = [("long_name", "custom-call.3 = ... op_name=\"jit(loop)/"
              "jit(paged_attn)/pallas_call\""), ("flops", 12)]
    assert tracing.kernel_name("custom-call.3", stats) == "paged_attn"
    assert tracing.kernel_name("fusion.12", [("x", "y")]) is None


def test_reduce_hand_made():
    dev = {"/device:TPU:0": [
        ("fusion.1", 0, 10, None),
        ("custom-call.2", 5, 15, "paged_attn"),
        ("fusion.3", 20, 30, None)]}
    host = [(tracing.WINDOW_SPAN, 0, 40, "t"), ("sync", 31, 39, "worker"),
            ("schedule", 16, 19, "worker")]
    r = tracing.reduce_events(dev, host)
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["kernel_s"] == {"paged_attn": pytest.approx(10e-9)}
    assert r["kernel_calls"] == {"paged_attn": 1}
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["worker: sync", pytest.approx(10e-9)]
    assert gaps[1] == ["worker: schedule", pytest.approx(5e-9)]
    assert r["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(20e-9)]


def test_reduce_clips_to_window_and_averages_chips():
    dev = {"/device:TPU:0": [("a", 0, 100, None)],
           "/device:TPU:1": [("a", 50, 60, None)]}
    r = tracing.reduce_events(dev, [], span=(40, 80))
    assert r["busy_s"] == pytest.approx((40 + 10) / 2 * 1e-9)


def _brute_busy(ops, w0, w1):
    """Busy time by marking every covered nanosecond step (slow, plain)."""
    pts = sorted({max(w0, min(w1, x)) for _, s, e, _ in ops for x in (s, e)}
                 | {w0, w1})
    busy = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for _, s, e, _ in ops):
            busy += b - a
    return busy


def test_recorded_chip_trace():
    rec = json.loads((DATA / "trace_events.json").read_text())
    dev = {k: [(n, s, e, tracing.kernel_name(n, [])) for n, s, e in v]
           for k, v in rec["device"].items()}
    host = [tuple(e) for e in rec["host"]]
    r = tracing.reduce_events(dev, host)
    (w0, w1), = [(s, e) for n, s, e, _ in host if n == tracing.WINDOW_SPAN]
    want = sum(_brute_busy(ops, w0, w1) for ops in dev.values()) / len(dev)
    assert r["busy_s"] == pytest.approx(want * 1e-9, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert set(r["kernel_s"]) == set(rec["kernels"])
    for k, v in r["kernel_s"].items():
        direct = sum(min(e, w1) - max(s, w0) for ops in dev.values()
                     for _, s, e, kk in ops if kk == k and e > w0 and s < w1)
        assert v == pytest.approx(direct / len(dev) * 1e-9)

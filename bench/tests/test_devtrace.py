"""Trace reduction on a hand-made trace and on a small recorded one."""
import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).parent / "data"


def _events():
    ms = 1e6
    return {
        "devices": {"/device:TPU:0": [
            ("fusion.1 [jit_step]", 10 * ms, 30 * ms),
            ("fusion.2 [jit_step]", 20 * ms, 40 * ms),
            ("custom-call.3 [jit_step]", 60 * ms, 90 * ms),
            ("fusion.1 [jit_step]", 95 * ms, 130 * ms)]},
        "host": [("bench.traced", 0, 100 * ms),
                 ("bench.step", 0, 100 * ms),
                 ("bench.decode_dispatch", 40 * ms, 60 * ms)]}


def test_reduce_busy_idle_and_gaps():
    r = devtrace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [10, 40] and [60, 90] and [95, 100] inside the window
    assert r["busy_s"] == pytest.approx(0.065)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.decode_dispatch"] == pytest.approx(0.02)
    assert gaps["bench.step"] == pytest.approx(0.015)
    ops = dict(r["device_ops"])
    assert ops["custom-call.3 [jit_step]"] == pytest.approx(0.03)
    assert ops["fusion.1 [jit_step]"] == pytest.approx(0.025)
    assert devtrace.seconds_matching(r["op_s"], "custom") == \
        pytest.approx(0.03)


def test_reduce_without_window_or_device_gives_nothing():
    ev = _events()
    assert devtrace.reduce({"devices": ev["devices"], "host": []}) is None
    assert devtrace.reduce({"devices": {}, "host": ev["host"]}) is None


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                                 (5, 9)]


def test_op_label_and_containers():
    assert devtrace.op_label(
        "%dequant_matmul_packed_pallas.40 = f32[128,16384]{1,0} custom-call("
        "bf16[128,2,2048] %reshape.381), custom_call_target=\"tpu_custom_"
        "call\"") == "dequant_matmul_packed_pallas"
    assert devtrace.op_label("%while.2 = (s32[]) while(%tuple.41)") == "while"
    assert devtrace.op_label("%fusion = bf16[16,4096] fusion()") == "fusion"
    ev = {"devices": {"/device:TPU:0": [("while", 0, 100), ("fusion", 10, 30),
                                        ("fusion", 40, 90)]},
          "host": [("bench.traced", 0, 100)]}
    r = devtrace.reduce(ev)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert dict(r["device_ops"]) == {"fusion": pytest.approx(70e-9)}


def test_reduce_recorded_tpu_trace():
    """70 ms of a traced minitron-8b decode window on one TPU v5e: the
    packed kernel is the largest operation, and the idle time is the
    host's step between decode dispatches."""
    ev = json.loads((DATA / "tpu_trace_events.json").read_text())
    r = devtrace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.07)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"][0][0] == "dequant_matmul_packed_pallas"
    assert all(name != "while" for name, _ in r["device_ops"])
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["idle_gaps"][0][0] == "bench.step"
    kernel = devtrace.seconds_matching(r["op_s"],
                                       "dequant_matmul_packed_pallas")
    assert 0.5 < kernel / r["busy_s"] < 1.0

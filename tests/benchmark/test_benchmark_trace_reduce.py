"""The trace reduction on a hand-made timeline and on a trace recorded on the
chip (``benchmark/tools/record_trace.py`` on one TPU v5e, PR 22)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu_v5e_1chip.xplane.pb")
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MS = 1e6  # the trace's clock is in nanoseconds


def timeline():
    """Two steps on two devices. Device 0: compute 0-40 ms, an all-reduce
    30-50 (10 ms of it after the compute ended: exposed), idle 50-70 while
    the host waits for data, then the second step 70-110 with its all-reduce
    hidden under compute. Device 1 is busy 0-100 throughout."""
    ev = [
        (D0, tr.MODULES_LINE, "jit_train_step(1)", 0 * MS, 50 * MS),
        (D0, tr.OPS_LINE, "%fusion.1 = bf16[8,8]{1,0} fusion(...), kind=kLoop", 0 * MS, 40 * MS),
        (D0, tr.OPS_LINE, "%all-reduce.7 = f32[16]{0} all-reduce(...)", 30 * MS, 20 * MS),
        (D0, tr.MODULES_LINE, "jit_train_step(1)", 70 * MS, 40 * MS),
        (D0, tr.OPS_LINE, "%fusion.1 = bf16[8,8]{1,0} fusion(f32[16]{0} %all-reduce.7), kind=kLoop",
         70 * MS, 40 * MS),  # reads the all-reduce's result: compute all the same
        (D0, tr.OPS_LINE, "%all-reduce.7 = f32[16]{0} all-reduce(...)", 80 * MS, 10 * MS),
        (D0, tr.MODULES_LINE, "jit_tiny(2)", 50 * MS, 1 * MS),
        (D1, tr.OPS_LINE, "%fusion.1 = bf16[8,8]{1,0} fusion(...), kind=kLoop", 0 * MS, 100 * MS),
        (HOST, "python", "bench:train", 0 * MS, 52 * MS),
        (HOST, "python", "bench:data_next", 52 * MS, 17 * MS),
        (HOST, "python", "bench:train", 69 * MS, 41 * MS),
    ]
    return ev


def test_busy_and_idle_over_devices():
    b = tr.busy(timeline())
    assert b["window_s"] == pytest.approx(0.110)
    assert b["per_device_busy_s"][D0] == pytest.approx(0.090)  # 0-50 and 70-110
    assert b["per_device_busy_s"][D1] == pytest.approx(0.100)
    assert b["busy_s"] == pytest.approx(0.095)
    assert tr.idle_pct(timeline()) == pytest.approx(100 * (1 - 0.095 / 0.110))
    clipped = tr.busy(timeline(), window=(20 * MS, 60 * MS))
    assert clipped["per_device_busy_s"][D0] == pytest.approx(0.030)


def test_gaps_go_to_the_span_the_host_had_open():
    gaps = dict(tr.idle_gaps(timeline()))
    # device 0 idles 50-70 ms: 17 ms of it under data_next, 2 under train
    assert list(gaps) == ["data_next"] and gaps["data_next"] == pytest.approx(0.020)
    no_spans = [e for e in timeline() if e[0] != HOST]
    assert dict(tr.idle_gaps(no_spans)) == {"no_span": pytest.approx(0.020)}


def test_per_step_is_per_run_of_the_largest_program():
    steps = tr.per_step(timeline())
    assert len(steps) == 2  # the tiny program in between is not a step
    first, second = steps
    # an all-reduce that outlasts the compute keeps the device busy all the same
    assert first["busy_s"] == pytest.approx(0.050) and second["busy_s"] == pytest.approx(0.040)
    assert first["module_s"] == pytest.approx(0.050) and second["module_s"] == pytest.approx(0.040)
    assert tr.largest_module(timeline(), D0) == "jit_train_step"


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6


def test_top_ops_and_names():
    ops = tr.top_ops(timeline())
    assert ops[0] == ["fusion.1 bf16[8,8] kLoop", pytest.approx(0.080)]
    assert ops[1] == ["all-reduce.7 f32[16]", pytest.approx(0.030)]
    assert tr.short_name("%while.41 = (u32[]{:T(128)}, bf16[8]{0}) while(...), condition=%c") == "while.41 u32[]"
    assert tr.short_name("no hlo text") == "no hlo text"


def test_recorded_trace_from_the_chip():
    """Four runs of a small program between the drivers' spans: three back
    to back with 2 ms of ``data_next`` sleep after each, then 10 ms of
    ``host_callback`` sleep, then the fourth."""
    assert os.path.getsize(RECORDED) < 256 * 1024
    events = tr.load(RECORDED)
    assert tr.device_planes(events) == [D0]
    runs = tr.module_runs(events, D0, "jit_step")
    assert len(runs) == 4
    b = tr.busy(events)
    assert 0 < b["busy_s"] < b["window_s"] and len(tr.per_step(events)) == 4
    assert 80 < tr.idle_pct(events) < 100  # a small program between sleeps
    gaps = dict(tr.idle_gaps(events))
    assert gaps["host_callback"] >= 0.010 and gaps["data_next"] >= 0.006
    assert gaps["host_callback"] + gaps["data_next"] == pytest.approx(
        b["window_s"] - b["busy_s"], rel=0.05)
    step = tr.per_step(events)[0]
    assert 0 < step["busy_s"] <= step["module_s"]
    assert len(tr.breakdown(events)["device_ops"]) > 3
    assert {"XLA Ops", "XLA Modules"} <= set(tr.describe(RECORDED)[D0])

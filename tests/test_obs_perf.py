"""obs/perf.py: shared flop/memory helpers, collective estimate, PerfMonitor
gauges (the live ``distar_perf_*`` surface the BaseLearner run loop feeds)."""
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distar_tpu.obs import MetricsRegistry
from distar_tpu.obs.perf import (
    PerfMonitor,
    estimate_collective_bytes,
    flops_of_compiled,
    flops_of_lowered,
    memory_report,
    peak_flops,
)


def test_peak_flops_table():
    assert peak_flops("TPU v5 lite") == 197e12
    assert peak_flops("TPU v5") == 459e12  # exact keys: no substring match
    assert peak_flops("cpu") is None
    assert peak_flops("") is None
    with pytest.raises(KeyError, match="unknown TPU device_kind"):
        peak_flops("TPU v5 lite pod")  # a TPU not in the table is an error


def test_flops_and_memory_helpers_on_real_lowering():
    @jax.jit
    def f(x, w):
        return jnp.dot(x, w)

    x = jnp.ones((64, 64), jnp.float32)
    lowered = f.lower(x, x)
    flops = flops_of_lowered(lowered)
    # 2*N^3 for a square matmul; cost analysis may add elementwise epsilon
    assert flops >= 2 * 64 ** 3
    compiled = lowered.compile()
    # CPU may or may not report optimized counts/memory — the helpers must
    # degrade to 0.0/{} rather than raise
    assert flops_of_compiled(compiled) >= 0.0
    mem = memory_report(compiled)
    assert isinstance(mem, dict)
    if mem:
        assert "total_mb" in mem


def test_flops_helpers_swallow_backend_errors():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no analysis on this backend")

        def memory_analysis(self):
            raise RuntimeError("nope")

    assert flops_of_lowered(Broken()) == 0.0
    assert flops_of_compiled(Broken()) == 0.0
    assert memory_report(Broken()) == {}


def test_estimate_collective_bytes_dp_and_fsdp():
    from distar_tpu.parallel import MeshSpec, make_mesh

    params = {"w": jnp.ones((1000,), jnp.float32)}  # 4000 bytes
    mesh = make_mesh(MeshSpec(dp=4), jax.devices()[:4])
    est = estimate_collective_bytes(mesh, params)
    assert est["param_bytes"] == 4000.0
    assert est["grad_allreduce"] == pytest.approx(2 * 3 / 4 * 4000)
    assert "fsdp_allgather" not in est
    mesh2 = make_mesh(MeshSpec(dp=2, fsdp=2), jax.devices()[:4])
    est2 = estimate_collective_bytes(mesh2, params)
    assert est2["grad_allreduce"] == pytest.approx(2 * 1 / 2 * 4000)
    assert est2["fsdp_allgather"] == pytest.approx(2 * 1 / 2 * 4000)
    assert est2["fsdp_reducescatter"] == pytest.approx(1 / 2 * 4000)
    assert est2["total"] == pytest.approx(
        est2["grad_allreduce"] + est2["fsdp_allgather"] + est2["fsdp_reducescatter"])


def _snapshot(reg):
    return reg.snapshot()


def test_perf_monitor_on_step_gauges():
    reg = MetricsRegistry()
    mon = PerfMonitor("t", registry=reg, mem_sample_every=10 ** 9)
    mon.on_step(0.5, frames=100.0)
    snap = _snapshot(reg)
    assert snap["distar_perf_frames_per_s{token=t}"] == pytest.approx(200.0)
    assert snap["distar_perf_step_seconds{token=t}"] == pytest.approx(0.5)
    # no flops yet -> tflops/mfu gauges stay at their registered zero
    assert snap["distar_perf_implied_tflops{token=t}"] == 0.0
    assert snap["distar_perf_mfu{token=t}"] == 0.0
    mon.flops_per_step = 1e12
    mon.peak = 2e12
    mon.on_step(1.0, frames=100.0)
    snap = _snapshot(reg)
    assert snap["distar_perf_implied_tflops{token=t}"] == pytest.approx(1.0)
    assert snap["distar_perf_mfu{token=t}"] == pytest.approx(0.5)
    assert mon.snapshot()["mfu"] == pytest.approx(0.5)
    # zero/negative step time is ignored, never a ZeroDivisionError
    mon.on_step(0.0, frames=100.0)


def test_perf_monitor_background_analysis_extracts_flops():
    reg = MetricsRegistry()
    mon = PerfMonitor("t", registry=reg)

    @jax.jit
    def step(x, w):
        return jnp.dot(x, w)

    x = jnp.ones((32, 32), jnp.float32)
    mon.note_step_args(step, x, x)
    mon.note_step_args(step, x, x)  # idempotent: one analysis thread only
    deadline = time.time() + 30.0
    while time.time() < deadline and not mon.flops_per_step:
        time.sleep(0.05)
    assert mon.flops_per_step >= 2 * 32 ** 3
    assert _snapshot(reg)["distar_perf_flops_per_step{token=t}"] == mon.flops_per_step


def test_perf_monitor_analysis_failure_counted_not_raised():
    reg = MetricsRegistry()
    mon = PerfMonitor("t", registry=reg)

    class Unlowerable:
        def lower(self, *a):
            raise RuntimeError("boom")

    mon.note_step_args(Unlowerable(), jnp.ones((2,)))
    deadline = time.time() + 10.0
    while time.time() < deadline:
        if _snapshot(reg).get(
                "distar_perf_analysis_failures_total{token=t}", 0.0):
            break
        time.sleep(0.05)
    assert _snapshot(reg)["distar_perf_analysis_failures_total{token=t}"] == 1.0


def test_perf_monitor_set_collectives_publishes_gauges():
    from distar_tpu.parallel import MeshSpec, make_mesh

    reg = MetricsRegistry()
    mon = PerfMonitor("t", registry=reg)
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2), jax.devices()[:4])
    mon.set_collectives(mesh, {"w": jnp.ones((100,), jnp.float32)})
    snap = _snapshot(reg)
    keys = [k for k in snap if k.startswith("distar_perf_collective_bytes_per_step")]
    assert len(keys) == 3  # grad_allreduce + fsdp_allgather + fsdp_reducescatter


def test_perf_monitor_thread_safety_of_note():
    # concurrent first-iteration calls from racing threads: exactly one wins
    reg = MetricsRegistry()
    mon = PerfMonitor("t", registry=reg)
    started = []

    class Probe:
        def lower(self, *a):
            started.append(1)
            raise RuntimeError("stop here")

    threads = [threading.Thread(target=mon.note_step_args, args=(Probe(), 1))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    time.sleep(0.3)
    assert len(started) <= 1

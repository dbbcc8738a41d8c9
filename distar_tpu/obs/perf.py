"""Live performance attribution: MFU / HBM / collective-traffic gauges.

One code path for XLA's cost and memory introspection:
``flops_of_lowered``/``flops_of_compiled`` extract flop counts,
``memory_report`` normalises ``memory_analysis()``, ``peak_flops`` looks a
device kind up in the datasheet bf16 peak table — and
``PerfMonitor`` turns them into the live ``distar_perf_*`` gauges the
BaseLearner run loop publishes every iteration, so the PR 3 telemetry
pipeline (TSDB, shipper, health rules) sees MFU and HBM fleet-wide.

jax is imported lazily (importing obs never imports jax); everything here
is best-effort — a backend without cost/memory introspection degrades to
frames/s + step-time gauges, never an exception in the train loop.
"""
from __future__ import annotations

import logging
import threading
from typing import Dict, Optional

from .registry import MetricsRegistry, get_registry

# peak bf16 matmul throughput per chip, keyed by the EXACT
# ``device.device_kind`` string jax reports. Sources: Google Cloud TPU
# documentation, system-architecture pages "TPU v4" (275 TFLOP/s), "TPU v5e"
# (197), "TPU v5p" (459), "TPU v6e" (918). Each generation is listed under
# both spellings jax's own sources use for it across libtpu versions.
PEAK_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device_kind: str) -> Optional[float]:
    """Datasheet bf16 peak for a ``device.device_kind`` string. A device
    that is not a TPU (CPU hosts) has no peak: None. A TPU kind missing from
    the table is an error, not a default — an MFU against a guessed peak, or
    one that silently vanishes, is worse than none."""
    kind = device_kind or ""
    if kind in PEAK_FLOPS:
        return PEAK_FLOPS[kind]
    if kind.upper().startswith("TPU"):
        raise KeyError(
            f"unknown TPU device_kind {kind!r}: add its datasheet peak to "
            "obs.perf.PEAK_FLOPS with the source")
    return None


def flops_of_lowered(lowered) -> float:
    """Unoptimized-HLO flop count off a ``jax.stages.Lowered`` (0.0 when the
    backend offers no cost analysis)."""
    try:
        cost = lowered.cost_analysis()
        return float(cost.get("flops", 0.0)) if cost else 0.0
    except Exception:
        return 0.0


def flops_of_compiled(compiled) -> float:
    """Post-optimization executable-level flop count — the honest MFU
    numerator (the unoptimized count can overcount fused/DCE'd work)."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        return float(cost.get("flops", 0.0)) if cost else 0.0
    except Exception:
        return 0.0


_MEM_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "generated_code_size_in_bytes",
)


def memory_report(compiled) -> Dict[str, float]:
    """XLA ``memory_analysis()`` as a flat ``*_mb`` dict (+``total_mb`` =
    argument+output+temp). Empty dict when the backend has no analysis —
    callers merge it with ``row.update(...)`` and lose nothing."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out: Dict[str, float] = {}
    for field in _MEM_FIELDS:
        v = getattr(mem, field, None)
        if v is not None:
            out[field.replace("_size_in_bytes", "_mb")] = round(v / 1e6, 1)
    total = sum(
        getattr(mem, f, 0) or 0
        for f in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes")
    )
    out["total_mb"] = round(total / 1e6, 1)
    return out


def estimate_collective_bytes(mesh, params) -> Dict[str, float]:
    """Analytic per-step collective traffic from the mesh + param tree:
    ring all-reduce of grads over dp costs ``2*(dp-1)/dp`` x param bytes,
    ZeRO-3 fsdp adds an all-gather of params (fwd+bwd, 2x) and a
    reduce-scatter of grads at ``(fsdp-1)/fsdp`` x param bytes each. A
    lower-bound ESTIMATE from the sharding specs (tp/sp activation traffic
    is shape-dependent and not counted) — the live sanity number to hold a
    profiler trace's collective bucket against."""
    import jax

    param_bytes = float(sum(
        x.size * getattr(x.dtype, "itemsize", 4)
        for x in jax.tree.leaves(params)
        if hasattr(x, "size")
    ))
    shape = dict(mesh.shape) if mesh is not None else {}
    dp = int(shape.get("dp", 1))
    fsdp = int(shape.get("fsdp", 1))
    out = {"param_bytes": param_bytes}
    if dp > 1:
        out["grad_allreduce"] = 2.0 * (dp - 1) / dp * param_bytes
    if fsdp > 1:
        frac = (fsdp - 1) / fsdp
        out["fsdp_allgather"] = 2.0 * frac * param_bytes
        out["fsdp_reducescatter"] = frac * param_bytes
    out["total"] = sum(v for k, v in out.items() if k != "param_bytes")
    return out


def shard_bytes_by_device(tree) -> Dict[str, float]:
    """Bytes each device really holds of a pytree of jax arrays, summed over
    ``addressable_shards`` (``platform:id`` -> bytes). Host leaves count
    nowhere."""
    import jax

    out: Dict[str, float] = {}
    for x in jax.tree.leaves(tree):
        for shard in getattr(x, "addressable_shards", ()):
            label = f"{shard.device.platform}:{shard.device.id}"
            out[label] = out.get(label, 0.0) + shard.data.nbytes
    return out


class PerfMonitor:
    """Per-learner live perf gauges.

    The run loop calls ``on_step`` every iteration (frames/s, step seconds,
    implied TFLOPs, MFU when the chip's peak is known) and ``note_step_args``
    once with the jitted step + its live args; flop extraction happens on a
    background daemon thread against shape specs (never the donated
    buffers), so the loop never pays a trace. HBM gauges sample
    ``device.memory_stats()`` — live allocator truth on TPU, absent on CPU.
    """

    def __init__(self, token: str, registry: Optional[MetricsRegistry] = None,
                 aot_compile: bool = False, mem_sample_every: int = 1):
        self._registry = registry or get_registry()
        self._token = token
        self._aot_compile = aot_compile
        self._mem_sample_every = max(1, int(mem_sample_every))
        self._lock = threading.Lock()
        self._analysis_started = False
        self._batch_noted = False
        self._steps_seen = 0
        self.flops_per_step = 0.0
        self.peak: Optional[float] = None
        self.last: Dict[str, float] = {}
        r = self._registry
        self._g_frames = r.gauge("distar_perf_frames_per_s",
                                 "learner throughput, frames per second",
                                 token=token)
        self._g_step = r.gauge("distar_perf_step_seconds",
                               "last device-step wall time", token=token)
        self._g_tflops = r.gauge("distar_perf_implied_tflops",
                                 "flops_per_step / step_time", token=token)
        self._g_mfu = r.gauge("distar_perf_mfu",
                              "implied flops share of the chip's bf16 peak",
                              token=token)
        self._g_flops = r.gauge("distar_perf_flops_per_step",
                                "train-step flop count (cost_analysis)",
                                token=token)
        self._c_fail = r.counter("distar_perf_analysis_failures_total",
                                 "background cost/memory analyses that failed",
                                 token=token)

    # ------------------------------------------------------------- AOT side
    def note_step_args(self, jitted, *args) -> None:
        """First-iteration hook, called AFTER the step's first call with
        arguments typed like the ones it got: snapshot their specs and
        extract flops (and, with ``aot_compile``, the static HBM footprint)
        in the background. Idempotent; never raises into the train loop."""
        with self._lock:
            if self._analysis_started:
                return
            self._analysis_started = True
        try:
            import jax

            # with the shardings: the types then match the live call's, so
            # lower() finds its trace in jit's cache instead of re-tracing
            specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None))
                if hasattr(x, "shape") and hasattr(x, "dtype") else x,
                args,
            )
        except Exception:
            self._c_fail.inc()
            return
        threading.Thread(
            target=self._analyze, args=(jitted, specs),
            name=f"perf-analysis-{self._token}", daemon=True,
        ).start()

    def _analyze(self, jitted, specs) -> None:
        try:
            import jax

            # an unknown TPU kind raises into the logged failure below
            self.peak = peak_flops(jax.devices()[0].device_kind)
            lowered = jitted.lower(*specs)
            flops = flops_of_lowered(lowered)
            if self._aot_compile:
                # opt-in: the compile is served by the persistent cache when
                # the live step already compiled this signature
                compiled = lowered.compile()
                flops = flops_of_compiled(compiled) or flops
                for kind, mb in memory_report(compiled).items():
                    self._registry.gauge(
                        "distar_perf_step_hbm_mb",
                        "static per-step HBM footprint (memory_analysis)",
                        token=self._token, kind=kind.replace("_mb", ""),
                    ).set(mb)
            if flops:
                self.flops_per_step = flops
                self._g_flops.set(flops)
        except Exception as e:  # analysis is telemetry, never training-fatal
            logging.warning("perf analysis failed: %r", e)
            self._c_fail.inc()

    # ------------------------------------------------------------ live side
    def on_step(self, step_time_s: float, frames: float) -> None:
        step_time_s = float(step_time_s)
        if step_time_s <= 0:
            return
        vals = {"step_seconds": step_time_s}
        self._g_step.set(step_time_s)
        if frames:
            vals["frames_per_s"] = frames / step_time_s
            self._g_frames.set(vals["frames_per_s"])
        if self.flops_per_step:
            tflops = self.flops_per_step / step_time_s / 1e12
            vals["implied_tflops"] = tflops
            self._g_tflops.set(tflops)
            if self.peak:
                vals["mfu"] = self.flops_per_step / step_time_s / self.peak
                self._g_mfu.set(vals["mfu"])
        self.last = vals
        self._steps_seen += 1
        if (self._steps_seen - 1) % self._mem_sample_every == 0:
            self.sample_memory()

    def sample_memory(self) -> None:
        """Per-local-device allocator stats into HBM gauges (no-op on
        backends without ``memory_stats``, e.g. CPU)."""
        try:
            import jax

            for d in jax.local_devices():
                stats = d.memory_stats()
                if not stats:
                    continue
                label = f"{d.platform}:{d.id}"
                in_use = stats.get("bytes_in_use")
                if in_use is not None:
                    self._registry.gauge(
                        "distar_perf_hbm_bytes_in_use",
                        "allocator bytes currently in use", device=label,
                    ).set(float(in_use))
                peak = stats.get("peak_bytes_in_use")
                if peak is not None:
                    self._registry.gauge(
                        "distar_perf_hbm_peak_bytes",
                        "allocator high-water mark", device=label,
                    ).set(float(peak))
                # on the TPU runtime a program's temporaries live in the
                # reserved pool, apart from peak_bytes_in_use (PERF.md §3)
                reserved = stats.get("peak_bytes_reserved")
                if reserved is not None:
                    self._registry.gauge(
                        "distar_perf_hbm_reserved_peak_bytes",
                        "reserved-pool high-water mark (program temporaries)",
                        device=label,
                    ).set(float(reserved))
        except Exception:
            self._c_fail.inc()

    def set_state_bytes(self, state) -> None:
        """Publish where the train state (params + optimizer state) lives:
        bytes per device, from the arrays' own shards."""
        for device, n in shard_bytes_by_device(state).items():
            self._registry.gauge(
                "distar_perf_state_bytes",
                "parameter + optimizer-state bytes held by each device",
                token=self._token, device=device,
            ).set(n)

    def note_batch(self, batch) -> None:
        """First placed batch only: bytes of it each device holds."""
        if self._batch_noted:
            return
        self._batch_noted = True
        for device, n in shard_bytes_by_device(batch).items():
            self._registry.gauge(
                "distar_perf_batch_bytes",
                "bytes of one placed training batch held by each device",
                token=self._token, device=device,
            ).set(n)

    def set_collectives(self, mesh, params) -> None:
        """Publish the analytic per-step collective estimate for this
        learner's mesh + params (docs/observability.md#perf)."""
        try:
            est = estimate_collective_bytes(mesh, params)
        except Exception:
            self._c_fail.inc()
            return
        for kind, v in est.items():
            if kind in ("total", "param_bytes"):
                continue
            self._registry.gauge(
                "distar_perf_collective_bytes_per_step",
                "estimated per-step collective traffic from sharding specs",
                token=self._token, kind=kind,
            ).set(v)

    def snapshot(self) -> Dict[str, float]:
        """Last-step view for the admin ``status`` route / opsctl digest."""
        out = dict(self.last)
        if self.flops_per_step:
            out["flops_per_step"] = self.flops_per_step
        if self.peak:
            out["peak_flops"] = self.peak
        return out

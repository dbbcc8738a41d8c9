"""jax.profiler session wrapper, the program's host spans and the names of
the jitted step's parts.

``ProfilerSession`` guards ``jax.profiler.start_trace``/``stop_trace`` behind
availability checks (profiling is best-effort telemetry: a missing/broken
profiler must never take down training) and counts sessions in the registry.
``Spans`` is the one place the program times a phase of a host loop: each
``with spans.span("<phase>")`` is a ``jax.profiler.TraceAnnotation`` named
``distar:<role>/<phase>`` (so the phase sits on the device trace's clock
whenever a profiler session is active, and costs nothing when none is) and
one observation of the seconds in the role's phase histogram. The learner
run loop (role ``loop``) and the feeder thread (role ``feed``) use it.
``STEP_SCOPES`` is the fixed vocabulary of ``jax.named_scope`` / Flax module
names under which every operation of a jitted train step is found;
``LM_STEP_SCOPES`` is the token-sequence learner's.
"""
from __future__ import annotations

import logging
import os
from time import perf_counter
from typing import Callable, Dict, Optional

from .registry import MetricsRegistry, get_registry


class ProfilerSession:
    """Start/stop wrapper for the device profiler.

    ``profiler`` is injectable (tests pass a stub); the default resolves
    ``jax.profiler`` lazily so importing obs never imports jax. Failures are
    never fatal but no longer silent either: each failed start/stop counts
    ``distar_profiler_failures_total{stage=...}``, and a successful stop
    records ``last_profile_path`` — the newest capture dir under the logdir,
    what the admin ``/profile`` route hands to the trace analyzer."""

    def __init__(self, logdir: str, profiler=None, registry: Optional[MetricsRegistry] = None):
        self.logdir = logdir
        self.active = False
        self.failures = 0
        self.last_profile_path: Optional[str] = None
        self._profiler = profiler
        self._registry = registry

    def _resolve(self):
        if self._profiler is None:
            import jax

            self._profiler = jax.profiler
        return self._profiler

    def _count_failure(self, stage: str) -> None:
        self.failures += 1
        reg = self._registry or get_registry()
        reg.counter(
            "distar_profiler_failures_total",
            "profiler start/stop failures (best-effort, training continues)",
            stage=stage,
        ).inc()

    def start(self) -> bool:
        if self.active:
            return True
        try:
            # surface an unwritable logdir HERE, typed, instead of letting
            # stop_trace throw away an entire captured session later
            os.makedirs(self.logdir, exist_ok=True)
            self._resolve().start_trace(self.logdir)
        except Exception as e:  # best-effort: never kill training over a trace
            logging.warning("profiler start_trace failed: %r", e)
            self._count_failure("start")
            return False
        self.active = True
        reg = self._registry or get_registry()
        reg.counter("distar_profiler_sessions_total", "profiler traces started").inc()
        return True

    def stop(self) -> bool:
        if not self.active:
            return False
        self.active = False
        try:
            self._resolve().stop_trace()
        except Exception as e:
            logging.warning("profiler stop_trace failed: %r", e)
            self._count_failure("stop")
            return False
        self.last_profile_path = self._newest_capture() or self.logdir
        return True

    def _newest_capture(self) -> Optional[str]:
        """Newest session dir under ``<logdir>/plugins/profile/`` (the
        layout ``jax.profiler`` writes); None when nothing landed."""
        root = os.path.join(self.logdir, "plugins", "profile")
        try:
            stamps = [os.path.join(root, d) for d in os.listdir(root)]
            stamps = [d for d in stamps if os.path.isdir(d)]
            return max(stamps, key=os.path.getmtime) if stamps else None
        except OSError:
            return None


# Every operation of a learner's jitted step carries one of these as an
# element of its ``op_name`` path (bare, or as ``jvp(<name>)`` /
# ``transpose(jvp(<name>))``), written by a Flax module's name or by a
# ``jax.named_scope``; ``loss`` has ``loss/vtrace``, ``loss/upgo``,
# ``loss/td``, ``loss/kl`` and ``loss/entropy`` beneath it in RL,
# ``diagnostics`` has ``grad_norm``, ``leaf_norms`` and ``dynamics_tree``.
# The actor's frozen pass is ``teacher`` (actor/inference.py). A trace
# reader gives an operation to the first of these on its path.
STEP_SCOPES = (
    "scalar_encoder", "entity_encoder", "scatter_connection", "spatial_encoder",
    "core_lstm", "action_type_head", "delay_head", "queued_head",
    "selected_units_head", "target_unit_head", "location_head", "value",
    "loss", "optimizer", "diagnostics",
)

# The token-sequence learner's step (``lm_train_step``): the models' parts
# (``model/lfm2.py``, ``model/nemotron_h.py``, ``model/deepseek_v3.py``,
# ``ops/moe.py``, ``ops/ssm.py``, ``ops/sequence.py``; a model has the parts its
# layers have) and the three names every step has. ``mla_core`` is latent
# attention's kernel alone, ``mla_proj`` everything else of that layer.
LM_STEP_SCOPES = (
    "embed", "short_conv", "attention", "dense_mlp",
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "lm_head",
    "loss", "optimizer", "diagnostics",
    "ssm_proj", "ssm_scan", "moe_shared",
    "mla_proj", "mla_core",
)

SPAN_PREFIX = "distar:"


class _Span:
    __slots__ = ("_name", "_hist", "_open", "_annotation", "_t0", "seconds")

    def __init__(self, name, hist, open_annotation):
        self._name, self._hist, self._open = name, hist, open_annotation
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = perf_counter()
        self._annotation = self._open(self._name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        self.seconds = perf_counter() - self._t0
        self._hist.observe(self.seconds)
        return False


class Spans:
    """The phases of one host loop: ``role`` names the loop in the trace
    (``distar:<role>/<phase>``), ``histogram`` gives the registry histogram a
    phase's seconds are observed into. A span observes on exit also when its
    body raised; ``span.seconds`` is readable after it. One instance serves
    one thread's loop; spans nest by ``with``."""

    def __init__(self, role: str, histogram: Callable[[str], object]):
        self.role = role
        self._histogram = histogram
        self._hists: Dict[str, object] = {}
        self._profiler = None

    def _resolve(self):
        if self._profiler is None:  # importing obs never imports jax
            import jax

            self._profiler = jax.profiler
        return self._profiler

    def _hist(self, phase: str):
        hist = self._hists.get(phase)
        if hist is None:
            hist = self._hists[phase] = self._histogram(phase)
        return hist

    def span(self, phase: str) -> _Span:
        return _Span(f"{SPAN_PREFIX}{self.role}/{phase}", self._hist(phase),
                     self._resolve().TraceAnnotation)

    def step(self, name: str, step_num: int, phase: str = "iteration") -> _Span:
        """One iteration of the loop as the profiler's own step marker
        (``StepTraceAnnotation``), observed as ``phase``."""
        profiler = self._resolve()
        return _Span(name, self._hist(phase),
                     lambda n: profiler.StepTraceAnnotation(n, step_num=step_num))


def loop_spans(registry: Optional[MetricsRegistry] = None) -> Spans:
    """The learner run loop's phases (``data_wait``, ``device_step`` and
    ``host_callback`` since PR 1; the finer ones since PR 23)."""
    reg = registry or get_registry()
    return Spans("loop", lambda phase: reg.histogram(
        "distar_learner_step_phase_seconds", "learner step time by phase", phase=phase))


def feed_spans(token: str, registry: Optional[MetricsRegistry] = None) -> Spans:
    """The feeder thread's phases: ``pull``, ``cap``, ``put``, ``put_wait``."""
    reg = registry or get_registry()
    return Spans("feed", lambda phase: reg.histogram(
        "distar_feeder_phase_seconds", "feeder thread time per batch by phase",
        phase=phase, token=token))

"""jax.profiler session wrapper, the program's host spans and the names of
the jitted step's parts.

``ProfilerSession`` guards ``jax.profiler.start_trace``/``stop_trace`` behind
availability checks (profiling is best-effort telemetry: a missing/broken
profiler must never take down training) and counts sessions in the registry.
``Spans`` is the one place the program times a phase of a host loop: each
``with spans.span("<phase>")`` is a ``jax.profiler.TraceAnnotation`` named
``distar:<role>/<phase>`` (so the phase sits on the device trace's clock
whenever a profiler session is active, and costs nothing when none is) and
one record of the seconds in the role's instrument: an observation of a
phase histogram for the learner run loop (role ``loop``) and the feeder
thread (role ``feed``), which pass through a phase every step; an increment
of a counter for a learner's set-up (role ``setup``), whose phases
(``SETUP_PHASES``) happen once.
``STEP_SCOPES`` is the fixed vocabulary of ``jax.named_scope`` / Flax module
names under which every operation of a jitted train step is found;
``LM_STEP_SCOPES`` is the token-sequence learner's.
"""
from __future__ import annotations

import logging
import os
import time
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
# ``model/qwen3_next.py``, ``model/laguna.py``, ``model/phi4flash.py``, ``ops/moe.py``, ``ops/ssm.py``,
# ``ops/delta.py``, ``ops/sequence.py``; a model has the parts its layers have)
# and the three names every step has. ``mla_core`` is latent attention's kernel
# alone, ``mla_proj`` everything else of that layer; ``gdn_scan`` is the chunked
# gated delta rule alone, ``gdn_proj`` everything else of a Gated DeltaNet layer.
# ``attn_core`` is grouped-query attention's full-causal kernel alone, ``swa_core``
# its banded kernel alone, ``attn_proj`` everything else of such a layer: the
# names of a model whose layers are not under ``attention`` as a whole (``laguna``,
# ``phi4flash``). ``mamba1_scan`` is Mamba-1's selective scan alone, ``mamba1_proj``
# everything else of such a layer; ``gmu`` a gated memory unit; ``cross_core`` the
# kernel of an attention layer that reads an earlier layer's keys and values.
LM_STEP_SCOPES = (
    "embed", "short_conv", "attention", "dense_mlp",
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "lm_head",
    "loss", "optimizer", "diagnostics",
    "ssm_proj", "ssm_scan", "moe_shared",
    "mla_proj", "mla_core",
    "gdn_proj", "gdn_scan",
    "attn_proj", "attn_core", "swa_core",
    "mamba1_proj", "mamba1_scan", "gmu", "cross_core",
)

SPAN_PREFIX = "distar:"


class _Span:
    __slots__ = ("_name", "_record", "_open", "_annotation", "_t0", "seconds")

    def __init__(self, name, record, open_annotation):
        self._name, self._record, self._open = name, record, open_annotation
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = perf_counter()
        self._annotation = self._open(self._name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        self.seconds = perf_counter() - self._t0
        self._record(self.seconds)
        return False


class Spans:
    """The phases of one host loop: ``role`` names the loop in the trace
    (``distar:<role>/<phase>``), ``record`` gives for a phase the call that
    takes its seconds (a registry histogram's ``observe``, a counter's
    ``inc``), ``opened`` is told the phase when a span of it is made. A span
    records on exit also when its body raised; ``span.seconds`` is readable
    after it. One instance serves one thread's loop; spans nest by ``with``."""

    def __init__(self, role: str, record: Callable[[str], Callable[[float], None]],
                 opened: Optional[Callable[[str], None]] = None):
        self.role = role
        self._record = record
        self._opened = opened
        self._records: Dict[str, Callable[[float], None]] = {}
        self._profiler = None

    def _resolve(self):
        if self._profiler is None:  # importing obs never imports jax
            import jax

            self._profiler = jax.profiler
        return self._profiler

    def _recorder(self, phase: str) -> Callable[[float], None]:
        record = self._records.get(phase)
        if record is None:
            record = self._records[phase] = self._record(phase)
        return record

    def span(self, phase: str) -> _Span:
        if self._opened is not None:
            self._opened(phase)
        return _Span(f"{SPAN_PREFIX}{self.role}/{phase}", self._recorder(phase),
                     self._resolve().TraceAnnotation)

    def step(self, name: str, step_num: int, phase: str = "iteration") -> _Span:
        """One iteration of the loop as the profiler's own step marker
        (``StepTraceAnnotation``), observed as ``phase``."""
        profiler = self._resolve()
        return _Span(name, self._recorder(phase),
                     lambda n: profiler.StepTraceAnnotation(n, step_num=step_num))


def loop_spans(registry: Optional[MetricsRegistry] = None) -> Spans:
    """The learner run loop's phases (``data_wait``, ``device_step`` and
    ``host_callback`` since PR 1; the finer ones since PR 23)."""
    reg = registry or get_registry()
    return Spans("loop", lambda phase: reg.histogram(
        "distar_learner_step_phase_seconds", "learner step time by phase", phase=phase).observe)


def feed_spans(token: str, registry: Optional[MetricsRegistry] = None) -> Spans:
    """The feeder thread's phases: ``pull``, ``cap``, ``put``, ``put_wait``."""
    reg = registry or get_registry()
    return Spans("feed", lambda phase: reg.histogram(
        "distar_feeder_phase_seconds", "feeder thread time per batch by phase",
        phase=phase, token=token).observe)


# A learner's set-up, from the first touch of the backend to the end of its
# first step, in the order the phases come; leaves, none inside another, and a
# learner has the phases it has (``fake_batch`` types the init of SL and RL,
# ``init_shapes`` the token learner's; ``restore`` runs where a launcher
# resumes). ``first_step`` is the first iteration of a ``run``, with the run
# loop's own spans beneath it in a trace.
SETUP_PHASES = (
    "backend_init", "learner_base", "dataloader", "fake_batch", "init_shapes",
    "model_init", "opt_init", "state_place", "restore", "state_ready", "first_step",
)

# What set-up is doing now, for the compile listener's ``during`` label
# (``utils/compile_cache.py``): the open phase, ``outside`` between phases and
# before the first, ``run`` once a first step is over. One name for the
# process: set-up is one thread.
setup_during = "outside"


def setup_spans(registry: Optional[MetricsRegistry] = None) -> Spans:
    """A learner's set-up phases (``SETUP_PHASES``): a phase's seconds add to
    ``distar_setup_seconds_total{phase}``, and ``setup_during`` names it
    while it is open."""
    reg = registry or get_registry()

    def opened(phase: str) -> None:
        global setup_during
        if phase not in SETUP_PHASES:
            raise ValueError(f"set-up phase {phase!r}: one of {SETUP_PHASES}")
        setup_during = phase

    def record(phase: str) -> Callable[[float], None]:
        counter = reg.counter(
            "distar_setup_seconds_total", "seconds of a learner's set-up by phase", phase=phase)

        def closed(seconds: float) -> None:
            global setup_during
            counter.inc(seconds)
            setup_during = "run" if phase == "first_step" else "outside"

        return closed

    return Spans("setup", record, opened)


def process_age() -> Optional[float]:
    """Seconds since this process started, by the kernel's record of its
    start (not by when a module was imported); None where ``/proc`` cannot
    say."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command, which may itself hold spaces
            # and brackets; the process's start is the 22nd of all, in clock
            # ticks since boot
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def mark_process_age(at: str, registry: Optional[MetricsRegistry] = None) -> None:
    """``distar_setup_process_age_seconds{at}``: how old the process is at a
    point of a learner's set-up (``learner_init``, ``learner_ready``,
    ``run_start``, ``first_step_done``), so that import, the backend and what
    the launcher did before and between are numbers beside the phases."""
    age = process_age()
    if age is not None:
        (registry or get_registry()).gauge(
            "distar_setup_process_age_seconds",
            "seconds since the process started, at a point of a learner's set-up", at=at).set(age)

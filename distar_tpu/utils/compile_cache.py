"""Where JAX's persistent compile cache lives, and what compiling cost.

One rule, applied by ``configure`` and nowhere else:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this program sets
  no cache directory in code, so whoever runs the program places the cache.
* unset: ``<checkout>/.jax_cache`` (git-ignored). The path is part of the
  cache key, so it never contains a pid, a time or a temp dir.

XLA:CPU entries bake in the COMPILING machine's CPU features and this
container moves between hosts (entries from another host segfaulted the
test suite), so when the backend is the CPU the default directory gets a
``cpu-<host key>`` sub-directory. Accelerator entries are keyed by the
compiler itself and share the top level.

``configure`` also subscribes to ``jax.monitoring`` once, so every process
counts its own traces, lowerings, compiles and cache hits in the metrics
registry (``distar_compile_*``), by stage, by the set-up phase they fell in
and by program — the numbers ``chip_smoke.py`` reports per phase.
"""
from __future__ import annotations

import hashlib
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Tuple

from ..obs import MetricsRegistry, get_registry, profiler

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_listening = False


def _host_cpu_key() -> str:
    # LLVM (and therefore XLA:CPU's machine type) picks the target CPU from
    # family/model/stepping, not the flag list alone — two hosts with
    # identical flags but different models get different machine types, so
    # the key includes the identity lines too
    ident: list[str] = []
    try:
        with open("/proc/cpuinfo") as f:
            seen_processor = False
            for line in f:
                key = line.split(":", 1)[0].strip()
                # one per-CPU block is enough (all cores are identical);
                # stop at the SECOND block rather than at any single key —
                # ARM lists 'CPU implementer'/'CPU part' AFTER 'Features',
                # so an early break there would drop the identity lines
                if key == "processor":
                    if seen_processor:
                        break
                    seen_processor = True
                # x86 lists 'flags'; ARM lists 'Features'
                if key in ("flags", "Features"):
                    ident.append(" ".join(sorted(line.split(":", 1)[1].split())))
                elif key in ("vendor_id", "cpu family", "model", "model name",
                             "stepping", "CPU implementer", "CPU part"):
                    ident.append(line.split(":", 1)[1].strip())
    except OSError:
        pass
    if ident:
        return hashlib.sha1("|".join(ident).encode()).hexdigest()[:8]
    import platform

    # last resort: the full uname tuple — never hash an empty string, which
    # would give distinct hosts the same key and reintroduce shared caches
    return hashlib.sha1("|".join(platform.uname()).encode()).hexdigest()[:8]


def cache_dir(backend: str) -> str:
    """The default directory for ``backend`` (``jax.default_backend()``)."""
    base = os.path.join(_CHECKOUT, ".jax_cache")
    return os.path.join(base, f"cpu-{_host_cpu_key()}") if backend == "cpu" else base


def active_dir() -> str:
    """The directory jax reads and writes right now, whoever placed it."""
    import jax

    return jax.config.jax_compilation_cache_dir


class _PerThread(threading.local):
    """What the listener remembers between a thread's events."""

    def __init__(self):
        # (start, seconds) of the traces no caller's trace has claimed yet, oldest first
        self.unclaimed: List[Tuple[float, float]] = []
        # the persistent cache's answer for the program now compiling: its
        # hit or miss event precedes the program's backend event
        self.cache = "none"


# a thread's unclaimed traces are those of the jits called straight from
# the traces still open, and the outermost ones, which nothing ever claims:
# past this many the older half is forgotten
_UNCLAIMED_MAX = 1 << 16


class _Listener:
    """``jax.monitoring``'s events into the registry. JAX sends a program's
    stages one after another on the thread that compiles it: the trace
    (``jaxpr_trace_duration``), the lowering (``jaxpr_to_mlir_module_duration``)
    and the backend compile (``backend_compile_duration``), these with the
    program's ``fun_name``; inside the last, where the persistent cache
    answers, ``cache_hits`` and ``cache_retrieval_time_sec`` or, where it
    stores, ``cache_misses``. A duration arrives when its stage ends, so a
    nested ``jit``'s trace arrives before its caller's and lies inside it:
    ``stage="trace"`` counts a second once, under the outermost trace.
    ``during`` is the set-up phase the event fell in (``obs.profiler.setup_during``).
    A flagship set-up sends 24,000-55,000 events: an event costs a few dictionary
    lookups, and the registry is asked for an instrument once per label set."""

    def __init__(self):
        self._registry = None
        self._instruments: Dict[object, object] = {}
        self._thread = _PerThread()

    def _instrument(self, key: object, make: Callable[[MetricsRegistry], object]):
        registry = get_registry()
        if registry is not self._registry:  # a test installed another
            self._registry, self._instruments = registry, {}
        instrument = self._instruments.get(key)
        if instrument is None:
            instrument = self._instruments[key] = make(registry)
        return instrument

    def _seconds(self, stage: str):
        during = profiler.setup_during
        return self._instrument(("seconds", stage, during), lambda registry: registry.counter(
            "distar_compile_seconds_total",
            "seconds compiling by stage (trace: outermost jit only; lower; backend: compile or "
            "cache retrieval; cache_load: retrieval alone) and by the set-up phase they fell in",
            stage=stage, during=during))

    def _outermost(self, seconds: float) -> float:
        """The part of a trace that just ended which no trace inside it has counted."""
        start = time.time() - seconds  # jax times its stages by this clock
        unclaimed = self._thread.unclaimed
        inside = 0.0
        while unclaimed and unclaimed[-1][0] >= start:
            inside += unclaimed.pop()[1]
        if len(unclaimed) >= _UNCLAIMED_MAX:
            del unclaimed[:_UNCLAIMED_MAX // 2]
        unclaimed.append((start, seconds))
        return max(seconds - inside, 0.0)

    def on_duration(self, event, duration_secs, fun_name="", **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self._seconds("trace").inc(self._outermost(duration_secs))
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self._seconds("lower").inc(duration_secs)
        elif event == "/jax/core/compile/backend_compile_duration":
            self._seconds("backend").inc(duration_secs)
            self._instrument("backend", lambda registry: registry.counter(
                "distar_compile_backend_seconds_total",
                "seconds in backend compile, cache retrieval included")).inc(duration_secs)
            during, cache = profiler.setup_during, self._thread.cache
            self._thread.cache = "none"
            self._instrument(("program", fun_name, during, cache), lambda registry: registry.counter(
                "distar_compile_programs_total",
                "programs compiled or loaded, by name, by the set-up phase and by what the "
                "persistent cache said (none: not asked, or too small to store)",
                program=fun_name, during=during, cache=cache)).inc()
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self._seconds("cache_load").inc(duration_secs)

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._thread.cache = "hit"
            self._instrument("hits", lambda registry: registry.counter(
                "distar_compile_cache_hits_total",
                "compiles served by the persistent cache")).inc()
        elif event == "/jax/compilation_cache/cache_misses":
            self._thread.cache = "miss"
            self._instrument("misses", lambda registry: registry.counter(
                "distar_compile_cache_misses_total",
                "compiles the persistent cache did not hold and now stores")).inc()


def _listen() -> None:
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    listener = _Listener()
    jax.monitoring.register_event_duration_secs_listener(listener.on_duration)
    jax.monitoring.register_event_listener(listener.on_event)


def configure() -> None:
    """Place the persistent compile cache by the module's one rule and start
    counting compiles. Call after the platform is chosen: it asks
    ``jax.default_backend()``, which initialises the backend."""
    import jax

    _listen()
    with profiler.setup_spans().span("backend_init"):
        backend = jax.default_backend()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = cache_dir(backend)
    try:
        jax.config.update("jax_compilation_cache_dir", path)
    except Exception as e:
        # losing the cache means cold multi-minute compiles — degrade, but
        # never silently
        logging.getLogger(__name__).warning(
            "persistent compile cache NOT configured at %s (%r); compiles "
            "will be cold", path, e,
        )

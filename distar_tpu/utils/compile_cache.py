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
counts its own traces, compiles and cache hits in the metrics registry
(``distar_compile_*``) — the numbers ``chip_smoke.py`` reports per phase.
"""
from __future__ import annotations

import hashlib
import logging
import os

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


def _listen() -> None:
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    from ..obs import get_registry

    def on_duration(event, duration_secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            get_registry().counter(
                "distar_compile_trace_seconds_total",
                "seconds tracing jitted functions (a nested jit counts again)",
            ).inc(duration_secs)
        elif event == "/jax/core/compile/backend_compile_duration":
            get_registry().counter(
                "distar_compile_backend_seconds_total",
                "seconds in backend compile, cache retrieval included",
            ).inc(duration_secs)

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            get_registry().counter(
                "distar_compile_cache_hits_total",
                "compiles served by the persistent cache",
            ).inc()
        elif event == "/jax/compilation_cache/cache_misses":
            get_registry().counter(
                "distar_compile_cache_misses_total",
                "compiles the persistent cache did not hold and now stores",
            ).inc()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def configure() -> None:
    """Place the persistent compile cache by the module's one rule and start
    counting compiles. Call after the platform is chosen: it asks
    ``jax.default_backend()``, which initialises the backend."""
    import jax

    _listen()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = cache_dir(jax.default_backend())
    try:
        jax.config.update("jax_compilation_cache_dir", path)
    except Exception as e:
        # losing the cache means cold multi-minute compiles — degrade, but
        # never silently
        logging.getLogger(__name__).warning(
            "persistent compile cache NOT configured at %s (%r); compiles "
            "will be cold", path, e,
        )

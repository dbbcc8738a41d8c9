"""The device a run is on: what JAX reports, the peaks table, memory."""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(RuntimeError):
    """The cell needs chips this machine does not hold: no result is printed."""


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip, by the exact ``device_kind``. A kind
    that is not in ``peaks.json`` is an error: a share of a guessed peak is
    worse than none."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json: "
                       "add its published peaks there with the source")
    return table[device_kind]


def require(chips: int, rehearse: bool) -> None:
    """Pin the platform before anything else touches a jax backend, by the
    program's own ``select_backend`` (which also places the compile cache by
    ``utils/compile_cache``'s one rule). A measured run needs a TPU with the
    cell's chips and never falls back; a rehearsal runs on (virtual) CPU
    devices."""
    from distar_tpu.parallel.executor import select_backend

    if rehearse:
        select_backend("cpu", host_devices=chips if chips > 1 else 0)
    else:
        try:
            select_backend("tpu")
        except Exception as e:
            raise NoAccelerator(f"no TPU answered: {e!r}") from e
    import jax

    if jax.device_count() < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), jax sees {jax.device_count()}")


def describe() -> Dict:
    """The contract line's ``device``: as JAX reports it, and the peak on the
    fullest chip."""
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        # a program's temporaries live in the reserved pool on this runtime, apart
        # from peak_bytes_in_use (parameters, optimizer state, batches): my chip run, PR 22
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def memory_stats() -> Dict:
    """Everything the runtime says about device 0's memory, for people."""
    import jax

    try:
        return dict(jax.devices()[0].memory_stats() or {})
    except Exception:
        return {}


def start_trace(logdir: str) -> None:
    """The profiler without the python tracer: its frames are most of a
    trace's size and none of what the reduction reads."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)

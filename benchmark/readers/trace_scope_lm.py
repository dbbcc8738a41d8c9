"""Device time of the token-sequence train step by the program's own names:
``trace_scope``'s reading (self time per top-level scope and pass, per run of
the step program on device 0, the median over the traced steps; its
``scope_of`` and ``pass_of``) with the vocabulary of that step
(``LM_STEP_SCOPES`` in ``distar_tpu/obs/profiler.py``) in place of the policy
step's. ``trace_scope.self_times`` resolves names against the policy's
vocabulary only, so the sweep over the operations is written again here.

A program that has no such vocabulary (a checkout from before it was
written) gives nothing to read, and neither does a step program none of
whose operations sits under ``loss`` and ``optimizer``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

from benchmark import trace_meta, window
from benchmark.readers.trace_scope import EVERY_STEP_HAS, pass_of, scope_of

try:
    from distar_tpu.obs import LM_STEP_SCOPES as VOCABULARY
except ImportError:
    VOCABULARY = ()


def self_times(ops, lo: float, hi: float) -> Dict[Tuple[str, str], float]:
    """ns per (scope, pass) inside [lo, hi]: every instant covered by an
    operation goes to the innermost one open at it (the latest started)."""
    out: Dict[Tuple[str, str], float] = {}
    open_ops: List = []  # (end, key), innermost last
    key_of = functools.lru_cache(None)(lambda path: (scope_of(path, VOCABULARY), pass_of(path)))
    t = lo

    def give(key, until):
        nonlocal t
        if until > t:
            out[key] = out.get(key, 0.0) + until - t
            t = until

    clipped = [(max(op.start, lo), min(op.end, hi), op.scope) for op in ops
               if op.start < hi and op.end > lo]
    for start, end, path in sorted(clipped, key=lambda o: (o[0], -o[1])):
        while open_ops and open_ops[-1][0] <= start:
            give(open_ops[-1][1], open_ops.pop()[0])
        if open_ops:
            give(open_ops[-1][1], start)
        t = max(t, start)
        open_ops.append((end, key_of(path)))
    while open_ops:
        give(open_ops[-1][1], open_ops.pop()[0])
    return out


_steps: Dict[int, Optional[List[Dict]]] = {}  # per parsed trace: a dozen metrics ask


def steps_of(result) -> Optional[List[Dict]]:
    meta = trace_meta.find(result) if VOCABULARY else None
    if meta is None:
        return None
    if id(meta) not in _steps:
        plane, runs = trace_meta.step_runs(result["events"])
        steps = [self_times(meta.ops.get(plane, []), lo, hi) for lo, hi in runs]
        if not all(any(scope == name for step in steps for scope, _ in step) for name in EVERY_STEP_HAS):
            steps = None  # the executable does not carry the program's scope names
        _steps[id(meta)] = steps
    return _steps[id(meta)]


def read(result, scopes=None, without=(), passes=None, scale=1e-6):
    """ms (``scale`` from ns) of the step's device time under ``scopes``
    (default: every scope and ``unnamed``) but not ``without``, in ``passes``
    (default: forward, backward and recompute)."""
    steps = steps_of(result)
    if not steps:
        return None
    return scale * window.median([
        sum(ns for (scope, pas), ns in step.items()
            if (scopes is None or scope in scopes) and scope not in without
            and (passes is None or pas in passes))
        for step in steps])

"""Device time of the train step by the program's own names, from the trace
on device 0: per run of the step program (as ``trace_step`` finds it), the
SELF time of each top-level scope, the median over the traced steps.

Every device operation carries the ``op_name`` path of its HLO instruction
(``trace_meta``): ``jit(train_step)/jvp(Model.sl_forward)/encoder/
scatter_connection/...``. An operation belongs to the FIRST element of its
path that is in ``VOCABULARY`` (bare, as ``jvp(<name>)`` or
``transpose(jvp(<name>))``, or as a Flax method scope ``<name>.<method>``),
``unnamed`` when none is. A container (``while``, ``conditional``, ``call``)
lasts as long as its children run: at every instant the time goes to the
innermost operation open then, so the scopes partition the union of the
step's operation intervals, which is ``step_busy_ms``.

The pass of an operation: ``recompute`` under ``transpose(`` with
``rematted_computation`` on its path (the forward replayed for the backward
by ``remat``), else ``backward`` under ``transpose(``, else ``forward``. An
operation without a path (the compiler wrote it itself) has no pass: the
``step_*_ms`` metrics leave ``unnamed`` out.

A step program none of whose operations sits under ``loss`` and under
``optimizer`` was not compiled from a program that writes these names (the
parent of PR 23, or an executable that JAX's compile cache, whose key leaves
the names out, kept from before they were written): nothing is read from it.
"""
from __future__ import annotations

import functools
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import trace_meta, trace_reduce, window

try:  # the names are the program's: it writes them, and keeps the list
    from distar_tpu.obs import STEP_SCOPES as VOCABULARY
except ImportError:  # a checkout from before PR 23 names nothing
    VOCABULARY = ()
UNNAMED = "unnamed"
EVERY_STEP_HAS = ("loss", "optimizer")
_INNERMOST = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


def scope_of(path: str, vocabulary: Sequence[str] = VOCABULARY) -> str:
    for element in path.split("/"):
        name = _INNERMOST.sub(r"\1", element).split(".")[0]
        if name in vocabulary:
            return name
    return UNNAMED


def pass_of(path: str) -> str:
    if "transpose(" not in path:
        return "forward"
    return "recompute" if "rematted_computation" in path else "backward"


def self_times(ops: Iterable[trace_meta.Op]) -> Dict[Tuple[str, str], float]:
    """ns per (scope, pass): every instant covered by an operation goes to
    the innermost one open at it (the latest started)."""
    out: Dict[Tuple[str, str], float] = {}
    open_ops: List[Tuple[float, Tuple[str, str]]] = []  # (end, key), innermost last
    key_of = functools.lru_cache(None)(lambda path: (scope_of(path), pass_of(path)))  # few paths, many ops
    t = 0.0

    def give(key, until):
        nonlocal t
        if until > t:
            out[key] = out.get(key, 0.0) + until - t
            t = until

    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while open_ops and open_ops[-1][0] <= op.start:
            end, key = open_ops.pop()
            give(key, end)
        if open_ops:
            give(open_ops[-1][1], op.start)
        t = max(t, op.start)
        open_ops.append((op.end, key_of(op.scope)))
    while open_ops:
        end, key = open_ops.pop()
        give(key, end)
    return out


def per_step(ops: Sequence[trace_meta.Op], runs: Sequence[trace_reduce.Interval]) -> List[Dict]:
    return [self_times(op._replace(start=max(op.start, lo), end=min(op.end, hi))
                       for op in ops if op.start < hi and op.end > lo)
            for lo, hi in runs]


_steps: Dict[int, Optional[List[Dict]]] = {}  # per parsed trace: two dozen metrics ask


def steps_of(result) -> Optional[List[Dict]]:
    meta = trace_meta.find(result)
    if meta is None:
        return None
    if id(meta) not in _steps:
        plane, runs = trace_meta.step_runs(result["events"])
        steps = per_step(meta.ops.get(plane, []), runs)
        missing = [name for name in EVERY_STEP_HAS
                   if not any(scope == name for step in steps for scope, _ in step)]
        if missing:
            print(f"benchmark: no operation of the step program is under {' or '.join(missing)}: "
                  "its executable does not carry the program's scope names (compiled before "
                  "they were written, or served so by the compile cache); no scope is read",
                  file=sys.stderr)
            steps = None
        _steps[id(meta)] = steps
    return _steps[id(meta)]


def read(result, scopes=None, without=(), passes=None, scale=1e-6):
    """ms (``scale`` from ns) of the step's device time under ``scopes``
    (default: all of them and ``unnamed``) but not ``without``, in ``passes``
    (default: all three)."""
    steps = steps_of(result)
    if not steps:
        return None
    return scale * window.median([
        sum(ns for (scope, pas), ns in step.items()
            if (scopes is None or scope in scopes) and scope not in without
            and (passes is None or pas in passes))
        for step in steps])

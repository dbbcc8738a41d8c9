"""The device's idle time per traced step by what the run loop was doing,
from the program's own spans (``distar:loop/<phase>``, written by
``distar_tpu/obs/profiler.py::Spans``): on device 0, every instant of a gap
between device operations goes to the INNERMOST ``loop`` span open at it
(``unspanned`` when none is).

The window holds whole cycles only: from the first traced step's start to
the LAST one's start, that is N - 1 steps, each with the gaps inside it and
the one gap that follows it, and the sum is divided by N - 1. (A window to
the last step's end would hold N steps but N - 1 gaps between them, and its
value per step would move with the number of traced steps.)
A gap is split where the spans change: the long gap between two steps runs
from ``fetch`` through the host's tail, ``data_wait`` and ``pre_step`` into
``dispatch``, and giving it whole to the span at its midpoint (the issue's
first rule) puts all of it under a ``data_wait`` that lasts half of it."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import trace_meta, trace_reduce

ROLE = "loop/"
UNSPANNED = "unspanned"


def innermost(spans: Sequence[trace_meta.Span], t: float) -> str:
    """The phase of the shortest ``loop`` span open at ``t``."""
    best, best_len = UNSPANNED, float("inf")
    for s in spans:
        if s.start <= t < s.end and s.end - s.start < best_len:
            best, best_len = s.name[len(ROLE):], s.end - s.start
    return best


def idle_by_phase(busy: Sequence[trace_reduce.Interval],
                  spans: Sequence[trace_meta.Span]) -> Dict[str, float]:
    """ns of the gaps between the merged ``busy`` intervals, per phase."""
    out: Dict[str, float] = {}
    for (_, lo), (hi, _) in zip(busy, busy[1:]):
        cuts = sorted({lo, hi} | {t for s in spans for t in (s.start, s.end) if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            phase = innermost(spans, (a + b) / 2.0)
            out[phase] = out.get(phase, 0.0) + b - a
    return out


def whole_cycles(ops: Sequence[trace_meta.Op], runs: Sequence[trace_reduce.Interval],
                 spans: Sequence[trace_meta.Span]) -> Dict[str, float]:
    """ns of idle per phase from the first run's start to the last run's
    start: ``len(runs) - 1`` steps, each with the gap behind it."""
    if len(runs) < 2:
        return {}
    lo, hi = runs[0][0], runs[-1][0]
    busy = trace_reduce.union((max(o.start, lo), min(o.end, hi))
                              for o in ops if o.start < hi and o.end > lo)
    return idle_by_phase([(lo, lo)] + busy + [(hi, hi)], spans)  # the last gap ends where the last run starts


_idle: Dict[int, Tuple[Dict[str, float], int]] = {}


def idle_of(result) -> Optional[Tuple[Dict[str, float], int]]:
    meta = trace_meta.find(result)
    if meta is None:
        return None
    if id(meta) not in _idle:
        plane, runs = trace_meta.step_runs(result["events"])
        spans = [s for s in meta.spans if s.name.startswith(ROLE)]
        # one run has no whole cycle; a program without the span helper has no spans
        cycles = max(len(runs) - 1, 0) if spans else 0
        _idle[id(meta)] = (whole_cycles(meta.ops.get(plane, []), runs, spans), cycles)
    return _idle[id(meta)]


def read(result, phases: List[str], scale=1e-6):
    found = idle_of(result)
    if found is None or not found[1]:
        return None
    by_phase, cycles = found
    return scale * sum(by_phase.get(p, 0.0) for p in phases) / cycles

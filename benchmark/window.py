"""Metric arithmetic over a run's timeline: the yardstick's own.

Plain functions over lists of times, so that a hand-made timeline tests them.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    v = sorted(values)
    if not v:
        return None
    mid = len(v) // 2
    return v[mid] if len(v) % 2 else (v[mid - 1] + v[mid]) / 2.0


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100); None on no samples."""
    v = sorted(values)
    if not v:
        return None
    return v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))]


def step_window(step_times: Sequence[float], t_open: float, seconds: float) -> Dict:
    """Steps completed in a window that opens at ``t_open`` and closes at
    the last step completed within ``seconds`` of it. The rate divides by the
    time to that last step, so a step cut by the deadline costs nothing."""
    inside = [t for t in step_times if t_open < t <= t_open + seconds]
    if not inside:
        return {"steps": 0, "seconds": 0.0, "per_s": None}
    length = max(inside) - t_open
    return {"steps": len(inside), "seconds": length, "per_s": len(inside) / length}

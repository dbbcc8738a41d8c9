"""From a profiler trace to numbers: the benchmark's own reduction.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
``jax.profiler.ProfileData``, nothing but JAX) into plain tuples; everything
else works on those tuples, so the arithmetic is tested on hand-made
timelines as well as on the recorded trace under ``tests/benchmark/data``.

An event is ``(plane, line, name, start_ns, duration_ns)``. Device planes
are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed operation, ``XLA Modules`` one per program run, ``Steps`` one per
step. Host planes (``/host:CPU``) hold one line per thread with the
``TraceAnnotation`` spans the drivers write.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the spans the benchmark's drivers write; other host events (the python
# tracer's frames, runtime internals) never explain a gap
SPAN_PREFIX = "bench:"


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    events.append((plane.name, line.name, ev.name,
                                   float(ev.start_ns), float(ev.duration_ns)))
    return events


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(2)))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def op_intervals(events: Iterable[Event], plane: str, line: str = OPS_LINE) -> List[Tuple[str, float, float]]:
    return [(n, s, s + d) for p, l, n, s, d in events if p == plane and l == line]


def busy(events: Sequence[Event], window: Optional[Interval] = None) -> Dict:
    """Seconds in which an operation ran on the device, per device plane and
    averaged over the planes, inside ``window`` (default: from the first
    device operation's start to the last one's end)."""
    planes = device_planes(events)
    per_plane = {p: union((a, b) for _, a, b in op_intervals(events, p)) for p in planes}
    spans = [iv for ivs in per_plane.values() for iv in ivs]
    if not spans:
        return {"busy_s": 0.0, "window_s": 0.0, "per_device_busy_s": {}, "window": None}
    if window is None:
        window = (min(a for a, _ in spans), max(b for _, b in spans))
    clip = lambda ivs: [(max(a, window[0]), min(b, window[1])) for a, b in ivs]
    per = {p: total(union(clip(ivs))) / 1e9 for p, ivs in per_plane.items()}
    return {"busy_s": sum(per.values()) / len(per), "window_s": (window[1] - window[0]) / 1e9,
            "per_device_busy_s": per, "window": window}


def idle_pct(events: Sequence[Event], window: Optional[Interval] = None) -> Optional[float]:
    b = busy(events, window)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"]) if b["window_s"] > 0 else None


def host_spans(events: Iterable[Event]) -> List[Tuple[str, float, float]]:
    return [(n[len(SPAN_PREFIX):], s, s + d) for p, _, n, s, d in events
            if not DEVICE_PLANE.match(p) and n.startswith(SPAN_PREFIX)]


def idle_gaps(events: Sequence[Event], plane: Optional[str] = None, top: int = 10) -> List[List]:
    """The device's idle time by what the host was doing: every gap between
    device operations is given to the benchmark span that covers most of it
    (``no_span`` when none was open), and the seconds are summed per span."""
    planes = device_planes(events)
    if not planes:
        return []
    plane = plane or planes[0]
    merged = union((a, b) for _, a, b in op_intervals(events, plane))
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    spans = host_spans(events)
    by_span: Dict[str, float] = {}
    for lo, hi in gaps:
        best, best_cover = "no_span", 0.0
        for name, a, b in spans:
            cover = min(hi, b) - max(lo, a)
            # the innermost (shortest) of the spans that cover the most
            if cover > best_cover:
                best, best_cover = name, cover
        by_span[best] = by_span.get(best, 0.0) + (hi - lo) / 1e9
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]


def top_ops(events: Sequence[Event], plane: Optional[str] = None, top: int = 10) -> List[List]:
    """Device operations by summed duration over the traced window, under
    XLA's own names (an instruction inside a loop sums its trips)."""
    planes = device_planes(events)
    if not planes:
        return []
    plane = plane or planes[0]
    sums: Dict[str, float] = {}
    for name, a, b in op_intervals(events, plane):
        sums[short_name(name)] = sums.get(short_name(name), 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:top]]


def short_name(hlo: str) -> str:
    """``%fusion.24 = bf16[196608,32]{...} fusion(...), kind=kCustom`` ->
    ``fusion.24 bf16[196608,32] kCustom``: the trace names an operation by
    its whole HLO text."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?", hlo)
    if not m:
        return hlo[:80]
    kind = re.search(r"kind=(\w+)", hlo)
    return " ".join(x for x in (m.group(1), m.group(2), kind.group(1) if kind else "") if x)


def module_runs(events: Sequence[Event], plane: str, name_part: str = "") -> List[Interval]:
    """One interval per program run on ``plane`` (the ``XLA Modules`` line),
    optionally only modules whose name contains ``name_part``."""
    return sorted((a, b) for n, a, b in op_intervals(events, plane, MODULES_LINE)
                  if name_part in n)


def largest_module(events: Sequence[Event], plane: str) -> str:
    """The program that took most of the device's time: in a train cell, the step."""
    sums: Dict[str, float] = {}
    for n, a, b in op_intervals(events, plane, MODULES_LINE):
        key = re.sub(r"\(\d+\)$", "", n)
        sums[key] = sums.get(key, 0.0) + (b - a)
    return max(sums, key=sums.get) if sums else ""


def per_step(events: Sequence[Event], plane: Optional[str] = None) -> List[Dict]:
    """For every run of the device's largest program (one train step): the
    seconds its operations kept the device busy, and the run's length."""
    planes = device_planes(events)
    if not planes:
        return []
    plane = plane or planes[0]
    runs = module_runs(events, plane, largest_module(events, plane))
    ops = op_intervals(events, plane)
    return [{"busy_s": total(union((max(a, lo), min(b, hi)) for _, a, b in ops
                                   if a < hi and b > lo)) / 1e9,
             "module_s": (hi - lo) / 1e9} for lo, hi in runs]


def breakdown(events: Sequence[Event]) -> Dict:
    return {"device_ops": top_ops(events), "idle_gaps": idle_gaps(events)}


def describe(path: str, samples: int = 4) -> Dict:
    """Planes, lines and a few event names of a trace file: what to look at
    by hand before trusting the reduction on a new runtime."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"events": len(evs),
                                "first": [[e.name, e.start_ns, e.duration_ns] for e in evs[:samples]]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys

    _path = sys.argv[1] if sys.argv[1].endswith(".pb") else find_xplane(sys.argv[1])
    print(json.dumps(describe(_path), indent=1))
    _events = load(_path)
    print(json.dumps({"busy": {k: v for k, v in busy(_events).items() if k != "window"},
                      "idle_pct": idle_pct(_events), "per_step": per_step(_events),
                      **breakdown(_events)}, indent=1))

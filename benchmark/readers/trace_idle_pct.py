"""The device's idle share of the traced window: 1 - busy / window, busy
being the union of the device's operation intervals, averaged over chips."""
from benchmark import trace_reduce


def read(result):
    events = result.get("events")
    return trace_reduce.idle_pct(events) if events else None

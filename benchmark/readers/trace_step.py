"""Per run of the device's largest program (one train step), from the
device trace on device 0: ``busy_s`` or ``module_s``; the median over the
traced steps."""
from benchmark import trace_reduce, window


def read(result, field, scale=1.0):
    events = result.get("events")
    if not events:
        return None
    steps = trace_reduce.per_step(events)
    return scale * window.median([s[field] for s in steps]) if steps else None

"""A reduction (median, p95, mean) over the observations one of the
program's histograms took between window open and close."""
from benchmark import window


def read(result, metric, labels=None, reduce="median", scale=1.0):
    tap = result.get("tap")
    if tap is None or "open" not in tap.marks or "close" not in tap.marks:
        return None
    values = tap.observed_between(metric, "open", "close", labels)
    if not values:
        return None
    if reduce == "median":
        return scale * window.median(values)
    if reduce == "mean":
        return scale * sum(values) / len(values)
    return scale * window.percentile(values, float(reduce.lstrip("p")))

"""A counter of the program's registry as it stood when the window opened:
what set-up cost (compile seconds, cache misses)."""


def read(result, metric, labels=None, scale=1.0):
    tap = result.get("tap")
    if tap is None or "open" not in tap.marks:
        return None
    value = tap.value_at("open", metric, labels)
    # a counter nothing ever incremented was never created: that is a zero
    return scale * (value or 0.0)

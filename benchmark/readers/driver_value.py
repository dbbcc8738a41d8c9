"""A number the cell's driver took itself (from its own hooks or wrappers)
and left under ``values[key]``."""


def read(result, key, scale=1.0):
    value = result.get("values", {}).get(key)
    return None if value is None else scale * value

"""Per-layer metric readers, looked up by the ``reader`` name a metric file gives."""

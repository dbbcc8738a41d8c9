"""Traffic generators, looked up by the ``generator`` name a traffic file gives."""

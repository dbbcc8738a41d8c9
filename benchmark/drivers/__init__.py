"""Cell drivers, looked up by the ``driver`` name a workload file gives."""

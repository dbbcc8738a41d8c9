"""The on-chip benchmark of distar_tpu: the yardstick later PRs are held to.

Everything here is the benchmark's own (BENCHMARK.json lists this directory
under ``paths``): traffic generation, metric arithmetic, the trace
reduction, the peaks table, the FLOP count and the comparison that decides
``correct``. From the program it takes the system under test and the
metrics it already records by name. ``README.md`` says how a later PR adds
a cell, a configuration, a traffic mix, a per-layer metric or a driver as
files of its own.
"""

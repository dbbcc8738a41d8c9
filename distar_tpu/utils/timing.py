"""Timing helpers.

``EasyTimer`` mirrors the reference's CUDA-event-aware timer
(distar/ctools/utils/time_helper.py) — on TPU the analogue of a device sync
is ``jax.block_until_ready`` on the step outputs, which callers invoke before
leaving the timed region (the timer itself stays device-agnostic).
"""
from __future__ import annotations

import time


class EasyTimer:
    """Context-manager wall-clock timer: ``with timer: ...; timer.value``."""

    def __init__(self):
        self.value = 0.0
        self._start = 0.0

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.value = time.perf_counter() - self._start
        return False

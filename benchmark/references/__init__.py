"""Plain references. ``python3 -m benchmark.references`` computes the first
step's loss vector of a cell on the CPU (see ``__main__``); the module that
knows one learner's forward pass is looked up by the name the configuration's
file gives (``reference.first_step``). ``Beside`` is what a driver holds of
the reference process that runs beside its set-up."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WAIT_S = 240.0  # a run has 360 s in all; the reference took 47-58 s beside a set-up of 66 s


class Beside:
    def __init__(self, workload: str, seed: int, rehearse: bool, out_dir: str):
        self.path = os.path.join(out_dir, "reference.json")
        if os.path.exists(self.path):
            os.remove(self.path)
        cmd = [sys.executable, "-m", "benchmark.references", "--workload", workload,
               "--seed", str(seed), "--out", self.path] + (["--rehearse"] if rehearse else [])
        self._log = open(os.path.join(out_dir, "reference.log"), "w")
        # the chip belongs to the run: the reference sees the CPU and nothing else
        self._proc = subprocess.Popen(cmd, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                                      stdout=self._log, stderr=subprocess.STDOUT)
        self.waited_s = 0.0  # how long the run stood still for it: part of its set-up

    def wait(self) -> None:
        """Until the reference has ended, so that it shares no core with the
        measured window; one that takes longer than ``WAIT_S`` is stopped and
        gives nothing."""
        t = time.perf_counter()
        try:
            self._proc.wait(WAIT_S)
        except subprocess.TimeoutExpired:
            self.stop()
        self.waited_s = time.perf_counter() - t

    def stop(self) -> None:
        if self._proc.poll() is None:
            self._proc.kill()
        self._proc.wait()
        self._log.close()

    def result(self) -> Optional[Dict]:
        """What the reference wrote (``first_step``, ``seconds``), or None if
        it failed or was stopped."""
        if self._proc.poll() != 0 or not os.path.exists(self.path):
            return None
        with open(self.path) as f:
            return json.load(f)

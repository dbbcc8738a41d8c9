"""The plain reference of a cell's first step, computed beside the run.

  python3 -m benchmark.references --workload <cell> --seed <n> --out <file> [--rehearse]

The cell's driver starts this as a process of its own while the chip sets
up. Pinned to the CPU, it builds the configuration's learner from the same
seed on one device with the configuration's ``reference.model`` settings
(float32 compute, XLA's own attention and scatter, the step-by-step pointer
decode, no remat), draws the traffic's first batch from the same seed and
asks ``references/<reference.first_step>.py`` for the loss vector of the
untrained weights on that batch: forward pass and loss, no update. The same
seed gives the same weights and the same bytes on either backend, so the
run's first step has to report these numbers to within the cell's
tolerance (``check.matches_reference``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, device  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", action="store_true", help="at the configuration's tiny preset")
    args = p.parse_args(argv)

    device.require(1, rehearse=True)  # the CPU, whatever else the machine holds
    import jax

    from benchmark.drivers.learner import build_learner, sized
    from distar_tpu.parallel import MeshSpec, make_mesh
    from distar_tpu.utils import deep_merge_dicts

    cell = cells.load_cell(args.workload)
    ref = cell["config"]["reference"]
    size = sized(cell, args.rehearse)
    learner = build_learner(cell, deep_merge_dicts(size["model"], ref["model"]), size["traffic"],
                            args.seed, make_mesh(MeshSpec(), jax.devices()[:1]),
                            os.path.join(os.path.dirname(os.path.abspath(args.out)), "reference_run"))
    gen = cells.module("gen", cell["traffic"]["generator"])
    batch = gen.build(args.seed, dict(size["traffic"], pool=1), model_cfg=learner.model_cfg)[0]
    first = cells.module("references", ref["first_step"]).first_step(learner, batch)
    with open(args.out + ".tmp", "w") as f:
        json.dump({"cell": cell["name"], "seed": args.seed, "model": ref["model"],
                   "seconds": time.perf_counter() - T0, "first_step": first}, f)
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

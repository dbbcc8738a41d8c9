"""``gradients_on_chip`` for a token model that has no router (``phi4flash``).

  python3 -m benchmark.tools.gradients_on_chip_no_router --workload <cell> --seed <n> [--rehearse]

The tool the LFM2 cell brought catches the input of every expert layer's
router beside the program's loss and hands the picks to its CPU child; a
model without an expert layer gives it nothing to catch, and both of its
sides read what is not there (``caught["intermediates"]``, ``handed["picks"]``).
A PR may not edit that file, so this one stands in front of it: the same
``main``, the same child, the same comparison and the same last line, with the
program's side written without the catch. It hands the child one placeholder
under ``picks`` (no layer has that index; the reference takes ``picks`` and
reads nothing of it), so that the child's reader has something to iterate.
PERF.md section 7 names the two lines of the tool that would make this file
unnecessary.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402
from benchmark.tools import gradients_on_chip as tool  # noqa: E402


def program_side(cell, size, seed, plain, out_dir):
    """The program's log and gradient leaves on the first sequence of the traffic's first batch
    (``lm_learner.forward_loss`` on the learner's model: what the train step differentiates); its weights and
    that sequence go to ``handed.npz``."""
    import jax
    import numpy as np

    from benchmark.drivers.learner import build_learner
    from distar_tpu.learner import lm_learner
    from distar_tpu.parallel import MeshSpec, make_mesh

    learner = build_learner(cell, size["model"], size["traffic"], seed,
                            make_mesh(MeshSpec(), jax.devices()[:1]), os.path.join(out_dir, "run"))
    first = cells.module("gen", cell["traffic"]["generator"]).build(
        seed, dict(size["traffic"], pool=1), model_cfg=learner.model_cfg)[0]
    seq = {k: first[k][:1] for k in ("tokens", "labels")}
    variables = learner.state["params"]
    (_, info), grads = jax.jit(jax.value_and_grad(
        lambda params, variables, batch: lm_learner.forward_loss(learner.model, variables, params, batch),
        has_aux=True))(variables["params"], variables, learner._put(seq))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(plain.plain_config(learner.model_cfg), f)
    np.savez(os.path.join(out_dir, "handed.npz"),
             **tool.leaves_of({"variables": variables, "picks": {"-1": np.zeros((1, 1), bool)}, **seq}))
    return lm_learner._flat_log(jax.device_get(info), learner._moe_layers), tool.leaves_of(grads)


if __name__ == "__main__":
    tool.program_side = program_side
    sys.exit(tool.main())

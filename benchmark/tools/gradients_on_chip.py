"""The backward pass of a token-sequence cell's program against the plain
reference, on the chip, at the cell's own sizes, on one sequence.

  python3 -m benchmark.tools.gradients_on_chip --workload <cell> --seed <n> [--rehearse]

A cell's run compares its first step's loss and statistics with the
reference; the reference's gradients at the published widths cost three more
passes over the batch on the CPU and do not fit beside a run's set-up. The
kernels that exist only on the chip (flash attention's and the grouped
products' backward kernels) are therefore held to the reference here, apart
from the timed runs:

- this process builds the cell's learner on the chip as a run does and takes
  the gradient of the program's own loss (``learner/lm_learner.forward_loss``
  on the learner's model: what the train step differentiates) on the first
  sequence of the traffic's first batch;
- a child, pinned to the CPU like a run's reference process, is handed the
  learner's untrained weights and that sequence in a file and asks the
  configuration's reference module for the loss, statistics and gradients in
  float32, and again with every product's operands rounded to ``CONTROL``
  (the precision below the configurations' bfloat16): the control, which has
  to come out NOT correct;
- the loss and statistics are compared through ``check.off_reference`` with
  the cell's own limits (``correct.reference``), each side routing by its own
  router; every gradient leaf is compared as a vector, ``|g - g_ref| /
  |g_ref|``, against ``correct.reference.gradients`` (``rtol``, and
  ``rtol_under`` for the leaves of the expert layers), and for that the
  reference and the control route by the PROGRAM's picks (the router inputs
  of the very program that is differentiated, caught beside its loss, turned
  into picks by the reference module's ``router``): one pick in a hundred falls on the
  other side of a threshold in bfloat16, and a pick that differs is a whole
  row of another expert's gradient, which would hide what the backward
  kernels do behind what the forward pass is already held to. Some picks
  still differ (under remat the backward pass routes by a forward pass
  computed again, whose roundings are not the first one's; PERF.md section
  6), which is what the expert layers' own limit allows for.

The last line of stdout is a JSON object; the exit code is 0 when the program
agrees with the reference and the control does not. ``--rehearse`` runs the
configuration's tiny preset on the CPU (the tier-1 test of this file).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, check, device  # noqa: E402

CHILD_LIMIT_S = 900.0
CONTROL = "float8_e4m3fn"


def leaves_of(tree):
    """``{"params/layer_1/attention/q_proj/kernel": array}`` of a tree of dicts."""
    import jax
    import numpy as np

    return {"/".join(str(k.key) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def tree_of(leaves):
    tree = {}
    for path, x in leaves.items():
        *parents, last = path.split("/")
        node = tree
        for name in parents:
            node = node.setdefault(name, {})
        node[last] = x
    return tree


def reference_side(plain, out_dir) -> None:
    """The child: the float32 reference and its ``CONTROL`` run on the
    weights, sequence and picks of ``handed.npz``, to files."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    with open(os.path.join(out_dir, "config.json")) as f:
        cfg = json.load(f)
    with np.load(os.path.join(out_dir, "handed.npz")) as f:
        handed = tree_of({k: jnp.asarray(f[k]) for k in f.files})
    variables, tokens, labels = handed["variables"], handed["tokens"], handed["labels"]
    picks = {int(i): mask.astype(jnp.float32) for i, mask in handed["picks"].items()}
    for name, products_in in (("reference", None), ("control", CONTROL)):
        t = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            total, (_, stats) = plain.loss(variables["params"], variables, cfg, tokens, labels, products_in)
            stats = jax.device_get(stats)
            grads = plain.gradients(variables, cfg, tokens, labels, products_in, picks)
        np.savez(os.path.join(out_dir, f"{name}.npz"), **leaves_of(grads))
        differ = {f"layer_{i}": float(np.abs(own - np.asarray(picks[i])).sum() / 2)
                  for i, own in zip(sorted(picks), stats.pop("picks"))}
        with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
            json.dump({"first_step": plain.named(total, stats, cfg), "picks_that_differ": differ,
                       "seconds": time.perf_counter() - t}, f)


def program_side(cell, size, seed, plain, out_dir):
    """The program's log and gradient leaves on the first sequence; its
    weights, that sequence and its routers' picks go to ``handed.npz``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers.learner import build_learner
    from distar_tpu.learner import lm_learner
    from distar_tpu.losses import compute_lm_loss
    from distar_tpu.parallel import MeshSpec, make_mesh

    learner = build_learner(cell, size["model"], size["traffic"], seed,
                            make_mesh(MeshSpec(), jax.devices()[:1]), os.path.join(out_dir, "run"))
    gen = cells.module("gen", cell["traffic"]["generator"])
    first = gen.build(seed, dict(size["traffic"], pool=1), model_cfg=learner.model_cfg)[0]
    seq = {k: first[k][:1] for k in ("tokens", "labels")}
    variables, cfg = learner.state["params"], plain.plain_config(learner.model_cfg)

    def loss(params, variables, batch):
        """``lm_learner.forward_loss``'s model and loss, and beside them the
        input of every expert layer's router as THIS program computes it:
        another program's rounds otherwise here and there, and its picks
        are not quite this one's."""
        (logits, stats), caught = learner.model.apply(
            {**variables, "params": params}, batch["tokens"], mutable=["intermediates"],
            capture_intermediates=lambda module, _: module.name == "norm")
        total, info = compute_lm_loss(logits, batch["labels"])
        return total, (dict(info, **stats), caught["intermediates"])

    (_, (info, inputs)), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"], variables, learner._put(seq))
    picks = {}
    with jax.default_matmul_precision("highest"):
        for layer, caught in inputs.items():
            u = caught["moe"]["norm"]["__call__"][0]
            moe, bias = variables["params"][layer]["moe"], variables["buffers"][layer]["moe"]["expert_bias"]
            picks[layer.split("_")[1]] = plain.router(
                moe, bias, u.astype(jnp.float32).reshape(-1, u.shape[-1]), cfg)[1] > 0
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    np.savez(os.path.join(out_dir, "handed.npz"),
             **leaves_of({"variables": variables, "picks": picks, **seq}))
    return lm_learner._flat_log(jax.device_get(info), learner._moe_layers), leaves_of(grads)


def off_by_leaf(got, want):
    import numpy as np

    return {k: float(np.linalg.norm(got[k] - want[k]) / max(np.linalg.norm(want[k]), 1e-30)) for k in want}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rehearse", action="store_true", help="at the configuration's tiny preset, on the CPU")
    p.add_argument("--reference-into", default="", help=argparse.SUPPRESS)  # the child's side
    args = p.parse_args(argv)

    from benchmark.drivers.learner import sized

    cell = cells.load_cell(args.workload)
    plain = cells.module("references", cell["config"]["reference"]["first_step"])
    if args.reference_into:
        device.require(1, rehearse=True)  # the CPU, whatever else the machine holds
        reference_side(plain, args.reference_into)
        return 0

    import numpy as np

    out_dir = os.path.join(ROOT, "benchmark_out", cell["name"] + ("_rehearsal" if args.rehearse else ""),
                           "gradients")
    os.makedirs(out_dir, exist_ok=True)
    device.require(cell["chips"], args.rehearse)
    t = time.perf_counter()
    size = sized(cell, args.rehearse)
    log, grads = program_side(cell, size, args.seed, plain, out_dir)
    program_s = time.perf_counter() - t
    with open(os.path.join(out_dir, "reference.log"), "w") as log_file:
        child = subprocess.run(
            [sys.executable, "-m", "benchmark.tools.gradients_on_chip", "--workload", args.workload,
             "--seed", str(args.seed), "--reference-into", out_dir], timeout=CHILD_LIMIT_S,
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=log_file, stderr=subprocess.STDOUT)
    if child.returncode != 0:
        print(f"the reference process ended with {child.returncode}: see {out_dir}/reference.log", file=sys.stderr)
        return 1

    want = cell["correct"]["reference"]
    limits = {k: v for k, v in want.items() if k in ("keys", "rtol", "rtol_of")}
    said = {}
    for name in ("reference", "control"):
        with open(os.path.join(out_dir, f"{name}.json")) as f:
            said[name] = json.load(f)
    with np.load(os.path.join(out_dir, "reference.npz")) as f:
        ref_grads = dict(f)
    with np.load(os.path.join(out_dir, "control.npz")) as f:
        by_leaf = {"program": off_by_leaf(grads, ref_grads), "control": off_by_leaf(dict(f), ref_grads)}
    out = {"cell": cell["name"], "seed": args.seed, "positions": int(size["traffic"]["unroll_len"]),
           "device": device.describe(),
           "seconds": {"program": program_s, **{name: said[name]["seconds"] for name in said}},
           "gradients_rtol": want["gradients"]["rtol"], "gradients_rtol_under": want["gradients"]["rtol_under"],
           "picks_that_differ": {"program": said["reference"]["picks_that_differ"],
                                 "control": said["control"]["picks_that_differ"]}}
    under = want["gradients"]["rtol_under"]  # part of a leaf's path -> its own limit
    limit = lambda leaf: next((v for part, v in under.items() if part in leaf), want["gradients"]["rtol"])
    for name, first in (("program", log), ("control", said["control"]["first_step"])):
        off = check.off_reference(first, said["reference"]["first_step"], **limits)
        worst = max(by_leaf[name], key=by_leaf[name].get)
        out[name] = {"first_step_off": off, "gradient_off_largest": by_leaf[name][worst], "at": worst,
                     "gradient_leaves_off": sorted(k for k, v in by_leaf[name].items() if not v <= limit(k)),
                     "gradient_off_by_leaf": by_leaf[name]}
        out[name]["correct"] = not off and not out[name]["gradient_leaves_off"]
    out["ok"] = out["program"]["correct"] and not out["control"]["correct"]
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Required FLOPs per frame of a train step, from shapes.

The count walks the forward pass as the model code writes it, traced
abstractly at the configuration's shapes (nothing runs, nothing compiles),
and sums the multiply-adds of its matrix multiplications and convolutions:
``dot_general`` and ``conv_general_dilated``, times the trip count inside a
``scan``. Elementwise work, softmaxes, gathers and the loss are left out:
they are a few percent of the step and not what a peak FLOP/s is a peak of.
A step requires three times its forward pass (the backward pass costs two
forwards: one product for the activations' gradient, one for the weights');
what rematerialisation or a fused loop recomputes is not required work and
is not counted. ``cost_analysis()`` of the compiled step is not used: with
two 64-step scans and recompute it counts what XLA executes, not what the
algorithm needs.

The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file (``required_flops_per_frame``), made by this module
when the configuration was added:

  JAX_PLATFORMS=cpu python -m benchmark.flops --config distar_sl_flagship

so that a later change to the model code cannot move the yardstick. What
the count takes from the program is the forward pass itself: where the
program multiplies by a one-hot matrix instead of gathering, the count has
that product in it.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    size = lambda shape, dims: float(np.prod([shape[i] for i in dims])) if dims else 1.0
    free = lambda shape, used: float(np.prod([d for i, d in enumerate(shape) if i not in used]))
    return (2.0 * size(lhs, lb) * size(lhs, lc)
            * free(lhs, tuple(lc) + tuple(lb)) * free(rhs, tuple(rc) + tuple(rb)))


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    out_ch = rhs[dn.rhs_spec[0]]
    # every output element sums over the kernel's window and its input channels
    per_out = np.prod(rhs) / out_ch
    return 2.0 * float(np.prod(out)) * float(per_out) / float(eqn.params.get("batch_group_count", 1))


def jaxpr_flops(jaxpr) -> float:
    """Matmul and convolution FLOPs of a (closed) jaxpr, loops unrolled."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * jaxpr_flops(eqn.params["jaxpr"])
        elif name == "cond":
            total += max(jaxpr_flops(b) for b in eqn.params["branches"])
        elif name == "while":
            raise ValueError("a while loop has no trip count to read from shapes")
        else:
            for v in eqn.params.values():
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    total += jaxpr_flops(v)
    return total


def forward_flops(fn, *args) -> float:
    """FLOPs of ``fn(*args)``; ``args`` may be ``ShapeDtypeStruct`` trees."""
    import jax

    return jaxpr_flops(jax.make_jaxpr(fn)(*args))


def _specs(tree):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.asarray(x).shape, jax.dtypes.canonicalize_dtype(np.asarray(x).dtype)), tree)


def sl_forward(model_cfg, batch_size: int, unroll_len: int):
    """The SL forward pass (``Model.sl_forward``) and abstract arguments for
    it: ``(fn, args)`` with ``fn(params, batch, hidden)``."""
    import jax
    import jax.numpy as jnp

    from distar_tpu.learner.data import fake_sl_batch
    from distar_tpu.model import Model

    model = Model(model_cfg)
    batch = fake_sl_batch(batch_size, 1)
    batch = jax.tree.map(lambda x: np.repeat(x, unroll_len, 0) if np.ndim(x) else x,
                         {k: v for k, v in batch.items() if k not in ("new_episodes", "traj_lens")})
    batch = _specs(batch)
    core = model_cfg["encoder"]["core_lstm"]
    h = jax.ShapeDtypeStruct((batch_size, core["hidden_size"]), jnp.float32)
    hidden = tuple((h, h) for _ in range(core["num_layers"]))

    def fn(params, b, hid):
        return model.apply(params, b["spatial_info"], b["entity_info"], b["scalar_info"],
                           b["entity_num"], b["action_info"], b["selected_units_num"], hid,
                           batch_size, method=model.sl_forward)

    params = jax.eval_shape(
        lambda r, b, hid: model.init(
            r, b["spatial_info"], b["entity_info"], b["scalar_info"], b["entity_num"],
            b["action_info"], b["selected_units_num"], hid, batch_size, method=model.sl_forward),
        jax.random.PRNGKey(0), batch, hidden)
    return fn, (params, batch, hidden)


def rl_forward(model_cfg, batch_size: int, unroll_len: int):
    """The RL forward pass (``Model.rl_forward``: policy and value towers)."""
    import jax

    from distar_tpu.learner.data import fake_rl_batch
    from distar_tpu.learner.rl_learner import _flatten_time
    from distar_tpu.model import Model

    model_cfg = dict(model_cfg, use_value_network=True)
    model = Model(model_cfg)
    core = model_cfg["encoder"]["core_lstm"]
    one = fake_rl_batch(batch_size, 1, hidden_size=core["hidden_size"],
                        hidden_layers=core["num_layers"])
    hidden = one.pop("hidden_state")
    one.pop("model_last_iter")

    def stretch(x):  # [1(+1), B, ...] -> [T(+1), B, ...]
        x = np.asarray(x)
        return jax.ShapeDtypeStruct((x.shape[0] - 1 + unroll_len,) + x.shape[1:],
                                    jax.dtypes.canonicalize_dtype(x.dtype))

    batch = jax.tree.map(stretch, one)
    hidden = _specs(hidden)

    def fn(params, b, hid):
        return model.apply(
            params, *(_flatten_time(b[k]) for k in ("spatial_info", "entity_info", "scalar_info")),
            b["entity_num"].reshape(-1), hid, b["action_info"], b["selected_units_num"],
            batch_size, unroll_len, value_feature=None, method=model.rl_forward)

    params = jax.eval_shape(
        lambda r, b, hid: model.init(
            r, *(_flatten_time(b[k]) for k in ("spatial_info", "entity_info", "scalar_info")),
            b["entity_num"].reshape(-1), hid, b["action_info"], b["selected_units_num"],
            batch_size, unroll_len, value_feature=None, method=model.rl_forward),
        jax.random.PRNGKey(0), batch, hidden)
    return fn, (params, batch, hidden)


FORWARDS = {"sl_step": sl_forward, "rl_step": rl_forward}


def required_per_frame(which: str, model_cfg, batch_size: int, unroll_len: int) -> Dict[str, float]:
    """Forward FLOPs per frame and the step's required FLOPs per frame (3x)."""
    fn, args = FORWARDS[which](model_cfg, batch_size, unroll_len)
    fwd = forward_flops(fn, *args) / (batch_size * unroll_len)
    return {"forward": fwd, "step": 3.0 * fwd}


if __name__ == "__main__":
    import argparse
    import json
    import os

    from benchmark import cells
    from distar_tpu.model import default_model_config
    from distar_tpu.utils import deep_merge_dicts, read_config

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--batch-size", type=int, default=0)
    a = p.parse_args()
    cfg = cells.load("configs", a.config)
    program = read_config(os.path.join(cells.ROOT, cfg["program_file"]))
    model_cfg = deep_merge_dicts(default_model_config(), program.get("model", {}))
    lc = cfg["as_run"]["learner"]
    print(json.dumps(required_per_frame(
        cfg["flops"], model_cfg, a.batch_size or lc["batch_size"], lc["unroll_len"])))

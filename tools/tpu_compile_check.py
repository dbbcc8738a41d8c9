"""Compile the flagship programs for a DESCRIBED TPU, without one attached.

The rehearsal to make before spending chip time (guide on-chip-measurement
§2.3): the TPU compiler is installed here, so whatever it would refuse on
the chip — a program that does not fit HBM, a kernel it cannot lower, a
sharding it cannot partition — it refuses here, for free. Nothing runs, so
this says nothing about results or times, and a compile that passes is not
a chip run.

Programs (shapes, dtype and batch from the committed configs that
``chip_smoke.py`` runs):

  sl     the SL train step (fwd + loss + bwd + Adam + dynamics tree), built
         by ``make_sl_train_step`` as ``SLLearner`` builds it
  rl     the RL train step (teacher KL, six baselines), ``make_rl_train_step``
  actor  ``sample_action`` and ``teacher_logits`` at the actor's env batch
  lm     the token-sequence train step (``make_lm_train_step`` as ``LMLearner``
         builds it) of ``configs/lfm2_24b_a2b_v5e.yaml``
  nh     the same step of ``configs/nemotron_twotower_30b_a3b_v5e.yaml``
  kimi   the same step of ``configs/kimi_vl_a3b_v5e.yaml``
  qwen   the same step of ``configs/qwen3_next_80b_a3b_v5e.yaml``
  laguna the same step of ``configs/laguna_s_v5e.yaml``
  phi4flash  the same step of ``configs/phi4_mini_flash_v5e.yaml``

Usage (CPU sandbox; minutes per step program, so not a tier-1 test):
  JAX_PLATFORMS=cpu python tools/tpu_compile_check.py --what sl,rl,actor
  JAX_PLATFORMS=cpu python tools/tpu_compile_check.py --what sl \\
      --batch-size 4 --mesh dp=2,fsdp=2        # four chips, per-device bytes

Prints one JSON line per program: trace / compile seconds and
``memory_analysis()`` bytes (per device). ``--hlo-dir DIR`` also writes each
compiled program's text (``compiled.as_text()``: layouts, the compiler's own
loops and copies, collectives) to ``DIR/<program>.hlo.txt``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SL_CONFIG = os.path.join(REPO, "configs", "sl_flagship_v5e.yaml")
RL_CONFIG = os.path.join(REPO, "configs", "rl_flagship_v5e.yaml")
# the token-sequence steps (``check_lm``), by ``--what``
LM_CONFIGS = {"lm": os.path.join(REPO, "configs", "lfm2_24b_a2b_v5e.yaml"),
              "nh": os.path.join(REPO, "configs", "nemotron_twotower_30b_a3b_v5e.yaml"),
              "kimi": os.path.join(REPO, "configs", "kimi_vl_a3b_v5e.yaml"),
              "qwen": os.path.join(REPO, "configs", "qwen3_next_80b_a3b_v5e.yaml"),
              "laguna": os.path.join(REPO, "configs", "laguna_s_v5e.yaml"),
              "phi4flash": os.path.join(REPO, "configs", "phi4_mini_flash_v5e.yaml")}


def _specs(tree, sharding):
    """Host batch -> ShapeDtypeStructs as ``jnp.asarray`` would type them."""
    import jax
    import numpy as np

    def spec(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(
            x.shape, jax.dtypes.canonicalize_dtype(x.dtype), sharding=sharding)

    return jax.tree.map(spec, tree)


def _with_sharding(shapes, shardings):
    import jax

    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


HLO_DIR = ""  # --hlo-dir


def _report(name, jitted, args, extra):
    t0 = time.monotonic()
    lowered = jitted.lower(*args)
    t1 = time.monotonic()
    compiled = lowered.compile()
    t2 = time.monotonic()
    mem = compiled.memory_analysis()
    row = {
        "program": name, **extra,
        "trace_lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
    }
    # donated arguments are aliased to outputs: count them once
    row["total_bytes"] = (row["argument_bytes"] + row["temp_bytes"]
                          + row["output_bytes"] - row["alias_bytes"])
    print(json.dumps(row), flush=True)
    if HLO_DIR:
        os.makedirs(HLO_DIR, exist_ok=True)
        with open(os.path.join(HLO_DIR, f"{name}.hlo.txt"), "w") as f:
            f.write(compiled.as_text())
    return compiled


def _learner_setup(topo, cfg, defaults, batch_size, mesh_spec):
    """What a learner's constructor would settle, without constructing one
    (it runs real compute): the merged learner config, the optimizer and
    dynamics spec exactly as ``BaseLearner`` builds them, the mesh over the
    described devices and the merged model config."""
    from distar_tpu.learner.base_learner import BaseLearner
    from distar_tpu.model import default_model_config
    from distar_tpu.parallel import MeshSpec, make_mesh
    from distar_tpu.utils import deep_merge_dicts

    user = dict(cfg.get("learner", {}))
    if batch_size:
        user["batch_size"] = batch_size
    shim = types.SimpleNamespace(cfg=deep_merge_dicts(defaults, {"learner": user}))
    spec = MeshSpec.parse(mesh_spec or "dp=1")
    mesh = make_mesh(spec, topo.devices[:spec.dp * spec.fsdp * spec.tp * spec.sp])
    model_cfg = deep_merge_dicts(default_model_config(), cfg.get("model", {}))
    return (shim.cfg.learner, BaseLearner._build_optimizer(shim),
            BaseLearner._dynamics_spec(shim), mesh, model_cfg)


def check_sl(topo, cfg, batch_size, mesh_spec):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distar_tpu.learner.data import fake_sl_batch
    from distar_tpu.learner.sl_learner import SL_LEARNER_DEFAULTS, make_sl_train_step
    from distar_tpu.losses import SupervisedLossConfig
    from distar_tpu.model import Model
    from distar_tpu.parallel.mesh import batch_sharding, fsdp_param_sharding

    lc, optimizer, dynamics, mesh, model_cfg = _learner_setup(
        topo, cfg, SL_LEARNER_DEFAULTS, batch_size, mesh_spec)
    B, T = lc.batch_size, lc.unroll_len
    model = Model(model_cfg)
    flat = batch_sharding(mesh, batch_size=B)
    repl = NamedSharding(mesh, P())

    batch = fake_sl_batch(B, T)
    batch.pop("new_episodes")
    batch.pop("traj_lens")
    batch = _specs(batch, flat)
    core = model_cfg.encoder.core_lstm
    h = jax.ShapeDtypeStruct((B, core.hidden_size), jnp.float32, sharding=flat)
    hidden = tuple((h, h) for _ in range(core.num_layers))

    params = jax.eval_shape(
        lambda rng, b, hid: model.init(
            rng, b["spatial_info"], b["entity_info"], b["scalar_info"],
            b["entity_num"], b["action_info"], b["selected_units_num"], hid, B,
            method=model.sl_forward),
        jax.random.PRNGKey(0), batch, hidden)
    param_sh = fsdp_param_sharding(mesh, params)
    opt = jax.eval_shape(optimizer.init, params)
    opt_sh = fsdp_param_sharding(mesh, opt)
    step = jax.jit(
        make_sl_train_step(model, SupervisedLossConfig(label_smooth=lc.label_smooth),
                           optimizer, B, dynamics=dynamics),
        donate_argnums=(0, 1), out_shardings=(param_sh, opt_sh, flat, repl))
    return _report(
        "sl_train_step", step,
        (_with_sharding(params, param_sh), _with_sharding(opt, opt_sh), batch, hidden),
        {"batch": B, "unroll": T, "dtype": model_cfg.dtype, "mesh": dict(mesh.shape)})


def check_rl(topo, cfg, batch_size, mesh_spec):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distar_tpu.learner.data import fake_rl_batch
    from distar_tpu.learner.rl_learner import (
        RL_LEARNER_DEFAULTS, _flatten_time, make_loss_config, make_rl_train_step,
    )
    from distar_tpu.model import Model
    from distar_tpu.parallel.mesh import (
        batch_sharding, fsdp_param_sharding, time_batch_sharding,
    )

    lc, optimizer, dynamics, mesh, model_cfg = _learner_setup(
        topo, cfg, RL_LEARNER_DEFAULTS, batch_size, mesh_spec)
    B, T = lc.batch_size, lc.unroll_len
    model_cfg.use_value_network = True
    model = Model(model_cfg)
    core = model_cfg.encoder.core_lstm
    flat = batch_sharding(mesh, batch_size=B)
    tb = time_batch_sharding(mesh)
    repl = NamedSharding(mesh, P())

    batch = fake_rl_batch(B, T, hidden_size=core.hidden_size,
                          hidden_layers=core.num_layers,
                          use_value_feature=model_cfg.use_value_feature)
    batch.pop("model_last_iter")
    hidden = batch.pop("hidden_state")
    batch = _specs(batch, tb)  # every leaf is [T(+1), B, ...]
    batch["hidden_state"] = _specs(hidden, flat)

    def init(rng, b):
        vf = b.get("value_feature")
        return model.init(
            rng,
            *(_flatten_time(b[k]) for k in ("spatial_info", "entity_info", "scalar_info")),
            b["entity_num"].reshape(-1), b["hidden_state"], b["action_info"],
            b["selected_units_num"], B, T,
            value_feature=_flatten_time(vf) if vf is not None else None,
            method=model.rl_forward)

    params = jax.eval_shape(init, jax.random.PRNGKey(0), batch)
    param_sh = fsdp_param_sharding(mesh, params)
    opt = jax.eval_shape(optimizer.init, params)
    opt_sh = fsdp_param_sharding(mesh, opt)
    step = jax.jit(
        make_rl_train_step(model, make_loss_config(lc), optimizer, B, T,
                           dynamics=dynamics),
        donate_argnums=(0, 1), out_shardings=(param_sh, opt_sh, repl))
    only_value = jax.ShapeDtypeStruct((), jnp.bool_, sharding=repl)
    return _report(
        "rl_train_step", step,
        (_with_sharding(params, param_sh), _with_sharding(opt, opt_sh), batch, only_value),
        {"batch": B, "unroll": T, "dtype": model_cfg.dtype, "mesh": dict(mesh.shape)})


def check_lm(topo, cfg, batch_size, mesh_spec):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distar_tpu.learner.lm_learner import LM_LEARNER_DEFAULTS, make_lm_train_step
    from distar_tpu.model import TOKEN_MODELS
    from distar_tpu.parallel.mesh import batch_sharding, fsdp_param_sharding
    from distar_tpu.utils import deep_merge_dicts

    lc, optimizer, dynamics, mesh, _ = _learner_setup(
        topo, cfg, LM_LEARNER_DEFAULTS, batch_size, mesh_spec)
    model_cls, defaults = TOKEN_MODELS[cfg.get("model", {}).get("model_type", "lfm2_moe")]
    model_cfg = deep_merge_dicts(defaults(), cfg.get("model", {}))
    B, S = lc.batch_size, lc.unroll_len
    model = model_cls(model_cfg)
    flat = batch_sharding(mesh, batch_size=B)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=flat)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    param_sh = fsdp_param_sharding(mesh, variables)
    opt = jax.eval_shape(optimizer.init, variables["params"])
    opt_sh = fsdp_param_sharding(mesh, opt)
    step = jax.jit(make_lm_train_step(model, optimizer, dynamics=dynamics),
                   donate_argnums=(0, 1),
                   out_shardings=(param_sh, opt_sh, NamedSharding(mesh, P())))
    n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
    return _report(
        "lm_train_step", step,
        (_with_sharding(variables, param_sh), _with_sharding(opt, opt_sh),
         {"tokens": tokens, "labels": tokens}),
        {"batch": B, "seq": S, "dtype": model_cfg.dtype, "params": n_params,
         "mesh": dict(mesh.shape)})


def check_actor(topo, cfg):
    """The two programs ``actor.inference.BatchedInference`` jits, at the
    actor's env batch, on one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from distar_tpu.lib import features as F
    from distar_tpu.model import Model, default_model_config
    from distar_tpu.utils import deep_merge_dicts

    n = int(cfg.get("actor", {}).get("env_num", 2))
    one = SingleDeviceSharding(topo.devices[0])
    model_cfg = deep_merge_dicts(default_model_config(), cfg.get("model", {}))
    model_cfg.use_value_network = False
    model = Model(model_cfg)
    core = model_cfg.encoder.core_lstm
    obs = _specs(F.batch_tree([F.fake_step_data(train=False)] * n), one)
    h = jax.ShapeDtypeStruct((n, core.hidden_size), jnp.float32, sharding=one)
    hidden = tuple((h, h) for _ in range(core.num_layers))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)

    def sample(p, d, hid, r):
        return model.apply(p, d["spatial_info"], d["entity_info"], d["scalar_info"],
                           d["entity_num"], hid, r, method=model.sample_action)

    params = jax.eval_shape(
        lambda r, d, hid, k: model.init(
            r, d["spatial_info"], d["entity_info"], d["scalar_info"],
            d["entity_num"], hid, k, method=model.sample_action),
        jax.random.PRNGKey(0), obs, hidden, jax.random.PRNGKey(1))
    on_one = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)
    params = on_one(params)
    extra = {"env_num": n, "dtype": model_cfg.dtype}
    _report("actor_sample_action", jax.jit(sample), (params, obs, hidden, key), extra)

    out = jax.eval_shape(sample, params, obs, hidden, key)
    action = on_one((out["action_info"], out["selected_units_num"]))

    def teacher(p, d, hid, a, sun):
        return model.apply(p, d["spatial_info"], d["entity_info"], d["scalar_info"],
                           d["entity_num"], hid, a, sun, method=model.teacher_logits)

    _report("actor_teacher_logits", jax.jit(teacher),
            (params, obs, hidden, *action), extra)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--what", default="sl,rl,actor")
    p.add_argument("--topology", default="v5e:2x2")
    p.add_argument("--mesh", default="",
                   help="sl/rl only: compile for this mesh over the topology's "
                        "devices (default: one device)")
    p.add_argument("--batch-size", type=int, default=0,
                   help="sl/rl/lm only: override the config's batch")
    p.add_argument("--hlo-dir", default="",
                   help="write each compiled program's text here")
    args = p.parse_args()
    global HLO_DIR
    HLO_DIR = args.hlo_dir

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from distar_tpu.utils import read_config

    # a described-device compile is written to the persistent cache but can
    # never be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    for what in args.what.split(","):
        if what == "sl":
            check_sl(topo, read_config(SL_CONFIG), args.batch_size, args.mesh)
        elif what == "rl":
            check_rl(topo, read_config(RL_CONFIG), args.batch_size, args.mesh)
        elif what == "actor":
            check_actor(topo, read_config(RL_CONFIG))
        elif what in LM_CONFIGS:
            check_lm(topo, read_config(LM_CONFIGS[what]), args.batch_size, args.mesh)
        else:
            raise SystemExit(f"unknown program {what!r} (sl, rl, actor, lm, nh, kimi, qwen, laguna, phi4flash)")


if __name__ == "__main__":
    main()

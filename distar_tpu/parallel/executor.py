"""Executed multi-chip sharded training: mesh spec in, trained steps out.

This is the subsystem entry the rest of the repo drives:

* ``__graft_entry__.dryrun_multichip`` is a thin wrapper over
  ``run_sharded_training`` (the "dryrun" IS the production path now — same
  learner, same feeder, same shardings);
* ``tools/chaos.py multichip-drill`` runs it as kill/resume children with
  sharded checkpoints across DIFFERENT mesh shapes;
* ``tests/test_parallel_exec.py`` runs it as the tier-1 smoke.

``select_backend`` is the one place the launchers choose a JAX platform and
place the compile cache; ``force_host_devices`` stands up the virtual
n-device CPU platform for multi-chip runs without chips.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional, Union

from .mesh import MeshSpec

# tiny flagship-shaped model: compiles in seconds on CPU, exercises every
# head/encoder the full model has (same shape tests/conftest.py exports)
SMOKE_MODEL = {
    "encoder": {
        "entity": {"layer_num": 1, "hidden_dim": 32, "output_dim": 16, "head_dim": 8},
        "spatial": {"down_channels": [4, 4, 8], "project_dim": 4, "resblock_num": 1, "fc_dim": 16},
        "scatter": {"output_dim": 4},
        "core_lstm": {"hidden_size": 32, "num_layers": 1},
    },
    "policy": {
        "action_type_head": {"res_dim": 16, "res_num": 1, "gate_dim": 32},
        "delay_head": {"decode_dim": 16},
        "queued_head": {"decode_dim": 16},
        "selected_units_head": {"func_dim": 16},
        "target_unit_head": {"func_dim": 16},
        "location_head": {"res_dim": 8, "res_num": 1, "upsample_dims": [4, 4, 1], "map_skip_dim": 8},
    },
    "value": {"res_dim": 8, "res_num": 1},
}


def select_backend(platform: str = "auto", host_devices: int = 0,
                   distributed: Optional[dict] = None) -> dict:
    """The launchers' ``--platform`` / ``--host-devices``: ``auto`` leaves the
    choice to JAX (``JAX_PLATFORMS`` is honoured), ``cpu``/``tpu`` pin it, and
    ``tpu`` raises when no TPU answers instead of training on the CPU. Must
    run before anything else touches a jax backend. ``distributed`` (kwargs
    of ``dist.dist_init``) joins the multi-host runtime at the one moment
    that works: after the platform is pinned, before the first backend
    query. Returns rank/world_size."""
    if host_devices:
        force_host_devices(host_devices)
        return {"rank": 0, "world_size": 1}
    import jax

    from ..utils.compile_cache import configure
    from .dist import dist_init

    if platform != "auto":
        jax.config.update("jax_platforms", platform)
    info = dist_init(**(distributed or {"method": "single_node"}))
    configure()
    if platform == "tpu" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"--platform tpu, but the jax backend is {jax.default_backend()!r}")
    return info


def force_host_devices(n_devices: int) -> None:
    """Pin a virtual n-device CPU platform BEFORE any jax backend init.

    Must run before the first device query in the process. Raises when the
    backend was already initialised with fewer devices (the caller forked
    too late)."""
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, flags
        )
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()

    import jax

    from ..utils.compile_cache import configure

    jax.config.update("jax_platforms", "cpu")
    configure()
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"virtual CPU mesh has {len(jax.devices())} devices, need "
            f"{n_devices}; the jax backend was initialised before "
            "force_host_devices ran"
        )


def run_sharded_training(
    mesh_spec: Union[str, MeshSpec],
    *,
    iters: int = 2,
    batch_size: Optional[int] = None,
    unroll_len: int = 2,
    model_cfg: Optional[dict] = None,
    experiment_name: str = "sharded_executor",
    save_dir: str = "",
    sharded_ckpt: bool = True,
    save_freq: int = 10 ** 9,
    resume: bool = False,
    kill_after_iter: Optional[int] = None,
    assert_fsdp: bool = False,
    assert_tp: bool = False,
    max_devices: Optional[int] = None,
) -> Dict[str, Any]:
    """Build a live mesh from ``mesh_spec``, train an RLLearner on it with
    the full executed path (GSPMD jitted step, ShardFeeder double-buffered
    feeding, sharded checkpoints), and return a structural report.

    ``resume`` restores from the save_dir's durable latest pointer first —
    across DIFFERENT mesh shapes (the resharding restore). ``kill_after_iter``
    is the chaos hook: after that iteration's hooks ran, force a durable
    sharded save and ``os._exit(137)`` — the parent supervises the restart.
    """
    import jax

    from ..learner import RLLearner
    from .mesh import make_mesh

    spec = MeshSpec.parse(mesh_spec) if not isinstance(mesh_spec, MeshSpec) else mesh_spec
    if max_devices is None and spec.dp != -1:
        # explicit spec: take exactly the devices it names ("dp=2" on an
        # 8-device host is a 2-chip mesh, not a config error)
        max_devices = spec.dp * spec.fsdp * spec.tp * spec.sp
    devices = jax.devices()[:max_devices] if max_devices else None
    mesh = make_mesh(spec, devices)
    n_dp = mesh.shape["dp"] * mesh.shape["fsdp"]
    B = batch_size if batch_size is not None else max(n_dp, 2)
    cfg = {
        "common": {"experiment_name": experiment_name,
                   **({"save_path": save_dir} if save_dir else {})},
        "learner": {
            "batch_size": B,
            "unroll_len": unroll_len,
            "save_freq": save_freq,
            "log_freq": 10 ** 9,
            "sharded_ckpt": sharded_ckpt,
        },
        "model": model_cfg if model_cfg is not None else SMOKE_MODEL,
    }
    learner = RLLearner(cfg, mesh=mesh)

    report: Dict[str, Any] = {
        "mesh": dict(learner.mesh.shape),
        "batch_size": B,
        "unroll_len": unroll_len,
        "devices": len(jax.devices()),
        "sharded_ckpt": sharded_ckpt,
        "resumed_from": None,
        "start_iter": 0,
    }
    if assert_fsdp:
        specs = [str(x.sharding.spec) for x in jax.tree.leaves(learner.state["params"])]
        if not any("fsdp" in s for s in specs):
            raise AssertionError("no param leaf sharded over fsdp")
    if assert_tp:
        flat = jax.tree_util.tree_flatten_with_path(learner.state["params"])[0]
        tp_leaves = [
            "/".join(getattr(p, "key", str(p)) for p in path)
            for path, x in flat
            if "tp" in str(x.sharding.spec)
        ]
        if not tp_leaves:
            raise AssertionError("no param leaf sharded over tp")
        if not any("Attention" in p for p in tp_leaves):
            raise AssertionError(
                f"no attention weight sharded over tp (tp leaves: {tp_leaves[:5]})"
            )
        report["tp_leaves"] = len(tp_leaves)

    if resume:
        resumed = learner.resume_latest()
        report["resumed_from"] = resumed
        report["start_iter"] = learner.last_iter.val

    # per-iteration device step wall time, measured around the learner's
    # _train itself (the run loop's log_buffer is drained by the log hook
    # before any later hook could read it)
    step_times = []
    orig_train = learner._train

    def timed_train(data):
        t0 = time.monotonic()
        out = orig_train(data)  # blocks on the device step's D2H log fetch
        step_times.append(time.monotonic() - t0)
        return out

    learner._train = timed_train

    if kill_after_iter is not None:
        from ..learner.hooks import LambdaHook

        def _chaos_kill(lrn):
            if lrn.last_iter.val >= kill_after_iter:
                # the chaos moment: durable sharded save, then die like a
                # preempted pod worker (no teardown, no atexit)
                lrn.save(lrn.checkpoint_path(), sync=True)
                os._exit(137)

        learner.hooks.add(LambdaHook("executor_chaos_kill", "after_iter", _chaos_kill))

    t0 = time.monotonic()
    learner.run(max_iterations=iters)
    wall_s = time.monotonic() - t0

    feeder = learner._dataloader
    feeder_stats = feeder.stats() if hasattr(feeder, "stats") else {}
    try:
        loss = float(learner.variable_record.get("total_loss").val)
    except KeyError:  # resumed at/past the target: zero fresh iterations
        loss = None
    report.update(
        iters=learner.last_iter.val,
        loss=loss,
        wall_s=round(wall_s, 3),
        step_times_s=[round(t, 4) for t in step_times],
        # steady-state step time: drop the first measured iter (it eats the
        # compile) when there is anything after it
        step_time_s=(
            round(min(step_times[1:] or step_times), 4) if step_times else None
        ),
        feeder=feeder_stats,
    )
    if save_freq < 10 ** 9 or kill_after_iter is not None:
        report["checkpoint_dir"] = os.path.join(learner.save_dir, "checkpoints")
    return report


def main_cli(argv=None) -> int:
    """``python -m distar_tpu.parallel.executor --mesh dp=4,fsdp=2 ...`` —
    the child-process surface the chaos multichip drill drives. Prints one
    ``REPORT {json}`` line."""
    import argparse
    import json

    p = argparse.ArgumentParser()
    p.add_argument("--mesh", default="dp=-1")
    p.add_argument("--iters", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--unroll-len", type=int, default=2)
    p.add_argument("--host-devices", type=int, default=0,
                   help="force a virtual n-device CPU platform (0 = use "
                        "the real backend)")
    p.add_argument("--save-dir", default="")
    p.add_argument("--save-freq", type=int, default=10 ** 9)
    p.add_argument("--no-sharded-ckpt", dest="sharded_ckpt",
                   action="store_false")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--kill-after", type=int, default=None)
    p.add_argument("--experiment-name", default="sharded_executor")
    args = p.parse_args(argv)
    select_backend(host_devices=args.host_devices)
    report = run_sharded_training(
        args.mesh,
        iters=args.iters,
        batch_size=args.batch_size,
        unroll_len=args.unroll_len,
        experiment_name=args.experiment_name,
        save_dir=args.save_dir,
        sharded_ckpt=args.sharded_ckpt,
        save_freq=args.save_freq,
        resume=args.resume,
        kill_after_iter=args.kill_after,
    )
    print("REPORT " + json.dumps(report), flush=True)  # lint: allow-print (CLI surface)
    return 0


if __name__ == "__main__":
    raise SystemExit(main_cli())

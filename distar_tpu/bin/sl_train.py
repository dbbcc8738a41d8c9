"""Supervised-learning launcher.

Role parity with the reference (reference: distar/bin/sl_train.py:28-50):
three roles —
  learner        train on decoded-replay data: a local ReplayDataset dir
                 (--data), trajectories pulled off the Adapter data plane
                 from remote replay actors (--remote), or — with neither —
                 schema-complete fake batches (the reference FakeDataloader
                 path);
  replay_actor   shard a replay list over SLURM tasks × workers, decode via
                 the two-pass SC2 decoder, push to the learner
                 (reference replay_actor.py);
  coordinator    the metadata broker both sides register with.
"""
from __future__ import annotations

import argparse

from .. import plugins
from ..utils import read_config
from .rl_train import (
    PLATFORM_HELP, PLATFORMS, _addr, _dynamics_cfg, _init_health, _mesh_kwargs,
    _restart_policy, _run_learner_supervised,
)


def _learner(args) -> None:
    from .rl_train import SMOKE_MODEL

    _init_health(
        args, roles=("learner", "trace"), source="sl_learner",
        shipper_addr=_addr(args.coordinator_addr) if args.remote else None,
    )
    user_cfg = read_config(args.config) if args.config else {}
    model_cfg = user_cfg.get("model", SMOKE_MODEL if args.smoke_model else {})
    learner = plugins.load_component(args.pipeline, "SLLearner")(
        {
            "common": {"experiment_name": args.experiment_name,
                       **({"save_path": args.save_path}
                          if args.save_path else {})},
            "learner": {
                # the config file's learner section (learning rate, clip, ...);
                # what the command line sets comes after it
                **user_cfg.get("learner", {}),
                "batch_size": args.batch_size,
                "unroll_len": args.traj_len,
                "log_freq": max(args.iters // 4, 1),
                "save_freq": 10 ** 9,
                "sharded_ckpt": (
                    bool(args.mesh) if args.sharded_ckpt is None
                    else bool(args.sharded_ckpt)
                ),
                **_dynamics_cfg(args),
            },
            "model": model_cfg,
        },
        **_mesh_kwargs(args),
    )
    if args.data:
        from ..learner.sl_dataloader import ReplayDataset, SLDataloader

        learner.set_dataloader(
            SLDataloader(ReplayDataset(args.data), args.batch_size, args.traj_len)
        )
    elif args.remote:
        from ..comm import Adapter
        from ..learner.replay_actor import RemoteSLDataloader

        adapter = Adapter(coordinator_addr=_addr(args.coordinator_addr))
        learner.set_dataloader(
            RemoteSLDataloader(adapter, args.batch_size, args.traj_len)
        )
    # else: the built-in fake dataloader (schema-complete random batches)
    if args.eval_data:
        # held-out metric pass every eval_freq iters (beyond the reference,
        # which only tracks train-set metrics): catches memorization that
        # train acc alone can't (tools/sl_curve.py demonstrates the split)
        import json

        from ..learner.hooks import LambdaHook
        from ..learner.sl_dataloader import ReplayDataset, SLDataloader

        eval_freq = args.eval_freq or max(args.iters // 8, 1)
        eval_batches = max(args.eval_batches, 1)  # 0 would drain an
        # infinite sampler; a bad path must fail BEFORE training starts
        eval_dataset = ReplayDataset(args.eval_data)

        def _eval(lrn):
            # SPMD: EVERY rank must run the jitted eval over the sharded
            # params (a rank-gated computation would hang the pod in the
            # first collective) — only the host-side print is rank-0
            metrics = lrn.evaluate(
                # fresh seed-2 loader per eval: the same fixed sample of
                # held-out windows every time, so the curve is comparable
                SLDataloader(eval_dataset, args.batch_size, args.traj_len,
                             seed=2),
                max_batches=eval_batches,
            )
            if getattr(lrn, "rank", 0) == 0:
                print("EVAL " + json.dumps(
                    {"iter": lrn.last_iter.val,
                     **{k: round(v, 4) for k, v in sorted(metrics.items())}}
                ), flush=True)

        learner.hooks.add(LambdaHook("holdout_eval", "after_iter", _eval,
                                     freq=eval_freq))
    if not getattr(args, "no_supervise", False):
        # restarted SL learner processes resume from their durable pointer
        learner.resume_latest()
    _run_learner_supervised(args, learner, args.iters)
    # the policy's learner reports action_type_acc, a token model token_acc
    accuracy = next(k for k in ("action_type_acc", "token_acc") if k in learner.variable_record.vars())
    print(
        f"sl_train done: {learner.last_iter.val} iters, "
        f"loss={learner.variable_record.get('total_loss').avg:.4f}, "
        f"{accuracy}={learner.variable_record.get(accuracy).avg:.4f}"
    )


def _replay_actor(args) -> None:
    import os

    from ..comm import Adapter
    from ..learner.replay_actor import ReplayActor

    # no replay-specific rules yet, but shipping makes decode throughput
    # visible in the broker's fleet view (/timeseries per source)
    _init_health(args, roles=("trace",), source=f"replay_actor:{os.getpid()}",
                 shipper_addr=_addr(args.coordinator_addr))
    decoder_cls = plugins.load_component(args.pipeline, "ReplayDecoder")
    coordinator = _addr(args.coordinator_addr)

    def run_actor():
        ReplayActor(
            replays=args.replays,
            adapter_factory=lambda: Adapter(coordinator_addr=coordinator),
            decoder_factory=lambda: decoder_cls(cfg={}),
            num_workers=args.num_workers,
            epochs=args.epochs,
        ).run()

    if getattr(args, "no_supervise", False):
        run_actor()
    else:
        from ..resilience import supervise_call

        supervise_call(run_actor, op="replay_actor", policy=_restart_policy(args))


def _coordinator(args) -> None:
    import time

    from ..comm import CoordinatorServer

    # broker-side rulebook over shipped telemetry (the fleet view)
    _init_health(args, roles=("learner", "actor", "coordinator", "trace"),
                 source="coordinator")
    server = CoordinatorServer(port=_addr(args.coordinator_addr)[1])
    server.start()
    print(f"coordinator serving on {server.host}:{server.port}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--type", default="learner",
                   choices=("learner", "replay_actor", "coordinator"))
    p.add_argument("--config", default="")
    p.add_argument("--iters", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--traj-len", type=int, default=None)
    p.add_argument("--experiment-name", default="sl_train")
    p.add_argument("--save-path", default="",
                   help="experiment root override (default "
                        "$DISTAR_EXPERIMENTS_ROOT or ./experiments/<name>)")
    p.add_argument("--mesh", default="",
                   help="device-mesh spec, e.g. 'dp=4,fsdp=2' — live-mesh "
                        "GSPMD train step + sharded checkpoints "
                        "(docs/parallel.md)")
    p.add_argument("--host-devices", type=int, default=0,
                   help="force a virtual n-device CPU platform before jax "
                        "init (multichip smoke without silicon)")
    p.add_argument("--sharded-ckpt", action="store_true", default=None,
                   help="one CRC'd blob per parameter shard + layout "
                        "manifest (default: on when --mesh is given)")
    p.add_argument("--no-sharded-ckpt", dest="sharded_ckpt",
                   action="store_false")
    p.add_argument("--data", default="",
                   help="local ReplayDataset directory (decoded trajectories)")
    p.add_argument("--eval-data", default="",
                   help="held-out ReplayDataset directory: run a no-grad "
                        "metric pass every --eval-freq iters")
    p.add_argument("--eval-freq", type=int, default=0,
                   help="held-out eval cadence (0 = iters/8)")
    p.add_argument("--eval-batches", type=int, default=8)
    p.add_argument("--remote", action="store_true",
                   help="pull trajectories from replay actors via the coordinator")
    p.add_argument("--smoke-model", action="store_true", default=True)
    p.add_argument("--full-model", dest="smoke_model", action="store_false")
    p.add_argument("--coordinator-addr", default="127.0.0.1:8422")
    p.add_argument("--pipeline", default="default",
                   help="learner implementation: 'default' or an importable "
                        "custom-pipeline module (plugins.py)")
    p.add_argument("--replays", default="", help="replay list file or directory")
    p.add_argument("--no-health", action="store_true",
                   help="disable the fleet-health subsystem (watchdog rules, "
                        "telemetry shipping, crash recorder)")
    p.add_argument("--dynamics-every", type=int, default=None,
                   help="training-dynamics gauge-export stride (learner "
                        "dynamics.every_n); 0 disables the in-jit "
                        "diagnostics tree entirely; default: config/10")
    p.add_argument("--no-supervise", action="store_true",
                   help="disable crash-restart supervision and learner "
                        "auto-resume from the latest checkpoint pointer")
    p.add_argument("--admin-port", type=int, default=None,
                   help="serve the learner admin API (status / save_ckpt "
                        "and on-demand POST /profile?steps=N trace capture; "
                        "see `opsctl profile`) on this port")
    p.add_argument("--restart-max", type=int, default=5,
                   help="restart budget per role within --restart-window-s")
    p.add_argument("--restart-window-s", type=float, default=300.0,
                   help="sliding window for the restart budget")
    p.add_argument("--num-workers", type=int, default=1)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--platform", default="auto", choices=PLATFORMS,
                   help=PLATFORM_HELP)
    return p


def main() -> None:
    args = build_parser().parse_args()
    if args.type == "learner":
        # must precede ANY jax backend init (device query) in this process
        from ..parallel.executor import select_backend

        select_backend(args.platform, args.host_devices)
    user_cfg = read_config(args.config) if args.config else {}
    learner_cfg = user_cfg.get("learner", {})
    if args.batch_size is None:
        args.batch_size = int(learner_cfg.get("batch_size", 2))
    if args.traj_len is None:
        args.traj_len = int(learner_cfg.get("unroll_len", 8))

    if args.type == "learner":
        _learner(args)
    elif args.type == "replay_actor":
        _replay_actor(args)
    else:
        _coordinator(args)


if __name__ == "__main__":
    main()

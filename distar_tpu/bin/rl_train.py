"""RL training launcher.

Role parity with the reference launcher (reference: distar/bin/
rl_train.py:19-162): spawns the four roles — coordinator, league, learner,
actor — either all-in-one (small-scale/smoke, mock env) or a single role for
multi-host runs (league/coordinator serve HTTP; learners/actors connect by
address).

Usage:
  python -m distar_tpu.bin.rl_train --type all --iters 4        # smoke loop
  python -m distar_tpu.bin.rl_train --type league --port 8421
  python -m distar_tpu.bin.rl_train --type learner --player-id MP0 ...
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

from ..actor import Actor
from ..comm import Adapter, Coordinator, CoordinatorServer
from ..envs import MockEnv
from ..league import League, LeagueAPIServer
from .. import plugins
from ..learner.rl_dataloader import RLDataLoader
from ..resilience import AlertRemediator, RestartPolicy, Supervisor, supervise_call
from ..utils import read_config

# --type values that run a model (everything else is host-only)
DEVICE_ROLES = ("all", "learner", "actor", "arena", "league-learner")
PLATFORMS = ("auto", "cpu", "tpu")
PLATFORM_HELP = ("jax backend: auto leaves the choice to jax (JAX_PLATFORMS "
                 "is honoured); tpu fails at start when no TPU is found")

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


def _model_cfg(args) -> dict:
    user_cfg = read_config(args.config) if args.config else {}
    return user_cfg.get("model", SMOKE_MODEL if args.smoke_model else {})


def _resolve_args(args) -> None:
    """Fill CLI sentinels from the user config's learner/actor blocks —
    explicit CLI flags win, then config, then defaults (the reference's
    deep-merge cascade applied to the launcher surface)."""
    user_cfg = read_config(args.config) if args.config else {}
    learner_cfg = user_cfg.get("learner", {})
    actor_cfg = user_cfg.get("actor", {})
    if args.batch_size is None:
        args.batch_size = int(learner_cfg.get("batch_size", 4))
    if args.traj_len is None:
        args.traj_len = int(learner_cfg.get("unroll_len", actor_cfg.get("traj_len", 4)))
    if args.env_num is None:
        args.env_num = int(actor_cfg.get("env_num", 2))


def _jaxenv_cfgs(args):
    """(EnvConfig, ScenarioConfig) from the --jaxenv-* CLI knobs."""
    from ..envs.jaxenv import EnvConfig, ScenarioConfig

    u = args.jaxenv_units
    return (EnvConfig(units_per_squad=u),
            ScenarioConfig(units_per_squad=u, max_units=u,
                           episode_len=args.jaxenv_episode_len))


def _env_fn(args):
    """Env factory: ``--env`` wins, then the user config's env block
    (``env.type: sc2`` launches real games through the client layer;
    ``jaxenv`` is the pure-JAX micro-battle world through the host
    adapter); the default mock env keeps game-free smoke loops working."""
    user_cfg = read_config(args.config) if args.config else {}
    env_cfg = dict(user_cfg.get("env", {}))
    env_type = getattr(args, "env", "") or env_cfg.pop("type", "mock")
    env_cfg.pop("type", None)
    if env_type == "sc2":
        from ..envs.sc2.launcher import make_sc2_env

        return lambda: make_sc2_env({"env": env_cfg})
    if env_type == "jaxenv":
        from ..envs.jaxenv import JaxMicroBattleEnv

        jcfg, scfg = _jaxenv_cfgs(args)
        return lambda: JaxMicroBattleEnv(jcfg, scfg)
    return lambda: MockEnv(episode_game_loops=args.episode_game_loops)


def _dynamics_cfg(args) -> dict:
    """--dynamics-every N -> the learner's training-dynamics block: 0
    disables the in-jit diagnostics tree entirely (the overhead A/B's off
    arm), N > 0 sets the gauge-export stride, absent keeps defaults."""
    every = getattr(args, "dynamics_every", None)
    if every is None:
        return {}
    if every <= 0:
        return {"dynamics": {"enabled": False}}
    return {"dynamics": {"every_n": every}}


def _learner_cfg(args, model_cfg: dict, load_path: str = "") -> dict:
    return {
        "common": {"experiment_name": args.experiment_name,
                   **({"save_path": args.save_path}
                      if getattr(args, "save_path", "") else {})},
        "learner": {
            "batch_size": args.batch_size,
            "unroll_len": args.traj_len,
            "log_freq": max(args.iters // 4, 1),
            "save_freq": 10 ** 9,
            # --mesh implies the distributed checkpoint layout (restorable
            # onto any other mesh shape); --sharded-ckpt/--no-sharded-ckpt
            # override either way
            "sharded_ckpt": (
                bool(getattr(args, "mesh", ""))
                if getattr(args, "sharded_ckpt", None) is None
                else bool(args.sharded_ckpt)
            ),
            **({"load_path": load_path} if load_path else {}),
            **_dynamics_cfg(args),
        },
        "model": model_cfg,
    }


def _mesh_from_args(args):
    """--mesh dp=K,fsdp=M,tp=N,sp=S -> a live jax mesh (None without the
    flag: learners build their own all-dp default). Typed MeshConfigError
    when the axes don't factor the devices."""
    if not getattr(args, "mesh", ""):
        return None
    import jax

    from ..parallel import MeshSpec, make_mesh

    spec = MeshSpec.parse(args.mesh)
    devices = None
    if spec.dp != -1:
        # fully explicit spec: take exactly that many devices (--mesh dp=4
        # on an 8-device host means a 4-chip mesh, not a config error)
        devices = jax.devices()[: spec.dp * spec.fsdp * spec.tp * spec.sp]
    return make_mesh(spec, devices)


def _mesh_kwargs(args) -> dict:
    mesh = _mesh_from_args(args)
    return {"mesh": mesh} if mesh is not None else {}


def _init_health(args, roles, source="local", shipper_addr=None):
    """Stand up the fleet-health subsystem for this process: TSDB sampler +
    the default rulebook for the roles it hosts + the crash flight recorder
    (bundles land under <experiment>/flight). With ``shipper_addr`` the
    process additionally ships registry snapshots to the coordinator so the
    broker-side rulebook sees the whole fleet. Disable with --no-health."""
    if getattr(args, "no_health", False):
        return None
    from ..obs import TelemetryShipper, default_rulebook, init_fleet_health

    fleet = init_fleet_health(
        rules=default_rulebook(roles),
        sample_interval_s=getattr(args, "health_sample_s", 1.0),
        eval_interval_s=getattr(args, "health_eval_s", 2.0),
        source=source,
    )
    from ..learner.base_learner import experiments_root

    artifact_dir = os.path.join(
        getattr(args, "save_path", "") or os.path.join(
            experiments_root(), getattr(args, "experiment_name", "run")),
        "flight",
    )
    fleet.recorder.install_crash_hook(artifact_dir, config=vars(args))
    if shipper_addr is not None:
        TelemetryShipper(
            source, coordinator_addr=shipper_addr,
            interval_s=getattr(args, "telemetry_interval_s", 5.0),
        ).start()
    return fleet


def _restart_policy(args) -> RestartPolicy:
    return RestartPolicy(
        max_restarts=getattr(args, "restart_max", 5),
        window_s=getattr(args, "restart_window_s", 300.0),
    )


def _run_learner_supervised(args, learner, iters) -> None:
    """Foreground crash-resume for the learner role: a crash restores from
    the durable ``latest`` pointer (corrupt newest generation falls back a
    checkpoint) and re-enters the run loop, bounded by the restart budget.
    The final failure still dies loudly (flight bundle + raise)."""
    if getattr(args, "admin_port", None) is not None:
        # live admin surface: update_config / save_ckpt / status and the
        # on-demand POST /profile?steps=N capture (opsctl profile)
        admin = learner.start_admin(port=args.admin_port)
        print(f"learner admin on http://{admin.host}:{admin.port}/learner/status",
              flush=True)
    if getattr(args, "no_supervise", False):
        learner.run(max_iterations=iters)
        return

    def resume(error):
        path = learner.resume_latest()
        print(f"learner restart after {error!r}: "
              f"resume={path or 'cold'} iter={learner.last_iter.val}", flush=True)

    supervise_call(
        lambda: learner.run(max_iterations=iters),
        op="learner", policy=_restart_policy(args), on_restart=resume,
    )


def _table_config(args):
    """Per-player replay-table settings from the CLI surface (the replay
    role's table factory; every player token gets one of these)."""
    from ..replay import TableConfig

    spi = args.replay_spi
    batch = max(args.batch_size or 1, 1)
    error_buffer = args.replay_error_buffer
    if error_buffer is None and spi > 0:
        # batch-aware slack (Reverb sizes its min/max_diff to the batch the
        # same way): the limiter must be able to admit a whole learner batch
        # or sampler and inserter deadlock trading timeouts — see
        # RateLimiter.max_sample_batch
        error_buffer = max(spi, 1.0) * batch
    return TableConfig(
        max_size=args.replay_max_size,
        sampler=args.replay_sampler,
        samples_per_insert=None if spi <= 0 else spi,
        # 0 = "the learner batch size": sampling can't start below one batch
        min_size_to_sample=max(args.replay_min_size or batch, 1),
        error_buffer=error_buffer,
        max_staleness_s=args.replay_max_staleness_s or None,
    )


def _build_replay_store(args, shard_id: str = "", spill_dir: Optional[str] = None):
    """Store + spill for a serving replay role; recovery runs before serving
    so acked-but-unsampled trajectories from a crashed generation are
    resident before the first sample lands (as pre-encoded payloads, so
    re-serving them skips the recompression pass). ``shard_id`` labels this
    member's metrics/stats when it is one of a fleet."""
    from ..replay import ReplayStore, SpillRing

    _table_config(args)  # fail fast on invalid combos (e.g. fifo + spi > 1)
    spill = None
    spill_dir = args.replay_spill_dir if spill_dir is None else spill_dir
    if spill_dir:
        spill = SpillRing(spill_dir, max_items=args.replay_spill_max)
    store = ReplayStore(table_factory=lambda name: _table_config(args),
                        spill=spill, shard_id=shard_id, recover_encoded=True)
    recovered = store.recover()
    if recovered:
        print(f"replay{f' shard {shard_id}' if shard_id else ''}: recovered "
              f"{recovered} acked trajectories from spill", flush=True)
    return store


def _learner_replay_client(args, addrs: str):
    """Sample-side client for a learner: ``inproc`` -> the colocated
    zero-copy handle, one address -> a plain ``SampleClient``, several ->
    the consistent-hash fleet's fan-in sampler (per-shard breakers,
    stalled shards skip), ``discover`` -> the coordinator's shard map."""
    from ..replay import (
        LocalReplayClient, SampleClient, ShardMap, ShardedSampleClient,
        is_inproc_addr,
    )

    compress = getattr(args, "replay_compress", True)
    transport = getattr(args, "transport", "auto")
    if is_inproc_addr(addrs):
        return LocalReplayClient()
    if addrs.strip().lower() == "discover":
        shard_map = ShardMap.discover(_addr(args.coordinator_addr))
    else:
        shard_map = ShardMap.parse(addrs)
    if len(shard_map) == 1:
        return SampleClient(*_addr(shard_map.addrs[0]), compress=compress,
                            transport=transport)
    return ShardedSampleClient(shard_map, mode=args.replay_fanin,
                               compress=compress, transport=transport)


def run_replay(args) -> None:
    """Standalone replay-store role: framed-TCP data plane on --port, HTTP
    admin/stats (+ /metrics + health routes) on --metrics-port, crash-restart
    under the supervisor with spill recovery on every (re)start. With
    --coordinator-addr the shard registers under the ``replay_shard`` token
    (lease + heartbeat), so actors/learners started with ``--replay-addr
    discover`` find the whole fleet without static address lists."""
    from ..replay import ReplayAdminServer, ReplayServer, register_shard

    shard_id = args.replay_shard_id or (f":{args.port}" if args.port else "")
    _init_health(
        args, roles=("replay",), source=f"replay{shard_id}" if shard_id else "replay",
        shipper_addr=_addr(args.coordinator_addr) if args.coordinator_addr else None,
    )

    def serve_loop(ctx):
        store = _build_replay_store(args, shard_id=shard_id)
        server = ReplayServer(store, port=args.port,
                              compress=args.replay_compress,
                              transport=args.transport)
        server.start()
        admin = None
        if args.metrics_port is not None:
            admin = ReplayAdminServer(store, port=args.metrics_port,
                                      server=server)
            admin.start()
            print(f"replay admin on http://{admin.host}:{admin.port}/replay/stats",
                  flush=True)
        heartbeat = None
        if args.coordinator_addr:
            heartbeat = register_shard(
                _addr(args.coordinator_addr), server.host, server.port,
                meta={"admin_port": args.metrics_port},
                lease_s=args.lease_s or None,
            )
        print(f"replay store serving on {server.host}:{server.port}", flush=True)
        try:
            while not ctx.should_exit:
                ctx.sleep(1.0)
        finally:
            if heartbeat is not None:
                heartbeat.stop_event.set()
            server.stop()
            if admin is not None:
                admin.stop()

    if getattr(args, "no_supervise", False):
        from ..resilience import TaskContext

        serve_loop(TaskContext())
        return
    supervisor = Supervisor(policy=_restart_policy(args))
    supervisor.add("replay", serve_loop)
    supervisor.start()
    supervisor.join()


def run_arena(args) -> None:
    """Standalone arena-evaluator role: pulls checkpoint generations via
    CheckpointManager role keys, plays deterministic head-to-head batches on
    jaxenv against the coordinator-scheduled opponent, and reports results
    under idempotent match keys — crash-restart under the supervisor is
    exactly-once by construction (the store re-issues the same assignment
    until its results are applied)."""
    from ..arena import ArenaEvaluator

    _init_health(
        args, roles=("arena",), source="arena",
        shipper_addr=_addr(args.coordinator_addr) if args.coordinator_addr else None,
    )
    roles = tuple(r.strip() for r in args.arena_roles.split(",")) \
        if args.arena_roles else ("",)
    env_cfg, scenario_cfg = _jaxenv_cfgs(args)

    def serve_loop(ctx):
        evaluator = ArenaEvaluator(
            ckpt_dir=args.arena_ckpt_dir,
            model_cfg=_model_cfg(args),
            coordinator_addr=_addr(args.coordinator_addr),
            roles=roles,
            episodes=args.arena_episodes,
            env_cfg=env_cfg,
            scenario_cfg=scenario_cfg,
        )
        print(f"arena evaluator on {args.arena_ckpt_dir} "
              f"(roles={','.join(r or 'main' for r in roles)})", flush=True)
        try:
            while not ctx.should_exit:
                out = evaluator.evaluate_once()
                if out is None:
                    ctx.sleep(args.arena_interval_s)
                    continue
                a = out["assignment"]
                print(f"arena: {a['home']} vs {a['away']} r{a['round']} "
                      f"win_rate={out['result']['win_rate']:.3f} "
                      f"applied={out['ack'].get('applied')}", flush=True)
                if args.arena_batches and \
                        evaluator.batches_done >= args.arena_batches:
                    break
        finally:
            if args.arena_artifact:
                ratings = _fetch_arena_ratings(args)
                evaluator.write_artifact(args.arena_artifact, ratings=ratings)
                print(f"arena artifact written to {args.arena_artifact}",
                      flush=True)

    if getattr(args, "no_supervise", False):
        from ..resilience import TaskContext

        serve_loop(TaskContext())
        return
    supervisor = Supervisor(policy=_restart_policy(args))
    supervisor.add("arena", serve_loop)
    supervisor.start()
    supervisor.join()


def _fetch_arena_ratings(args) -> Optional[dict]:
    """GET /arena/ratings from the coordinator for the artifact ledger;
    None when the store isn't hosted there (artifact stays throughput-only)."""
    import urllib.request

    host, port = _addr(args.coordinator_addr)
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}/arena/ratings", timeout=10.0) as resp:
            return json.loads(resp.read())
    except Exception:
        return None


def _maybe_serve_metrics(args, coordinator=None):
    """Start an HTTP server exposing GET /metrics for this process's registry
    when --metrics-port is given (CoordinatorServer doubles as the exporter;
    for non-broker roles its POST routes simply go unused). Returns the
    server or None."""
    if args.metrics_port is None:
        return None
    server = CoordinatorServer(coordinator=coordinator, port=args.metrics_port)
    server.start()
    print(f"metrics on http://{server.host}:{server.port}/metrics", flush=True)
    return server


def _make_learner(args, model_cfg: dict, load_path: str = ""):
    """The learner this process hosts: the RL teacher by default, or — with
    ``--distill`` — the student-tier distillation learner, which consumes
    the SAME batch stream (teacher logits already ride every flush) and
    publishes checkpoints under the ``student`` role key so teacher resume
    can never cross tiers (docs/training_guide.md distillation quickstart)."""
    if getattr(args, "distill", False):
        from ..learner import DistillLearner

        return DistillLearner(_learner_cfg(args, model_cfg, load_path=load_path))
    return plugins.load_component(args.pipeline, "RLLearner")(
        _learner_cfg(args, model_cfg, load_path=load_path), **_mesh_kwargs(args))


def run_all(args) -> None:
    """Single-process league-RL loop on the mock env (the small-scale config
    path; swaps to the real SC2 env behind the same interfaces)."""
    user_cfg = read_config(args.config) if args.config else {}
    model_cfg = _model_cfg(args)
    league = League(user_cfg)
    co = Coordinator()
    # one process hosts every role, so the full rulebook applies locally
    roles = ("learner", "actor", "coordinator", "trace") + (
        ("replay",) if args.replay else ()) + (
        ("distill",) if args.distill else ())
    fleet = _init_health(args, roles=roles)
    _maybe_serve_metrics(args, coordinator=co)
    actor_adapter = Adapter(coordinator=co)
    learner_adapter = Adapter(coordinator=co)

    # --replay: an in-process store between actor and learner — the smoke
    # configuration of the store path. Three shapes:
    #   * default: ONE real server + clients on loopback TCP;
    #   * --replay-shards N: N servers, actors route by consistent hash,
    #     the learner fans in (the fleet smoke — real sharded data plane);
    #   * --replay-fast-path: no server at all — the Sebulba colocated
    #     layout hands actor and learner a direct store handle (zero
    #     serialization on push AND sample).
    replay_servers = []
    actor_replay_cfg = {}
    if args.replay and args.replay_fast_path:
        if args.replay_shards > 1:
            raise SystemExit("--replay-fast-path is the single colocated "
                             "store; it cannot combine with --replay-shards")
        from ..replay import set_local_store

        set_local_store(_build_replay_store(args))
        actor_replay_cfg = {"replay": {"enabled": True, "addr": "inproc"}}
        print("replay store (colocated zero-copy fast path)", flush=True)
    elif args.replay:
        from ..replay import ReplayServer

        spill_root = args.replay_spill_dir
        for i in range(max(args.replay_shards, 1)):
            shard_id = f"s{i}" if args.replay_shards > 1 else ""
            spill_dir = os.path.join(spill_root, shard_id) \
                if (spill_root and shard_id) else spill_root
            store = _build_replay_store(args, shard_id=shard_id,
                                        spill_dir=spill_dir)
            replay_servers.append(
                ReplayServer(store, port=0,
                             compress=args.replay_compress,
                             transport=args.transport).start())
        addrs = ",".join(f"{s.host}:{s.port}" for s in replay_servers)
        actor_replay_cfg = {"replay": {"enabled": True, "addr": addrs,
                                       "compress": args.replay_compress,
                                       "transport": args.transport}}
        print(f"replay store{'s' if len(replay_servers) > 1 else ''} "
              f"(in-process) on {addrs}", flush=True)

    player_id = list(league.active_players.keys())[0]
    traj_len = args.traj_len
    actor = Actor(
        cfg={"actor": {"env_num": args.env_num, "traj_len": traj_len,
                       "plane": _plane_cfg(args),
                       **actor_replay_cfg}},
        league=league,
        adapter=actor_adapter,
        model_cfg=model_cfg,
        env_fn=_env_fn(args),
    )

    if args.replay:
        from ..learner.rl_dataloader import ReplayDataLoader

        loader = ReplayDataLoader(
            _learner_replay_client(args, actor_replay_cfg["replay"]["addr"]),
            player_id, args.batch_size,
        )
    else:
        loader = RLDataLoader(learner_adapter, player_id, args.batch_size)

    # --no-supervise means what its help says here too: the actor loop gets
    # no restarts, so its first crash is final
    supervisor = Supervisor(policy=RestartPolicy(max_restarts=0)
                            if args.no_supervise else _restart_policy(args))

    def actor_loop(ctx):
        while not ctx.should_exit:
            actor.run_job(episodes=1)

    def actor_gave_up(error):
        # the learner would otherwise wait forever for trajectories (the
        # store-backed loader times out on its own)
        if isinstance(loader, RLDataLoader):
            loader.fail(RuntimeError(f"actor loop died for good: {error!r}"))

    supervisor.add("actor", actor_loop, on_giveup=actor_gave_up)
    supervisor.start()
    if fleet is not None and not getattr(args, "no_supervise", False):
        # detect -> remediate: a firing env-starvation alert bounces the
        # actor loop instead of waiting for a human
        AlertRemediator(
            supervisor, {"actor_env_starvation": "actor"}
        ).attach(fleet.evaluator)

    learner = _make_learner(args, model_cfg)
    learner.set_dataloader(loader)
    if not args.distill:
        # the student tier publishes via checkpoints + fleet rollout, not
        # the league's weight-push plane (its league player is the teacher)
        learner.attach_comm(learner_adapter, player_id, league=league,
                            send_model_freq=4, send_train_info_freq=4)
    _run_learner_supervised(args, learner, args.iters)
    # let the actor finish its in-flight job, and end the feeder that places
    # its last trajectories on the device: a daemon thread killed inside a
    # jax call aborts the interpreter teardown (exit 134 after a clean run)
    supervisor.stop(timeout=120)
    if isinstance(loader, RLDataLoader):
        loader.close()
    if hasattr(learner._dataloader, "close"):
        learner._dataloader.close()
    for server in replay_servers:
        server.stop()
    if args.replay and args.replay_fast_path:
        from ..replay import set_local_store

        set_local_store(None)
    actor_status = supervisor.status()["actor"]
    print(
        f"rl_train done: {learner.last_iter.val} iters, "
        f"loss={learner.variable_record.get('total_loss').avg:.4f}, "
        f"games={league.all_players[player_id].total_game_count}, "
        f"actor_restarts={actor_status['restarts']}"
    )
    if actor_status["gave_up"]:
        # the learner had enough data to finish, but a run whose actor is
        # dead did not succeed
        raise SystemExit(f"actor loop died for good: {actor_status['last_error']}")


def _addr(s: str):
    """``"host:port"`` -> ``(host, port)``; an HA comma list
    (``"h1:p1,h2:p2"``) passes through as ``(spec, None)``, which
    ``coordinator_request`` resolves with leadership failover."""
    if "," in s:
        return s, None
    host, _, port = s.rpartition(":")
    return host or "127.0.0.1", int(port)


def _plane_cfg(args) -> dict:
    """Rollout-plane block for the actor config (docs/serving.md): which
    backend serves policy forwards — per-actor inline (default), one shared
    in-process gateway per player (local), or a remote bin/serve gateway
    (remote, needs --plane-addr)."""
    if args.plane == "remote" and not args.plane_addr:
        raise SystemExit("--plane remote requires --plane-addr host:port "
                         "(or 'h1:p1,h2:p2', or 'discover')")
    if args.plane == "remote" and args.plane_addr == "discover" \
            and not args.coordinator_addr:
        raise SystemExit("--plane-addr discover requires --coordinator-addr "
                         "(gateways register under the serve_gateway token)")
    return {
        "backend": args.plane,
        "addr": args.plane_addr,
        "slots": args.plane_slots,
        "coordinator_addr": args.coordinator_addr or "",
        "transport": getattr(args, "transport", "auto"),
    }


def run_learner(args) -> None:
    """Standalone learner role connecting to remote league + coordinator
    (reference rl_train.py:19-53 learner_run)."""
    import os

    from ..league.remote import RemoteLeague

    info = args.dist_info  # main() joined the multi-host runtime
    league = RemoteLeague(*_addr(args.league_addr)) if args.league_addr else None
    adapter = Adapter(coordinator_addr=_addr(args.coordinator_addr))
    _init_health(
        args,
        roles=("learner", "trace") + (("distill",) if args.distill else ()),
        source=(f"distill:{args.player_id}:{info['rank']}" if args.distill
                else f"learner:{args.player_id}:{info['rank']}"),
        shipper_addr=_addr(args.coordinator_addr),
    )
    _maybe_serve_metrics(args)
    model_cfg = _model_cfg(args)
    load_path = ""
    if league is not None:
        reply = league.register_learner(args.player_id, rank=info["rank"],
                                        world_size=info["world_size"])
        # resume from the league-assigned player checkpoint when it exists
        # (reference learner_run loads the assigned ckpt)
        ckpt = reply.get("checkpoint_path", "")
        if ckpt and os.path.exists(ckpt):
            load_path = ckpt
    learner = _make_learner(args, model_cfg, load_path=load_path)
    if not load_path and not getattr(args, "no_supervise", False):
        # a restarted learner process (k8s/systemd) picks up its own durable
        # latest pointer before cold-starting — zero manual intervention
        learner.resume_latest()
    if args.replay_addr:
        # store-backed sampling mode: batches come from the replay table(s)
        # instead of the point-to-point pull cache — a comma-separated list
        # (or 'discover') fans in across the shard fleet (docs/data_plane.md)
        from ..learner.rl_dataloader import ReplayDataLoader

        learner.set_dataloader(ReplayDataLoader(
            _learner_replay_client(args, args.replay_addr),
            args.player_id, args.batch_size,
        ))
    else:
        learner.set_dataloader(RLDataLoader(adapter, args.player_id, args.batch_size))
    if not args.distill:
        learner.attach_comm(adapter, args.player_id, league=league)
    _run_learner_supervised(args, learner, args.iters)
    print(f"learner done: {learner.last_iter.val} iters")


def run_actor(args) -> None:
    """Standalone actor role (reference rl_train.py:54-67 actor_run)."""
    from ..league.remote import RemoteLeague

    league = RemoteLeague(*_addr(args.league_addr))
    adapter = Adapter(coordinator_addr=_addr(args.coordinator_addr))
    _init_health(
        args, roles=("actor", "trace"), source=f"actor:{os.getpid()}",
        shipper_addr=_addr(args.coordinator_addr),
    )
    _maybe_serve_metrics(args)
    model_cfg = _model_cfg(args)
    actor_cfg = {"env_num": args.env_num, "traj_len": args.traj_len,
                 "plane": _plane_cfg(args)}
    if args.replay_addr:
        replay_addr = args.replay_addr
        if replay_addr.strip().lower() == "discover":
            # resolve the fleet once at launch from the coordinator's shard
            # registrations (the actor config carries plain addresses)
            from ..replay import ShardMap

            replay_addr = ",".join(
                ShardMap.discover(_addr(args.coordinator_addr)).addrs)
            print(f"replay: discovered shard fleet {replay_addr}", flush=True)
        actor_cfg["replay"] = {"enabled": True, "addr": replay_addr,
                               "compress": args.replay_compress,
                               "transport": args.transport}
    actor = Actor(
        cfg={"actor": actor_cfg},
        league=league,
        adapter=adapter,
        model_cfg=model_cfg,
        env_fn=_env_fn(args),
    )

    def job_loop():
        while True:
            actor.run_job(episodes=1)

    if getattr(args, "no_supervise", False):
        job_loop()
    else:
        # a crashed job loop (league blip, env death) restarts with backoff
        # instead of retiring the whole actor host
        supervise_call(job_loop, op="actor", policy=_restart_policy(args))


def run_anakin(args) -> None:
    """Fused on-device training: the Anakin loop (envs/jaxenv/anakin.py)
    replaces the whole actor plane — env step + sample_action + LSTM carry
    compiled into one scanned XLA program feeding the learner directly.
    ``--batch-size`` is the number of vmapped env lanes, ``--traj-len`` the
    window length. Startup asserts the fused loop is device-pure (no
    host-callback primitives in its jaxpr) and refuses to run otherwise."""
    from ..envs.jaxenv import AnakinDataLoader, AnakinRunner

    model_cfg = _model_cfg(args)
    _init_health(args, roles=("learner", "trace"))
    _maybe_serve_metrics(args)
    learner = _make_learner(args, model_cfg)
    # no host-side prefetch on the fused path: batches are produced ON
    # DEVICE, so the feeder's look-ahead buys nothing — and its producer
    # thread would be sitting inside the NEXT window's jitted rollout when
    # run() returns (minutes at large B); a daemon thread dying inside XLA
    # at interpreter teardown aborts the process (the run_all in-flight-job
    # hazard, reached through the dataloader instead of the actor)
    learner.cfg.learner["prefetch_depth"] = 0
    jcfg, scfg = _jaxenv_cfgs(args)
    runner = AnakinRunner(
        learner.model, batch_size=args.batch_size, unroll_len=args.traj_len,
        env_cfg=jcfg, scenario_cfg=scfg)

    def live_params():
        state = getattr(learner, "_state", None)
        return state["params"] if state else None

    loader = AnakinDataLoader(runner, params_provider=live_params)
    report = runner.purity_report(loader._params(), runner.init_carry())
    print(f"anakin device purity: {report}", flush=True)
    if not report["pure"]:
        raise SystemExit(
            f"anakin loop is not device-pure: {report['offending']}")
    print(f"anakin: B={runner.B} lanes x T={runner.T} steps "
          f"({runner.B * runner.T} env steps/window), "
          f"units_per_squad={jcfg.units_per_squad}", flush=True)
    learner.set_dataloader(loader)
    _run_learner_supervised(args, learner, args.iters)
    print(f"learner done: {learner.last_iter.val} iters")


def run_league_learner(args) -> None:
    """One league learner process (league/runtime/runner.py): register with
    the coordinator-hosted matchmaker, then loop matchmade rounds — fused
    Anakin rollout with the opponent on the away seat, report matches under
    idempotent keys, record checkpoint generations into this player's
    CheckpointManager role-key lineage, and stream train-info (snapshot
    minting happens server-side)."""
    import zlib

    from ..envs.jaxenv import AnakinDataLoader, AnakinRunner
    from ..league.remote import RemoteLeagueService
    from ..league.runtime.runner import LeagueLearnerLoop
    from ..learner.base_learner import experiments_root

    player_id = args.player_id
    _init_health(
        args, roles=("learner", "trace"), source=f"league:{player_id}",
        shipper_addr=_addr(args.coordinator_addr),
    )
    _maybe_serve_metrics(args)
    remote = RemoteLeagueService(args.coordinator_addr)
    cfg = _learner_cfg(args, _model_cfg(args))
    # isolated checkpoint lineage per league player: a per-player save
    # subtree keeps file names (and logs) collision-free across concurrent
    # learners, and the role-keyed pointer file means generations can never
    # cross on resume even if lineages are later merged into one directory
    cfg["common"]["save_path"] = os.path.join(
        cfg["common"].get("save_path")
        or os.path.join(experiments_root(), args.experiment_name),
        player_id)
    cfg["learner"]["ckpt_role"] = player_id
    learner = plugins.load_component(args.pipeline, "RLLearner")(
        cfg, **_mesh_kwargs(args))
    learner.cfg.learner["prefetch_depth"] = 0  # run_anakin teardown hazard
    jcfg, scfg = _jaxenv_cfgs(args)
    runner = AnakinRunner(
        learner.model, batch_size=args.batch_size, unroll_len=args.traj_len,
        env_cfg=jcfg, scenario_cfg=scfg,
        seed=zlib.crc32(player_id.encode()) & 0x7FFFFFFF,
        opponent_seat=True)
    loop = LeagueLearnerLoop(
        player_id, remote, learner, loader=None,
        rounds=args.league_rounds,
        iters_per_round=args.league_iters_per_round)
    loader = AnakinDataLoader(
        runner,
        params_provider=lambda: (learner._state or {}).get("params"),
        opponent_provider=loop.opponent_params)
    loop.loader = loader
    learner.set_dataloader(loader)
    report = runner.purity_report(loader._params(), runner.init_carry(),
                                  loader._opponent_params())
    if not report["pure"]:
        raise SystemExit(
            f"league-learner fused loop is not device-pure: "
            f"{report['offending']}")
    if not getattr(args, "no_supervise", False):
        learner.resume_latest()  # supervised restart resumes the lineage

    def run_loop():
        out = loop.run()
        print(f"league-learner {player_id} done: {json.dumps(out)}",
              flush=True)

    if getattr(args, "no_supervise", False):
        run_loop()
        return
    supervise_call(
        run_loop, op=f"league-learner:{player_id}",
        policy=_restart_policy(args),
        on_restart=lambda e: learner.resume_latest(),
    )


def _check_league_devices(args, n_players: int) -> None:
    """An accelerator belongs to one process at a time, every league learner
    is its own process, and nothing here hands each learner a device of its
    own: on an accelerator host the second learner would hang at backend
    start, however many devices there are. Ask a short-lived child for the
    backend (this launcher must stay off jax, or IT would hold the devices)
    and refuse with the way out."""
    if args.host_devices or args.platform == "cpu" or n_players < 2:
        return
    import subprocess
    import sys

    pin = ("" if args.platform == "auto"
           else f"jax.config.update('jax_platforms', {args.platform!r}); ")
    out = subprocess.run(
        [sys.executable, "-c",
         f"import jax; {pin}print(jax.default_backend(), jax.device_count())"],
        capture_output=True, text=True, timeout=300,
    )
    if out.returncode:
        raise SystemExit(f"league-run: no jax backend:\n{out.stderr[-2000:]}")
    backend, count = out.stdout.split()[-2:]
    if backend != "cpu":
        raise SystemExit(
            f"league-run: {n_players} players are {n_players} learner "
            f"processes, but a {backend} device serves one process at a time "
            f"({count} here) and league-run cannot give each learner its own. "
            "Run one player per league-run on this host, or the whole league "
            "on the CPU with --platform cpu or --host-devices N.")


def run_league_run(args) -> None:
    """The self-play economy launcher: coordinator (LeagueService +
    ArenaStore + HA journal) in this process, one league-learner subprocess
    per player (docs/league.md quickstart). Exits 0 only when every learner
    exits 0, at least one historical snapshot was minted from a checkpoint
    generation, and the payoff matrix has real off-diagonal entries."""
    from ..league.runtime.runner import LeagueRunner
    from ..learner.base_learner import experiments_root

    player_ids = [s.strip() for s in args.league_players.split(",") if s.strip()]
    _check_league_devices(args, len(player_ids))
    save_path = args.save_path or os.path.join(
        experiments_root(), args.experiment_name)
    journal = args.journal_dir
    if not journal:
        journal = os.path.join(save_path, "league_journal")
    elif journal.lower() == "none":
        journal = ""  # chaos counter-demo: run the economy un-journaled
    extra = [
        "--batch-size", str(args.batch_size),
        "--traj-len", str(args.traj_len),
        "--jaxenv-units", str(args.jaxenv_units),
        "--jaxenv-episode-len", str(args.jaxenv_episode_len),
        "--experiment-name", args.experiment_name,
    ]
    if args.host_devices:
        extra += ["--host-devices", str(args.host_devices)]
    elif args.platform != "auto":
        extra += ["--platform", args.platform]
    if args.mesh:
        extra += ["--mesh", args.mesh]
    if args.no_health:
        extra += ["--no-health"]
    if args.no_supervise:
        extra += ["--no-supervise"]
    runner = LeagueRunner(
        player_ids=player_ids,
        save_path=save_path,
        journal_dir=journal,
        arena_store_path=os.path.join(save_path, "arena_store.pkl"),
        lease_s=args.lease_s or 30.0,
        # first-round asks sit behind each learner's XLA compile on small
        # hosts; a short TTL would count those as orphans
        job_ttl_s=600.0,
        learner_argv_extra=extra,
        rounds=args.league_rounds,
        iters_per_round=args.league_iters_per_round,
        actors_per_player=args.league_actors_per_player,
        reassign=args.league_actors_per_player > 0,
    )
    digest = runner.run(port=args.port)
    raise SystemExit(0 if digest.get("ok") else 1)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--type", default="all",
                   choices=["all", "league", "coordinator", "learner", "actor",
                            "replay", "arena", "league-run", "league-learner"])
    p.add_argument("--config", default="")
    p.add_argument("--iters", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--traj-len", type=int, default=None)
    p.add_argument("--env-num", type=int, default=None)
    p.add_argument("--episode-game-loops", type=int, default=300)
    p.add_argument("--env", default="",
                   choices=("", "mock", "sc2", "jaxenv"),
                   help="environment backend; overrides the config's "
                        "env.type (default mock)")
    p.add_argument("--anakin", action="store_true",
                   help="fused on-device rollout: train the learner from "
                        "the jaxenv Anakin loop (implies --env jaxenv; "
                        "replaces the actor plane entirely)")
    p.add_argument("--jaxenv-units", type=int, default=4,
                   help="jaxenv units per squad (padded squad width)")
    p.add_argument("--jaxenv-episode-len", type=int, default=32,
                   help="jaxenv env steps until episode timeout")
    p.add_argument("--experiment-name", default="rl_train")
    p.add_argument("--save-path", default="",
                   help="experiment root override (default "
                        "$DISTAR_EXPERIMENTS_ROOT or ./experiments/<name>); "
                        "scope smoke runs to tmp dirs so stale checkpoints "
                        "never poison auto-resume")
    p.add_argument("--mesh", default="",
                   help="device-mesh spec for the learner, e.g. "
                        "'dp=4,fsdp=2,tp=1' — compiles the jitted train "
                        "step with NamedSharding in/out shardings on the "
                        "live mesh and turns on sharded checkpoints "
                        "(docs/parallel.md)")
    p.add_argument("--host-devices", type=int, default=0,
                   help="force a virtual n-device CPU platform before jax "
                        "init (multichip smoke without silicon: "
                        "--host-devices 8 --mesh dp=4,fsdp=2)")
    p.add_argument("--sharded-ckpt", action="store_true", default=None,
                   help="checkpoint as one CRC'd blob per parameter shard "
                        "+ layout manifest (default: on when --mesh is "
                        "given, off otherwise)")
    p.add_argument("--no-sharded-ckpt", dest="sharded_ckpt",
                   action="store_false")
    p.add_argument("--smoke-model", action="store_true", default=True)
    p.add_argument("--full-model", dest="smoke_model", action="store_false")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--admin-port", type=int, default=None,
                   help="serve the learner admin API (status / save_ckpt / "
                        "update_config and on-demand POST /profile?steps=N "
                        "trace capture -> ranked bucket report; see "
                        "`opsctl profile`) on this port (learner-hosting "
                        "roles)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve GET /metrics (Prometheus text) on this port; "
                        "the coordinator role serves it on --port already "
                        "(plus /healthz, /alerts, /timeseries)")
    p.add_argument("--no-health", action="store_true",
                   help="disable the fleet-health subsystem (TSDB sampler, "
                        "watchdog rules, telemetry shipping, crash recorder)")
    p.add_argument("--dynamics-every", type=int, default=None,
                   help="training-dynamics gauge-export stride (learner "
                        "dynamics.every_n); 0 disables the in-jit "
                        "diagnostics tree entirely; default: config/10")
    p.add_argument("--health-sample-s", type=float, default=1.0,
                   help="registry->TSDB sampling cadence")
    p.add_argument("--health-eval-s", type=float, default=2.0,
                   help="health rulebook evaluation cadence")
    p.add_argument("--telemetry-interval-s", type=float, default=5.0,
                   help="snapshot shipping cadence to the coordinator "
                        "(learner/actor roles)")
    p.add_argument("--no-supervise", action="store_true",
                   help="disable the resilience layer's role supervision "
                        "(crash-restart of actor loops, learner auto-resume "
                        "from the latest checkpoint pointer)")
    p.add_argument("--restart-max", type=int, default=5,
                   help="restart budget per role within --restart-window-s")
    p.add_argument("--restart-window-s", type=float, default=300.0,
                   help="sliding window for the restart budget")
    p.add_argument("--league-resume", default="",
                   help="league role: resume-journal path; loaded on start "
                        "when present, then autosaved periodically")
    p.add_argument("--league-autosave-s", type=float, default=30.0,
                   help="league resume-journal cadence (0 = league config "
                        "save_resume_freq_s)")
    p.add_argument("--lease-s", type=float, default=0.0,
                   help="coordinator role: lease TTL for registrations; "
                        "endpoints that stop heartbeating are evicted "
                        "(0 = leases disabled)")
    p.add_argument("--journal-dir", default="",
                   help="coordinator role: write-ahead-journal directory "
                        "(comm/ha.py) — every mutating route is journaled, "
                        "a restart replays it, and standbys can tail it "
                        "('' = in-memory broker, the pre-HA behavior)")
    p.add_argument("--ha-role", default="auto",
                   choices=("auto", "primary", "standby"),
                   help="coordinator HA role: auto probes --ha-peers and "
                        "joins a live primary as standby, else leads")
    p.add_argument("--ha-peers", default="",
                   help="comma list of peer coordinator host:port addrs "
                        "(the other members of the HA pair/set)")
    p.add_argument("--ha-port", type=int, default=0,
                   help="journal follower-feed TCP port (0 = ephemeral; "
                        "peers discover it via GET /coordinator/ha)")
    p.add_argument("--ha-advertise", default="",
                   help="host:port this coordinator advertises to peers "
                        "and clients (default 127.0.0.1:--port)")
    p.add_argument("--ha-takeover-grace-s", type=float, default=3.0,
                   help="standby promotes after this long without contact "
                        "from the primary's follower feed")
    p.add_argument("--league-addr", default="", help="host:port of the league server")
    p.add_argument("--coordinator-addr", default="",
                   help="host:port of the coordinator (HA fleets: a comma "
                        "list 'h1:p1,h2:p2' — clients follow leadership "
                        "across failovers)")
    p.add_argument("--plane", default="inline",
                   choices=("inline", "local", "remote"),
                   help="rollout inference plane backend (docs/serving.md): "
                        "inline = per-actor BatchedInference (legacy), "
                        "local = one shared in-process gateway per player, "
                        "remote = framed-TCP against a bin/serve gateway "
                        "(--plane-addr)")
    p.add_argument("--plane-addr", default="",
                   help="--plane remote target: one 'host:port' bin/serve "
                        "TCP frontend, a 'h1:p1,h2:p2' gateway fleet (rides "
                        "the serve.fleet session-affinity router), or "
                        "'discover' to build the fleet from the "
                        "coordinator's serve_gateway registrations "
                        "(needs --coordinator-addr)")
    p.add_argument("--plane-slots", type=int, default=0,
                   help="shared local engine lanes (0 = this job's env_num); "
                        "sessions reserve exact capacity, so size it for "
                        "every concurrent job on the host")
    p.add_argument("--replay", action="store_true",
                   help="--type all: route trajectories through an "
                        "in-process replay store (smoke config of the "
                        "store path) instead of the point-to-point shuttle")
    p.add_argument("--replay-addr", default="",
                   help="replay data-plane target: one 'host:port', a "
                        "comma-separated shard fleet (consistent-hash "
                        "routing + learner fan-in), or 'discover' to read "
                        "the fleet from the coordinator's replay_shard "
                        "registrations (default: the legacy shuttle path)")
    p.add_argument("--replay-shards", type=int, default=1,
                   help="--type all: stand up this many in-process replay "
                        "shards (actors route by consistent hash, the "
                        "learner fans in with per-shard rate limiting)")
    p.add_argument("--replay-fast-path", action="store_true",
                   help="--type all: zero-copy colocated store — actor "
                        "pushes and learner samples through a direct "
                        "in-process handle, no sockets, no serialization "
                        "(the Sebulba layout's data plane)")
    p.add_argument("--transport", default="auto",
                   choices=("auto", "shm", "tcp"),
                   help="data-plane transport for every colocated hop "
                        "(replay push/sample, rollout-plane remote): auto "
                        "negotiates shared-memory rings per connection "
                        "when client and server share a host (TCP stays "
                        "the control channel + fallback leg), shm is the "
                        "same policy, tcp refuses rings everywhere "
                        "(docs/data_plane.md transport negotiation)")
    p.add_argument("--no-replay-compress", dest="replay_compress",
                   action="store_false", default=True,
                   help="disable wire compression on replay data-plane "
                        "connections (negotiated per connection; servers "
                        "started with this flag refuse it for all peers)")
    p.add_argument("--replay-fanin", default="round_robin",
                   choices=("round_robin", "weighted"),
                   help="shard order for learner fan-in sampling: strict "
                        "rotation, or fullest-shard-first (weighted by "
                        "resident items)")
    p.add_argument("--replay-shard-id", default="",
                   help="--type replay: metrics/stats label for this fleet "
                        "member (default ':<port>')")
    p.add_argument("--replay-max-size", type=int, default=1024,
                   help="replay role: per-table item cap (FIFO eviction)")
    p.add_argument("--replay-spi", type=float, default=1.0,
                   help="replay role: samples-per-insert ratio enforced by "
                        "the rate limiter (<=0 disables ratio enforcement)")
    p.add_argument("--replay-min-size", type=int, default=0,
                   help="replay role: inserts required before sampling "
                        "starts (0 = the learner batch size)")
    p.add_argument("--replay-error-buffer", type=float, default=None,
                   help="replay role: limiter slack in sample units "
                        "(default max(1, spi) * batch size, so a whole "
                        "learner batch is always admissible)")
    p.add_argument("--replay-sampler", default="fifo",
                   choices=("fifo", "uniform", "prioritized"),
                   help="replay role: table sampler (fifo = consume-once "
                        "legacy semantics; prioritized = sum-tree PER)")
    p.add_argument("--replay-spill-dir", default="",
                   help="replay role: disk-spill directory; acked inserts "
                        "survive a store crash (empty = no durability)")
    p.add_argument("--replay-spill-max", type=int, default=4096,
                   help="replay role: spill ring bound (oldest dropped past it)")
    p.add_argument("--replay-max-staleness-s", type=float, default=0.0,
                   help="replay role: evict items older than this "
                        "(0 = no staleness eviction)")
    p.add_argument("--distill", action="store_true",
                   help="learner-hosting roles: run the student-tier "
                        "DISTILLATION learner instead of the RL teacher — "
                        "trains model.student_model_config on the same "
                        "trajectory batches via masked per-head KL against "
                        "the teacher logits already riding every flush, "
                        "publishes checkpoints under the 'student' "
                        "CheckpointManager role key, and exports the "
                        "distar_distill_* drift gauges "
                        "(docs/training_guide.md distillation quickstart)")
    p.add_argument("--arena-ckpt-dir", default="",
                   help="--type arena: checkpoint directory whose "
                        "CheckpointManager generations form the model roster")
    p.add_argument("--arena-roles", default="",
                   help="--type arena: comma-separated CheckpointManager "
                        "role keys to rate ('' = the default/teacher "
                        "lineage, shown as main)")
    p.add_argument("--arena-episodes", type=int, default=8,
                   help="--type arena: episodes per scheduled scenario batch")
    p.add_argument("--arena-batches", type=int, default=0,
                   help="--type arena: stop after N batches (0 = run forever)")
    p.add_argument("--arena-interval-s", type=float, default=5.0,
                   help="--type arena: idle sleep when no assignment is "
                        "available")
    p.add_argument("--arena-artifact", default="",
                   help="--type arena: write the ARENA_r*.json ledger "
                        "(matches/s + ratings, honesty flags in-band) here "
                        "on exit")
    p.add_argument("--arena-store", default="",
                   help="--type coordinator: host the durable ArenaStore, "
                        "journaled at this path (league-autosave idiom); "
                        "enables the /arena/* routes")
    p.add_argument("--player-id", default="MP0")
    p.add_argument("--league-players", default="MP0,EP0,ME0",
                   help="--type league-run: comma list of active league "
                        "player ids (prefix picks the class: MP main, EP "
                        "exploiter, ME main-exploiter, ...); one learner "
                        "subprocess is spawned per player")
    p.add_argument("--league-rounds", type=int, default=2,
                   help="league-run/league-learner: matchmade rounds per "
                        "learner (each: ask -> train -> report -> "
                        "checkpoint generation -> train-info)")
    p.add_argument("--league-iters-per-round", type=int, default=1,
                   help="optimizer steps per matchmade round")
    p.add_argument("--league-actors-per-player", type=int, default=0,
                   help="--type league-run: seed each player's elastic "
                        "actor-slot fleet with this many members and run "
                        "the payoff-driven reassigner over them (0 = no "
                        "actor fleets; the fused learners roll out "
                        "on-device)")
    p.add_argument("--pipeline", default="default",
                   help="learner implementation to run: 'default' or an "
                        "importable custom-pipeline module (plugins.py)")
    p.add_argument("--dist-method", default="single_node",
                   choices=["auto", "slurm", "single_node", "explicit"])
    p.add_argument("--dist-coordinator-address", default="",
                   help="host:port for jax.distributed (explicit mode)")
    p.add_argument("--dist-num-processes", type=int, default=None)
    p.add_argument("--dist-process-id", type=int, default=None)
    p.add_argument("--platform", default="auto", choices=PLATFORMS,
                   help=PLATFORM_HELP)
    return p


def main() -> None:
    args = build_parser().parse_args()
    _resolve_args(args)
    if args.dist_method == "explicit" and not (
        args.dist_coordinator_address
        and args.dist_num_processes is not None
        and args.dist_process_id is not None
    ):
        raise SystemExit(
            "--dist-method explicit requires --dist-coordinator-address, "
            "--dist-num-processes and --dist-process-id"
        )
    if args.anakin or args.type in DEVICE_ROLES:
        # must precede ANY jax backend init (device query) in this process;
        # the other roles never touch a backend, so a broker or a league-run
        # parent on an accelerator host leaves the devices to the learners
        from ..parallel.executor import select_backend

        args.dist_info = select_backend(
            args.platform, args.host_devices,
            distributed=dict(
                method=args.dist_method,
                coordinator_address=args.dist_coordinator_address or None,
                num_processes=args.dist_num_processes,
                process_id=args.dist_process_id,
            ) if args.type == "learner" else None,
        )

    if args.anakin:
        if args.env and args.env != "jaxenv":
            raise SystemExit("--anakin requires --env jaxenv")
        run_anakin(args)
    elif args.type == "all":
        run_all(args)
    elif args.type == "league":
        league = League(read_config(args.config) if args.config else {})
        if args.league_resume:
            # pick the league up where the last journal left it — a broker
            # restart must not reset all payoff/ELO state
            if os.path.exists(args.league_resume):
                league.load_resume(args.league_resume)
            league.start_autosave(args.league_resume,
                                  interval_s=args.league_autosave_s or None)
        server = LeagueAPIServer(league, port=args.port)
        server.start()
        print(f"league serving on {server.host}:{server.port}", flush=True)
        while True:
            time.sleep(3600)
    elif args.type == "replay":
        run_replay(args)
    elif args.type == "coordinator":
        # the broker evaluates the FULL rulebook: shipped telemetry gives it
        # per-source learner/actor/serve series for the whole fleet
        _init_health(args, roles=("learner", "actor", "coordinator", "trace",
                                  "serve", "replay", "distill", "arena"),
                     source="coordinator")
        if args.arena_store:
            # host the skill ledger: reload the journal (ratings, payoff AND
            # the idempotency key set survive a broker restart), then keep
            # journaling on the autosave thread
            from ..arena import ArenaStore, set_arena_store

            store = ArenaStore(path=args.arena_store)
            if store.maybe_load():
                print(f"arena store resumed from {args.arena_store}",
                      flush=True)
            store.start_autosave(interval_s=args.league_autosave_s or 30.0)
            set_arena_store(store)
        co = Coordinator(default_lease_s=args.lease_s or None)
        server = CoordinatorServer(coordinator=co, port=args.port)
        ha_state = None
        if args.journal_dir:
            # HA broker: journal every mutating route, replay on restart,
            # serve the follower feed; with --ha-peers, lease-based
            # leadership + epoch fencing (docs/resilience.md)
            from ..comm.ha import HAState

            ha_state = HAState(
                co, args.journal_dir,
                advertise=args.ha_advertise or f"127.0.0.1:{server.port}",
                feed_port=args.ha_port,
                peers=[p for p in (args.ha_peers or "").split(",") if p],
                role=args.ha_role,
                takeover_grace_s=args.ha_takeover_grace_s,
            )
            ha_state.boot()
            server.attach_ha(ha_state)
            print(f"coordinator HA: role={ha_state.role} "
                  f"epoch={ha_state.epoch} journal={args.journal_dir} "
                  f"feed=:{ha_state.feed_port}", flush=True)
        server.start()
        print(f"coordinator serving on {server.host}:{server.port}", flush=True)
        if args.arena_store or ha_state is not None:
            # a drained broker must not lose the tail of the match ledger
            # or the journal: turn SIGTERM into SystemExit so the final
            # journaling below runs (SIGKILL is exactly what the WAL and
            # the arena autosave bound the damage of)
            import signal as _signal
            import sys as _sys

            _signal.signal(_signal.SIGTERM, lambda *_: _sys.exit(0))
        try:
            while True:
                time.sleep(3600)
        finally:
            if args.arena_store:
                store.save()
                print("arena store journaled on shutdown", flush=True)
            if ha_state is not None:
                ha_state.final_snapshot()
                ha_state.stop()
                print("coordinator journal snapshotted on shutdown", flush=True)
    elif args.type == "arena":
        if not (args.coordinator_addr and args.arena_ckpt_dir):
            raise SystemExit(
                "--type arena requires --coordinator-addr and --arena-ckpt-dir")
        run_arena(args)
    elif args.type == "league-run":
        run_league_run(args)
    elif args.type == "league-learner":
        if not args.coordinator_addr:
            raise SystemExit(
                "--type league-learner requires --coordinator-addr")
        run_league_learner(args)
    elif args.type == "learner":
        if not args.coordinator_addr:
            raise SystemExit("--type learner requires --coordinator-addr (and usually --league-addr)")
        run_learner(args)
    elif args.type == "actor":
        if not (args.league_addr and args.coordinator_addr):
            raise SystemExit("--type actor requires --league-addr and --coordinator-addr")
        run_actor(args)


if __name__ == "__main__":
    main()

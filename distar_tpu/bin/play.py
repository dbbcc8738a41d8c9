"""Play/eval launcher: agent-vs-agent / agent-vs-bot / human-vs-agent
matches on a real SC2 install, plus a game-free mock mode.

Role parity with the reference (reference: distar/bin/play.py:27-120):
resolves the SC2 install (SC2PATH), installs bundled maps, pins the matchup
by game_type, loads a checkpoint per side (native checkpoints or reference
torch .pth via ref_convert), runs realtime games, and reports winrates.
Human mode gives the human their own full-screen client (env.py:191-197);
the realtime clock is SC2's own.
"""
from __future__ import annotations

import argparse
import os
from collections import Counter

from ..actor import Actor
from ..envs import MockEnv
from ..utils.checkpoint import load_checkpoint

GAME_TYPES = ("agent_vs_agent", "agent_vs_bot", "human_vs_agent", "mock")


def find_sc2() -> str:
    """Locate the SC2 install via the platform run config (single source of
    truth for discovery, envs/sc2/run_configs.py)."""
    from ..envs.sc2 import run_configs

    data_dir = run_configs.get().data_dir
    if not os.path.isdir(data_dir):
        raise SystemExit(
            f"StarCraft II install not found at '{data_dir}': set the SC2PATH "
            "environment variable (or use --game_type mock for a game-free "
            "smoke run)."
        )
    return data_dir


def load_params(path: str, model_cfg):
    """Checkpoint -> Flax params; reference torch .pth checkpoints convert
    on the fly (model/ref_convert.convert_model)."""
    if path.endswith((".pth", ".pt")):
        import torch

        sd = torch.load(path, map_location="cpu")
        sd = sd.get("model", sd)
        from ..model.ref_convert import convert_model

        return convert_model(sd, model_cfg)
    from ..utils.checkpoint import load_params as load_native_params

    return load_native_params(path)


def side_name(path: str, default: str) -> str:
    if not path:
        return default
    return os.path.basename(path).rsplit(".", 1)[0] or default


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model1", default="", help="checkpoint for side 0")
    p.add_argument("--model2", default="", help="checkpoint for side 1 (or botN)")
    p.add_argument("--game_type", default="human_vs_agent", choices=GAME_TYPES)
    p.add_argument("--map", dest="map_name", default="KairosJunction")
    p.add_argument("--race1", default="zerg")
    p.add_argument("--race2", default="zerg")
    p.add_argument("--game-count", type=int, default=1)
    p.add_argument("--maps-dir", default="", help="bundled .SC2Map dir to auto-install")
    p.add_argument("--z-path", default="", help="Z strategy library for both sides")
    p.add_argument("--save-replay-episodes", type=int, default=0)
    p.add_argument("--replay-dir", default="replays")
    p.add_argument("--no-realtime", action="store_true",
                   help="lockstep stepping instead of wall-clock (agent games only)")
    p.add_argument("--episode-game-loops", type=int, default=300, help="mock mode only")
    p.add_argument("--env-num", type=int, default=1)
    p.add_argument("--smoke-model", action="store_true", default=None,
                   help="tiny model dims for fast smoke runs (default for "
                        "checkpoint-less mock games)")
    p.add_argument("--full-model", dest="smoke_model", action="store_false",
                   help="force full-scale model dims")
    p.add_argument("--platform", default="auto", choices=("auto", "cpu", "tpu"),
                   help="inference device; cpu works anywhere (the reference's "
                        "--cpu flag), auto uses the default jax backend (mock "
                        "games: the cpu), tpu fails when no TPU is found")
    p.add_argument("--lan-host", action="store_true",
                   help="HUMAN side of a remote showmatch: host a LAN game "
                        "full-screen and print the handshake port for the "
                        "agent machine (role of reference play_vs_agent)")
    p.add_argument("--lan", default="",
                   help="AGENT side of a remote showmatch: host:port of the "
                        "human machine's handshake (reference lan_sc2_env)")
    args = p.parse_args()

    if args.lan_host:
        return run_lan_host(args)

    from ..parallel.executor import select_backend

    # a checkpoint-less mock game is a smoke run: keep it off the accelerator
    select_backend("cpu" if args.platform == "auto" and args.game_type == "mock"
                   else args.platform)

    from .rl_train import SMOKE_MODEL

    if args.smoke_model is None:
        # checkpoints require the full-scale dims; a checkpoint-less mock
        # smoke shouldn't compile the full model
        args.smoke_model = args.game_type == "mock" and not args.model1
    model_cfg = SMOKE_MODEL if args.smoke_model else {}

    if args.game_type == "mock":
        from ..model.config import default_model_config
        from ..utils.config import deep_merge_dicts

        cfg = deep_merge_dicts(default_model_config(), model_cfg)
        player_params = {}
        if args.model1:
            player_params["model1"] = load_params(args.model1, cfg)
        if args.model2:
            player_params["model2"] = load_params(args.model2, cfg)
        actor = Actor(
            cfg={"actor": {"env_num": args.env_num, "traj_len": 10 ** 9}},
            model_cfg=model_cfg,
            env_fn=lambda: MockEnv(episode_game_loops=args.episode_game_loops),
            player_params=player_params,
        )
        job = {
            "player_ids": ["model1", "model2"],
            "send_data_players": [],
            "update_players": [],
            "teacher_player_ids": ["none", "none"],
            "branch": "eval_test",
            "env_info": {"map_name": "mock"},
        }
        results = actor.run_job(episodes=args.game_count, job=job)
        report(results)
        return

    sc2_dir = find_sc2()
    # auto-install the bundled Ladder2019Season2 maps (or a user-supplied
    # dir) so offline hosts play without ad-hoc downloads (role of the
    # reference auto-install, rl_train.py:115-116); a read-only install dir
    # is fine — run_configs.map_data falls back to the bundle at load time
    from ..envs.sc2 import maps as map_registry

    try:
        map_registry.install_maps(args.maps_dir or None, sc2_dir)
    except OSError as e:
        import logging

        logging.warning(f"map auto-install into {sc2_dir} failed ({e!r}); "
                        "relying on the bundled-map fallback")

    from ..model.config import default_model_config
    from ..utils.config import deep_merge_dicts

    full_model_cfg = deep_merge_dicts(default_model_config(), model_cfg)

    if args.lan:
        # agent side of a remote showmatch: join the human's hosted game
        from ..envs.sc2.lan import LanSC2Env

        host, sep, port = args.lan.rpartition(":")
        if not sep or not port.isdigit():
            raise SystemExit(
                f"--lan expects host:port (the endpoint --lan-host printed), "
                f"got {args.lan!r}"
            )
        host = host or "127.0.0.1"
        name1 = side_name(args.model1, "model1")
        player_params = {}
        if args.model1:
            player_params[name1] = load_params(args.model1, full_model_cfg)
        job = {
            "player_ids": [name1],
            "send_data_players": [],
            "update_players": [],
            "teacher_player_ids": ["none"],
            "branch": "eval_test",
            "env_info": {"map_name": args.map_name},
            "z_path": [args.z_path] if args.z_path else [],
            "opponent_id": "remote_human",
        }
        actor = Actor(
            cfg={"actor": {"env_num": 1, "traj_len": 10 ** 9}},
            model_cfg=model_cfg,
            env_fn=lambda: LanSC2Env(host, int(port), agent_race=args.race1),
            player_params=player_params,
        )
        results = actor.run_job(episodes=1, job=job)
        report(results)
        return

    # matchup -> env player ids + the model-driven sides (reference
    # play.py:101-112)
    name1 = side_name(args.model1, "model1")
    realtime = not args.no_realtime
    player_params = {}
    if args.game_type == "agent_vs_agent":
        name2 = side_name(args.model2, "model2")
        if name2 == name1:
            name2 = name1 + "(1)"
        env_player_ids = [name1, name2]
        agent_ids = [name1, name2]
        if args.model2:
            player_params[name2] = load_params(args.model2, full_model_cfg)
    elif args.game_type == "agent_vs_bot":
        import re

        if args.model2 and not re.fullmatch(r"bot\d+", args.model2):
            raise SystemExit(
                f"agent_vs_bot expects --model2 botN (built-in bot level), "
                f"got {args.model2!r}; use --game_type agent_vs_agent for a "
                "checkpoint opponent"
            )
        bot = args.model2 or "bot10"
        env_player_ids = [name1, bot]
        agent_ids = [name1]
    else:  # human_vs_agent
        env_player_ids = [name1, "human"]
        agent_ids = [name1]
        realtime = True  # the human plays in wall-clock time
    if args.model1:
        player_params[name1] = load_params(args.model1, full_model_cfg)

    env_cfg = {
        "env": {
            "map_name": args.map_name,
            "player_ids": env_player_ids,
            "races": [args.race1, args.race2],
            "realtime": realtime,
            "save_replay_episodes": args.save_replay_episodes,
            "replay_dir": args.replay_dir,
        }
    }

    from ..envs.sc2.launcher import make_sc2_env

    z_paths = [args.z_path, args.z_path] if args.z_path else []
    job = {
        "player_ids": agent_ids,
        "send_data_players": [],
        "update_players": [],
        "teacher_player_ids": ["none"] * len(agent_ids),
        "branch": "eval_test",
        "env_info": {"map_name": args.map_name},
        "z_path": z_paths,
        "opponent_id": env_player_ids[-1],
    }
    actor = Actor(
        cfg={"actor": {"env_num": args.env_num, "traj_len": 10 ** 9}},
        model_cfg=model_cfg,
        env_fn=lambda: make_sc2_env(env_cfg),
        player_params=player_params,
    )
    results = actor.run_job(episodes=args.game_count, job=job)
    report(results)


def run_lan_host(args) -> None:
    """Human side of a remote showmatch: host the LAN game, print the
    handshake endpoint, then play full-screen until the game ends."""
    import socket
    import time

    find_sc2()
    from ..envs.sc2 import maps as map_registry
    from ..envs.sc2.lan import host_lan_game

    try:
        map_registry.install_maps(args.maps_dir or None)
    except OSError:
        pass
    controller, handshake_port, proc, join_thread = host_lan_game(
        args.map_name, race=args.race1, realtime=True
    )
    # the outward-facing address: a connected UDP socket reveals the local
    # interface IP without sending a packet (gethostbyname(hostname) often
    # resolves to 127.0.1.1 via /etc/hosts — useless to a remote machine)
    try:
        probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        probe.connect(("10.255.255.255", 1))
        ip = probe.getsockname()[0]
        probe.close()
    except OSError:
        ip = socket.gethostbyname(socket.gethostname())
    print(
        f"LAN game hosted. On the agent machine run:\n"
        f"  python -m distar_tpu.bin.play --lan {ip}:{handshake_port} "
        f"--model1 <ckpt> --race1 {args.race2}\n"
        f"(substitute this machine's reachable IP if {ip} is wrong)\n"
        f"Waiting for the agent to join...",
        flush=True,
    )
    join_thread.join()
    print("Agent joined — play! (this process exits when the game ends)", flush=True)
    try:
        while True:
            time.sleep(5)
            controller.ping()
    except Exception:
        pass
    finally:
        if proc is not None:
            proc.close()


def report(results) -> None:
    outcomes = Counter(
        "side0" if r["0"]["winloss"] > 0 else
        ("side1" if r["0"]["winloss"] < 0 else "tie")
        for r in results
    )
    n = max(len(results), 1)
    print(
        f"games={len(results)} side0_winrate={outcomes['side0'] / n:.2f} "
        f"side1_winrate={outcomes['side1'] / n:.2f} ties={outcomes['tie']}"
    )


if __name__ == "__main__":
    main()

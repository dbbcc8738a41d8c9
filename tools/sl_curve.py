"""SL learning curve with a HELD-OUT eval set (SURVEY §7 milestone 4,
VERDICT r4 #4a).

Builds a family of scripted fake-server replays sharing one behavioral rule
(a build -> train -> attack command cycle; per-replay seeds vary unit
choices, build positions, pacing and length), two-pass-decodes them through
the PRODUCTION client stack (websocket + protos + RemoteController +
ReplayDecoder), trains the SL learner on the train split, and evaluates
action_type_acc on decoded replays the learner NEVER saw. The rule is
recoverable from the decoded features (last_action_type drives the cycle),
so held-out accuracy rising past chance and plateauing demonstrates
GENERALIZED imitation, not memorization — the game-free analogue of the
reference's SL milestone (replays -> sl_train -> accuracy climbing).

Usage:  python tools/sl_curve.py [--rounds 12] [--iters-per-round 40]
        [--out artifacts/sl_curve_r05.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMALL_MODEL = {
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


def _pin_cpu() -> None:
    from distar_tpu.parallel.executor import select_backend

    select_backend("cpu")


def make_scripted_replay(seed: int, n_actions: int = 30):
    """One replay from the shared behavioral rule, seed-varied in every
    non-rule dimension (acting units, build sites, pacing, length)."""
    from distar_tpu.lib import actions as ACT

    def gab(name):
        return next(a["general_ability_id"] for a in ACT.ACTIONS if a["name"] == name)

    rng = np.random.default_rng(seed)
    build = gab("Build_Hatchery_pt")
    train = gab("Train_Drone_quick")
    attack = gab("Attack_unit")
    actions = []
    loop = int(rng.integers(8, 14))
    n = n_actions + int(rng.integers(-4, 5))
    for i in range(n):
        tag = [10000 + int(rng.integers(0, 8))]
        kind = i % 3  # THE rule: build -> train -> attack, forever
        if kind == 0:
            site = (18.0 + float(rng.integers(0, 12)), 28.0 + float(rng.integers(0, 8)))
            actions.append((loop, build, tag, site))
        elif kind == 1:
            actions.append((loop, train, tag, None))
        else:
            actions.append((loop, attack, tag, 20001))
        loop += int(rng.integers(22, 40))
    return {
        "base_build": 75689,
        "game_version": "4.10.0",
        "data_version": "FAKE",
        "map_name": "KairosJunction",
        "game_duration_loops": loop + 50,
        "players": [
            {"player_id": 1, "race": 2, "mmr": 4800, "apm": 160, "result": 1},
            {"player_id": 2, "race": 2, "mmr": 4600, "apm": 140, "result": 2},
        ],
        "actions": actions,
    }


def decode_family(root: str, seeds) -> int:
    """Decode one replay per seed into ``root`` (ReplayDataset layout)."""
    from distar_tpu.envs.replay_decoder import ReplayDecoder
    from distar_tpu.envs.sc2.fake_sc2 import FakeGameCore, FakeSC2Server
    from distar_tpu.envs.sc2.remote_controller import RemoteController
    from distar_tpu.learner.sl_dataloader import ReplayDataset

    decoded = 0
    for seed in seeds:
        server = FakeSC2Server(game=FakeGameCore(end_at=100_000))
        server.game.replay_library["r.SC2Replay"] = make_scripted_replay(seed)
        dec = ReplayDecoder(
            cfg={"minimum_action_length": 2, "parse_race": "Z"},
            controller_provider=lambda v, port=server.port: RemoteController(
                "127.0.0.1", port, timeout_seconds=5
            ),
        )
        try:
            traj = dec.run("r.SC2Replay", player_index=0)
        finally:
            dec.close()
            server.stop()
        if traj:
            ReplayDataset.save(root, f"s{seed:04d}", traj)
            decoded += 1
    return decoded


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=12)
    p.add_argument("--iters-per-round", type=int, default=40)
    p.add_argument("--train-replays", type=int, default=8)
    p.add_argument("--eval-replays", type=int, default=4)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--unroll", type=int, default=4)
    p.add_argument("--out", default="artifacts/sl_curve_r05.json")
    args = p.parse_args()
    _pin_cpu()

    import tempfile

    from distar_tpu.learner import SLLearner
    from distar_tpu.learner.sl_dataloader import ReplayDataset, SLDataloader

    work = tempfile.mkdtemp(prefix="sl_curve_")
    train_root = os.path.join(work, "train")
    eval_root = os.path.join(work, "eval")
    t0 = time.perf_counter()
    n_train = decode_family(train_root, range(100, 100 + args.train_replays))
    n_eval = decode_family(eval_root, range(900, 900 + args.eval_replays))
    decode_s = time.perf_counter() - t0
    assert n_train and n_eval, (n_train, n_eval)

    learner = SLLearner(
        {
            "common": {"experiment_name": "sl_curve"},
            "learner": {
                "batch_size": args.batch, "unroll_len": args.unroll,
                "save_freq": 10 ** 9, "log_freq": 10 ** 9,
                "learning_rate": 3e-4,
            },
            "model": SMALL_MODEL,
        }
    )
    learner.set_dataloader(
        SLDataloader(ReplayDataset(train_root), args.batch, args.unroll, seed=1)
    )

    curve = []
    total_iters = 0
    for _ in range(args.rounds):
        learner.run(max_iterations=total_iters + args.iters_per_round)
        total_iters += args.iters_per_round
        train_acc = float(learner.variable_record.get("action_type_acc").avg)
        ev = learner.evaluate(
            SLDataloader(ReplayDataset(eval_root), args.batch, args.unroll, seed=2),
            max_batches=10,
        )
        curve.append(
            {
                "iter": total_iters,
                "train_action_type_acc": round(train_acc, 4),
                "eval_action_type_acc": round(ev["action_type_acc"], 4),
                "eval_total_loss": round(ev["total_loss"], 2),
            }
        )
        print(json.dumps(curve[-1]), flush=True)

    accs = [c["eval_action_type_acc"] for c in curve]
    chance = 1.0 / 3.0  # the rule cycles three action types
    report = {
        "metric": "held-out action_type_acc (scripted-rule replay family)",
        "decode": {"train_replays": n_train, "eval_replays": n_eval,
                   "decode_s": round(decode_s, 1)},
        "config": {"batch": args.batch, "unroll": args.unroll,
                   "iters_per_round": args.iters_per_round,
                   "rounds": args.rounds, "model": "small"},
        "curve": curve,
        "summary": {
            "first_eval_acc": accs[0],
            "best_eval_acc": max(accs),
            "final_eval_acc": accs[-1],
            "chance_level": round(chance, 4),
            "rises_past_chance": max(accs) > chance + 0.1,
            # plateau: the last quarter moves < 5 points
            "plateaued": (max(accs[-max(len(accs) // 4, 2):])
                          - min(accs[-max(len(accs) // 4, 2):])) < 0.05,
        },
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["summary"]))


if __name__ == "__main__":
    main()

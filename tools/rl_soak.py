"""RL end-to-end soak: ~100 league-RL iterations on the mock env, with
invariant checks the 2-iteration smoke can't see.

Role: the long-horizon proof of the reference rl_train call stack
(SURVEY.md §3.1 — actor rollouts -> adapter data plane -> learner train
step -> weight publication -> league train-info/snapshot), asserting:

  * weight propagation: the actor's received-model high-water mark keeps
    rising and tracks the learner within the publication cadence
  * off-policy staleness: bounded (mean/max) across every batch
  * league lifecycle: train-info advances the player's total_train_steps
    and the one_phase_step snapshot fires (historical player appears)
  * compute-time stability: median train time of the last quarter vs the
    first quarter after warmup — catches leaks/regressions that creep in
    over minutes, the failure mode a 2-iter smoke can't see (wall iter time
    is reported but not asserted: it settles at the actor production rate)

Usage:  python tools/rl_soak.py [--iters 100] [--out artifacts/rl_soak.json]
The JSON report is ALWAYS written (long-run telemetry must survive a failed
bound); invariant violations land in report["invariant_violations"] and
main() exits 1 when any are present.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

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
    """The soak is a host-side correctness run: keep it off the accelerator."""
    from distar_tpu.parallel.executor import select_backend

    select_backend("cpu")


def run_soak(iters: int = 100, batch_size: int = 4, traj_len: int = 2,
             env_num: int = 2, features: bool = False, actor_threads: int = 1,
             win_rule: str = "random", opponent_pipeline: str = "default",
             learn: bool = False, episode_game_loops: int = 300,
             cache_size: int = 64, prefill: int = 0,
             prefill_timeout: float = 1800.0,
             opponent_heavy: bool = False) -> dict:
    """``features=True`` additionally exercises the round-4 knobs in
    combination for the whole soak: actor+learner pad-to-bucket entity
    caps, per-parameter save_grad logging, and periodic ASYNC checkpoint
    saves racing the train loop.

    Round-5 regimes on top:
      * ``actor_threads``/``env_num`` scale trajectory production; on a
        single host the per-frame cost ratio (actor rollout+teacher vs
        learner fwd+bwd) caps how learner-bound the live equilibrium can
        get, so ``prefill`` additionally banks N trajectories BEFORE the
        learner starts — the drain then measures the SATURATED regime (the
        TPU-learner + CPU-fleet shape: data_share ~0, occupancy ~1,
        queue-aged staleness) with the same machinery
      * ``win_rule='battle'`` + ``opponent_pipeline='scripted.random'`` +
        ``learn=True`` is the SKILL regime (VERDICT r4 #4b): the learnable
        mock-world rule, a model-free random opponent, and RL hyperparams
        that let the policy move (teacher-KL off, modest entropy, higher
        lr) — winrate vs the scripted opponent and the ELO gap are recorded
        every iteration so the report carries a curve."""
    _pin_cpu()
    # sized so >=1 one_phase_step snapshot fires inside the soak
    one_phase_step = max(1, int(iters * batch_size * traj_len * 0.6))
    from distar_tpu.actor import Actor
    from distar_tpu.comm import Adapter, Coordinator
    from distar_tpu.envs import MockEnv
    from distar_tpu.league import League
    from distar_tpu.learner import RLLearner
    from distar_tpu.learner.hooks import LambdaHook
    from distar_tpu.learner.rl_dataloader import RLDataLoader

    league_cfg = {
        "league": {
            # opponent-heavy matchmaking fills the vs-HP0 payoff meter from
            # game 1, so a skill run's winrate curve shows the CLIMB (with
            # the default sp-heavy mix the meter only fills after learning
            # has already moved the policy)
            **({"branch_probs": {
                "MainPlayer": {"sp": 0.1, "pfsp": 0.7, "eval": 0.2},
            }} if opponent_heavy else {}),
            "active_players": {
                "player_id": ["MP0"],
                "checkpoint_path": ["mp0.ckpt"],
                "pipeline": ["default"],
                "frac_id": [1],
                "z_path": ["3map.json"],
                "z_prob": [0.0],
                "teacher_id": ["T"],
                "teacher_path": ["t.ckpt"],
                "one_phase_step": [one_phase_step],
                "chosen_weight": [1.0],
            },
            "historical_players": {
                "player_id": ["HP0"],
                "checkpoint_path": ["hp0.ckpt"],
                "pipeline": [opponent_pipeline],
                "frac_id": [1],
                "z_path": ["3map.json"],
                "z_prob": [0.0],
            },
        }
    }
    league = League(league_cfg)
    co = Coordinator()
    learner_adapter = Adapter(coordinator=co)
    actors = []
    for a_i in range(actor_threads):
        actors.append(Actor(
            cfg={"actor": {"env_num": env_num, "traj_len": traj_len,
                           "seed": 7 + a_i,
                           **({"max_entities": 256} if features else {})}},
            league=league,
            adapter=Adapter(coordinator=co),
            model_cfg=SMALL_MODEL,
            env_fn=lambda a_i=a_i: MockEnv(
                episode_game_loops=episode_game_loops, seed=11 + a_i,
                win_rule=win_rule,
            ),
        ))

    stop = threading.Event()
    actor_err: list = []

    def actor_loop(actor):
        while not stop.is_set():
            try:
                actor.run_job(episodes=1)
            except Exception as e:  # pragma: no cover - surfaced in report
                actor_err.append(repr(e))
                return

    threads = [
        threading.Thread(target=actor_loop, args=(a,), daemon=True) for a in actors
    ]
    for t in threads:
        t.start()

    learner = RLLearner(
        {
            "common": {"experiment_name": "rl_soak"},
            # features spread LAST: dict literals resolve duplicates
            # last-wins, so it must override the base save_freq
            "learner": {"batch_size": batch_size, "unroll_len": traj_len,
                        "save_freq": 10 ** 9, "log_freq": 25,
                        **({"max_entities": 256, "save_grad": True,
                            "save_freq": max(iters // 5, 1)} if features else {}),
                        # skill regime: policy must be free to move — the
                        # teacher is the random init, so its KL would pin
                        # the policy to noise (reference turns this dial
                        # through its rl yaml too)
                        **({"learning_rate": 5e-4,
                            "loss": {"kl_weight": 0.0,
                                     "action_type_kl_weight": 0.0,
                                     "entropy_weight": 3e-5}} if learn else {})},
            "model": SMALL_MODEL,
        }
    )
    # the pull cache bounds worst-case staleness when the LEARNER is the
    # bottleneck: every buffered trajectory ages one learner iter per
    # consumed batch, so depth is a freshness/throughput dial (the reference
    # measures-but-never-drops, rl_learner.py:90-101 — same policy here)
    dataloader = RLDataLoader(learner_adapter, "MP0", batch_size,
                              cache_size=cache_size)
    learner.set_dataloader(dataloader)
    learner.attach_comm(learner_adapter, "MP0", league=league,
                        send_model_freq=4, send_train_info_freq=4)

    telemetry = {
        "iter_times": [], "train_times": [], "data_times": [],
        "staleness_mean": [], "staleness_max": [],
        "total_loss": [], "grad_norm": [], "actor_model_iter": [],
        "historical_count": [], "winrate_hp0": [], "elo_gap": [],
        "games": [], "prefetch_occupancy": [], "actor_model_iter_min": [],
        "broker_depth": [],
    }
    last_t = [time.perf_counter()]

    def record(lrn):
        now = time.perf_counter()
        telemetry["iter_times"].append(now - last_t[0])
        last_t[0] = now
        vr = lrn.variable_record
        telemetry["train_times"].append(vr.get("train_time").val)
        telemetry["data_times"].append(vr.get("data_time").val)
        telemetry["staleness_mean"].append(vr.get("staleness/mean").val)
        telemetry["staleness_max"].append(vr.get("staleness/max").val)
        telemetry["total_loss"].append(vr.get("total_loss").val)
        telemetry["grad_norm"].append(vr.get("grad_norm").val)
        per_actor = [
            max(a.model_iter_highwater.values() or [0]) for a in actors
        ]
        telemetry["actor_model_iter"].append(max(per_actor))
        # the LAGGIEST producer drives trajectory staleness; the freshest
        # one would under-credit the accounting bound (multi-actor runs)
        telemetry["actor_model_iter_min"].append(min(per_actor))
        telemetry["historical_count"].append(len(league.historical_players))
        mp0 = league.all_players["MP0"]
        telemetry["winrate_hp0"].append(
            round(mp0.payoff.win_rate_opponent("HP0", use_prior=False), 4)
        )
        ratings = league.elo.ratings()
        telemetry["elo_gap"].append(
            round(ratings.get("MP0", 0.0) - ratings.get("HP0", 0.0), 2)
        )
        telemetry["games"].append(int(mp0.total_game_count))
        telemetry["prefetch_occupancy"].append(round(dataloader.occupancy(), 3))
        # live backlog only: records past the producers' 120s serve window
        # are expired payloads (loss, not aging)
        telemetry["broker_depth"].append(co.depth(dataloader.token, max_age_s=120.0))

    learner.hooks.add(LambdaHook("soak_record", "after_iter", record, freq=1))
    if prefill > cache_size:
        print(f"[soak] prefill {prefill} clamped to cache {cache_size} "
              "(the pull cache caps what can be banked)", flush=True)
    prefill = min(max(prefill, 0), cache_size)
    prefill_s = 0.0
    if prefill:
        t_pf = time.perf_counter()
        while dataloader.buffered() < prefill:
            if time.perf_counter() - t_pf > prefill_timeout:
                break  # run with whatever banked; the report shows how much
            if actor_err:
                # dead actors can't refill: running on would drain the bank
                # then busy-wait forever — abort while there is nothing to lose
                raise RuntimeError(f"actor died during prefill: {actor_err}")
            time.sleep(1.0)
        prefill_s = time.perf_counter() - t_pf
        print(f"[soak] prefill: {dataloader.buffered()} trajectories "
              f"banked in {prefill_s:.0f}s", flush=True)
    t0 = time.perf_counter()
    learner.run(max_iterations=iters)
    wall = time.perf_counter() - t0
    stop.set()
    for t in threads:
        t.join(timeout=120)

    # ---- invariants -----------------------------------------------------
    # collected, not raised: a violated bound must never DISCARD an hour of
    # telemetry — the report carries the violations and main() exits nonzero
    violations = []

    def check(ok: bool, msg: str) -> None:
        if not ok:
            violations.append(msg)

    check(not actor_err, f"actor loop died: {actor_err}")
    check(learner.last_iter.val == iters,
          f"learner stopped at iter {learner.last_iter.val}, wanted {iters}")

    propagated = telemetry["actor_model_iter"]
    check(propagated[-1] > 0, "actor never received published weights")
    check(propagated[-1] >= iters - 24,
          f"actor weights stale at end: iter {propagated[-1]} vs learner {iters}")
    # (no monotonicity assertion on the high-water mark — it is
    # non-decreasing by construction; backwards application of a stale
    # publication is prevented at the source by _refresh_models' iter guard)

    smax = max(telemetry["staleness_max"])
    check(smax <= iters, f"staleness {smax} exceeds total iterations")
    smean_tail = statistics.fmean(telemetry["staleness_mean"][iters // 2:])
    occ_tail = statistics.fmean(telemetry["prefetch_occupancy"][iters // 2:])
    # staleness decomposes EXACTLY into (a) how far the producing actor's
    # weights lagged the learner and (b) how long the trajectory aged in
    # the queue — so the bound is an accounting check built from the
    # measured components (+32 slack), not a flat number: unexplained
    # staleness (e.g. a recycled-trajectory bug) still fails, while a
    # starved-core refresh lag or a deliberately saturated queue doesn't
    # false-alarm. Both components are themselves visible in the report.
    lag_tail = statistics.fmean(
        (i + 1) - p
        for i, p in enumerate(telemetry["actor_model_iter_min"])
        if i >= iters // 2
    )
    # queue aging spans BOTH buffered hops: the learner-side pull cache AND
    # the broker backlog (trajectories registered but not yet fetched, aging
    # in producer serve windows — curve-regime runs bank 40+ there while
    # the client cache reads empty)
    broker_tail = statistics.fmean(telemetry["broker_depth"][iters // 2:])
    queue_tail = (
        (occ_tail * cache_size + broker_tail) / max(batch_size, 1) * 8
    )
    staleness_bound = 32.0 + max(lag_tail, 0.0) + queue_tail
    check(smean_tail < staleness_bound,
          f"tail staleness mean {smean_tail:.1f} exceeds {staleness_bound:.0f} "
          f"(actor lag {lag_tail:.1f} + queue {queue_tail:.1f} + 32 slack)")
    # crediting measured lag must not let the publication path itself rot:
    # refresh lag from a starved core grows with run speed, so the cap
    # scales with iters, but a sustained mid-run propagation stall (lag ~
    # iters/2) still fails even though the endpoint check recovered
    lag_cap = max(48.0, 0.25 * iters)
    check(lag_tail < lag_cap,
          f"tail actor weight lag {lag_tail:.1f} exceeds {lag_cap:.0f} — "
          "publication path stalling mid-run")

    train_steps = league.all_players["MP0"].total_agent_step
    check(train_steps > 0, "league never saw train info")
    snapshots = telemetry["historical_count"][-1] - telemetry["historical_count"][0]
    check(snapshots >= 1,
          f"no league snapshot fired in {iters} iters "
          f"(train_steps={train_steps}, one_phase_step={one_phase_step})")

    # leak check on COMPUTE time only: wall iter time legitimately settles
    # at the actor's production rate once the compile-window trajectory
    # backlog drains (off-policy equilibrium), so data wait is reported, not
    # asserted
    times = telemetry["train_times"][5:]  # drop compile/warmup
    q = max(len(times) // 4, 1)
    head, tail = times[:q], times[-q:]
    ratio = statistics.median(tail) / max(statistics.median(head), 1e-9)
    check(ratio < 2.5, f"train time drifted {ratio:.2f}x over the soak")

    finite = [x for x in telemetry["total_loss"] if x == x and abs(x) != float("inf")]
    check(len(finite) == len(telemetry["total_loss"]), "non-finite loss seen")

    def curve(series, buckets=10):
        """Bucket means over the iteration axis: a compact trend curve."""
        if not series:
            return []
        step = max(len(series) // buckets, 1)
        return [
            round(statistics.fmean(series[i:i + step]), 4)
            for i in range(0, len(series), step)
        ]

    return {
        "features_on": bool(features),
        "invariant_violations": violations,
        "regime": {
            "actor_threads": actor_threads, "env_num": env_num,
            "batch_size": batch_size, "traj_len": traj_len,
            "win_rule": win_rule, "opponent_pipeline": opponent_pipeline,
            "learn": bool(learn), "episode_game_loops": episode_game_loops,
            "cache_size": cache_size, "prefill": prefill,
            "prefill_s": round(prefill_s, 1),
            "opponent_heavy": bool(opponent_heavy),
        },
        "skill": {
            # read winrate points against games_curve: buckets before the
            # first finished game show the meter's empty default, not play
            "winrate_vs_HP0_curve": curve(telemetry["winrate_hp0"]),
            "elo_gap_curve": curve(telemetry["elo_gap"]),
            "games_curve": curve(telemetry["games"]),
            "final_winrate_vs_HP0": telemetry["winrate_hp0"][-1] if telemetry["winrate_hp0"] else None,
            "final_elo_gap": telemetry["elo_gap"][-1] if telemetry["elo_gap"] else None,
            "games_played": telemetry["games"][-1] if telemetry["games"] else 0,
        },
        "iters": iters,
        "wall_s": round(wall, 1),
        "train_time_s": {
            "median": round(statistics.median(times), 3),
            "p90": round(sorted(times)[int(len(times) * 0.9)], 3),
            "head_median": round(statistics.median(head), 3),
            "tail_median": round(statistics.median(tail), 3),
            "drift_ratio": round(ratio, 3),
        },
        "wall_iter_s": {
            "median": round(statistics.median(telemetry["iter_times"][5:]), 3),
            # the reference bar: 0.67 learner steps/s (BASELINE.md, derived)
            "steps_per_sec": round(
                1.0 / max(statistics.median(telemetry["iter_times"][5:]), 1e-9), 3
            ),
            "data_share": round(
                sum(telemetry["data_times"]) /
                max(sum(telemetry["data_times"]) + sum(telemetry["train_times"]), 1e-9),
                3,
            ),
            "prefetch_occupancy_tail_mean": round(
                statistics.fmean(telemetry["prefetch_occupancy"][iters // 2:]), 3
            ) if telemetry["prefetch_occupancy"] else None,
        },
        "staleness": {
            "mean_tail": round(smean_tail, 2),
            "max": int(smax),
            "actor_lag_tail": round(lag_tail, 2),
            "queue_age_tail": round(queue_tail, 2),
            "broker_depth_tail": round(broker_tail, 2),
        },
        "weights": {
            "actor_final_iter": int(propagated[-1]),
        },
        "league": {
            "train_steps": int(train_steps),
            "snapshots": int(snapshots),
            "games": int(league.all_players["MP0"].total_game_count),
            "elo_games": int(league.elo.game_count),
        },
        "loss": {
            "first10_mean": round(statistics.fmean(telemetry["total_loss"][:10]), 4),
            "last10_mean": round(statistics.fmean(telemetry["total_loss"][-10:]), 4),
        },
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--out", default="artifacts/rl_soak.json")
    p.add_argument("--features", action="store_true",
                   help="soak with entity caps + save_grad + async saves on")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--traj-len", type=int, default=2)
    p.add_argument("--env-num", type=int, default=2)
    p.add_argument("--actor-threads", type=int, default=1)
    p.add_argument("--win-rule", default="random",
                   choices=("random", "first", "battle"))
    p.add_argument("--opponent-pipeline", default="default",
                   help="HP0 pipeline, e.g. scripted.random")
    p.add_argument("--learn", action="store_true",
                   help="skill regime: teacher-KL off, higher lr")
    p.add_argument("--episode-loops", type=int, default=300)
    p.add_argument("--cache", type=int, default=64,
                   help="pull-cache depth (trajectories); staleness dial")
    p.add_argument("--prefill", type=int, default=0,
                   help="bank N trajectories before the learner starts "
                        "(saturated-regime measurement)")
    p.add_argument("--vs-opponent-heavy", action="store_true",
                   help="matchmaking mix weighted toward HP0 so the "
                        "winrate curve fills from game 1")
    args = p.parse_args()
    if args.cache < 1:
        p.error("--cache must be >= 1 (a zero-depth pull cache deadlocks)")
    if args.prefill < 0:
        p.error("--prefill must be >= 0")
    if args.prefill > args.cache:
        p.error(f"--prefill {args.prefill} exceeds --cache {args.cache}; "
                "the pull cache caps what can be banked")
    report = run_soak(
        args.iters, batch_size=args.batch, traj_len=args.traj_len,
        env_num=args.env_num, features=args.features,
        actor_threads=args.actor_threads, win_rule=args.win_rule,
        opponent_pipeline=args.opponent_pipeline, learn=args.learn,
        episode_game_loops=args.episode_loops, cache_size=args.cache,
        prefill=args.prefill, opponent_heavy=args.vs_opponent_heavy,
    )
    report["invariants"] = [
        "actor weights propagate and end within 24 iters of the learner",
        "staleness max <= total iters; tail staleness mean < "
        "measured actor lag + queue aging + 32 (accounting bound)",
        "league train-info advances and >=1 one_phase_step snapshot fires",
        "median TRAIN time drifts < 2.5x from first to last quarter (wall iter time reported, not asserted)",
        "every loss value finite",
    ]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if report["invariant_violations"]:
        print("INVARIANT VIOLATIONS:", report["invariant_violations"],
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Anakin fused rollout loop (envs/jaxenv/anakin.py): batch contract parity
with the learner's collate layout, device purity of the fused program, the
window metrics, and a tier-1 SMALL_MODEL training smoke on a vmap'd
scenario batch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SMALL_MODEL  # shared tiny model config

from distar_tpu.envs.jaxenv import (
    AnakinDataLoader,
    AnakinRunner,
    EnvConfig,
    ScenarioConfig,
)
from distar_tpu.learner.data import fake_rl_batch
from distar_tpu.obs import get_registry

TINY_B, TINY_T = 2, 3
TINY_ENV = EnvConfig(units_per_squad=2)
TINY_SCN = ScenarioConfig(units_per_squad=2, min_units=1, max_units=2,
                          episode_len=8, spawn_margin=30.0, spawn_spread=6.0)


@pytest.fixture(scope="module")
def learner(tmp_path_factory):
    from distar_tpu.learner import RLLearner

    tmp = tmp_path_factory.mktemp("anakin_rl")
    cfg = {
        "common": {"experiment_name": "anakin_t", "save_path": str(tmp)},
        "learner": {
            "batch_size": TINY_B,
            "unroll_len": TINY_T,
            "save_freq": 100000,
            "log_freq": 1,
        },
        "model": SMALL_MODEL,
    }
    return RLLearner(cfg)


@pytest.fixture(scope="module")
def runner(learner):
    return AnakinRunner(learner.model, batch_size=TINY_B, unroll_len=TINY_T,
                        env_cfg=TINY_ENV, scenario_cfg=TINY_SCN, seed=0)


@pytest.fixture(scope="module")
def loader(learner, runner):
    return AnakinDataLoader(
        runner, params_provider=lambda: learner._state["params"])


@pytest.fixture(scope="module")
def batch(loader):
    return next(loader)


def _shapes(tree):
    return jax.tree.map(lambda x: tuple(np.shape(x)), tree)


def test_batch_layout_matches_collate_contract(batch):
    """Leaf-by-leaf structural parity with fake_rl_batch — the same layout
    collate_trajectories hands the learner, so RLLearner trains on fused
    batches with zero adapter code."""
    lstm = SMALL_MODEL["encoder"]["core_lstm"]
    fake = fake_rl_batch(TINY_B, TINY_T, hidden_size=lstm["hidden_size"],
                         hidden_layers=lstm["num_layers"])
    fake_shapes = _shapes(fake)
    got_shapes = _shapes(batch)
    assert jax.tree.structure(got_shapes) == jax.tree.structure(fake_shapes)
    flat_got = jax.tree_util.tree_flatten_with_path(got_shapes)[0]
    flat_fake = jax.tree.leaves(fake_shapes)
    bad = [(jax.tree_util.keystr(p), g, f)
           for (p, g), f in zip(flat_got, flat_fake) if g != f]
    assert not bad, f"shape mismatches vs collate contract: {bad[:8]}"
    # every leaf already lives on device — the learner's shard_batch
    # (device_put of the arrays as they are) must not trigger a host round-trip
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(batch))
    # time-major windows: done/step are [T, B], obs leaves [T+1, B, ...]
    assert batch["done"].shape == (TINY_T, TINY_B)
    assert batch["entity_num"].shape == (TINY_T + 1, TINY_B)


def test_fused_rollout_is_device_pure(runner, loader):
    """Acceptance witness: the jitted scan contains no callback / infeed /
    outfeed / host primitives anywhere in its jaxpr (recursively), and a
    transfer guard sees no host transfer during a whole fused window."""
    report = runner.purity_report(loader._params(), runner.init_carry())
    assert report["pure"] is True, report
    assert report["offending"] == []
    # steady state: carry built and first window compiled outside the guard
    # (compile-time constant uploads are one-off), then a whole fused window
    # must execute with the guard up — no per-step host traffic
    params = loader._params()
    carry, _ = runner.rollout(params, runner.init_carry())
    with jax.transfer_guard("disallow"):
        carry, out = runner.rollout(params, carry)
    assert out["done"].shape == (TINY_T, TINY_B)


def test_window_metrics_and_progression(loader, batch):
    snap = get_registry().snapshot()
    assert snap["distar_rollout_plane_backend{backend=anakin}"] == 1.0
    assert snap["distar_anakin_batches_total"] >= 1.0
    assert snap["distar_anakin_env_steps_per_s"] > 0.0
    assert snap["distar_anakin_window_seconds_count"] >= 1.0
    # the next window continues the same lanes: env step counters advance
    batch2 = next(loader)
    assert float(batch2["step"].min()) > float(batch["step"].min()) or (
        float(batch2["done"].sum()) > 0.0)


def test_small_model_trains_on_fused_batches(learner, loader):
    """Satellite 3 tier-1 smoke: SMALL_MODEL runs a real optimizer step on a
    vmap'd-scenario Anakin batch (self-teacher => KL leg is exactly 0)."""
    learner.set_dataloader(iter(loader))
    learner.run(max_iterations=1)
    assert learner.last_iter.val >= 1
    total = learner.variable_record.get("total_loss").avg
    assert np.isfinite(total)


# ---------------------------------------------------------------- away seat


@pytest.fixture(scope="module")
def opp_runner(learner):
    return AnakinRunner(learner.model, batch_size=TINY_B, unroll_len=TINY_T,
                        env_cfg=TINY_ENV, scenario_cfg=TINY_SCN, seed=0,
                        opponent_seat=True)


@pytest.fixture(scope="module")
def opp_loader(learner, opp_runner):
    return AnakinDataLoader(
        opp_runner, params_provider=lambda: learner._state["params"])


def test_away_seat_batch_layout_matches_single_policy(batch, opp_loader):
    """A league exploiter trains against a frozen opponent with zero learner
    changes: the opponent-seat batch is structurally identical to the
    single-policy batch (the match_result leaf is stripped host-side)."""
    opp_batch = next(opp_loader)
    assert "match_result" not in opp_batch
    got = _shapes(opp_batch)
    ref = _shapes(batch)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    assert jax.tree.leaves(got) == jax.tree.leaves(ref)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(opp_batch))


def test_away_seat_match_results_drain(opp_loader):
    """Finished episodes surface exactly once through drain_results() with a
    home/away/draw verdict — the feed LeagueService.report consumes."""
    for _ in range(6):  # 6 windows x 3 steps > episode_len=8: episodes finish
        next(opp_loader)
    results = opp_loader.drain_results()
    assert results, "no episodes finished across 6 windows"
    assert {r["winner"] for r in results} <= {"home", "away", "draw"}
    assert all(r["steps"] >= 1 for r in results)
    # drained means drained — the buffer does not replay old outcomes
    assert opp_loader.drain_results() == []


def test_away_seat_rollout_is_device_pure(opp_runner, opp_loader):
    """The two-policy fused program stays callback/infeed/outfeed-free: the
    frozen opponent runs in-scan, not via host ping-pong."""
    report = opp_runner.purity_report(
        opp_loader._params(), opp_runner.init_carry(),
        opp_loader._opponent_params())
    assert report["pure"] is True, report
    assert report["offending"] == []


def test_away_seat_requires_opponent_params(opp_runner, runner, opp_loader):
    """The seat is explicit: an opponent-seat runner demands opponent params
    and a single-policy runner rejects them — no silent self-play fallback."""
    params = opp_loader._params()
    with pytest.raises(AssertionError):
        opp_runner.rollout(params, opp_runner.init_carry())
    with pytest.raises(AssertionError):
        runner.rollout(params, runner.init_carry(),
                       opponent_params=opp_loader._opponent_params())


def test_away_seat_trains_exploiter(learner, opp_loader):
    """End-to-end: the learner takes a real optimizer step on an away-seat
    batch — the exploiter training loop a league learner runs."""
    learner.set_dataloader(iter(opp_loader))
    learner.run(max_iterations=1)
    total = learner.variable_record.get("total_loss").avg
    assert np.isfinite(total)


def test_failed_window_drops_poisoned_carry():
    """The fused call donates the carry; if a window raises, the loader must
    drop its carry reference so a supervised retry re-initialises instead of
    re-passing deleted buffers (the league learner's restart path)."""
    from types import SimpleNamespace

    calls = {"init": 0}

    def init_carry(key=None):
        calls["init"] += 1
        return ("carry", calls["init"])

    def rollout(params, carry, opponent_params=None):
        raise RuntimeError("window failed mid-donation")

    stub = SimpleNamespace(opponent_seat=False, init_carry=init_carry,
                           rollout=rollout, B=1, T=1, _seed=0)
    dl = AnakinDataLoader(stub, params_provider=lambda: {"w": 1})
    with pytest.raises(RuntimeError):
        next(dl)
    assert dl._carry is None
    assert calls["init"] == 1
    with pytest.raises(RuntimeError):
        next(dl)
    assert calls["init"] == 2

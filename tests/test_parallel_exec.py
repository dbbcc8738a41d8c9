"""Sharded training executor: feeder assembly, distributed checkpoints with
resharding restore, typed mesh config errors, and the tier-1 multichip smoke
(executed GSPMD train step on the forced 8-device CPU mesh — see conftest)."""
import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from distar_tpu.parallel import (
    MeshConfigError,
    MeshSpec,
    ShardFeeder,
    assemble_global,
    batch_sharding,
    make_mesh,
    param_sharding,
)
from distar_tpu.parallel import ckpt as shck
from distar_tpu.utils.checkpoint import (
    CheckpointManager,
    CheckpointCorruptError,
    CheckpointMismatchError,
    load_checkpoint,
    verify_checkpoint,
)

from conftest import SMALL_MODEL  # shared tiny model config


# ------------------------------------------------------------- mesh satellite

def test_mesh_spec_parse():
    spec = MeshSpec.parse("dp=4,fsdp=2")
    assert (spec.dp, spec.fsdp, spec.tp, spec.sp) == (4, 2, 1, 1)
    assert MeshSpec.parse("dp=4, fsdp=2, tp=1, sp=1").sizes(8) == (4, 2, 1, 1)
    assert MeshSpec.parse("").sizes(8) == (8, 1, 1, 1)  # dp absorbs


def test_mesh_spec_parse_typed_errors():
    with pytest.raises(MeshConfigError, match="unknown mesh axis"):
        MeshSpec.parse("dq=4")
    with pytest.raises(MeshConfigError, match="integer size"):
        MeshSpec.parse("dp=four")


def test_mesh_sizes_typed_error_when_devices_dont_factor():
    with pytest.raises(MeshConfigError, match="does not factor"):
        MeshSpec.parse("dp=3").sizes(8)
    with pytest.raises(MeshConfigError, match="must be positive"):
        MeshSpec(dp=0).sizes(8)


def test_batch_sharding_rejects_indivisible_batch():
    mesh = make_mesh(MeshSpec(dp=4, fsdp=2))
    with pytest.raises(MeshConfigError, match="not divisible"):
        batch_sharding(mesh, batch_size=6)
    # divisible passes and still shards over (dp, fsdp)
    sh = batch_sharding(mesh, batch_size=16)
    assert "dp" in str(sh.spec)


def test_assemble_global_rejects_indivisible_dim():
    mesh = make_mesh(MeshSpec(dp=8))
    sh = batch_sharding(mesh)
    with pytest.raises(MeshConfigError, match="cannot shard"):
        assemble_global(np.zeros((6, 3), np.float32), sh)


# ------------------------------------------------------------------- feeder

def test_feeder_shard_assembly_round_trip():
    """Host batches -> global device arrays on a dp=4,fsdp=2 mesh of the 8
    forced host devices; every yielded leaf is sharded (8 distinct shards
    over the batch axis) and round-trips bit-identically to the host."""
    mesh = make_mesh(MeshSpec(dp=4, fsdp=2))
    sh = batch_sharding(mesh)
    rng = np.random.default_rng(0)
    batches = [
        {"x": rng.standard_normal((8, 5)).astype(np.float32),
         "y": np.full((8,), i, np.float32)}
        for i in range(4)
    ]

    def place(b):
        return {k: assemble_global(v, sh) for k, v in b.items()}

    feeder = ShardFeeder(iter(list(batches)), place, depth=2, token="test")
    out = list(feeder)
    assert len(out) == 4
    for i, b in enumerate(out):
        assert len(b["x"].addressable_shards) == 8
        # each device holds a distinct 1-row batch shard
        assert b["x"].addressable_shards[0].data.shape == (1, 5)
        np.testing.assert_array_equal(np.asarray(b["x"]), batches[i]["x"])
        np.testing.assert_array_equal(np.asarray(b["y"]), batches[i]["y"])
    stats = feeder.stats()
    assert stats["batches"] == 4 and stats["place_s_mean"] >= 0.0


def test_feeder_propagates_producer_error():
    def boom():
        yield {"x": np.zeros(8)}
        raise RuntimeError("collate died")

    mesh = make_mesh(MeshSpec(dp=8))
    sh = batch_sharding(mesh)
    feeder = ShardFeeder(boom(), lambda b: {k: assemble_global(v, sh) for k, v in b.items()})
    next(feeder)
    with pytest.raises(RuntimeError, match="collate died"):
        next(feeder)


def test_feeder_close_joins_producer():
    import itertools

    feeder = ShardFeeder(itertools.count(), lambda b: b, depth=2)
    assert next(feeder) == 0
    thread = feeder._thread
    feeder.close()
    assert not thread.is_alive(), "close() must reap the producer thread"


# --------------------------------------------------- sharded ckpt + reshard

def _param_tree(mesh, seed=0):
    rng = np.random.default_rng(seed)
    host = {
        "params": {
            "dense": {"kernel": rng.standard_normal((16, 8)).astype(np.float32),
                      "bias": rng.standard_normal((8,)).astype(np.float32)},
            "scale": np.float32(rng.standard_normal()),
        },
        "opt": (rng.standard_normal((16, 8)).astype(np.float32),
                np.int32(7)),
    }
    sh = param_sharding(mesh, host)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), host, sh), host


def test_sharded_ckpt_save_mesh_a_restore_mesh_b_bit_identical(tmp_path):
    mesh_a = make_mesh(MeshSpec(dp=4, fsdp=2))
    tree, host = _param_tree(mesh_a)
    path = str(tmp_path / "it1.ckpt")
    shck.save_sharded(path, tree, metadata={"last_iter": 1})
    assert shck.is_sharded_checkpoint(path)
    assert verify_checkpoint(path)

    out = load_checkpoint(path)  # routes through utils.checkpoint
    assert out["metadata"]["last_iter"] == 1
    restored = out["state"]
    np.testing.assert_array_equal(
        restored["params"]["dense"]["kernel"], host["params"]["dense"]["kernel"]
    )
    # restore onto a DIFFERENT mesh (dp=8) — bit-identical after re-place
    mesh_b = make_mesh(MeshSpec(dp=8))
    placed = jax.device_put(
        restored["params"]["dense"]["kernel"],
        param_sharding(mesh_b, host["params"]["dense"]["kernel"]),
    )
    np.testing.assert_array_equal(np.asarray(placed), host["params"]["dense"]["kernel"])
    # ... and onto a single chip (serve/eval)
    single = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    placed1 = jax.device_put(
        restored["params"]["dense"]["kernel"],
        param_sharding(single, host["params"]["dense"]["kernel"]),
    )
    np.testing.assert_array_equal(np.asarray(placed1), host["params"]["dense"]["kernel"])
    # layout manifest recorded the save-side mesh for the reshard counter
    assert shck.saved_mesh_shape(path) == {"dp": 4, "fsdp": 2, "tp": 1, "sp": 1}


def test_sharded_ckpt_restores_into_target_structure(tmp_path):
    mesh = make_mesh(MeshSpec(dp=4, fsdp=2))
    tree, host = _param_tree(mesh)
    path = str(tmp_path / "it2.ckpt")
    shck.save_sharded(path, tree)
    target = jax.tree.map(np.zeros_like, host)
    out = load_checkpoint(path, target=target)
    # tuples stay tuples through the target overlay (optax state shapes)
    assert isinstance(out["state"]["opt"], tuple)
    np.testing.assert_array_equal(out["state"]["opt"][0], host["opt"][0])
    assert int(out["state"]["opt"][1]) == 7


def test_corrupt_one_shard_fails_typed_and_falls_back(tmp_path):
    """One flipped bit in ONE parameter shard fails the whole generation
    (CheckpointCorruptError) and the manager falls back to the previous
    generation — PR 4's durability contract extended to the sharded layout."""
    mesh = make_mesh(MeshSpec(dp=4, fsdp=2))
    mgr = CheckpointManager(str(tmp_path))
    tree1, host1 = _param_tree(mesh, seed=1)
    tree2, _ = _param_tree(mesh, seed=2)
    p1, p2 = str(tmp_path / "it1.ckpt"), str(tmp_path / "it2.ckpt")
    shck.save_sharded(p1, tree1, metadata={"last_iter": 1})
    mgr.record(p1, step=1)
    shck.save_sharded(p2, tree2, metadata={"last_iter": 2})
    mgr.record(p2, step=2)

    # newest generation: flip one bit in one shard blob
    shard = sorted(glob.glob(os.path.join(p2, "*.shard")))[0]
    blob = bytearray(open(shard, "rb").read())
    blob[-1] ^= 0x01
    open(shard, "wb").write(bytes(blob))

    assert not verify_checkpoint(p2)
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(p2)
    resolved = mgr.resolve_latest()
    assert resolved is not None and resolved["path"] == p1
    out = mgr.load_latest()
    assert out["path"] == p1
    np.testing.assert_array_equal(
        out["state"]["params"]["dense"]["kernel"],
        host1["params"]["dense"]["kernel"],
    )


def test_missing_shard_fails_typed(tmp_path):
    mesh = make_mesh(MeshSpec(dp=8))
    tree, _ = _param_tree(mesh)
    path = str(tmp_path / "it3.ckpt")
    shck.save_sharded(path, tree)
    os.unlink(sorted(glob.glob(os.path.join(path, "*.shard")))[0])
    assert not verify_checkpoint(path)
    with pytest.raises(CheckpointCorruptError, match="missing shard"):
        load_checkpoint(path)


# ------------------------------------------------ stale-resume poisoning fix

def test_experiments_root_env_scopes_default_dirs(monkeypatch, tmp_path):
    from distar_tpu.learner.base_learner import experiments_root

    monkeypatch.setenv("DISTAR_EXPERIMENTS_ROOT", str(tmp_path / "scoped"))
    assert experiments_root() == str(tmp_path / "scoped")
    monkeypatch.delenv("DISTAR_EXPERIMENTS_ROOT")
    assert experiments_root() == os.path.join(os.getcwd(), "experiments")


def test_resume_rejects_mismatched_checkpoint(tmp_path):
    """Auto-resume validation: a latest-pointer generation whose leaves
    don't fit this learner (stale experiment dir from a different model
    config) raises CheckpointMismatchError on direct restore, and
    resume_latest skips it — falling back to an OLDER generation that DOES
    fit instead of silently training on foreign weights."""
    from distar_tpu.learner import RLLearner
    from distar_tpu.utils.checkpoint import save_checkpoint

    learner = RLLearner({
        "common": {"experiment_name": "mismatch", "save_path": str(tmp_path)},
        "learner": {"batch_size": 2, "unroll_len": 2,
                    "save_freq": 10 ** 9, "log_freq": 10 ** 9},
        "model": SMALL_MODEL,
    })
    ckpt_dir = os.path.join(str(tmp_path), "checkpoints")
    # generation 1: a GOOD checkpoint of this very learner
    good = os.path.join(ckpt_dir, "iteration_1.ckpt")
    save_checkpoint(good, learner.state, metadata={"last_iter": 1})
    learner.checkpoint_manager.record(good, step=1)
    # generation 2 (newest): same tree paths, param leaves reshaped — the
    # stale foreign-run poison (a different model config under the same
    # experiment name)
    host = jax.tree.map(np.asarray, learner.state)
    poisoned_state = dict(host, params=jax.tree.map(
        lambda x: np.zeros(x.shape + (2,), x.dtype), host["params"]))
    bad = os.path.join(ckpt_dir, "iteration_2.ckpt")
    save_checkpoint(bad, poisoned_state, metadata={"last_iter": 2})
    learner.checkpoint_manager.record(bad, step=2)

    with pytest.raises(CheckpointMismatchError, match="does not fit"):
        learner.restore(bad)
    resumed = learner.resume_latest()
    assert resumed == good
    assert learner.last_iter.val == 1


# --------------------------------------------------- tier-1 multichip smoke

def test_multichip_smoke_executed_train_step(tmp_path):
    """The acceptance smoke: a 2-step --mesh dp=2 train on the forced host
    devices runs the EXECUTED (non-dryrun) GSPMD path — live-mesh jitted
    step, ShardFeeder double-buffered sharded feeding, sharded checkpoint
    on exit — and the prefetch overlap contract holds (the feeder is ahead
    of the steps)."""
    from distar_tpu.parallel.executor import run_sharded_training

    rep = run_sharded_training(
        "dp=2", iters=2, batch_size=2, unroll_len=2,
        model_cfg=SMALL_MODEL, experiment_name="mc_smoke",
        save_dir=str(tmp_path / "exp"), save_freq=1, sharded_ckpt=True,
        max_devices=2,
    )
    assert rep["iters"] == 2
    assert rep["mesh"]["dp"] == 2
    assert np.isfinite(rep["loss"])
    # batches actually flowed through the feeder and steps consumed them
    assert rep["feeder"]["batches"] >= rep["iters"]
    # prefetch overlap: when the last step ended a placed batch was waiting
    # in the double buffer, so the feeder placed behind the steps and not
    # between them. Counts, not a clock: with the suite's workers sharing
    # the cores the first fill alone (which no buffer can hide) took longer
    # than a warm step: `wait_s_mean` 0.84 s against `step_time_s` 0.22
    assert rep["feeder"]["occupancy"] > 0
    # the run-exit save produced a SHARDED checkpoint that verifies and
    # reloads bit-identically
    gens = CheckpointManager(os.path.join(str(tmp_path / "exp"), "checkpoints")).generations()
    assert gens, "no generation recorded"
    assert shck.is_sharded_checkpoint(gens[0]["path"])
    assert verify_checkpoint(gens[0]["path"])
    out = load_checkpoint(gens[0]["path"])
    assert out["metadata"]["last_iter"] == 2


def test_rl_train_cli_mesh_wiring():
    """--mesh reaches the learner constructor and flips sharded_ckpt on by
    default (no training here — parse/wiring only)."""
    import argparse

    from distar_tpu.bin.rl_train import _learner_cfg, _mesh_from_args

    args = argparse.Namespace(
        mesh="dp=4,fsdp=2", sharded_ckpt=None, experiment_name="t",
        save_path="", batch_size=8, traj_len=2, iters=4,
    )
    mesh = _mesh_from_args(args)
    assert dict(mesh.shape) == {"dp": 4, "fsdp": 2, "tp": 1, "sp": 1}
    cfg = _learner_cfg(args, {})
    assert cfg["learner"]["sharded_ckpt"] is True
    args.sharded_ckpt = False
    assert _learner_cfg(args, {})["learner"]["sharded_ckpt"] is False
    args.mesh = ""
    args.sharded_ckpt = None
    assert _mesh_from_args(args) is None
    assert _learner_cfg(args, {})["learner"]["sharded_ckpt"] is False


# ------------------------------------------------------------ slow coverage

@pytest.mark.slow
def test_chaos_multichip_drill(tmp_path):
    """The chaos acceptance: learner killed after a sharded save on
    dp=4,fsdp=2 resumes on dp=8 and finishes unassisted."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "chaos.py"),
         "multichip-drill", "--dir", str(tmp_path), "--iters", "4",
         "--kill-after", "2"],
        capture_output=True, text=True, timeout=1800, cwd=repo,
        env={**os.environ, "DISTAR_EXPERIMENTS_ROOT": str(tmp_path / "expr")},
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "finished unassisted" in out.stdout

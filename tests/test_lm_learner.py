"""The token-sequence learner (``learner/lm_learner.py``) on ``BaseLearner``:
the run loop, the feeder, checkpoints, the launcher's plugin registry."""
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distar_tpu.learner.lm_learner import LMLearner, fake_token_batch  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "experts_held": {"offset": 2, "count": 4},
        "vocab_size": 128}
# the second token model, picked by ``model_type``: state-space, attention and expert layers
TINY_NH = {"model_type": "nemotron_h", "hidden_size": 64, "hybrid_override_pattern": "MEM*E",
           "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 8,
           "num_experts_per_tok": 2, "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
           "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128}
MODELS = {"lfm2": TINY, "nemotron_h": TINY_NH}
both_models = pytest.mark.parametrize("model", list(MODELS))


def learner(tmp_path, name="lm", model="lfm2", **lc):
    return LMLearner({
        "common": {"experiment_name": name, "save_path": str(tmp_path / name)},
        "learner": {"batch_size": 2, "unroll_len": 32, "save_freq": 100000, "log_freq": 1, **lc},
        "model": MODELS[model],
    })


def repeated(batch):
    while True:
        yield dict(batch)


@both_models
def test_runs_through_the_run_loop_and_the_loss_falls_on_a_repeated_batch(tmp_path, model):
    lrn = learner(tmp_path, model=model, learning_rate=1e-3)  # the default is a warm-up's first steps
    lrn.set_dataloader(repeated(fake_token_batch(2, 32, 128)))
    losses = []
    from distar_tpu.learner.hooks import LambdaHook

    lrn.hooks.add(LambdaHook("losses", "after_iter", lambda l: losses.append(l.log_buffer["total_loss"]),
                             priority=5))
    lrn.run(max_iterations=12)
    lrn._dataloader.close()
    assert lrn.last_iter.val == 12 and len(losses) == 12
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.1
    # the batch went through the feeder as two device arrays, and the step reported its experts
    leaves = [fam for fam in lrn.metrics.collect() if fam["name"] == "distar_feeder_batch_leaves"]
    assert leaves and any(inst.count for _, inst in leaves[0]["series"])
    log = lrn.variable_record.vars()
    assert {"moe_rows_here", "moe_load_max_over_mean", "moe_overflow_rows", "token_acc",
            "residual_rms/layer_4", "moe_rows/layer_1/expert_3", "dyn/grad_norm/layer_2"} <= set(log)
    assert "moe_rows/layer_0/expert_0" not in log  # layer 0 is the dense one, or a state-space one
    if model == "lfm2":
        assert "ff_rms/layer_0" in log and "mixer_rms/layer_0" not in log
    else:  # a layer is one mixer; the state-space layers (0 and 2 of MEM*E) report their last state
        assert {"mixer_rms/layer_3", "ssm_state_rms/layer_0", "ssm_state_rms/layer_2"} <= set(log)
        assert "ssm_state_rms/layer_1" not in log and "ff_rms/layer_0" not in log
        assert "moe_rows/layer_4/expert_0" in log and "moe_rows/layer_3/expert_0" not in log
    # the expert bias is a buffer: twelve AdamW steps with weight decay left it as drawn
    fresh = learner(tmp_path, "fresh", model=model)
    for a, b in zip(jax.tree.leaves(lrn.state["params"]["buffers"]),
                    jax.tree.leaves(fresh.state["params"]["buffers"])):
        np.testing.assert_array_equal(a, b)
    assert "buffers" not in str(jax.tree_util.tree_structure(lrn.state["opt_state"]))


@both_models
def test_saves_and_restores(tmp_path, model):
    # leaf by leaf for the model whose published-width file asks for it (its state is 8 GB on the host)
    lc = {"sharded_ckpt": True} if model == "nemotron_h" else {}
    a = learner(tmp_path, "a", model=model, **lc)
    a.set_dataloader(repeated(fake_token_batch(2, 32, 128)))
    a.run(max_iterations=3)
    a._dataloader.close()
    path = a.checkpoint_path()
    a.save(path, sync=True)
    assert os.path.isdir(path) == bool(lc)
    b = learner(tmp_path, "b", model=model, **lc)
    b.restore(path)
    assert b.last_iter.val == 3
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and trains on from there: the restored state is placed as the step was compiled for
    b.set_dataloader(repeated(fake_token_batch(2, 32, 128)))
    b.run(max_iterations=5)
    b._dataloader.close()
    assert b.last_iter.val == 5 and np.isfinite(b.log_buffer.get("total_loss", 0.0))


@both_models
def test_step_lowered_from_its_arguments_types_is_the_calls_program(tmp_path, model):
    """As ``tests/test_learner.py`` asks of the SL and RL steps: the
    benchmark's traced run lowers the step from the types of its first
    call's arguments and must get that call's program, not a second one."""
    lrn = learner(tmp_path)
    jitted, texts = lrn._train_step, []

    def tap(*args):
        if not texts:  # before the call: it donates the state
            specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None))
                if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)
            texts.extend(jitted.lower(*a).as_text() for a in (args, specs))
        return jitted(*args)

    lrn._train_step = tap
    lrn.run(max_iterations=1)
    lrn._dataloader.close()
    assert len(texts) == 2 and texts[0] == texts[1]
    assert "lm_train_step" in texts[0]


def test_evaluate_is_the_first_steps_forward_pass_without_an_update(tmp_path):
    lrn = learner(tmp_path)
    batch = fake_token_batch(2, 32, 128)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(lrn.state["params"])]
    held_out = lrn.evaluate(iter([dict(batch)]), max_batches=1)
    for x, y in zip(before, jax.tree.leaves(lrn.state["params"])):
        np.testing.assert_array_equal(x, np.asarray(y))
    first = lrn._train(dict(batch))
    for k in ("total_loss", "moe_rows_here", "residual_rms/layer_2", "moe_rows/layer_3/expert_1"):
        assert held_out[k] == pytest.approx(first[k], rel=1e-6), k
    assert "grad_norm" not in held_out and "grad_norm" in first


@both_models
def test_overflow_is_counted_as_zero_and_a_row_not_computed_stops_the_run(tmp_path, model):
    """The expert buffer is the provable bound, so the counter stays at 0;
    the learner does not train on if the step ever reports otherwise."""
    lrn = learner(tmp_path, model=model)
    counted = lambda: [inst.value for fam in lrn.metrics.collect()
                       if fam["name"] == "distar_moe_overflow_rows_total" for _, inst in fam["series"]]
    for _ in range(2):
        assert lrn._train(fake_token_batch(2, 32, 128))["moe_overflow_rows"] == 0.0
    before = counted()  # the registry is the process's: the other model's case counted into it
    step = lrn._train_step
    lrn._train_step = lambda *a: (lambda v, o, info: (v, o, dict(info, overflow=info["overflow"] + 3)))(*step(*a))
    lrn._perf_note_step_args = lambda *a: None
    with pytest.raises(RuntimeError, match="3 rows routed to the experts held here were not computed"):
        lrn._train(fake_token_batch(2, 32, 128))
    assert counted() == [before[0] + 3]


def test_buffer_rows_walked_are_observed_once_a_step(tmp_path):
    """``distar_moe_buffer_rows``: one observation a step, the sum over the
    expert layers of (chunks that held rows) x ``N``: a layer walks the first
    chunk of ``N`` = 64 rows always and the second when more than 64 rows are
    routed to its experts (top-2 of 8 with 4 held: 64 expected)."""
    lrn = learner(tmp_path)
    hist = lambda: [inst for fam in lrn.metrics.collect() if fam["name"] == "distar_moe_buffer_rows"
                    for _, inst in fam["series"]][0]
    N, walked = 2 * 32, []
    count, total = hist().count, hist().sum  # the registry is the process's: other tests' steps are in it
    for step in range(3):
        log = lrn._train(fake_token_batch(2, 32, 128, np.random.default_rng(step)))
        per_layer = [sum(v for k, v in log.items() if k.startswith(f"moe_rows/layer_{i}/")) for i in range(1, 5)]
        assert sum(per_layer) == log["moe_rows_here"]
        walked.append(sum(N * max(1, -(-int(rows) // N)) for rows in per_layer))
        assert log["moe_buffer_rows"] == walked[-1] and 4 * N <= walked[-1] <= 8 * N
        assert hist().count == count + step + 1 and hist().sum == total + sum(walked)
    assert any(w > 4 * N for w in walked)  # some layer of some step took its second chunk


def test_the_layers_that_moved_rows_by_buffer_row_are_logged_and_observed(tmp_path):
    """``moe_row_indexed_layers`` / ``distar_moe_row_indexed_layers``: every
    expert layer of a step (four here), whatever length its buffer was walked to."""
    lrn = learner(tmp_path)
    hist = lambda: [inst for fam in lrn.metrics.collect() if fam["name"] == "distar_moe_row_indexed_layers"
                    for _, inst in fam["series"]][0]
    count, total = hist().count, hist().sum
    log = lrn._train(fake_token_batch(2, 32, 128, np.random.default_rng(0)))
    assert log["moe_row_indexed_layers"] == 4 == len(lrn._moe_layers)
    assert (hist().count, hist().sum) == (count + 1, total + 4)


def test_sl_train_reaches_it_through_the_plugin_registry(tmp_path, monkeypatch, capsys):
    from distar_tpu import plugins
    from distar_tpu.bin import sl_train

    assert plugins.load_component("distar_tpu.learner.lm_learner", "SLLearner") is LMLearner
    config = tmp_path / "tiny.yaml"
    import yaml

    config.write_text(yaml.safe_dump({"learner": {"batch_size": 2, "unroll_len": 32, "learning_rate": 1e-3},
                                      "model": TINY}))
    monkeypatch.setattr(sys, "argv", [
        "sl_train", "--pipeline", "distar_tpu.learner.lm_learner", "--config", str(config),
        "--iters", "3", "--no-supervise", "--no-health", "--platform", "cpu",
        "--save-path", str(tmp_path / "run")])
    built = []
    whole = LMLearner.__init__
    monkeypatch.setattr(LMLearner, "__init__", lambda self, *a, **k: (whole(self, *a, **k), built.append(self))[0])
    sl_train.main()
    out = capsys.readouterr().out
    assert "sl_train done: 3 iters" in out and "token_acc=" in out
    # the config file's learner section reaches the learner; the command line's sizes come after it
    assert built[0].cfg.learner.learning_rate == 1e-3 and built[0].cfg.learner.unroll_len == 32
    assert built[0].model_cfg.hidden_size == 64

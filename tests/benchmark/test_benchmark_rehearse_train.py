"""``--rehearse`` of the train cells end to end at the tiny preset, on the CPU
(four virtual devices for the dp=4 cell), each beside its plain reference,
and ``correct`` turning false when the optimizer update is dropped or the
step's loss parts from the reference."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(capsys, cell, trace=0, seconds=2.5):
    assert run.main(["--workload", cell, "--seed", "3", "--seconds", str(seconds),
                     "--trace", str(trace), "--rehearse"]) == 0
    lines = [ln for ln in capsys.readouterr().out.strip().split("\n") if ln.startswith("{")]
    return json.loads(lines[-1]), json.loads(lines[-2])


@pytest.mark.parametrize("cell,devices", [("sl_b6t64", 1), ("sl_dp4_b24t64", 4),
                                          ("rl_learn_b6t64", 1)])
def test_train_cells_rehearse(capsys, cell, devices):
    line, notes = rehearse(capsys, cell)
    assert KEYS <= set(line) and line["correct"] is True, notes["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    # a rehearsal carries counts only: no rate, no time
    assert line["metrics"] == {} and line["rehearsed"] == ["setup_s", "train_frames_per_s"]
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= devices
    assert notes["checks"]["no_compile_in_window"] and notes["checks"]["params_changed"]
    # the reference ran in a process of its own, on one device in float32, and agrees
    assert notes["checks"]["first_step_matches_reference"]
    reference = json.load(open(os.path.join(run.OUT, cell + "_rehearsal", "reference.json")))
    assert reference["seed"] == 3 and reference["model"]["dtype"] == "float32"
    if devices == 4:
        assert notes["checks"]["replicas_agree"] and notes["checks"]["batch_share"]
    steps = json.load(open(os.path.join(run.OUT, cell + "_rehearsal", "steps.json")))
    assert len(steps["scalars"]) >= line["attempted"] and steps["seed"] == 3


def test_traced_rehearsal_names_the_cells_per_layer_metrics(capsys):
    line, _ = rehearse(capsys, "sl_b6t64", trace=1)
    assert line["correct"] is True and line["metrics"] == {}
    # what the registry and the hooks give exists on the CPU too; what the
    # device trace gives does not, and its readers return nothing
    assert {"data_wait_ms", "device_step_ms", "host_callback_ms", "feed_place_ms",
            "compile_backend_s", "cache_misses", "retraces_in_window",
            "program_hbm_gb"} <= set(line["rehearsed"])
    assert not {"step_busy_ms", "device_idle_pct", "mfu_pct"} & set(line["rehearsed"])


def test_correct_turns_false_when_the_update_is_dropped(capsys, monkeypatch):
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    line, notes = rehearse(capsys, "sl_b6t64")
    assert line["correct"] is False
    assert not notes["checks"]["loss_went_down"] and not notes["checks"]["params_changed"]
    assert notes["checks"]["losses_finite"] and line["failed"] == 0


def test_correct_turns_false_when_the_loss_parts_from_the_reference(capsys, monkeypatch):
    from distar_tpu.learner import sl_learner

    whole = sl_learner.compute_sl_loss

    def delay_head_off_by_a_tenth(*args, **kwargs):
        total, info = whole(*args, **kwargs)
        return total, {k: v * 0.9 if k == "delay_loss" else v for k, v in info.items()}

    # in this process only: the reference is a process of its own and computes the whole loss
    monkeypatch.setattr(sl_learner, "compute_sl_loss", delay_head_off_by_a_tenth)
    line, notes = rehearse(capsys, "sl_b6t64")
    assert line["correct"] is False and not notes["checks"]["first_step_matches_reference"]
    assert notes["checks"]["loss_went_down"] and notes["checks"]["params_changed"]


def test_seed_is_restored_on_the_learner_class():
    from distar_tpu.learner import RLLearner, SLLearner

    assert SLLearner.init_prng_seed == 0 and RLLearner.init_prng_seed == 0


def test_without_a_chip_a_measured_run_prints_no_result(capsys):
    assert run.main(["--workload", "sl_b6t64", "--seed", "0", "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out.strip() == ""

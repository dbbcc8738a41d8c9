"""The per-layer metrics of ``setup_s`` (layer ``Start-up``): data files read
by ``readers/counter_at_open`` from the program's set-up phases, process ages
and compile seconds, in the five cells that were there before the sixth."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, run  # noqa: E402
from benchmark.registry_tap import RegistryTap  # noqa: E402

FIVE = ["sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64", "lfm2_train_b4s8k",
        "nemotron_twotower_train_b2s8k"]
SIXTH = "kimi_vl_train_b2s8k"
# name -> the family it reads and the labels it sums over
NEW = {
    "setup_before_learner_s": ("distar_setup_process_age_seconds", {"at": "learner_init"}),
    "setup_learner_ready_s": ("distar_setup_process_age_seconds", {"at": "learner_ready"}),
    "setup_run_start_s": ("distar_setup_process_age_seconds", {"at": "run_start"}),
    "setup_to_first_step_s": ("distar_setup_process_age_seconds", {"at": "first_step_done"}),
    "setup_fake_batch_s": ("distar_setup_seconds_total", {"phase": "fake_batch"}),
    "setup_model_init_s": ("distar_setup_seconds_total", {"phase": "model_init"}),
    "setup_first_step_s": ("distar_setup_seconds_total", {"phase": "first_step"}),
    "setup_init_trace_s": ("distar_compile_seconds_total", {"stage": "trace", "during": "model_init"}),
    "setup_init_shapes_trace_s": ("distar_compile_seconds_total", {"stage": "trace", "during": "init_shapes"}),
    "setup_step_trace_s": ("distar_compile_seconds_total", {"stage": "trace", "during": "first_step"}),
    "setup_step_lower_s": ("distar_compile_seconds_total", {"stage": "lower", "during": "first_step"}),
    "setup_step_backend_s": ("distar_compile_seconds_total", {"stage": "backend", "during": "first_step"}),
    "setup_cache_load_s": ("distar_compile_seconds_total", {"stage": "cache_load"}),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_a_data_file_of_the_reader_that_is_there(name):
    m = cells.load("layer_metrics", name)
    metric, labels = NEW[name]
    assert m["reader"] == "counter_at_open" and m["params"] == {"metric": metric, "labels": labels}
    assert (m["moves"], m["layer"], m["unit"], m["better"], m["source"]) == (
        "setup_s", "Start-up", "s", "lower", "program_counter")
    assert m["workloads"] == FIVE and SIXTH not in m["workloads"]
    # the phase, stage and point it names are the program's own
    from distar_tpu.obs import SETUP_PHASES

    assert labels.get("phase", "first_step") in SETUP_PHASES
    assert labels.get("during", "first_step") in SETUP_PHASES


def test_the_files_are_every_start_up_metric_and_the_cells_report_them():
    start_up = {n for n in cells.names("layer_metrics")
                if cells.load("layer_metrics", n)["layer"] == "Start-up"}
    assert start_up == set(NEW)
    for cell in FIVE:
        assert set(NEW) <= {m["name"] for m in cells.layer_metrics(cells.load_cell(cell))}
    assert not set(NEW) & {m["name"] for m in cells.layer_metrics(cells.load_cell(SIXTH))}


def test_the_reader_sums_the_label_sets_that_match_and_gives_zero_for_a_program_without_them():
    from distar_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    for stage, during, seconds in (("cache_load", "model_init", 1.5), ("cache_load", "first_step", 2.0),
                                   ("trace", "first_step", 30.0), ("backend", "run", 9.0)):
        reg.counter("distar_compile_seconds_total", stage=stage, during=during).inc(seconds)
    reg.gauge("distar_setup_process_age_seconds", at="learner_init").set(12.25)
    tap = RegistryTap(reg)
    tap.mark("open")
    got = run.per_layer({"name": "a_cell", "per_layer": sorted(NEW)}, {"tap": tap})
    assert got["setup_cache_load_s"] == {"value": 3.5, "unit": "s"}
    assert got["setup_step_trace_s"]["value"] == 30.0 and got["setup_step_backend_s"]["value"] == 0.0
    assert got["setup_before_learner_s"]["value"] == 12.25
    # the parent commit writes none of these: every reader gives 0 and none raises
    bare = RegistryTap(MetricsRegistry())
    bare.mark("open")
    assert {m["value"] for m in run.per_layer({"name": "a_cell", "per_layer": sorted(NEW)},
                                              {"tap": bare}).values()} == {0.0}


def test_the_manifest_gained_the_entries_at_its_end_and_kept_the_rest():
    assert subprocess.run([sys.executable, "-m", "benchmark.tools.manifest", "--check"],
                          cwd=REPO).returncode == 0
    entries = run.manifest()["per_layer"]
    names = [e["name"] for e in entries]
    assert names[-len(NEW):] == sorted(NEW)
    old = names[:-len(NEW)]
    # what was there stands where it stood, from the first entry to PR 31's last
    assert len(old) == 63 and old[:2] == ["cache_misses", "compile_backend_s"]
    assert old[-1] == "mla_core_roofline_pct" and not [n for n in old if n.startswith("setup_")]
    for e in entries[-len(NEW):]:
        assert e == {"name": e["name"], "unit": "s", "better": "lower", "source": "program_counter",
                     "layer": "Start-up", "moves": "setup_s", "workloads": FIVE}
    # the two that setup_s had keep their place and their layer
    had = {e["name"]: e for e in entries}
    assert had["compile_backend_s"]["layer"] == had["cache_misses"]["layer"] == "Compile and cache"


def test_a_traced_rehearsal_lists_the_new_names(capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    assert run.main(["--workload", "sl_b6t64", "--seed", "3400000007", "--seconds", "2.5",
                     "--trace", "1", "--rehearse"]) == 0
    lines = [ln for ln in capsys.readouterr().out.strip().split("\n") if ln.startswith("{")]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert set(NEW) <= set(line["rehearsed"])

"""The ``nemotron_h`` cell: its rehearsal end to end beside its plain
reference, its traffic, its FLOP count, its two roofline readers, its scopes,
its configuration against the published one, and the gradient check at the
tiny preset. ``correct`` turning false for each omitted term is in
``test_benchmark_nemotron_h_faults.py``."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, flops, flops_nemotron_h, kernels_ssm, run  # noqa: E402
from benchmark.gen import lm_pool  # noqa: E402
from benchmark.readers import kernel_roofline_ssm, trace_scope_lm  # noqa: E402
from benchmark.trace_meta import Op  # noqa: E402

CELL = "nemotron_twotower_train_b2s8k"
CONFIG = "nemotron_twotower_30b_a3b_ep16_l9"
TRAFFIC = "lm_zipf_pool4_b2s8192_v16384"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("lm_ssm_proj_ms", "lm_ssm_scan_ms", "lm_moe_shared_ms", "ssm_scan_roofline_pct",
               "nh_moe_experts_roofline_pct")


def rehearse(capsys, trace=0, seed="3000000029"):
    assert run.main(["--workload", CELL, "--seed", seed, "--seconds", "2.5",
                     "--trace", str(trace), "--rehearse"]) == 0
    lines = [ln for ln in capsys.readouterr().out.strip().split("\n") if ln.startswith("{")]
    return json.loads(lines[-1]), json.loads(lines[-2])


# ------------------------------------------------------------ the rehearsal
def test_the_cell_rehearses_end_to_end_beside_its_plain_reference(capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    line, notes = rehearse(capsys, trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 12, notes["checks"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert all(notes["checks"].values()), notes["checks"]
    assert {"moe_rows_here", "moe_load_max_over_mean", "data_wait_ms", "device_step_ms", "feed_put_ms",
            "loop_dispatch_ms", "retraces_in_window", "program_hbm_gb"} <= set(line["rehearsed"])
    # what the trace gives does not exist on the CPU, the roofline shares among it
    assert not [n for n in line["rehearsed"] if n.startswith(("lm_", "idle_", "scope_")) or "roofline" in n]
    reference = json.load(open(tmp_path / (CELL + "_rehearsal") / "reference.json"))
    first = reference["first_step"]
    assert reference["seed"] == 3000000029 and first["moe_overflow_rows"] == 0.0
    # the tiny preset is ME*: one layer of each kind: 4 held experts, 3 layers x 2 RMS, a state, the gradient norms
    assert len([k for k in first if k.startswith("moe_rows/")]) == 4
    assert len([k for k in first if k.startswith(("residual_rms/", "mixer_rms/"))]) == 6
    assert sorted(k for k in first if k.startswith("ssm_state_rms/")) == ["ssm_state_rms/layer_0"]
    assert {"dyn/grad_norm/embedding", "dyn/grad_norm/lm_head", "dyn/grad_norm/layer_0", "dyn/grad_norm/layer_2",
            "dyn/grad_norm/final_norm"} <= set(first)
    steps = json.load(open(tmp_path / (CELL + "_rehearsal") / "steps.json"))
    log = steps["scalars"][0]
    assert log["moe_overflow_rows"] == 0.0
    assert log["moe_rows/layer_1/expert_1"] == first["moe_rows/layer_1/expert_1"]
    assert log["ssm_state_rms/layer_0"] == pytest.approx(first["ssm_state_rms/layer_0"], rel=1e-4)
    assert log["mixer_rms/layer_2"] == pytest.approx(first["mixer_rms/layer_2"], rel=1e-4)


def test_gradient_check_passes_the_program_and_fails_the_control_in_float8(capsys, tmp_path, monkeypatch):
    """``tools/gradients_on_chip`` (the tool the LFM2 cell brought, unedited)
    at this cell's tiny preset: loss, statistics and every gradient leaf of
    the program agree with the reference on one sequence, the literal
    recurrence's backward pass among them, and the float8 control does not."""
    from benchmark.tools import gradients_on_chip

    monkeypatch.setattr(gradients_on_chip, "ROOT", str(tmp_path))
    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.chdir(REPO)
    child = gradients_on_chip.subprocess.run
    monkeypatch.setattr(gradients_on_chip.subprocess, "run", lambda cmd, cwd, **kw: child(cmd, cwd=REPO, **kw))
    assert gradients_on_chip.main(["--workload", CELL, "--seed", "3000000031", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    assert out["ok"] and out["positions"] == 32 and len(out["program"]["gradient_off_by_leaf"]) == 9 + 6 + 5 + 3
    assert out["program"]["correct"] and out["program"]["gradient_off_largest"] < 1e-4  # float32 both, at this size
    assert {"layer_0/mamba/A_log", "layer_0/mamba/dt_bias", "layer_0/mamba/D", "layer_0/mamba/conv_bias",
            "layer_1/moe/shared_w1", "lm_head"} <= set(out["program"]["gradient_off_by_leaf"])
    control = out["control"]
    assert not control["correct"] and control["first_step_off"] and control["gradient_leaves_off"]
    assert control["gradient_off_largest"] > 2 * out["gradients_rtol"]


# ------------------------------------------------------------- the traffic
def test_traffic_is_the_lfm2_cells_at_half_the_batch_and_twice_the_slice():
    mine, theirs = (cells.load("traffic", n)["params"] for n in (TRAFFIC, "lm_zipf_pool4_b4s8192"))
    assert {k: v for k, v in mine.items() if mine[k] != theirs[k]} == {"batch_size": 2, "vocab_size": 16384}
    cfg = cells.load("configs", CONFIG)
    assert mine["vocab_size"] == cfg["vocab_size"] and mine["unroll_len"] == cfg["as_run"]["learner"]["unroll_len"]
    assert mine["batch_size"] == cfg["as_run"]["learner"]["batch_size"]
    pool = lm_pool.build(2 ** 31 + 17, dict(mine, pool=2))
    again = lm_pool.build(2 ** 31 + 17, dict(mine, pool=2))
    assert len(pool) == 2 and pool[0]["tokens"].shape == (2, 8192) and pool[0]["tokens"].dtype == np.int32
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(pool, again) for k in a)
    ids = np.concatenate([b["tokens"].reshape(-1) for b in pool])
    assert ids.min() >= 0 and 8192 < ids.max() < 16384      # the ids reach into the second half of the slice
    assert np.array_equal(pool[0]["tokens"][:, 1:], pool[0]["labels"][:, :-1])


# --------------------------------------------------------------- the count
def test_flops_count_is_the_walkers_on_the_dense_parts():
    """``flops.py`` walks the traced forward pass; on a model without expert
    layers it sees every product this module counts, the chunked scan's among
    them. Attention through the program's XLA path multiplies each query
    against all S keys, which is this module's count at twice the sequence."""
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import NemotronH, default_nemotron_h_config
    from distar_tpu.utils import deep_merge_dicts

    B, S = 2, 64
    m = deep_merge_dicts(default_nemotron_h_config(), dict(
        cells.load("configs", CONFIG)["tiny"]["model"], hybrid_override_pattern="M*M", remat=False, chunk_size=16))
    model = NemotronH(m)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    walked = flops.forward_flops(lambda v, t: model.apply(v, t)[0], variables, tokens) / (B * S)
    parts = flops_nemotron_h.forward_parts(m, 2 * S)
    assert parts["moe_experts"] == parts["moe_shared"] == parts["moe_router"] == 0
    assert walked == pytest.approx(sum(parts.values()), rel=1e-9)
    assert parts["ssm_scan"] == 2 * flops_nemotron_h.scan_per_position(m) > 0
    assert flops_nemotron_h.forward_parts(m, S)["attention"] < parts["attention"]


def test_recorded_count_is_what_the_module_gives_for_the_program_file():
    cfg = cells.load("configs", CONFIG)
    model = cells.program_config(cfg)["model"]
    got = flops_nemotron_h.required_per_frame(model, cfg["as_run"]["learner"]["unroll_len"])
    assert got["step"] == cfg["required_flops_per_frame"] == pytest.approx(2.1528e9, rel=1e-4)
    parts = flops_nemotron_h.forward_parts(model, 8192)
    # the state-space layers are 45% of the forward FLOPs and attention 16%; the routed experts 4%
    share = lambda *names: sum(parts[n] for n in names) / got["forward"]
    assert share("ssm_proj", "ssm_scan") == pytest.approx(0.45, abs=0.005)
    assert share("attention") == pytest.approx(0.16, abs=0.005) and share("moe_experts") == pytest.approx(0.04, abs=0.005)
    assert flops_nemotron_h.scan_per_position(model) == 2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 4 * 64 * 128 * 64
    assert parts["moe_experts"] == 4 * 0.375 * 4 * 2688 * 1856
    assert parts["moe_shared"] == 4 * 4 * 2688 * 3712 and parts["lm_head"] == 2 * 2688 * 16384


# -------------------------------------------------- the configuration file
def test_configuration_holds_every_published_number_and_lists_what_it_cut():
    cfg = cells.load("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
    assert cfg["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert set(cfg["reduced_why"]) == set(cfg["reduced"]) and len(cfg["source"]) <= 200
    run_model = cfg["as_run"]["model"]
    # no width among the cuts: what the program runs is what was published
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
                "chunk_size", "use_conv_bias", "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "norm_eps", "time_step_min", "time_step_max", "time_step_floor"):
        assert run_model[key] == cfg[key], key
    assert run_model["n_routed_experts"] == cfg["num_experts_routed_over"] == 128  # the router's width
    assert run_model["experts_held"] == {"offset": 0, "count": cfg["n_routed_experts"]}
    held = "".join(cfg["hybrid_override_pattern"][i] for i in cfg["layers_held"])
    assert held == run_model["hybrid_override_pattern"] == "MEMEM*EME" and len(held) == cfg["num_hidden_layers"]
    whole = cfg["hybrid_override_pattern"]
    assert len(whole) == 52 and [whole.count(c) for c in "ME*"] == [23, 23, 6]
    assert [held.count(c) for c in "ME*"] == [4, 4, 1]
    assert cfg["model_type"] == run_model["model_type"] == "nemotron_h"
    assert {"positions", "towers", "objective", "expert_bias", "loss", "weights", "parameters"} <= set(cfg["assumed"])
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert cfg["parameters"]["state_bytes"] == 16 * cfg["parameters"]["total"] >= 0.25 * 16e9
    by_part = cfg["parameters"]["by_part"]
    assert cfg["parameters"]["total"] == 4 * by_part["M layer"] + 4 * by_part["E layer"] + by_part["* layer"] + \
        by_part["embedding"] + by_part["lm_head"] + by_part["final_norm"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if '"Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"' in ln)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert sorted(differs) == sorted(cfg["reduced"])


def test_the_programs_parameter_count_is_the_files():
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import NemotronH, default_nemotron_h_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", CONFIG)
    model = NemotronH(deep_merge_dicts(default_nemotron_h_config(), cells.program_config(cfg)["model"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 512), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    p, by_part = shapes["params"], cfg["parameters"]["by_part"]
    assert count(p) == cfg["parameters"]["total"] == 666962944
    assert {"M layer": count(p["layer_0"]), "E layer": count(p["layer_1"]), "* layer": count(p["layer_5"]),
            "embedding": count(p["embedding"]), "lm_head": count(p["lm_head"]),
            "final_norm": count(p["final_norm"])} == by_part
    assert p["layer_1"]["moe"]["router"].shape == (2688, 128) and p["layer_1"]["moe"]["w1"].shape == (8, 2688, 1856)
    assert p["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (2688, 10304)


def test_the_cell_lists_the_generic_metrics_and_the_new_five_and_the_manifest_has_them():
    cell, lfm2 = cells.load("workloads", CELL), cells.load("workloads", "lfm2_train_b4s8k")
    assert cell["per_layer"][:23] == lfm2["per_layer"][:23] and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert tuple(cell["per_layer"][-5:]) == NEW_METRICS
    assert not {"lm_short_conv_ms", "lm_dense_mlp_ms", "moe_experts_roofline_pct"} & set(cell["per_layer"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert m["workloads"][-1] == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1, "why": cell["why"]}
    assert m["configs"][-1]["name"] == CONFIG and m["configs"][-1]["reduced"] == cells.load("configs", CONFIG)["reduced"]
    mine = [e for e in m["per_layer"] if e["workloads"] == [CELL]]
    assert sorted(e["name"] for e in mine) == sorted(NEW_METRICS) and m["per_layer"][-5:] == mine
    assert all(e["moves"] == "train_frames_per_s" and e["layer"] == "Jitted step" for e in mine)
    reported = {e["name"] for e in m["per_layer"] if CELL in e["workloads"]}
    assert reported == set(cell["per_layer"])


# ------------------------------------------------ scopes and roofline shares
def op(start, end, scope):
    return Op("x", float(start), float(end), scope, "")


def test_the_new_scopes_partition_a_step_with_the_old_ones():
    head = "jit(lm_train_step)/jvp(NemotronH)/"
    back = "jit(lm_train_step)/transpose(jvp(NemotronH))/"
    ops = [op(0, 10, head + "layer_0/checkpoint/ssm_proj/operator_norm/mul"),
           op(10, 40, head + "layer_0/checkpoint/mamba/ssm_proj/in_proj/dot_general"),
           op(40, 100, head + "layer_0/checkpoint/mamba/ssm_scan/while"),                      # container
           op(50, 90, head + "layer_0/checkpoint/mamba/ssm_scan/while/body/checkpoint/dot_general"),
           op(100, 120, head + "layer_1/checkpoint/moe/moe_shared/dot_general"),
           op(120, 150, back + "layer_1/rematted_computation/moe/moe_experts/gmm"),
           op(150, 190, back + "layer_0/mamba/ssm_scan/while/body/rematted_computation/dot_general"),
           op(190, 200, back + "layer_0/mamba/ssm_scan/while/body/dot_general"),
           op(200, 230, head + "layer_5/checkpoint/attention/attention/flash"),
           op(230, 240, "")]
    got = trace_scope_lm.self_times(ops, 0.0, 300.0)
    assert got == {("ssm_proj", "forward"): 40.0, ("ssm_scan", "forward"): 60.0, ("moe_shared", "forward"): 20.0,
                   ("moe_experts", "recompute"): 30.0, ("ssm_scan", "recompute"): 40.0,
                   ("ssm_scan", "backward"): 10.0, ("attention", "forward"): 30.0, ("unnamed", "forward"): 10.0}
    # the cell's scope metrics cover every scope once: over one step they sum to all of it
    step = {**got, ("loss", "forward"): 5.0, ("optimizer", "forward"): 7.0, ("embed", "forward"): 1.0,
            ("moe_router", "forward"): 2.0, ("diagnostics", "forward"): 3.0}
    files = [cells.load("layer_metrics", n) for n in cells.load("workloads", CELL)["per_layer"]]
    scope_files = [m for m in files if m["reader"] == "trace_scope_lm" and "passes" not in m["params"]]
    assert len(scope_files) == 9
    import unittest.mock as mock

    with mock.patch.object(trace_scope_lm, "steps_of", lambda result: [step]):
        total = sum(trace_scope_lm.read(None, scale=1.0, **m["params"]) for m in scope_files)
        assert total == pytest.approx(sum(step.values()))
        scan = cells.load("layer_metrics", "ssm_scan_roofline_pct")["params"]
        # the roofline share's time: the scan without its recomputes
        assert trace_scope_lm.read(None, scale=1.0, scopes=scan["scopes"], passes=scan["passes"]) == 70.0
    covered = [s for m in scope_files for s in m["params"]["scopes"]]
    from distar_tpu import obs

    assert sorted(covered) == sorted(set(obs.LM_STEP_SCOPES) - {"short_conv", "dense_mlp"} | {"unnamed"})


def test_scan_roofline_is_required_time_over_scope_time(monkeypatch):
    shape = cells.load("layer_metrics", "ssm_scan_roofline_pct")["params"]["shape"]
    need = kernels_ssm.chunked_scan(**shape)
    per_position = 2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 4 * 64 * 128 * 64      # 3.41 MFLOP forward
    assert need["flops"] == 16384 * 4 * 3 * per_position
    # forward: x and y (4096 each) and B, C (1024 each) in bf16, dt (64) in float32; backward: dy, x, B, C in, dx, dB, dC out
    forward, backward = 2 * (2 * 4096 + 2 * 1024) + 4 * 64, 2 * (3 * 4096 + 4 * 1024) + 2 * 4 * 64
    assert need["bytes"] == 16384 * 4 * (forward + backward) and forward + backward == 54016
    # the bytes bound it: 4.3 ms against 3.4 ms of products
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    assert need["bytes"] / 819e9 == pytest.approx(4.32e-3, rel=0.01)
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 108.0)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline_ssm.read(result, "chunked_scan", ["ssm_scan"], shape, passes=["forward", "backward"])
    assert share == pytest.approx(100.0 * (need["bytes"] / 819e9) / 0.108) and 3.9 < share < 4.1
    # nothing to read without a trace, off the chip, or from a program without the scope
    assert kernel_roofline_ssm.read({"device": {"platform": "cpu", "kind": "cpu"}}, "chunked_scan", ["ssm_scan"],
                                    shape) is None
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: None)
    assert kernel_roofline_ssm.read(result, "chunked_scan", ["ssm_scan"], shape) is None


def test_expert_roofline_is_required_time_over_scope_time(monkeypatch):
    params = cells.load("layer_metrics", "nh_moe_experts_roofline_pct")["params"]
    shape = params["shape"]
    rows = 4 * 6144.0                                                              # the expected rows of a step
    need = kernels_ssm.grouped_relu2(rows, **shape)
    assert need["flops"] == 12.0 * rows * 2688 * 1856
    weights = 2 * 8 * 2688 * 1856 * 4
    assert need["bytes"] == 2 * (5 * rows * 2688 + 3 * weights)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9                          # compute-bound at 768 rows an expert
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 25.0)
    from benchmark.readers import histogram_window

    asked = []
    monkeypatch.setattr(histogram_window, "read", lambda result, **kw: asked.append(kw) or rows)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline_ssm.read(result, **params)
    assert asked == [{"metric": "distar_moe_rows_here", "reduce": "median"}]
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.025) and 25 < share < 35
    # no rows observed (a program whose window never closed): nothing to read
    monkeypatch.setattr(histogram_window, "read", lambda result, **kw: None)
    assert kernel_roofline_ssm.read(result, **params) is None

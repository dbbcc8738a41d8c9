"""The ``phi4flash`` cell (Phi-4-mini-flash-reasoning's language model): ``correct`` refusing the stored readings of
the float8 control and of each omission at the published widths and passing the reference itself, its traffic, its
FLOP and byte counts against hand counts and the plain products, its configuration against the published one, its
parameter count, its scopes and roofline readers, the reference's first step at the tiny preset as the run's
reference process asks for it, and the manifest. Nothing here is pinned to a place in a list, to a count of cells
or to the whole of ``LM_STEP_SCOPES``: a later PR's files and scopes only append."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, check, flops_laguna, flops_phi4flash, kernels_phi4flash  # noqa: E402
from benchmark.gen import lm_pool  # noqa: E402
from benchmark.readers import kernel_roofline_phi4flash, trace_scope_lm  # noqa: E402
from benchmark.trace_meta import Op  # noqa: E402

CELL = "phi4_flash_train_b2s8k"
CONFIG = "phi4_mini_flash_v8_l6"
TRAFFIC = "lm_zipf_pool4_b2s8192_v25008"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SCOPE_METRICS = ("lm_mamba1_proj_ms", "lm_mamba1_scan_ms", "lm_gmu_ms", "lm_cross_core_ms")
ROOFLINES = ("mamba1_scan_roofline_pct", "phi4_attn_core_roofline_pct", "phi4_swa_core_roofline_pct")
CONTROLS = os.path.join(REPO, "tests", "benchmark", "data", "phi4flash_controls.json")
# the terms the cell's limits are held to, and a component that sees each
MUST_FAIL = {"window": "mixer_rms/layer_1", "lambda": "mixer_rms/layer_", "pair_norm": "mixer_rms/layer_",
             "lambda_scale": "mixer_rms/layer_", "layer_index": "diff_lambda/layer_", "gated_memory": "memory_rms",
             "skip": "memory_rms", "dt_bias": "ssm_state_rms/layer_", "carry": "ssm_state_rms/layer_"}


def limits():
    return {k: v for k, v in cells.load("workloads", CELL)["correct"]["reference"].items() if k in ("keys", "rtol", "rtol_of")}


# ------------------------------------------------- what ``correct`` refuses
@pytest.mark.parametrize("omission", MUST_FAIL)
def test_the_stored_reading_of_each_omission_fails_the_cells_limits(omission):
    """What the reference without the term reports at the published widths on the cell's own traffic (a CPU run of
    ``phi4flash_plain.first_step(without=)``, kept in ``tests/benchmark/data``) is not ``correct`` by the limits in
    the cell's file."""
    stored = json.load(open(CONTROLS))
    off = check.off_reference(stored["without"][omission], stored["reference"], **limits())
    assert off and any(MUST_FAIL[omission] in line for line in off), (omission, off)


def test_the_stored_float8_control_fails_and_the_reference_itself_passes():
    stored = json.load(open(CONTROLS))
    assert check.off_reference(stored["reference"], stored["reference"], **limits()) == []
    off = check.off_reference(stored["float8_e4m3fn"], stored["reference"], **limits())
    # the precision below the configuration's fails by some of the limits, and not by each
    assert any("mixer_rms/" in line for line in off) and any("residual_rms/" in line for line in off)
    compared = [k for k in stored["reference"] if any(part in k for part in limits()["keys"])]
    assert 3 <= len(off) < len(compared)
    assert set(stored["without"]) >= set(MUST_FAIL) and stored["seed"] > 2 ** 31 and stored["cell"] == CELL


def test_every_compared_component_has_a_limit_of_its_own():
    ref = cells.load("workloads", CELL)["correct"]["reference"]
    stored = json.load(open(CONTROLS))
    compared = [k for k in stored["reference"] if any(part in k for part in ref["keys"]) and stored["reference"][k] != 0]
    assert {k for k in compared if k not in ref["rtol_of"]} == set()
    assert {"total_loss", "memory_rms", "ssm_state_rms/layer_0", "ssm_state_rms/layer_2", "diff_lambda/layer_1",
            "diff_lambda/layer_3", "diff_lambda/layer_5", "mixer_rms/layer_4", "ff_rms/layer_5"} <= set(compared)
    assert ref["rtol_of"]["total_loss"] <= 1e-4 and all(ref["rtol_of"][k] <= 1e-2 for k in compared if "rms" in k)
    assert "moe_overflow_rows" in ref["keys"] and stored["reference"]["moe_overflow_rows"] == 0.0   # exactly 0


def test_the_reference_process_first_step_at_the_tiny_preset_names_what_the_run_logs():
    """``references/__main__`` hands ``first_step`` a learner and the traffic's first batch; at the tiny preset the
    names are the learner's log's, the gradient norms among them, and the run's own first step agrees (the
    rehearsal, end to end, is the builder's: ``python -m benchmark.run --workload <cell> --rehearse``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.references import phi4flash_plain as plain
    from distar_tpu.learner.lm_learner import _flat_log, forward_loss
    from distar_tpu.model import Phi4Flash, default_phi4flash_config
    from distar_tpu.utils import deep_merge_dicts

    tiny = cells.load("configs", CONFIG)["tiny"]["model"]
    cfg = deep_merge_dicts(default_phi4flash_config(), tiny)
    model = Phi4Flash(cfg)
    batch = lm_pool.build(2 ** 31 + 42, dict(cells.load("traffic", TRAFFIC)["params"], **cells.load("traffic", TRAFFIC)["tiny"]),
                          model_cfg=cfg)[0]
    variables = jax.jit(model.init)(jax.random.PRNGKey(7), jnp.asarray(batch["tokens"]))

    class Learner:
        model_cfg, state = cfg, {"params": variables}

    first = plain.first_step(Learner(), batch)
    log = _flat_log(jax.device_get(jax.jit(lambda v: forward_loss(model, v, v["params"], {k: jnp.asarray(x) for k, x in batch.items()})[1])(
        variables)), [])
    want = {"total_loss", "memory_rms", "moe_overflow_rows"} | {f"{k}/layer_{i}" for k in ("residual_rms", "mixer_rms", "ff_rms") for i in range(6)} \
        | {"ssm_state_rms/layer_0", "ssm_state_rms/layer_2", "diff_lambda/layer_1", "diff_lambda/layer_3", "diff_lambda/layer_5"}
    assert want <= set(first) and want <= set(log)
    assert {f"dyn/grad_norm/{m}" for m in ["embedding", "final_norm"] + [f"layer_{i}" for i in range(6)]} <= set(first)
    assert check.off_reference(log, {k: first[k] for k in want}, keys=limits()["keys"], rtol=1e-4) == []


# ------------------------------------------------------------- the traffic
def test_traffic_is_the_sibling_cells_over_this_slice():
    mine, theirs = (cells.load("traffic", n)["params"] for n in (TRAFFIC, "lm_zipf_pool4_b2s8192_v16384"))
    assert {k: v for k, v in mine.items() if mine[k] != theirs[k]} == {"vocab_size": 25008}
    assert cells.load("traffic", TRAFFIC)["generator"] == "lm_pool"
    cfg = cells.load("configs", CONFIG)
    assert mine["vocab_size"] == cfg["vocab_size"] == 200064 // 8 and 200064 % 8 == 0
    assert mine["unroll_len"] == cfg["as_run"]["learner"]["unroll_len"] and mine["batch_size"] == cfg["as_run"]["learner"]["batch_size"]
    pool = lm_pool.build(2 ** 31 + 23, dict(mine, pool=2))
    again = lm_pool.build(2 ** 31 + 23, dict(mine, pool=2))
    assert len(pool) == 2 and pool[0]["tokens"].shape == (2, 8192) and pool[0]["tokens"].dtype == np.int32
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(pool, again) for k in a)
    ids = np.concatenate([b["tokens"].reshape(-1) for b in pool])
    assert ids.min() >= 0 and 20000 < ids.max() < 25008
    assert np.array_equal(pool[0]["tokens"][:, 1:], pool[0]["labels"][:, :-1])


# --------------------------------------------------------------- the counts
def test_recorded_count_is_what_the_module_gives_for_the_program_file_and_a_hand_count():
    cfg = cells.load("configs", CONFIG)
    model = cells.program_config(cfg)["model"]
    got = flops_phi4flash.required_per_frame(model, cfg["as_run"]["learner"]["unroll_len"])
    assert got["step"] == cfg["required_flops_per_frame"] == pytest.approx(4.581e9, rel=1e-4)
    parts = flops_phi4flash.forward_parts(model, 8192)
    assert parts["dense_mlp"] == 6 * 6 * 2560 * 10240 == 943_718_400
    assert parts["mamba1_proj"] == 2 * (2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * 2560) == 164_495_360
    assert parts["gmu"] == 2 * 2 * 2560 * 5120 == 52_428_800
    # layers 15 and 17 project q, k, v and o, layer 19 q and o alone
    assert parts["attn_proj"] == 2 * (2 * 2 * 2560 * 2560 + 2 * 2 * 2560 * 1280) + 2 * 2 * 2560 * 2560 == 104_857_600
    # 20 pairs x 2 maps, scores over 64 and a value 128 wide, S/2 keys a causal query
    assert parts["attn_core"] == parts["cross_core"] == 40 * (2 * 64 + 2 * 128) * 4096 == 62_914_560
    assert parts["swa_core"] == pytest.approx(40 * 384 * flops_laguna.band_keys(8192, 512)) and flops_laguna.band_keys(8192, 512) == pytest.approx(496.03, abs=0.01)
    assert parts["lm_head"] == 2 * 2560 * 25008 and "mamba1_scan" not in parts          # the scan is no matrix product
    share = lambda *names: sum(parts[n] for n in names) / got["forward"]
    assert share("dense_mlp") == pytest.approx(0.618, abs=0.005) and share("lm_head") == pytest.approx(0.084, abs=0.005)
    assert share("mamba1_proj", "gmu", "attn_proj", "attn_core", "swa_core", "cross_core") == pytest.approx(0.298, abs=0.005)
    assert [flops_phi4flash.layer_kind(model, i) for i in model["layers_held"]] == ["mamba", "sliding", "mamba", "full", "gmu", "cross"]


def test_projection_and_core_counts_are_the_plain_products():
    import jax
    import jax.numpy as jnp

    m = dict(cells.load("configs", CONFIG)["as_run"]["model"], **cells.load("configs", CONFIG)["tiny"]["model"])
    d, rows, e, N, R, H = 64, 32, 128, 16, 4, 8
    cost = lambda fn, *shapes: jax.jit(fn).lower(*(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)).compile().cost_analysis()["flops"]
    parts = flops_phi4flash.forward_parts(m, 32)
    mamba = cost(lambda u, a, x, b, r, c, y, o: (u @ a, x @ b, r @ c, y @ o), (rows, d), (d, 2 * e), (rows, e), (e, R + 2 * N), (rows, R),
                 (R, e), (rows, e), (e, d))
    assert parts["mamba1_proj"] * rows == pytest.approx(2 * mamba, rel=1e-6)
    assert parts["gmu"] * rows == pytest.approx(cost(lambda u, a, y, o: (u @ a, y @ o), (rows, d), (d, e), (rows, e), (e, d)), rel=1e-6)
    D, S = d // H, 32
    one_map = lambda q, k, v: jnp.einsum("hqk,hkd->hqd", jnp.einsum("hqd,hkd->hqk", q, k), v)
    all_keys = cost(one_map, (H, S, D), (H, S, D), (H, S, 2 * D))
    assert parts["attn_core"] * S == pytest.approx(all_keys / 2, rel=1e-6) and parts["cross_core"] == parts["attn_core"]
    assert parts["swa_core"] == H * 6 * D * flops_laguna.band_keys(S, 16)


def test_kernel_counts_are_hand_counts_at_the_published_sizes():
    scan = kernels_phi4flash.selective_scan(16384, 5120, 16, 2)
    # x, dt, y | dy, x, dt | dx, ddt of 5,120 a position, and B, C three times over of 16: 82,112 bytes in bf16
    assert scan["flops"] == 0.0 and scan["bytes"] == 16384 * 2 * 2 * (8 * 5120 + 6 * 16) == 16384 * 2 * 82112
    assert scan["bytes"] / 819e9 == pytest.approx(3.285e-3, rel=1e-3)
    full = kernels_phi4flash.diff_core(16384, 8192, 40, 20, 64, 2)
    assert full["flops"] == 16384 * 2 * 3 * 40 * 384 * 4096 == pytest.approx(6.185e12, rel=1e-3)
    # q 2,560, k and v 1,280 each, the maps' outputs 5,120: forward 10,240, backward 15,360 in and 5,120 out
    assert full["bytes"] == 16384 * 2 * 2 * (10240 + 15360 + 5120) and full["flops"] / 197e12 > 10 * full["bytes"] / 819e9
    band = kernels_phi4flash.diff_banded_core(16384, 8192, 512, 40, 20, 64, 1)
    assert band["flops"] == pytest.approx(16384 * 3 * 40 * 384 * (512 - 512 * 511 / 16384)) and band["bytes"] == full["bytes"] / 2
    assert band["flops"] / 197e12 > band["bytes"] / 819e9
    # a kernel that computes every key block of the causal triangle does not raise the required work
    assert kernels_phi4flash.diff_banded_core(16384, 8192, 8192, 40, 20, 64, 1)["flops"] == pytest.approx(full["flops"] / 2 * 4096.5 / 4096)


# -------------------------------------------------- the configuration file
def test_configuration_holds_every_published_number_and_lists_what_it_cut():
    cfg = cells.load("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"] and set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 6, "vocab_size": 25008}
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 200064} and cfg["layers_held"] == [14, 15, 16, 17, 18, 19]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    run_model = cfg["as_run"]["model"]
    # no width among the cuts: what the program runs is what was published
    for key in ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "sliding_window", "mb_per_layer",
                "layer_norm_eps", "tie_word_embeddings", "vocab_size"):
        assert run_model[key] == cfg[key], key
    # the program keeps the published depth (a layer's kind and lambda_init read its published index) and names its layers beside it
    assert run_model["num_hidden_layers"] == cfg["published"]["num_hidden_layers"] and run_model["layers_held"] == cfg["layers_held"]
    assert len(run_model["layers_held"]) == cfg["num_hidden_layers"] >= 4 and cfg["vocab_size"] * 8 >= 200064     # the floors
    assert (run_model["mamba_d_state"], run_model["mamba_d_conv"], run_model["mamba_expand"], run_model["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert {"differential_attention", "head_pairing", "window", "memory", "cross_attention", "biases", "norms", "positions", "mamba",
            "loss", "weights", "parameters"} <= set(cfg["assumed"])
    assert "eight chips hold the vocabulary in eighths" in cfg["deployment"] and "no layer is divided" in cfg["deployment"]
    why = cfg["reduced_why"]["num_hidden_layers"]
    assert "9 Mamba : 8 window : 1 full : 7 memory units : 7 cross" in why and "device_idle_pct" in why       # what the cut distorts is said
    assert cfg["parameters"]["state_bytes"] == 16 * cfg["parameters"]["total"] and 11.1e9 < cfg["parameters"]["state_bytes"] < 11.2e9
    assert cfg["reference"]["first_step"] == "phi4flash_plain" and "tiny_why" in cfg
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if '"Phi-4-mini-flash-reasoning"' in ln)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if k not in cfg or cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"])


def test_the_programs_parameter_count_is_the_files():
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import Phi4Flash, default_phi4flash_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", CONFIG)
    model = Phi4Flash(deep_merge_dicts(default_phi4flash_config(), cells.program_config(cfg)["model"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 512), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    p, by_part = shapes["params"], cfg["parameters"]["by_part"]
    assert count(p) == cfg["parameters"]["total"] == 697_094_272 and list(shapes) == ["params"]
    part = lambda start: next(v for k, v in by_part.items() if k.startswith(start))
    assert part("Mamba-1 mixer") == count(p["layer_0"]["mamba"]) == count(p["layer_2"]["mamba"])
    assert part("differential attention") == count(p["layer_1"]["attn"]) == count(p["layer_3"]["attn"])
    assert part("cross-attention") == count(p["layer_5"]["attn"]) and part("gated memory unit") == count(p["layer_4"]["gmu"])
    assert part("feed-forward") == count(p["layer_3"]["dense_mlp"]) and part("embedding") == count(p["embedding"])
    assert part("operator norm and ffn norm") == count(p["layer_4"]["operator_norm"]) + count(p["layer_4"]["ffn_norm"])
    assert cfg["parameters"]["total"] == 2 * part("Mamba-1 mixer") + 2 * part("differential attention") + part("cross-attention") \
        + part("gated memory unit") + 6 * (part("feed-forward") + part("operator norm and ffn norm")) + part("embedding") + part("final_norm")
    assert p["layer_0"]["mamba"]["A_log"].shape == (5120, 16) and p["layer_0"]["mamba"]["x_proj"]["kernel"].shape == (5120, 192)
    assert p["layer_1"]["attn"]["k_proj"]["kernel"].shape == (2560, 1280) and p["layer_1"]["attn"]["pair_norm"]["scale"].shape == (128,)
    assert "k_proj" not in p["layer_5"]["attn"] and "lm_head" not in p and p["embedding"].shape == (25008, 2560)


# ------------------------------------------------------------- the manifest
def test_the_manifest_gained_the_cell_and_its_metrics_and_lost_nothing():
    cell, laguna = cells.load("workloads", CELL), cells.load("workloads", "laguna_train_b1s16k")
    assert cell["per_layer"][:36] == laguna["per_layer"][:36] and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(SCOPE_METRICS + ROOFLINES) <= set(cell["per_layer"])
    assert {"mfu_pct", "program_hbm_gb", "device_idle_pct", "lm_attn_proj_ms", "lm_attn_core_ms", "lm_swa_core_ms", "lm_dense_mlp_ms",
            "lm_embed_head_ms", "lm_optimizer_ms", "lm_unnamed_ms", "lm_forward_ms", "lm_backward_ms", "lm_recompute_ms"} <= set(cell["per_layer"])
    setup = {n[:-5] for n in os.listdir(os.path.join(REPO, "benchmark", "layer_metrics")) if n.startswith("setup_")}
    assert len(setup) >= 13 and setup <= set(cell["per_layer"])                     # a new cell lists them itself
    # no experts, and the siblings' rooflines are at their shapes, not this cell's
    assert not [n for n in cell["per_layer"] if "moe" in n] and not {"swa_core_roofline_pct", "attn_core_roofline_pct",
                                                                    "ssm_scan_roofline_pct", "lm_ssm_scan_ms"} & set(cell["per_layer"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    by_name = lambda group: {e["name"]: e for e in m[group]}
    assert by_name("workloads")[CELL] == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1, "why": cell["why"]}
    mine = by_name("configs")[CONFIG]
    assert mine["reduced"] == ["num_hidden_layers", "vocab_size"] and mine["file"] == f"benchmark/configs/{CONFIG}.json"
    assert mine["source"] == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    for name in SCOPE_METRICS + ROOFLINES:
        e = by_name("per_layer")[name]
        assert e["workloads"] == [CELL] and e["moves"] == "train_frames_per_s" and e["layer"] == "Jitted step", name
        assert e["unit"] == ("%" if name.endswith("_pct") else "ms") and e["source"] == "device_trace"
    assert {e["name"] for e in m["per_layer"] if CELL in e["workloads"]} == set(cell["per_layer"])
    # every data file has its entry and what was there is there: supersets, so that a later cell breaks nothing here
    assert set(by_name("workloads")) >= set(cells.names("workloads")) >= {
        "sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64", "lfm2_train_b4s8k", "nemotron_twotower_train_b2s8k", "kimi_vl_train_b2s8k",
        "qwen3_next_train_b2s8k", "laguna_train_b1s16k", CELL}
    assert {"ssm_scan_roofline_pct", "swa_core_roofline_pct", "attn_core_roofline_pct", "mfu_pct", "setup_first_step_s"} <= set(by_name("per_layer"))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(len(m["workloads"]) // 4, 1)
    from benchmark.tools import manifest

    assert manifest.build(m) == m


# ------------------------------------------------ scopes and roofline shares
def op(start, end, scope):
    return Op("x", float(start), float(end), scope, "")


def test_the_new_scopes_partition_a_step_with_the_old_ones():
    head = "jit(lm_train_step)/jvp(Phi4Flash)/"
    back = "jit(lm_train_step)/transpose(jvp(Phi4Flash))/"
    ops = [op(0, 10, head + "layer_0/checkpoint/mamba1_proj/operator_norm/mul"),
           op(10, 40, head + "layer_0/checkpoint/mamba/mamba1_proj/in_proj/dot_general"),
           op(40, 100, head + "layer_0/checkpoint/mamba/mamba1_scan/cond/branch_1_fun/jit(_s6_forward)/mamba1_scan_fwd"),
           op(100, 120, head + "layer_3/checkpoint/attn/attn_core/cond/branch_1_fun/pallas_call"),
           op(120, 135, head + "layer_4/checkpoint/gmu/gmu/in_proj/dot_general"),
           op(135, 150, head + "layer_5/checkpoint/attn/cross_core/cond/branch_1_fun/pallas_call"),
           op(150, 190, back + "layer_0/rematted_computation/mamba/mamba1_scan/cond/branch_1_fun/mamba1_scan_fwd"),
           op(190, 290, back + "layer_0/mamba/mamba1_scan/cond/branch_1_fun/jit(_s6_backward)/mamba1_scan_bwd"),
           op(290, 300, back + "layer_5/attn/cross_core/cond/branch_1_fun/pallas_call"),
           op(300, 310, "")]
    got = trace_scope_lm.self_times(ops, 0.0, 400.0)
    assert got == {("mamba1_proj", "forward"): 40.0, ("mamba1_scan", "forward"): 60.0, ("attn_core", "forward"): 20.0,
                   ("gmu", "forward"): 15.0, ("cross_core", "forward"): 15.0, ("mamba1_scan", "recompute"): 40.0,
                   ("mamba1_scan", "backward"): 100.0, ("cross_core", "backward"): 10.0, ("unnamed", "forward"): 10.0}
    # the cell's scope metrics cover every scope this model has once: over one step they sum to all of it
    step = {**got, ("loss", "forward"): 5.0, ("optimizer", "forward"): 7.0, ("embed", "forward"): 1.0, ("lm_head", "backward"): 6.0,
            ("attn_proj", "forward"): 2.0, ("swa_core", "backward"): 1.5, ("diagnostics", "forward"): 3.0, ("dense_mlp", "backward"): 9.0}
    files = [cells.load("layer_metrics", n) for n in cells.load("workloads", CELL)["per_layer"]]
    scope_files = [m for m in files if m["reader"] == "trace_scope_lm" and "passes" not in m["params"]]
    import unittest.mock as mock

    with mock.patch.object(trace_scope_lm, "steps_of", lambda result: [step]):
        assert sum(trace_scope_lm.read(None, scale=1.0, **m["params"]) for m in scope_files) == pytest.approx(sum(step.values()))
        scan = cells.load("layer_metrics", "mamba1_scan_roofline_pct")["params"]
        # the roofline share's time: the scan's two kernels without the layer's replay
        assert trace_scope_lm.read(None, scale=1.0, scopes=scan["scopes"], passes=scan["passes"]) == 160.0
        both = cells.load("layer_metrics", "phi4_attn_core_roofline_pct")["params"]
        assert trace_scope_lm.read(None, scale=1.0, scopes=both["scopes"], passes=both["passes"]) == 45.0
    covered = [s for m in scope_files for s in m["params"].get("scopes", [])]
    assert len(covered) == len(set(covered))                                      # no scope is counted twice
    from distar_tpu import obs

    mine = {"embed", "mamba1_proj", "mamba1_scan", "gmu", "attn_proj", "attn_core", "swa_core", "cross_core", "dense_mlp", "lm_head",
            "loss", "optimizer", "diagnostics"}
    assert mine <= set(obs.LM_STEP_SCOPES) and mine <= set(covered)
    assert not (set(covered) - set(obs.LM_STEP_SCOPES) - {"unnamed"})


def test_the_steps_scopes_on_the_lowered_program_are_the_ones_the_cell_covers():
    """The tiny preset's lowered ``lm_train_step`` carries this model's names and none of the other mixers'."""
    import jax
    import jax.numpy as jnp
    import optax

    from distar_tpu.learner.lm_learner import make_lm_train_step
    from distar_tpu.model import Phi4Flash, default_phi4flash_config
    from distar_tpu.obs import LM_STEP_SCOPES, tree_spec
    from distar_tpu.utils import deep_merge_dicts

    model = Phi4Flash(deep_merge_dicts(default_phi4flash_config(), cells.load("configs", CONFIG)["tiny"]["model"]))
    tokens = jnp.zeros((1, 32), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    optimizer = optax.adam(1e-3)
    step = jax.jit(make_lm_train_step(model, optimizer, dynamics=tree_spec({}, {"type": "none"})))
    text = step.lower(variables, jax.eval_shape(optimizer.init, variables["params"]),
                      {"tokens": tokens, "labels": tokens}).as_text(debug_info=True)
    there = {name for name in LM_STEP_SCOPES if f"/{name}" in text or f"({name})" in text}
    assert {"mamba1_proj", "mamba1_scan", "gmu", "attn_proj", "attn_core", "swa_core", "cross_core", "dense_mlp", "embed", "lm_head",
            "loss", "optimizer"} <= there
    assert not there & {"attention", "short_conv", "ssm_proj", "ssm_scan", "mla_proj", "mla_core", "gdn_proj", "gdn_scan", "moe_experts"}


@pytest.mark.parametrize("name,kernel,ms,least,most", [("mamba1_scan_roofline_pct", "selective_scan", 60.0, 5.0, 6.0),
                                                        ("phi4_attn_core_roofline_pct", "diff_core", 80.0, 38.0, 40.0),
                                                        ("phi4_swa_core_roofline_pct", "diff_banded_core", 6.0, 31.0, 33.0)])
def test_a_roofline_share_is_required_time_over_scope_time_at_the_published_sizes(monkeypatch, name, kernel, ms, least, most):
    metric = cells.load("layer_metrics", name)
    params = metric["params"]
    assert metric["reader"] == "kernel_roofline_phi4flash" and params["kernel"] == kernel and params["passes"] == ["forward", "backward"]
    shape = params["shape"]
    assert shape["positions"] == 16384 and shape["bytes_per_value"] == 2
    if kernel == "selective_scan":
        assert (shape["channels"], shape["state"], shape["layers"]) == (5120, 16, 2) and params["scopes"] == ["mamba1_scan"]
    else:
        assert (shape["heads"], shape["kv_heads"], shape["head_dim"], shape["seq_len"]) == (40, 20, 64, 8192)
        assert (shape["layers"], params["scopes"]) == ((2, ["attn_core", "cross_core"]) if kernel == "diff_core" else (1, ["swa_core"]))
    need = getattr(kernels_phi4flash, kernel)(**shape)
    least_s = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: ms)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline_phi4flash.read(result, **params)
    assert share == pytest.approx(100.0 * least_s / (ms * 1e-3)) and least < share < most
    # nothing to read without a trace, off the chip, or from a program without the scope (the parent commit)
    assert kernel_roofline_phi4flash.read({"device": {"platform": "cpu", "kind": "cpu"}}, **params) is None
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: None)
    assert kernel_roofline_phi4flash.read(result, **params) is None

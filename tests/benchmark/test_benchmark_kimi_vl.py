"""The ``deepseek_v3`` cell (Kimi-VL-A3B-Instruct's language model): its
rehearsal end to end beside its plain reference, ``correct`` turning false for
each term the run leaves out and for a dropped update, its traffic, its FLOP
and kernel counts against ``cost_analysis()`` of the plain products, its
scopes, its configuration against the published one, the gradient check at the
tiny preset, and the manifest. Nothing here is pinned to a place in a list or
to a count of cells: a later PR's files only append."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, flops, flops_deepseek_v3, kernels_lm, kernels_mla, run  # noqa: E402
from benchmark.gen import lm_pool  # noqa: E402
from benchmark.readers import kernel_roofline, kernel_roofline_mla, trace_scope_lm  # noqa: E402
from benchmark.trace_meta import Op  # noqa: E402

CELL = "kimi_vl_train_b2s8k"
CONFIG = "kimi_vl_a3b_ep8_l6"
TRAFFIC = "lm_zipf_pool4_b2s8192_v20480"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("lm_mla_proj_ms", "lm_mla_core_ms", "mla_core_roofline_pct", "kimi_moe_experts_roofline_pct")


def rehearse(capsys, trace=0, seed="3100000029"):
    assert run.main(["--workload", CELL, "--seed", seed, "--seconds", "2.5",
                     "--trace", str(trace), "--rehearse"]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.strip().split("\n") if ln.startswith("{")]
    return json.loads(lines[-1]), json.loads(lines[-2]), out


# ------------------------------------------------------------ the rehearsal
def test_the_cell_rehearses_end_to_end_beside_its_plain_reference(capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    line, notes, _ = rehearse(capsys, trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 12, notes["checks"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert all(notes["checks"].values()), notes["checks"]
    assert {"moe_rows_here", "moe_load_max_over_mean", "data_wait_ms", "device_step_ms", "feed_put_ms",
            "loop_dispatch_ms", "retraces_in_window", "program_hbm_gb"} <= set(line["rehearsed"])
    # what the trace gives does not exist on the CPU, the roofline shares among it
    assert not [n for n in line["rehearsed"] if n.startswith(("lm_", "idle_", "scope_")) or "roofline" in n]
    reference = json.load(open(tmp_path / (CELL + "_rehearsal") / "reference.json"))
    first = reference["first_step"]
    assert reference["seed"] == 3100000029 and first["moe_overflow_rows"] == 0.0
    # the tiny preset: a dense layer and two expert layers of 4 held experts; 3 layers x 3 RMS; the gradient norms
    assert len([k for k in first if k.startswith("moe_rows/")]) == 8
    assert len([k for k in first if k.startswith(("residual_rms/", "attn_rms/", "ff_rms/"))]) == 9
    assert sorted(k for k in first if k.startswith("moe_rows_sum/")) == ["moe_rows_sum/layer_1", "moe_rows_sum/layer_2"]
    assert {"dyn/grad_norm/embedding", "dyn/grad_norm/lm_head", "dyn/grad_norm/layer_0", "dyn/grad_norm/layer_2",
            "dyn/grad_norm/final_norm"} <= set(first)
    steps = json.load(open(tmp_path / (CELL + "_rehearsal") / "steps.json"))
    log = steps["scalars"][0]
    assert log["moe_overflow_rows"] == 0.0
    assert log["moe_rows/layer_1/expert_1"] == first["moe_rows/layer_1/expert_1"]
    assert log["attn_rms/layer_0"] == pytest.approx(first["attn_rms/layer_0"], rel=1e-4)
    assert log["ff_rms/layer_2"] == pytest.approx(first["ff_rms/layer_2"], rel=1e-4)


# ------------------------------------------------- a term left out of the run
def _no_rope(monkeypatch):
    from distar_tpu.ops import sequence

    monkeypatch.setattr(sequence, "rope_interleaved", lambda x, theta: x)


def _k_pe_zeroed(monkeypatch):
    """The one shared rotary key (a single head) is zeros; the queries' rotary parts are as they were."""
    import jax.numpy as jnp

    from distar_tpu.ops import sequence

    whole = sequence.rope_interleaved
    monkeypatch.setattr(sequence, "rope_interleaved",
                        lambda x, theta: jnp.zeros_like(x) if x.shape[2] == 1 else whole(x, theta))


def _no_latent_norm(monkeypatch):
    from distar_tpu.ops import sequence

    whole = sequence.RMSNorm
    monkeypatch.setattr(sequence, "RMSNorm",
                        lambda eps, name=None: (lambda c: c) if name == "kv_norm" else whole(eps, name=name))


def _scale_of_the_part_without_positions(monkeypatch):
    """``1/sqrt(qk_nope_head_dim)`` in place of ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``."""
    from distar_tpu.ops import sequence

    whole = sequence.causal_attention
    model = cells.load("configs", CONFIG)["tiny"]["model"]
    ratio = ((model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) / model["qk_nope_head_dim"]) ** 0.5
    monkeypatch.setattr(sequence, "causal_attention", lambda q, k, v, scale: whole(q, k, v, scale * ratio))


def _no_shared_experts(monkeypatch):
    import jax.numpy as jnp

    from distar_tpu.ops import moe

    monkeypatch.setattr(moe, "shared_expert", lambda body, u, ws: jnp.zeros_like(u))


def _no_scaling_factor(monkeypatch):
    from distar_tpu.ops import moe

    whole = moe.route
    monkeypatch.setattr(moe, "route", lambda logits, bias, k, scaling=1.0: whole(logits, bias, k, 1.0))


# the fault, and the components of which at least one sees it. A rotation left out changes no statistic in
# the mean over random weights (the scores keep their distribution): it shows in what follows the re-drawn
# attention pattern, the residual stream and the loss, and at the published widths in ``attn_rms`` by 5-6%
FAULTS = {"no_rope": (_no_rope, ("attn_rms/", "residual_rms/", "total_loss")),
          "k_pe_zeroed": (_k_pe_zeroed, ("attn_rms/", "residual_rms/")),
          "no_latent_norm": (_no_latent_norm, ("attn_rms/layer_0",)),
          "scale_of_128": (_scale_of_the_part_without_positions, ("attn_rms/", "residual_rms/")),
          "no_shared_experts": (_no_shared_experts, ("ff_rms/layer_1",)),
          "no_scaling_factor": (_no_scaling_factor, ("ff_rms/layer_1",))}


@pytest.mark.parametrize("fault", FAULTS)
def test_correct_turns_false_when_the_run_leaves_a_term_out(capsys, tmp_path, monkeypatch, fault):
    """The fault is put into the program in this process only: the reference
    is a process of its own and computes the whole model. The run still
    trains; what fails is the first step against the reference, at the cell's
    own limits."""
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    inject, seen_by = FAULTS[fault]
    inject(monkeypatch)
    line, notes, out = rehearse(capsys, seed="3100000037")
    assert line["correct"] is False and not notes["checks"]["first_step_matches_reference"]
    assert notes["checks"]["loss_went_down"] and notes["checks"]["params_changed"]
    assert notes["checks"]["ran_to_its_end"] and line["failed"] == 0
    off = next(ln for ln in out.split("\n") if ln.startswith("benchmark: first step against the reference"))
    assert any(name in off for name in seen_by), off


def test_correct_turns_false_when_the_update_is_dropped(capsys, tmp_path, monkeypatch):
    import optax

    monkeypatch.setattr(run, "OUT", str(tmp_path))
    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    line, notes, _ = rehearse(capsys, seed="3100000041")
    assert line["correct"] is False
    assert not notes["checks"]["loss_went_down"] and not notes["checks"]["params_changed"]
    assert notes["checks"]["first_step_matches_reference"] and line["failed"] == 0


def test_gradient_check_passes_the_program_and_fails_the_control_in_float8(capsys, tmp_path, monkeypatch):
    """``tools/gradients_on_chip`` (the tool the LFM2 cell brought, unedited)
    at this cell's tiny preset: loss, statistics and every gradient leaf of
    the program agree with the reference on one sequence, and the float8
    control does not."""
    from benchmark.tools import gradients_on_chip

    monkeypatch.setattr(gradients_on_chip, "ROOT", str(tmp_path))
    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.chdir(REPO)
    child = gradients_on_chip.subprocess.run
    monkeypatch.setattr(gradients_on_chip.subprocess, "run", lambda cmd, cwd, **kw: child(cmd, cwd=REPO, **kw))
    assert gradients_on_chip.main(["--workload", CELL, "--seed", "3100000031", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    assert out["ok"] and out["positions"] == 32 and len(out["program"]["gradient_off_by_leaf"]) == 3 * 7 + 3 + 2 * 7 + 3
    assert out["program"]["correct"] and out["program"]["gradient_off_largest"] < 1e-4  # float32 both, at this size
    assert {"layer_0/mla/kv_a_proj/kernel", "layer_0/mla/kv_norm/scale", "layer_1/mla/kv_b_proj/kernel",
            "layer_1/moe/shared_w3", "lm_head"} <= set(out["program"]["gradient_off_by_leaf"])
    control = out["control"]
    assert not control["correct"] and control["first_step_off"] and control["gradient_leaves_off"]
    assert control["gradient_off_largest"] > 1.5 * out["gradients_rtol"]


# ------------------------------------------------------------- the traffic
def test_traffic_is_the_nemotron_cells_over_this_slice():
    mine, theirs = (cells.load("traffic", n)["params"] for n in (TRAFFIC, "lm_zipf_pool4_b2s8192_v16384"))
    assert {k: v for k, v in mine.items() if mine[k] != theirs[k]} == {"vocab_size": 20480}
    cfg = cells.load("configs", CONFIG)
    assert mine["vocab_size"] == cfg["vocab_size"] and mine["unroll_len"] == cfg["as_run"]["learner"]["unroll_len"]
    assert mine["batch_size"] == cfg["as_run"]["learner"]["batch_size"]
    pool = lm_pool.build(2 ** 31 + 19, dict(mine, pool=2))
    again = lm_pool.build(2 ** 31 + 19, dict(mine, pool=2))
    assert len(pool) == 2 and pool[0]["tokens"].shape == (2, 8192) and pool[0]["tokens"].dtype == np.int32
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(pool, again) for k in a)
    ids = np.concatenate([b["tokens"].reshape(-1) for b in pool])
    assert ids.min() >= 0 and 16384 < ids.max() < 20480     # the ids reach beyond the other cell's slice
    assert np.array_equal(pool[0]["tokens"][:, 1:], pool[0]["labels"][:, :-1])


# --------------------------------------------------------------- the counts
def test_flops_count_is_the_walkers_on_the_dense_parts():
    """``flops.py`` walks the traced forward pass; on a model without expert
    layers it sees every product this module counts. Attention through the
    program's XLA path multiplies each query against all S keys, which is this
    module's count at twice the sequence."""
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import DeepseekV3, default_deepseek_v3_config
    from distar_tpu.utils import deep_merge_dicts

    B, S = 2, 64
    m = deep_merge_dicts(default_deepseek_v3_config(), dict(
        cells.load("configs", CONFIG)["tiny"]["model"], num_hidden_layers=2, first_k_dense_replace=2, remat=False))
    model = DeepseekV3(m)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    walked = flops.forward_flops(lambda v, t: model.apply(v, t)[0], variables, tokens) / (B * S)
    parts = flops_deepseek_v3.forward_parts(m, 2 * S)
    assert parts["moe_experts"] == parts["moe_shared"] == parts["moe_router"] == 0
    assert walked == pytest.approx(sum(parts.values()), rel=1e-9)
    assert parts["mla_core"] == 2 * flops_deepseek_v3.core_per_position(m, 2 * S) > 0
    assert flops_deepseek_v3.forward_parts(m, S)["mla_core"] < parts["mla_core"]


def test_core_count_is_cost_analysis_of_the_plain_products_at_a_small_size():
    """The kernel's required FLOPs are half of what the plain products over
    ALL keys cost (a causal query sees S/2 keys in the mean), forward, and
    three times that with the backward pass; the expert and shared SwiGLU
    counts are the plain products' own."""
    import jax
    import jax.numpy as jnp

    S, H, Dqk, Dv = 256, 4, 24, 16
    q, k, v = (jax.ShapeDtypeStruct((H, S, d), jnp.float32) for d in (Dqk, Dqk, Dv))
    products = lambda q, k, v: jnp.einsum("hqk,hkd->hqd", jnp.einsum("hqd,hkd->hqk", q, k), v)
    all_keys = jax.jit(products).lower(q, k, v).compile().cost_analysis()["flops"]
    need = kernels_mla.causal_core(positions=S, seq_len=S, heads=H, qk_dim=Dqk, v_dim=Dv, layers=1)
    assert need["flops"] == pytest.approx(3 * all_keys / 2, rel=1e-6)
    m = {"num_attention_heads": H, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": Dv}
    assert flops_deepseek_v3.core_per_position(m, S) * S == pytest.approx(all_keys / 2, rel=1e-6)
    # bytes: q, k, v, o and their gradients once each, and the inputs read again by the backward pass
    assert need["bytes"] == S * 2 * (2 * H * Dqk + 2 * H * Dv + 4 * H * Dqk + 4 * H * Dv)
    rows, d, width = 96, 32, 24
    u, w13, w2 = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((rows, d), (d, width), (width, d)))
    swiglu = lambda u, w1, w3, w2: (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
    plain = jax.jit(swiglu).lower(u, w13, w13, w2).compile().cost_analysis()["flops"]
    products_only = 3 * 2 * rows * d * width
    assert products_only <= plain <= 1.2 * products_only                  # the gate's elementwise work on top
    assert kernels_lm.grouped_swiglu(rows, d, width, 1, 1)["flops"] == 3 * products_only


def test_recorded_count_is_what_the_module_gives_for_the_program_file():
    cfg = cells.load("configs", CONFIG)
    model = cells.program_config(cfg)["model"]
    got = flops_deepseek_v3.required_per_frame(model, cfg["as_run"]["learner"]["unroll_len"])
    assert got["step"] == cfg["required_flops_per_frame"] == pytest.approx(2.6349e9, rel=1e-4)
    parts = flops_deepseek_v3.forward_parts(model, 8192)
    share = lambda *names: sum(parts[n] for n in names) / got["forward"]
    # latent attention is 47% of the forward FLOPs, the shared experts 20%, the routed experts held here 7%
    assert share("mla_proj", "mla_core") == pytest.approx(0.47, abs=0.005)
    assert share("moe_shared") == pytest.approx(0.20, abs=0.005) and share("moe_experts") == pytest.approx(0.07, abs=0.005)
    assert flops_deepseek_v3.core_per_position(model, 8192) == 2 * 16 * (192 + 128) * 4096
    assert parts["mla_proj"] == 6 * 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048)
    assert parts["moe_experts"] == 5 * 0.75 * 6 * 2048 * 1408 and parts["moe_shared"] == 5 * 6 * 2048 * 2816
    assert parts["dense_mlp"] == 6 * 2048 * 11264 and parts["lm_head"] == 2 * 2048 * 20480


# -------------------------------------------------- the configuration file
def test_configuration_holds_every_published_number_and_lists_what_it_cut():
    cfg = cells.load("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 20480}
    assert cfg["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    assert set(cfg["reduced_why"]) == set(cfg["reduced"]) and len(cfg["source"]) <= 200
    run_model = cfg["as_run"]["model"]
    # no width among the cuts: what the program runs is what was published
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta", "first_k_dense_replace",
                "num_experts_per_tok", "n_shared_experts", "routed_scaling_factor", "rms_norm_eps"):
        assert run_model[key] == cfg[key], key
    assert run_model["n_routed_experts"] == cfg["num_experts_routed_over"] == 64   # the router's width
    assert run_model["experts_held"] == {"offset": 0, "count": cfg["n_routed_experts"]}
    assert run_model["num_hidden_layers"] == cfg["num_hidden_layers"] == len(cfg["layers_held"])
    assert cfg["layers_held"] == list(range(6)) and run_model["model_type"] == "deepseek_v3"
    assert cfg["q_lora_rank"] is None and cfg["n_group"] == cfg["topk_group"] == 1
    assert {"vision", "model_type", "expert_bias", "auxiliary_loss", "router_epsilon", "weights",
            "parameters"} <= set(cfg["assumed"])
    assert "eight chips share each layer" in cfg["deployment"]
    assert cfg["parameters"]["state_bytes"] == 16 * cfg["parameters"]["total"] >= 0.25 * 16e9
    by_part = cfg["parameters"]["by_part"]
    assert cfg["parameters"]["total"] == by_part["dense layer"] + 5 * by_part["expert layer"] + \
        by_part["embedding"] + by_part["lm_head"] + by_part["final_norm"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if '"Kimi-VL-A3B-Instruct"' in ln)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if k not in cfg or cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"])


def test_the_programs_parameter_count_is_the_files():
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import DeepseekV3, default_deepseek_v3_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", CONFIG)
    model = DeepseekV3(deep_merge_dicts(default_deepseek_v3_config(), cells.program_config(cfg)["model"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 512), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    p, by_part = shapes["params"], cfg["parameters"]["by_part"]
    assert count(p) == cfg["parameters"]["total"] == 668890112
    assert {"latent attention, a layer": count(p["layer_3"]["mla"]), "dense layer": count(p["layer_0"]),
            "expert layer": count(p["layer_1"]), "embedding": count(p["embedding"]), "lm_head": count(p["lm_head"]),
            "final_norm": count(p["final_norm"])} == by_part
    assert p["layer_1"]["moe"]["router"].shape == (2048, 64) and p["layer_1"]["moe"]["w1"].shape == (8, 2048, 1408)
    assert p["layer_1"]["moe"]["shared_w2"].shape == (2816, 2048)
    assert p["layer_0"]["mla"]["q_proj"]["kernel"].shape == (2048, 16 * 192)
    assert p["layer_0"]["mla"]["kv_b_proj"]["kernel"].shape == (512, 16 * 256)


# ------------------------------------------------------------- the manifest
def test_the_manifest_gained_the_cell_and_its_metrics_and_lost_nothing():
    cell, lfm2 = cells.load("workloads", CELL), cells.load("workloads", "lfm2_train_b4s8k")
    assert cell["per_layer"][:23] == lfm2["per_layer"][:23] and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(NEW_METRICS) <= set(cell["per_layer"]) and "mfu_pct" in cell["per_layer"]
    assert not {"lm_short_conv_ms", "lm_attention_ms", "lm_ssm_scan_ms", "moe_experts_roofline_pct"} & set(cell["per_layer"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    by_name = lambda group: {e["name"]: e for e in m[group]}
    assert by_name("workloads")[CELL] == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
                                          "why": cell["why"]}
    mine = by_name("configs")[CONFIG]
    assert mine["reduced"] == cells.load("configs", CONFIG)["reduced"] and mine["file"] == f"benchmark/configs/{CONFIG}.json"
    assert mine["source"] == "https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json"
    for name in NEW_METRICS:
        e = by_name("per_layer")[name]
        assert e["workloads"] == [CELL] and e["moves"] == "train_frames_per_s" and e["layer"] == "Jitted step", name
    assert {e["name"] for e in m["per_layer"] if CELL in e["workloads"]} == set(cell["per_layer"])
    # every data file still has its entry: nothing that was there went
    assert set(by_name("workloads")) == set(cells.names("workloads"))
    assert {"sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64", "lfm2_train_b4s8k",
            "nemotron_twotower_train_b2s8k"} <= set(by_name("workloads"))
    assert {"ssm_scan_roofline_pct", "moe_experts_roofline_pct", "mfu_pct", "lm_attention_ms"} <= set(by_name("per_layer"))
    from benchmark.tools import manifest

    assert manifest.build(m) == m


# ------------------------------------------------ scopes and roofline shares
def op(start, end, scope):
    return Op("x", float(start), float(end), scope, "")


def test_the_new_scopes_partition_a_step_with_the_old_ones():
    head = "jit(lm_train_step)/jvp(DeepseekV3)/"
    back = "jit(lm_train_step)/transpose(jvp(DeepseekV3))/"
    ops = [op(0, 10, head + "layer_0/checkpoint/mla_proj/operator_norm/mul"),
           op(10, 40, head + "layer_0/checkpoint/mla/mla_proj/q_proj/dot_general"),
           op(40, 100, head + "layer_0/checkpoint/mla/mla_core/vmap(splash_mha_fwd)/pallas_call"),
           op(100, 110, head + "layer_0/checkpoint/mla/mla_proj/o_proj/dot_general"),
           op(110, 130, head + "layer_0/checkpoint/dense_mlp/dense_mlp/w1/dot_general"),
           op(130, 150, head + "layer_1/checkpoint/moe/moe_shared/dot_general"),
           op(150, 190, back + "layer_1/rematted_computation/mla/mla_core/pallas_call"),
           op(190, 260, back + "layer_1/mla/mla_core/vmap(splash_mha_dkv)/pallas_call"),
           op(260, 270, back + "layer_1/mla/mla_proj/kv_b_proj/dot_general"),
           op(270, 280, "")]
    got = trace_scope_lm.self_times(ops, 0.0, 300.0)
    assert got == {("mla_proj", "forward"): 50.0, ("mla_core", "forward"): 60.0, ("dense_mlp", "forward"): 20.0,
                   ("moe_shared", "forward"): 20.0, ("mla_core", "recompute"): 40.0, ("mla_core", "backward"): 70.0,
                   ("mla_proj", "backward"): 10.0, ("unnamed", "forward"): 10.0}
    # the cell's scope metrics cover every scope once: over one step they sum to all of it
    step = {**got, ("loss", "forward"): 5.0, ("optimizer", "forward"): 7.0, ("embed", "forward"): 1.0,
            ("moe_router", "forward"): 2.0, ("moe_experts", "backward"): 4.0, ("diagnostics", "forward"): 3.0}
    files = [cells.load("layer_metrics", n) for n in cells.load("workloads", CELL)["per_layer"]]
    scope_files = [m for m in files if m["reader"] == "trace_scope_lm" and "passes" not in m["params"]]
    assert len(scope_files) == 9
    import unittest.mock as mock

    with mock.patch.object(trace_scope_lm, "steps_of", lambda result: [step]):
        total = sum(trace_scope_lm.read(None, scale=1.0, **m["params"]) for m in scope_files)
        assert total == pytest.approx(sum(step.values()))
        core = cells.load("layer_metrics", "mla_core_roofline_pct")["params"]
        # the roofline share's time: the kernel without the layer's replay
        assert trace_scope_lm.read(None, scale=1.0, scopes=core["scopes"], passes=core["passes"]) == 130.0
    covered = [s for m in scope_files for s in m["params"]["scopes"]]
    from distar_tpu import obs

    assert sorted(covered) == sorted(
        set(obs.LM_STEP_SCOPES) - {"short_conv", "attention", "ssm_proj", "ssm_scan"} | {"unnamed"})


def test_core_roofline_is_required_time_over_scope_time_at_the_published_head_sizes(monkeypatch):
    params = cells.load("layer_metrics", "mla_core_roofline_pct")["params"]
    shape = params["shape"]
    assert (shape["qk_dim"], shape["v_dim"], shape["heads"], shape["layers"]) == (192, 128, 16, 6)
    need = kernels_mla.causal_core(**shape)
    assert need["flops"] == 16384 * 6 * 3 * 2 * 16 * 320 * 4096 == pytest.approx(12.37e12, rel=1e-3)
    assert need["flops"] / 197e12 == pytest.approx(62.8e-3, rel=1e-2)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9                                       # compute-bound
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 157.0)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline_mla.read(result, **params)
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.157) and 39 < share < 41
    # a kernel that pads the value head to 192 does more work in more time: the required work stays
    assert kernels_mla.causal_core(**dict(shape, v_dim=192))["flops"] > need["flops"]
    # nothing to read without a trace, off the chip, or from a program without the scope (the parent commit)
    assert kernel_roofline_mla.read({"device": {"platform": "cpu", "kind": "cpu"}}, **params) is None
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: None)
    assert kernel_roofline_mla.read(result, **params) is None


def test_expert_roofline_reads_the_existing_reader_at_this_cells_shape(monkeypatch):
    metric = cells.load("layer_metrics", "kimi_moe_experts_roofline_pct")
    params = metric["params"]
    assert metric["reader"] == "kernel_roofline" and params["kernel"] == "grouped_swiglu"
    assert params["shape"] == {"d": 2048, "width": 1408, "experts": 8, "layers": 5, "bytes_per_value": 2}
    rows = 5 * 12288.0                                                             # the expected rows of a step
    need = kernels_lm.grouped_swiglu(rows, **params["shape"])
    assert need["flops"] == 18.0 * rows * 2048 * 1408
    assert need["flops"] / 197e12 > need["bytes"] / 819e9                          # compute-bound at 1,536 rows an expert
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 40.0)
    from benchmark.readers import histogram_window

    monkeypatch.setattr(histogram_window, "read", lambda result, **kw: rows)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline.read(result, **params)
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.040) and 35 < share < 45

"""The ``qwen3_next`` cell (Qwen3-Next-80B-A3B-Instruct's language model): its
rehearsal end to end beside its plain reference, ``correct`` turning false for
each term the run leaves out and for a dropped update, its traffic, its FLOP
and kernel counts against ``cost_analysis()`` of the plain products, its
scopes, its configuration against the published one, the gradient check at the
tiny preset, and the manifest. Nothing here is pinned to a place in a list, to
a count of cells or to the whole of ``LM_STEP_SCOPES``: a later PR's files and
scopes only append, and these tests ask "contains" and "is a superset"."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, flops_qwen3_next, kernels_gdn, kernels_lm, run  # noqa: E402
from benchmark.gen import lm_pool  # noqa: E402
from benchmark.readers import kernel_roofline, kernel_roofline_gdn, trace_scope_lm  # noqa: E402
from benchmark.trace_meta import Op  # noqa: E402

CELL = "qwen3_next_train_b2s8k"
CONFIG = "qwen3_next_80b_a3b_ep16_l4"
TRAFFIC = "lm_zipf_pool4_b2s8192_v18992"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("lm_gdn_proj_ms", "lm_gdn_scan_ms", "gdn_scan_roofline_pct", "qwen_moe_experts_roofline_pct")


def rehearse(capsys, trace=0, seed="3600000029"):
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
            "loop_dispatch_ms", "retraces_in_window", "program_hbm_gb", "setup_first_step_s"} <= set(line["rehearsed"])
    # what the trace gives does not exist on the CPU, the roofline shares among it
    assert not [n for n in line["rehearsed"] if n.startswith(("lm_", "idle_", "scope_")) or "roofline" in n]
    reference = json.load(open(tmp_path / (CELL + "_rehearsal") / "reference.json"))
    first = reference["first_step"]
    assert reference["seed"] == 3600000029 and first["moe_overflow_rows"] == 0.0
    # the tiny preset: a Gated DeltaNet layer and an attention layer, each with 4 held experts; 2 layers x 3 RMS
    assert len([k for k in first if k.startswith("moe_rows/")]) == 8
    assert len([k for k in first if k.startswith(("residual_rms/", "mixer_rms/", "ff_rms/"))]) == 6
    assert sorted(k for k in first if k.startswith("moe_rows_sum/")) == ["moe_rows_sum/layer_0", "moe_rows_sum/layer_1"]
    assert {"gdn_state_rms/layer_0", "gdn_decay_mean/layer_0", "attn_gate_mean/layer_1", "dyn/grad_norm/embedding",
            "dyn/grad_norm/lm_head", "dyn/grad_norm/layer_0", "dyn/grad_norm/layer_1", "dyn/grad_norm/final_norm"} <= set(first)
    assert "gdn_state_rms/layer_1" not in first and "attn_gate_mean/layer_0" not in first
    steps = json.load(open(tmp_path / (CELL + "_rehearsal") / "steps.json"))
    log = steps["scalars"][0]
    assert log["moe_overflow_rows"] == 0.0
    assert log["moe_rows/layer_1/expert_1"] == first["moe_rows/layer_1/expert_1"]
    for key in ("mixer_rms/layer_0", "mixer_rms/layer_1", "ff_rms/layer_1", "gdn_state_rms/layer_0",
                "gdn_decay_mean/layer_0", "attn_gate_mean/layer_1"):
        assert log[key] == pytest.approx(first[key], rel=1e-4), key
    assert 0.5 < log["gdn_decay_mean/layer_0"] < 0.999     # the drawn decays: neither dead nor absent


# ------------------------------------------------- a term left out of the run
def _rule_with(monkeypatch, change):
    """``ops.delta.chunked_delta_rule`` with its arguments changed on the way in."""
    from distar_tpu.ops import delta

    whole = delta.chunked_delta_rule
    monkeypatch.setattr(delta, "chunked_delta_rule", lambda q, k, v, g, beta, *rest: whole(*change(q, k, v, g, beta), *rest))


def _no_decay(monkeypatch):
    import jax.numpy as jnp

    _rule_with(monkeypatch, lambda q, k, v, g, beta: (q, k, v, jnp.zeros_like(g), beta))


def _beta_of_one(monkeypatch):
    import jax.numpy as jnp

    _rule_with(monkeypatch, lambda q, k, v, g, beta: (q, k, v, g, jnp.ones_like(beta)))


def _no_carry_across_chunks(monkeypatch):
    """Every chunk starts from an empty state: the chunks of a sequence are run as sequences of their own."""
    from distar_tpu.ops import delta

    whole = delta.chunked_delta_rule

    def dropped(q, k, v, g, beta, chunk, *rest):
        b, S = v.shape[:2]
        assert S % chunk == 0 and S > chunk
        apart = lambda t: t.reshape(b * (S // chunk), chunk, *t.shape[2:])
        o, last = whole(*map(apart, (q, k, v, g, beta)), chunk, *rest)
        return o.reshape(b, S, *o.shape[2:]), last.reshape(b, S // chunk, *last.shape[1:])[:, -1]

    monkeypatch.setattr(delta, "chunked_delta_rule", dropped)


def _no_output_gate(monkeypatch):
    """Gated DeltaNet's ``RMSNorm(o) * w_o`` without ``silu(z)``."""
    import jax.numpy as jnp

    from distar_tpu.ops import delta

    whole = delta.output_gate
    one = 1.2784645  # silu(one) = 1
    monkeypatch.setattr(delta, "output_gate", lambda o, z, scale, eps: whole(o, jnp.full_like(z, one), scale, eps))


def _no_attention_gate(monkeypatch):
    from distar_tpu.ops import sequence

    whole = sequence.open_gate
    monkeypatch.setattr(sequence, "open_gate", lambda out, gate: (out, whole(out, gate)[1]))


def _no_shared_gate(monkeypatch):
    import jax.numpy as jnp

    from distar_tpu.ops import moe

    monkeypatch.setattr(moe, "shared_gate", lambda u, w_gate: jnp.ones((u.shape[0], 1), jnp.float32))


def _whole_head_rotated(monkeypatch):
    from distar_tpu.ops import sequence

    monkeypatch.setattr(sequence, "rope_first", lambda x, theta, rotary_dim: sequence.rope(x, theta))


# the fault, and the components of which at least one sees it
FAULTS = {"no_decay": (_no_decay, ("mixer_rms/layer_0", "gdn_state_rms/layer_0")),
          "beta_of_one": (_beta_of_one, ("mixer_rms/layer_0", "gdn_state_rms/layer_0")),
          "no_carry_across_chunks": (_no_carry_across_chunks, ("gdn_state_rms/layer_0", "mixer_rms/layer_0")),
          "no_output_gate": (_no_output_gate, ("mixer_rms/layer_0",)),
          "no_attention_gate": (_no_attention_gate, ("mixer_rms/layer_1",)),
          "no_shared_gate": (_no_shared_gate, ("ff_rms/layer_0",)),
          "whole_head_rotated": (_whole_head_rotated, ("mixer_rms/layer_1", "residual_rms/layer_1", "total_loss"))}


@pytest.mark.parametrize("fault", FAULTS)
def test_correct_turns_false_when_the_run_leaves_a_term_out(capsys, tmp_path, monkeypatch, fault):
    """The fault is put into the program in this process only: the reference
    is a process of its own and computes the whole model. The run still
    trains; what fails is the first step against the reference, at the cell's
    own limits."""
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    inject, seen_by = FAULTS[fault]
    inject(monkeypatch)
    line, notes, out = rehearse(capsys, seed="3600000037")
    assert line["correct"] is False and not notes["checks"]["first_step_matches_reference"]
    assert notes["checks"]["loss_went_down"] and notes["checks"]["params_changed"]
    assert notes["checks"]["ran_to_its_end"] and line["failed"] == 0
    off = next(ln for ln in out.split("\n") if ln.startswith("benchmark: first step against the reference"))
    assert any(name in off for name in seen_by), off


def test_correct_turns_false_when_the_update_is_dropped(capsys, tmp_path, monkeypatch):
    import optax

    monkeypatch.setattr(run, "OUT", str(tmp_path))
    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    line, notes, _ = rehearse(capsys, seed="3600000041")
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
    assert gradients_on_chip.main(["--workload", CELL, "--seed", "3600000031", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    # a Gated DeltaNet layer 1 + 7 leaves, an attention layer 1 + 6, each layer's expert block 9, the three ends
    assert out["ok"] and out["positions"] == 32 and len(out["program"]["gradient_off_by_leaf"]) == 8 + 7 + 2 * 9 + 3
    assert out["program"]["correct"] and out["program"]["gradient_off_largest"] < 1e-3  # float32 both, at this size
    assert {"layer_0/gdn/A_log", "layer_0/gdn/dt_bias", "layer_0/gdn/conv_kernel", "layer_0/gdn/out_norm",
            "layer_0/gdn/in_proj_ba/kernel", "layer_1/attention/q_norm/w", "layer_1/attention/q_proj/kernel",
            "layer_1/moe/shared_gate", "layer_0/moe/norm/w", "lm_head"} <= set(out["program"]["gradient_off_by_leaf"])
    control = out["control"]
    assert not control["correct"] and control["first_step_off"] and control["gradient_leaves_off"]
    assert control["gradient_off_largest"] > 1.5 * out["gradients_rtol"]


# ------------------------------------------------------------- the traffic
def test_traffic_is_the_kimi_cells_over_this_slice():
    mine, theirs = (cells.load("traffic", n)["params"] for n in (TRAFFIC, "lm_zipf_pool4_b2s8192_v20480"))
    assert {k: v for k, v in mine.items() if mine[k] != theirs[k]} == {"vocab_size": 18992}
    assert mine == {"pool": 4, "batch_size": 2, "unroll_len": 8192, "vocab_size": 18992, "zipf_exponent": 1.0,
                    "doc_len_median": 600, "doc_len_sigma": 1.2}
    cfg = cells.load("configs", CONFIG)
    assert mine["vocab_size"] == cfg["vocab_size"] == 151936 // 8
    assert mine["unroll_len"] == cfg["as_run"]["learner"]["unroll_len"]
    assert mine["batch_size"] == cfg["as_run"]["learner"]["batch_size"]
    pool = lm_pool.build(2 ** 31 + 23, dict(mine, pool=2))
    again = lm_pool.build(2 ** 31 + 23, dict(mine, pool=2))
    assert len(pool) == 2 and pool[0]["tokens"].shape == (2, 8192) and pool[0]["tokens"].dtype == np.int32
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(pool, again) for k in a)
    ids = np.concatenate([b["tokens"].reshape(-1) for b in pool])
    assert ids.min() >= 0 and 16384 < ids.max() < 18992
    assert np.array_equal(pool[0]["tokens"][:, 1:], pool[0]["labels"][:, :-1])


# --------------------------------------------------------------- the counts
def test_rule_count_is_cost_analysis_of_the_recurrences_own_products():
    """What the recurrence requires of a position and head: the state read,
    the rank-one write and the read-out, ``2 K V`` each; the kernel's count is
    three times that (forward and backward) over positions, heads and layers."""
    import jax
    import jax.numpy as jnp

    K, V = 24, 16
    S, k, e, q = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((K, V), (K,), (V,), (K,)))
    products = lambda S, k, e, q: (jnp.einsum("kv,k->v", S, k), S + k[:, None] * e[None, :], jnp.einsum("kv,k->v", S, q))
    plain = jax.jit(products).lower(S, k, e, q).compile().cost_analysis()["flops"]
    assert plain == pytest.approx(6 * K * V, rel=0.02)
    m = {"linear_key_head_dim": K, "linear_value_head_dim": V, "linear_num_value_heads": 5}
    assert flops_qwen3_next.rule_per_position(m) == 5 * 6 * K * V
    need = kernels_gdn.delta_rule(positions=100, key_heads=3, value_heads=5, key_dim=K, value_dim=V, layers=2)
    assert need["flops"] == 100 * 2 * 3 * 5 * 6 * K * V
    # bytes: q, k, v, o and their gradients once each in two bytes, the inputs read again by the backward pass, g and beta in four
    assert need["bytes"] == 100 * 2 * (2 * (6 * 3 * K + 5 * 5 * V) + 4 * 6 * 5)


def test_projection_attention_and_expert_counts_are_the_plain_products():
    import jax
    import jax.numpy as jnp

    cfg = cells.load("configs", CONFIG)
    m = dict(cfg["tiny"]["model"], num_hidden_layers=4, full_attention_interval=4)
    d, rows = m["hidden_size"], 64
    cost = lambda fn, *shapes: jax.jit(fn).lower(*(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)).compile().cost_analysis()["flops"]
    Hk, K, H, V = m["linear_num_key_heads"], m["linear_key_head_dim"], m["linear_num_value_heads"], m["linear_value_head_dim"]
    widths = (2 * Hk * K + 2 * H * V, 2 * H)
    gdn = cost(lambda u, a, b, y, c: (u @ a, u @ b, y @ c), (rows, d), (d, widths[0]), (d, widths[1]), (rows, H * V), (H * V, d))
    parts = flops_qwen3_next.forward_parts(m, 128)
    assert parts["gdn_proj"] * rows == pytest.approx(3 * gdn, rel=1e-6) and parts["gdn_scan"] == 3 * 6 * K * V * H
    Hq, Hkv, D, S = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], 128
    proj = cost(lambda u, a, b, c, y, o: (u @ a, u @ b, u @ c, y @ o), (rows, d), (d, 2 * Hq * D), (d, Hkv * D), (d, Hkv * D),
                (rows, Hq * D), (Hq * D, d))
    all_keys = cost(lambda q, k, v: jnp.einsum("hqk,hkd->hqd", jnp.einsum("hqd,hkd->hqk", q, k), v), *[(Hq, S, D)] * 3)
    assert flops_qwen3_next.core_per_position(m, S) * S == pytest.approx(all_keys / 2, rel=1e-6)
    assert parts["attention"] * rows == pytest.approx(proj + flops_qwen3_next.core_per_position(m, S) * rows, rel=1e-6)
    f_e, f_s, E = m["moe_intermediate_size"], m["shared_expert_intermediate_size"], m["num_experts"]
    swiglu = lambda u, w1, w3, w2: (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
    one = cost(swiglu, (rows, d), (d, f_e), (d, f_e), (f_e, d))
    assert 6 * rows * d * f_e <= one <= 1.2 * 6 * rows * d * f_e                  # the gate's elementwise work on top
    assert kernels_lm.grouped_swiglu(rows, d, f_e, 1, 1)["flops"] == 3 * 6 * rows * d * f_e
    held = m["num_experts_per_tok"] * m["experts_held"]["count"] / E
    assert parts["moe_experts"] == 4 * held * 6 * d * f_e and parts["moe_router"] == 4 * 2 * d * E
    assert parts["moe_shared"] == 4 * (6 * d * f_s + 2 * d) and parts["lm_head"] == 2 * d * m["vocab_size"]


def test_recorded_count_is_what_the_module_gives_for_the_program_file():
    cfg = cells.load("configs", CONFIG)
    model = cells.program_config(cfg)["model"]
    got = flops_qwen3_next.required_per_frame(model, cfg["as_run"]["learner"]["unroll_len"])
    assert got["step"] == cfg["required_flops_per_frame"] == pytest.approx(1.3808e9, rel=1e-4)
    parts = flops_qwen3_next.forward_parts(model, 8192)
    share = lambda *names: sum(parts[n] for n in names) / got["forward"]
    # the Gated DeltaNet layers are 46% of the forward FLOPs, attention 26%, the four expert blocks 11%, the head 17%
    assert share("gdn_proj", "gdn_scan") == pytest.approx(0.46, abs=0.005)
    assert share("attention") == pytest.approx(0.264, abs=0.005) and share("lm_head") == pytest.approx(0.169, abs=0.005)
    assert share("moe_router", "moe_shared", "moe_experts") == pytest.approx(0.107, abs=0.005)
    assert parts["gdn_proj"] == 3 * 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) and parts["gdn_scan"] == 3 * 6 * 128 * 128 * 32
    assert parts["attention"] == 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) + 4 * 16 * 256 * 4096
    assert parts["moe_experts"] == 4 * 0.625 * 6 * 2048 * 512 and parts["lm_head"] == 2 * 2048 * 18992


# -------------------------------------------------- the configuration file
def test_configuration_holds_every_published_number_and_lists_what_it_cut():
    cfg = cells.load("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert set(cfg["reduced_why"]) == set(cfg["reduced"]) and len(cfg["source"]) <= 200
    run_model = cfg["as_run"]["model"]
    # no width among the cuts: what the program runs is what was published
    for key in ("hidden_size", "full_attention_interval", "linear_num_key_heads", "linear_key_head_dim",
                "linear_num_value_heads", "linear_value_head_dim", "linear_conv_kernel_dim", "num_attention_heads",
                "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta", "num_experts_per_tok",
                "moe_intermediate_size", "shared_expert_intermediate_size", "norm_topk_prob", "rms_norm_eps"):
        assert run_model[key] == cfg[key], key
    assert run_model["num_experts"] == cfg["num_experts_routed_over"] == 512   # the router's width
    assert run_model["experts_held"] == {"offset": 0, "count": cfg["num_experts"]}
    assert run_model["num_hidden_layers"] == cfg["num_hidden_layers"] == len(cfg["layers_held"])
    assert cfg["layers_held"] == list(range(4)) and run_model["model_type"] == cfg["model_type"] == "qwen3_next"
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0           # whole periods
    assert {"mtp", "weights", "A_log_and_dt_bias", "fused_projection_order", "auxiliary_loss", "router", "loss",
            "parameters"} <= set(cfg["assumed"])
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert cfg["parameters"]["state_bytes"] == 16 * cfg["parameters"]["total"] >= 0.25 * 16e9
    by_part = cfg["parameters"]["by_part"]
    assert cfg["parameters"]["total"] == 3 * by_part["gated delta net, a layer"] + by_part["gated attention, a layer"] + \
        4 * (by_part["norms, router and gated shared expert, a layer"] + by_part["32 held experts, a layer"]) + \
        by_part["embedding"] + by_part["lm_head"] + by_part["final_norm"]
    assert cfg["tiny"]["model"]["hidden_size"] == cfg["hidden_size"] and "tiny_why" in cfg
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if '"Qwen3-Next-80B-A3B-Instruct"' in ln)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if k not in cfg or cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"])


def test_the_programs_parameter_count_is_the_files():
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import Qwen3Next, default_qwen3_next_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", CONFIG)
    model = Qwen3Next(deep_merge_dicts(default_qwen3_next_config(), cells.program_config(cfg)["model"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 512), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    p, by_part = shapes["params"], cfg["parameters"]["by_part"]
    assert count(p) == cfg["parameters"]["total"] == 625667136
    experts = sum(count(p["layer_0"]["moe"][n]) for n in ("w1", "w2", "w3"))
    assert {"gated delta net, a layer": count(p["layer_0"]["gdn"]), "gated attention, a layer": count(p["layer_3"]["attention"]),
            "norms, router and gated shared expert, a layer": count(p["layer_0"]["moe"]) - experts + count(p["layer_0"]["operator_norm"]),
            "32 held experts, a layer": experts, "embedding": count(p["embedding"]), "lm_head": count(p["lm_head"]),
            "final_norm": count(p["final_norm"])} == by_part
    assert p["layer_1"]["moe"]["router"].shape == (2048, 512) and p["layer_1"]["moe"]["w1"].shape == (32, 2048, 512)
    assert p["layer_1"]["moe"]["shared_w2"].shape == (512, 2048) and p["layer_1"]["moe"]["shared_gate"].shape == (2048,)
    assert p["layer_0"]["gdn"]["in_proj_qkvz"]["kernel"].shape == (2048, 12288)
    assert p["layer_0"]["gdn"]["conv_kernel"].shape == (4, 8192) and p["layer_0"]["gdn"]["A_log"].shape == (32,)
    assert p["layer_3"]["attention"]["q_proj"]["kernel"].shape == (2048, 16 * 512)
    assert p["layer_3"]["attention"]["k_proj"]["kernel"].shape == (2048, 2 * 256)


# ------------------------------------------------------------- the manifest
def test_the_manifest_gained_the_cell_and_its_metrics_and_lost_nothing():
    cell, kimi = cells.load("workloads", CELL), cells.load("workloads", "kimi_vl_train_b2s8k")
    assert cell["per_layer"][:23] == kimi["per_layer"][:23] and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(NEW_METRICS) <= set(cell["per_layer"]) and "mfu_pct" in cell["per_layer"]
    setup = {n[:-5] for n in os.listdir(os.path.join(REPO, "benchmark", "layer_metrics")) if n.startswith("setup_")}
    assert len(setup) >= 13 and setup <= set(cell["per_layer"])                     # a new cell lists them itself
    assert {"lm_attention_ms", "lm_moe_shared_ms", "lm_moe_route_ms", "lm_moe_experts_ms"} <= set(cell["per_layer"])
    assert not {"lm_short_conv_ms", "lm_ssm_scan_ms", "lm_mla_core_ms", "lm_dense_mlp_ms", "moe_experts_roofline_pct",
                "ssm_scan_roofline_pct"} & set(cell["per_layer"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    by_name = lambda group: {e["name"]: e for e in m[group]}
    assert by_name("workloads")[CELL] == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
                                          "why": cell["why"]}
    mine = by_name("configs")[CONFIG]
    assert mine["reduced"] == cells.load("configs", CONFIG)["reduced"] and mine["file"] == f"benchmark/configs/{CONFIG}.json"
    assert mine["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    for name in NEW_METRICS:
        e = by_name("per_layer")[name]
        assert CELL in e["workloads"] and e["moves"] == "train_frames_per_s" and e["layer"] == "Jitted step", name
        assert not set(e["workloads"]) & {"lfm2_train_b4s8k", "nemotron_twotower_train_b2s8k", "kimi_vl_train_b2s8k"}
    assert {e["name"] for e in m["per_layer"] if CELL in e["workloads"]} == set(cell["per_layer"])
    # every data file has its entry and what was there is there: supersets, so that a later cell breaks nothing here
    assert set(by_name("workloads")) >= set(cells.names("workloads")) >= {
        "sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64", "lfm2_train_b4s8k", "nemotron_twotower_train_b2s8k",
        "kimi_vl_train_b2s8k", CELL}
    assert {"ssm_scan_roofline_pct", "moe_experts_roofline_pct", "mla_core_roofline_pct", "mfu_pct", "lm_attention_ms",
            "setup_first_step_s"} <= set(by_name("per_layer"))
    assert {"setup_s", "train_frames_per_s"} <= set(by_name("end_to_end"))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= len(m["workloads"]) // 4 or sum(w["chips"] == 4 for w in m["workloads"]) == 1
    from benchmark.tools import manifest

    assert manifest.build(m) == m


# ------------------------------------------------ scopes and roofline shares
def op(start, end, scope):
    return Op("x", float(start), float(end), scope, "")


def test_the_new_scopes_partition_a_step_with_the_old_ones():
    head = "jit(lm_train_step)/jvp(Qwen3Next)/"
    back = "jit(lm_train_step)/transpose(jvp(Qwen3Next))/"
    ops = [op(0, 10, head + "layer_0/checkpoint/gdn_proj/operator_norm/mul"),
           op(10, 40, head + "layer_0/checkpoint/gdn/gdn_proj/in_proj_qkvz/dot_general"),
           op(40, 100, head + "layer_0/checkpoint/gdn/gdn_scan/while/body/checkpoint/dot_general"),
           op(100, 110, head + "layer_0/checkpoint/gdn/gdn_proj/out_proj/dot_general"),
           op(110, 130, head + "layer_3/checkpoint/attention/attention/q_proj/dot_general"),
           op(130, 150, head + "layer_1/checkpoint/moe/moe_shared/dot_general"),
           op(150, 190, back + "layer_1/rematted_computation/gdn/gdn_scan/while/body/dot_general"),
           op(190, 260, back + "layer_1/gdn/gdn_scan/while/body/transpose/dot_general"),
           op(260, 270, back + "layer_1/gdn/gdn_proj/in_proj_ba/dot_general"),
           op(270, 280, "")]
    got = trace_scope_lm.self_times(ops, 0.0, 300.0)
    assert got == {("gdn_proj", "forward"): 50.0, ("gdn_scan", "forward"): 60.0, ("attention", "forward"): 20.0,
                   ("moe_shared", "forward"): 20.0, ("gdn_scan", "recompute"): 40.0, ("gdn_scan", "backward"): 70.0,
                   ("gdn_proj", "backward"): 10.0, ("unnamed", "forward"): 10.0}
    # the cell's scope metrics cover every scope this model has once: over one step they sum to all of it
    step = {**got, ("loss", "forward"): 5.0, ("optimizer", "forward"): 7.0, ("embed", "forward"): 1.0,
            ("lm_head", "backward"): 6.0, ("moe_router", "forward"): 2.0, ("moe_dispatch", "forward"): 1.5,
            ("moe_combine", "backward"): 2.5, ("moe_experts", "backward"): 4.0, ("diagnostics", "forward"): 3.0}
    files = [cells.load("layer_metrics", n) for n in cells.load("workloads", CELL)["per_layer"]]
    scope_files = [m for m in files if m["reader"] == "trace_scope_lm" and "passes" not in m["params"]]
    import unittest.mock as mock

    with mock.patch.object(trace_scope_lm, "steps_of", lambda result: [step]):
        total = sum(trace_scope_lm.read(None, scale=1.0, **m["params"]) for m in scope_files)
        assert total == pytest.approx(sum(step.values()))
        rule = cells.load("layer_metrics", "gdn_scan_roofline_pct")["params"]
        # the roofline share's time: the rule without the layer's and the head groups' replays
        assert trace_scope_lm.read(None, scale=1.0, scopes=rule["scopes"], passes=rule["passes"]) == 130.0
    covered = [s for m in scope_files for s in m["params"].get("scopes", [])]
    assert len(covered) == len(set(covered))                                      # no scope is counted twice
    from distar_tpu import obs

    mine = {"embed", "attention", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared", "lm_head",
            "loss", "optimizer", "diagnostics", "gdn_proj", "gdn_scan"}
    assert mine <= set(obs.LM_STEP_SCOPES) and mine <= set(covered)
    # what the cell's files do not cover are parts only other models have
    assert not (set(covered) - set(obs.LM_STEP_SCOPES) - {"unnamed"})


def test_the_steps_scopes_on_the_lowered_program_are_the_ones_the_cell_covers():
    """The tiny preset's lowered ``lm_train_step`` carries ``gdn_proj``,
    ``gdn_scan`` and ``attention`` and none of the other mixers' names."""
    import jax
    import jax.numpy as jnp
    import optax

    from distar_tpu.learner.lm_learner import make_lm_train_step
    from distar_tpu.model import Qwen3Next, default_qwen3_next_config
    from distar_tpu.obs import LM_STEP_SCOPES, tree_spec
    from distar_tpu.utils import deep_merge_dicts

    tiny = dict(cells.load("configs", CONFIG)["tiny"]["model"], hidden_size=64)
    model = Qwen3Next(deep_merge_dicts(default_qwen3_next_config(), tiny))
    tokens = jnp.zeros((2, 32), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    optimizer = optax.adam(1e-3)
    step = jax.jit(make_lm_train_step(model, optimizer, dynamics=tree_spec({}, {"type": "none"})))
    text = step.lower(variables, jax.eval_shape(optimizer.init, variables["params"]),
                      {"tokens": tokens, "labels": tokens}).as_text(debug_info=True)
    there = {name for name in LM_STEP_SCOPES if f"/{name}" in text or f"({name})" in text}
    assert {"gdn_proj", "gdn_scan", "attention", "moe_shared", "moe_router", "moe_experts", "embed", "lm_head", "loss",
            "optimizer"} <= there
    assert not there & {"short_conv", "dense_mlp", "ssm_proj", "ssm_scan", "mla_proj", "mla_core"}


def test_rule_roofline_is_required_time_over_scope_time_at_the_published_sizes(monkeypatch):
    params = cells.load("layer_metrics", "gdn_scan_roofline_pct")["params"]
    shape = params["shape"]
    assert (shape["key_heads"], shape["value_heads"], shape["key_dim"], shape["value_dim"], shape["layers"]) == (16, 32, 128, 128, 3)
    need = kernels_gdn.delta_rule(**shape)
    assert need["flops"] == 16384 * 3 * 3 * 6 * 128 * 128 * 32 == pytest.approx(463.9e9, rel=1e-3)
    assert need["bytes"] == 16384 * 3 * (2 * (6 * 2048 + 5 * 4096) + 4 * 6 * 32) == pytest.approx(3.259e9, rel=1e-3)
    assert need["bytes"] / 819e9 > need["flops"] / 197e12                                       # bound by its bytes
    assert need["bytes"] / 819e9 == pytest.approx(3.98e-3, rel=1e-2)
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 80.0)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline_gdn.read(result, **params)
    assert share == pytest.approx(100.0 * (need["bytes"] / 819e9) / 0.080) and 4.9 < share < 5.1
    # a chunked form that spends more products in more time does not raise the required work
    assert kernels_gdn.delta_rule(**dict(shape, layers=6))["flops"] == 2 * need["flops"]
    # nothing to read without a trace, off the chip, or from a program without the scope (the parent commit)
    assert kernel_roofline_gdn.read({"device": {"platform": "cpu", "kind": "cpu"}}, **params) is None
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: None)
    assert kernel_roofline_gdn.read(result, **params) is None


def test_expert_roofline_reads_the_existing_reader_at_this_cells_shape(monkeypatch):
    metric = cells.load("layer_metrics", "qwen_moe_experts_roofline_pct")
    params = metric["params"]
    assert metric["reader"] == "kernel_roofline" and params["kernel"] == "grouped_swiglu"
    assert params["shape"] == {"d": 2048, "width": 512, "experts": 32, "layers": 4, "bytes_per_value": 2}
    rows = 4 * 10240.0                                                             # the expected rows of a step
    need = kernels_lm.grouped_swiglu(rows, **params["shape"])
    assert need["flops"] == 18.0 * rows * 2048 * 512
    # 320 rows an expert: the two bounds meet (3.92 ms of products, 3.97 ms of bytes, most of them the matrices')
    assert need["flops"] / 197e12 == pytest.approx(need["bytes"] / 819e9, rel=0.05)
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 20.0)
    from benchmark.readers import histogram_window

    monkeypatch.setattr(histogram_window, "read", lambda result, **kw: rows)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline.read(result, **params)
    assert share == pytest.approx(100.0 * (need["bytes"] / 819e9) / 0.020) and 18 < share < 22

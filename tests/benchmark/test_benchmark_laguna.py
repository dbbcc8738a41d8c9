"""The ``laguna`` cell (Laguna-S-2.1's language model): its rehearsal end to
end beside its plain reference, ``correct`` turning false for a dropped
update, for a term the run leaves out, and for the stored reading of each
omission and of the float8 control at the published widths, its traffic, its
FLOP and kernel counts against ``cost_analysis()`` of the plain products and a
hand count, its scopes, its configuration against the published one, the
gradient check at the tiny preset, and the manifest. Nothing here is pinned to
a place in a list, to a count of cells or to the whole of ``LM_STEP_SCOPES``: a
later PR's files and scopes only append, and these tests ask "contains" and
"is a superset"."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, check, flops_laguna, kernels_lm, kernels_mla, kernels_swa, run  # noqa: E402
from benchmark.gen import lm_pool  # noqa: E402
from benchmark.readers import kernel_roofline, kernel_roofline_mla, kernel_roofline_swa, trace_scope_lm  # noqa: E402
from benchmark.trace_meta import Op  # noqa: E402

CELL = "laguna_train_b1s16k"
CONFIG = "laguna_s_118b_ep32_tp2_l5"
TRAFFIC = "lm_zipf_pool4_b1s16384_v12544"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("lm_attn_proj_ms", "lm_attn_core_ms", "lm_swa_core_ms", "swa_core_roofline_pct", "attn_core_roofline_pct",
               "laguna_moe_experts_roofline_pct")
CONTROLS = os.path.join(REPO, "tests", "benchmark", "data", "laguna_controls.json")
# the terms the cell's limits are held to (the reference's ``window_minus_1`` is the off-by-one that a tolerance on
# the chip cannot see: tests/test_laguna.py holds the program to it exactly)
MUST_FAIL = ("window", "window_1024", "yarn", "yarn_factor", "rope_whole", "thetas", "gate", "scaling", "shared")


def rehearse(capsys, trace=0, seed="3800000029"):
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
    assert reference["seed"] == 3800000029 and first["moe_overflow_rows"] == 0.0
    # the tiny preset: a full layer with the dense feed-forward, a sliding and a full layer with 4 held experts each
    assert len([k for k in first if k.startswith("moe_rows/")]) == 8
    assert len([k for k in first if k.startswith(("residual_rms/", "mixer_rms/", "ff_rms/", "attn_gate_mean/"))]) == 12
    assert sorted(k for k in first if k.startswith("moe_rows_sum/")) == ["moe_rows_sum/layer_1", "moe_rows_sum/layer_2"]
    assert {"dyn/grad_norm/embedding", "dyn/grad_norm/lm_head", "dyn/grad_norm/layer_0", "dyn/grad_norm/layer_2",
            "dyn/grad_norm/final_norm"} <= set(first)
    steps = json.load(open(tmp_path / (CELL + "_rehearsal") / "steps.json"))
    log = steps["scalars"][0]
    assert log["moe_overflow_rows"] == 0.0 and log["moe_rows/layer_1/expert_1"] == first["moe_rows/layer_1/expert_1"]
    for key in ("mixer_rms/layer_0", "mixer_rms/layer_1", "ff_rms/layer_0", "ff_rms/layer_2", "attn_gate_mean/layer_0",
                "attn_gate_mean/layer_1"):
        assert log[key] == pytest.approx(first[key], rel=1e-4), key
    assert 0.3 < log["attn_gate_mean/layer_1"] < 0.7


# ------------------------------------------------- a term left out of the run
def _no_window(monkeypatch):
    from distar_tpu.ops import sequence

    whole = sequence.causal_attention
    monkeypatch.setattr(sequence, "causal_attention", lambda q, k, v, scale, window=None: whole(q, k, v, scale))


def _no_gate(monkeypatch):
    from distar_tpu.ops import sequence

    whole = sequence.open_gate
    monkeypatch.setattr(sequence, "open_gate", lambda out, gate: (out, whole(out, gate)[1]))


def _no_yarn_factor(monkeypatch):
    from distar_tpu.ops import sequence

    whole = sequence.yarn_first
    monkeypatch.setattr(sequence, "yarn_first", lambda x, attention_factor, **kw: whole(x, attention_factor=1.0, **kw))


# the fault, and the components of which at least one sees it (layer 1 is the tiny preset's sliding layer)
FAULTS = {"no_window": (_no_window, ("mixer_rms/layer_1",)), "no_gate": (_no_gate, ("mixer_rms/layer_0", "mixer_rms/layer_1")),
          "no_yarn_factor": (_no_yarn_factor, ("mixer_rms/layer_0", "mixer_rms/layer_2"))}


@pytest.mark.parametrize("fault", FAULTS)
def test_correct_turns_false_when_the_run_leaves_a_term_out(capsys, tmp_path, monkeypatch, fault):
    """The fault is put into the program in this process only: the reference
    is a process of its own and computes the whole model. The run still
    trains; what fails is the first step against the reference, at the cell's
    own limits."""
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    inject, seen_by = FAULTS[fault]
    inject(monkeypatch)
    line, notes, out = rehearse(capsys, seed="3800000037")
    assert line["correct"] is False and not notes["checks"]["first_step_matches_reference"]
    assert notes["checks"]["loss_went_down"] and notes["checks"]["params_changed"]
    assert notes["checks"]["ran_to_its_end"] and line["failed"] == 0
    off = next(ln for ln in out.split("\n") if ln.startswith("benchmark: first step against the reference"))
    assert any(name in off for name in seen_by), off


def test_correct_turns_false_when_the_update_is_dropped(capsys, tmp_path, monkeypatch):
    import optax

    monkeypatch.setattr(run, "OUT", str(tmp_path))
    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    line, notes, _ = rehearse(capsys, seed="3800000041")
    assert line["correct"] is False
    assert not notes["checks"]["loss_went_down"] and not notes["checks"]["params_changed"]
    assert notes["checks"]["first_step_matches_reference"] and line["failed"] == 0


def limits():
    return {k: v for k, v in cells.load("workloads", CELL)["correct"]["reference"].items() if k in ("keys", "rtol", "rtol_of")}


@pytest.mark.parametrize("omission", MUST_FAIL)
def test_the_stored_reading_of_each_omission_fails_the_cells_limits(omission):
    """What the reference without the term reports at the published widths on
    the cell's own traffic (a CPU run of ``laguna_plain.first_step(without=)``,
    kept in ``tests/benchmark/data``) is not ``correct`` by the limits in the
    cell's file."""
    stored = json.load(open(CONTROLS))
    off = check.off_reference(stored["without"][omission], stored["reference"], **limits())
    assert off, omission
    seen = {"window": "mixer_rms/layer_1", "window_1024": "mixer_rms/layer_2", "yarn": "mixer_rms/layer_0",
            "yarn_factor": "mixer_rms/layer_4", "rope_whole": "mixer_rms/layer_0", "thetas": "mixer_rms/layer_",
            "gate": "mixer_rms/layer_", "scaling": "ff_rms/layer_", "shared": "ff_rms/layer_"}[omission]
    assert any(seen in line for line in off), off


def test_the_stored_float8_control_fails_and_the_reference_itself_passes():
    stored = json.load(open(CONTROLS))
    assert check.off_reference(stored["reference"], stored["reference"], **limits()) == []
    off = check.off_reference(stored["float8_e4m3fn"], stored["reference"], **limits())
    # the precision below the configuration's fails by some of the limits, and not by each
    assert any("mixer_rms/" in line for line in off) and any("residual_rms/" in line for line in off)
    assert any("total_loss" in line for line in off)
    compared = [k for k in stored["reference"] if any(part in k for part in limits()["keys"])]
    assert 3 <= len(off) < len(compared)
    assert set(stored["without"]) >= set(MUST_FAIL) and stored["seed"] > 2 ** 31


def test_every_compared_component_has_a_limit_of_its_own_between_its_readings():
    """Each limit stands above what a dropped rounding does and below what the
    float8 control or an omission does to that component: none is the
    catch-all ``rtol``, but for the gradient norms of the rehearsal."""
    ref = cells.load("workloads", CELL)["correct"]["reference"]
    stored = json.load(open(CONTROLS))
    compared = [k for k in stored["reference"] if any(part in k for part in ref["keys"]) and stored["reference"][k] != 0]
    assert {k for k in compared if k not in ref["rtol_of"]} == set()
    assert ref["rtol_of"]["total_loss"] <= 1e-4 and all(ref["rtol_of"][k] <= 5e-3 for k in compared if "rms" in k)
    assert "moe_overflow_rows" in ref["keys"] and stored["reference"]["moe_overflow_rows"] == 0.0   # exactly 0


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
    assert gradients_on_chip.main(["--workload", CELL, "--seed", "3800000031", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    # three attention layers of 1 + 7 leaves, a dense block 1 + 3, two expert blocks of 8, the three ends
    assert out["ok"] and out["positions"] == 64 and len(out["program"]["gradient_off_by_leaf"]) == 3 * 8 + 4 + 2 * 8 + 3
    assert out["program"]["correct"] and out["program"]["gradient_off_largest"] < 1e-3  # float32 both, at this size
    assert {"layer_0/attn/g_proj/kernel", "layer_1/attn/q_norm/scale", "layer_1/attn/q_proj/kernel", "layer_2/attn/o_proj/kernel",
            "layer_0/dense_mlp/w1/kernel", "layer_1/moe/router", "layer_2/moe/shared_w2", "lm_head"} <= set(
                out["program"]["gradient_off_by_leaf"])
    control = out["control"]
    assert not control["correct"] and control["first_step_off"] and control["gradient_leaves_off"]
    assert control["gradient_off_largest"] > 1.5 * out["gradients_rtol"]


# ------------------------------------------------------------- the traffic
def test_traffic_is_the_qwen_cells_at_one_sequence_of_twice_the_length_over_this_slice():
    mine, theirs = (cells.load("traffic", n)["params"] for n in (TRAFFIC, "lm_zipf_pool4_b2s8192_v18992"))
    assert {k: v for k, v in mine.items() if mine[k] != theirs[k]} == {"batch_size": 1, "unroll_len": 16384, "vocab_size": 12544}
    assert mine == {"pool": 4, "batch_size": 1, "unroll_len": 16384, "vocab_size": 12544, "zipf_exponent": 1.0,
                    "doc_len_median": 600, "doc_len_sigma": 1.2}
    assert cells.load("traffic", TRAFFIC)["generator"] == "lm_pool"
    cfg = cells.load("configs", CONFIG)
    assert mine["vocab_size"] == cfg["vocab_size"] == 100352 // 8
    assert mine["unroll_len"] == cfg["as_run"]["learner"]["unroll_len"]
    assert mine["batch_size"] == cfg["as_run"]["learner"]["batch_size"]
    pool = lm_pool.build(2 ** 31 + 23, dict(mine, pool=2))
    again = lm_pool.build(2 ** 31 + 23, dict(mine, pool=2))
    assert len(pool) == 2 and pool[0]["tokens"].shape == (1, 16384) and pool[0]["tokens"].dtype == np.int32
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(pool, again) for k in a)
    ids = np.concatenate([b["tokens"].reshape(-1) for b in pool])
    assert ids.min() >= 0 and 10000 < ids.max() < 12544
    assert np.array_equal(pool[0]["tokens"][:, 1:], pool[0]["labels"][:, :-1])
    # documents end inside the sequence: the separator is there some twenty times in 16,384 positions
    assert 5 < (pool[0]["tokens"] == 12543).sum() < 80


# --------------------------------------------------------------- the counts
def test_the_band_is_counted_as_the_band():
    """A query sees ``window`` keys but for the first ``window - 1`` of a
    sequence: the average over the sequence by hand, and against the literal
    mask's count."""
    assert flops_laguna.band_keys(16384, 512) == 512 - 512 * 511 / (2 * 16384) == pytest.approx(504.02, abs=0.01)
    i, j = np.arange(300)[:, None], np.arange(300)[None, :]
    assert flops_laguna.band_keys(300, 37) == pytest.approx(((i - j >= 0) & (i - j < 37)).sum() / 300)
    assert flops_laguna.band_keys(64, 100) == flops_laguna.band_keys(64, 64) == pytest.approx(32.5)   # a window beyond the sequence: causal
    assert flops_laguna.core_per_position(36, 128, 512) == 4 * 36 * 128 * 512 == 9_437_184


def test_projection_core_and_expert_counts_are_the_plain_products():
    import jax
    import jax.numpy as jnp

    cfg = cells.load("configs", CONFIG)
    m = dict(cfg["tiny"]["model"], hidden_size=64)
    d, rows, D = m["hidden_size"], 64, m["head_dim"]
    cost = lambda fn, *shapes: jax.jit(fn).lower(*(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)).compile().cost_analysis()["flops"]
    parts = flops_laguna.forward_parts(m, 64)
    # the tiny preset: 2 | 3 | 2 query heads over 1 key/value head of 32
    proj = sum(cost(lambda u, q, k, v, g, y, o: (u @ q, u @ k, u @ v, u @ g, y @ o), (rows, d), (d, H * D), (d, D), (d, D), (d, H),
                    (rows, H * D), (H * D, d)) for H in (2, 3, 2))
    assert parts["attn_proj"] * rows == pytest.approx(proj, rel=1e-6)
    S = 64
    all_keys = cost(lambda q, k, v: jnp.einsum("hqk,hkd->hqd", jnp.einsum("hqd,hkd->hqk", q, k), v), *[(2, S, D)] * 3)
    assert parts["attn_core"] * S == pytest.approx(2 * all_keys / 2, rel=1e-6)          # two full layers, half the keys
    assert parts["swa_core"] == 4 * 3 * D * flops_laguna.band_keys(S, 16)                # one sliding layer of 3 heads
    f, f_e, f_s, E = m["intermediate_size"], m["moe_intermediate_size"], m["shared_expert_intermediate_size"], m["num_experts"]
    swiglu = lambda u, w1, w3, w2: (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
    one = cost(swiglu, (rows, d), (d, f_e), (d, f_e), (f_e, d))
    assert 6 * rows * d * f_e <= one <= 1.2 * 6 * rows * d * f_e                          # the gate's elementwise work on top
    assert kernels_lm.grouped_swiglu(rows, d, f_e, 1, 1)["flops"] == 3 * 6 * rows * d * f_e
    held = m["num_experts_per_tok"] * m["experts_held"]["count"] / E
    assert parts["dense_mlp"] == 6 * d * f and parts["moe_experts"] == 2 * held * 6 * d * f_e
    assert parts["moe_router"] == 2 * 2 * d * E and parts["moe_shared"] == 2 * 6 * d * f_s
    assert parts["lm_head"] == 2 * d * m["vocab_size"]


def test_recorded_count_is_what_the_module_gives_for_the_program_file_and_a_hand_count():
    cfg = cells.load("configs", CONFIG)
    model = cells.program_config(cfg)["model"]
    got = flops_laguna.required_per_frame(model, cfg["as_run"]["learner"]["unroll_len"])
    assert got["step"] == cfg["required_flops_per_frame"] == pytest.approx(2.7478e9, rel=1e-4)
    parts = flops_laguna.forward_parts(model, 16384)
    # by hand, at the heads held: two full layers of 24 query heads and three sliding of 36, each over 4 key/value heads
    full = 2 * 3072 * (24 * 128 * 2 + 2 * 4 * 128 + 24)
    sliding = 2 * 3072 * (36 * 128 * 2 + 2 * 4 * 128 + 36)
    assert parts["attn_proj"] == 2 * full + 3 * sliding == 277_782_528
    assert parts["attn_core"] == 2 * 4 * 24 * 128 * 8192 == 201_326_592
    assert parts["swa_core"] == pytest.approx(3 * 4 * 36 * 128 * 504.0156, rel=1e-6)
    assert parts["dense_mlp"] == 6 * 3072 * 12288 and parts["lm_head"] == 2 * 3072 * 12544
    assert parts["moe_router"] == 4 * 2 * 3072 * 256 and parts["moe_shared"] == 4 * 6 * 3072 * 1024
    assert parts["moe_experts"] == 4 * (10 * 8 / 256) * 6 * 3072 * 1024
    share = lambda *names: sum(parts[n] for n in names) / got["forward"]
    # attention 55% (projections 30%, the two full cores 22%, the three banded cores 3%), dense 25%, experts 12%, head 8%
    assert share("attn_proj") == pytest.approx(0.303, abs=0.005) and share("attn_core") == pytest.approx(0.220, abs=0.005)
    assert share("swa_core") == pytest.approx(0.030, abs=0.003) and share("dense_mlp") == pytest.approx(0.247, abs=0.005)
    assert share("moe_router", "moe_shared", "moe_experts") == pytest.approx(0.115, abs=0.005)
    assert share("lm_head") == pytest.approx(0.084, abs=0.005)
    # a program that masks the band without skipping would spend 36/24 of a full core in each sliding layer
    assert 3 * 1.5 * parts["attn_core"] / 2 == pytest.approx(453e6, rel=1e-3) and parts["swa_core"] < 28.3e6


# -------------------------------------------------- the configuration file
def test_configuration_holds_every_published_number_and_lists_what_it_cut():
    cfg = cells.load("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "num_key_value_heads", "num_attention_heads_per_layer",
                              "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 5, "num_experts": 8, "num_key_value_heads": 4,
                                                    "num_attention_heads_per_layer": [24, 36, 36, 36, 24], "vocab_size": 12544}
    assert cfg["published"]["num_hidden_layers"] == 48 and cfg["published"]["num_experts"] == 256
    assert cfg["published"]["num_key_value_heads"] == 8 and cfg["published"]["vocab_size"] == 100352
    assert set(cfg["reduced_why"]) == set(cfg["reduced"]) and len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    run_model = cfg["as_run"]["model"]
    # no width among the cuts: what the program runs is what was published
    for key in ("hidden_size", "intermediate_size", "head_dim", "sliding_window", "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "norm_topk_prob", "rms_norm_eps", "moe_routed_scaling_factor", "gating"):
        assert run_model[key] == cfg[key], key
    for kind, turn in cfg["rope_parameters"].items():
        assert {k: v for k, v in run_model["rope_parameters"][kind].items()} == turn, kind
    # the program keeps the published counts and names its shares beside them
    assert run_model["num_experts"] == cfg["num_experts_routed_over"] == 256   # the router's width
    assert run_model["experts_held"] == {"offset": 0, "count": cfg["num_experts"]}
    assert run_model["num_key_value_heads"] == 8 and run_model["kv_heads_held"] == {"count": cfg["num_key_value_heads"]}
    assert run_model["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert [h // 8 * 4 for h in run_model["num_attention_heads_per_layer"]] == cfg["num_attention_heads_per_layer"]
    assert run_model["layer_types"] == cfg["layer_types"][:5] and run_model["mlp_layer_types"] == cfg["mlp_layer_types"][:5]
    assert len(run_model["layer_types"]) == cfg["num_hidden_layers"] == len(cfg["layers_held"]) and cfg["layers_held"] == list(range(5))
    # the leading dense layer once, then a whole period and the four layers the floor asks for
    assert run_model["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 and run_model["layer_types"][1:].count("sliding_attention") == 3
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 100352                  # the floors
    assert {"router", "qk_norm", "shared_expert", "rope_pairs", "auxiliary_loss", "loss", "weights", "parameters"} <= set(cfg["assumed"])
    assert "thirty-two chips share each layer" in cfg["deployment"] and "2 ways" in cfg["deployment"]
    assert "one layer in five" in cfg["reduced_why"]["num_hidden_layers"]                # what the cut distorts is said
    assert cfg["parameters"]["state_bytes"] == 16 * cfg["parameters"]["total"] and 10.7e9 < cfg["parameters"]["state_bytes"] < 10.8e9
    assert cfg["tiny"]["model"]["hidden_size"] == cfg["hidden_size"] and "tiny_why" in cfg
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if '"Laguna-S-2.1"' in ln)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if k not in cfg or cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"])


def test_the_programs_parameter_count_is_the_files():
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import Laguna, default_laguna_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", CONFIG)
    model = Laguna(deep_merge_dicts(default_laguna_config(), cells.program_config(cfg)["model"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 512), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    p, by_part = shapes["params"], cfg["parameters"]["by_part"]
    assert count(p) == cfg["parameters"]["total"] == 672_127_232
    part = lambda start: next(v for k, v in by_part.items() if k.startswith(start))
    experts = sum(count(p["layer_1"]["moe"][n]) for n in ("w1", "w2", "w3"))
    assert part("full-attention mixer") == count(p["layer_0"]["attn"]) == count(p["layer_4"]["attn"])
    assert part("sliding-window mixer") == count(p["layer_1"]["attn"]) == count(p["layer_3"]["attn"])
    assert part("dense feed-forward") == count(p["layer_0"]["dense_mlp"]) and part("8 held experts") == experts
    assert part("router, shared expert") == count(p["layer_1"]["moe"]) - experts
    assert part("operator norm") == count(p["layer_2"]["operator_norm"]) == count(p["layer_0"]["ffn_norm"])
    assert (part("embedding"), part("lm_head"), part("final_norm")) == (count(p["embedding"]), count(p["lm_head"]), count(p["final_norm"]))
    # the parts sum to what the program holds
    assert cfg["parameters"]["total"] == 2 * part("full-attention mixer") + 3 * part("sliding-window mixer") + part("dense feed-forward") \
        + 4 * (part("8 held experts") + part("router, shared expert")) + 6 * part("operator norm") + part("embedding") \
        + part("lm_head") + part("final_norm")
    assert p["layer_1"]["moe"]["router"].shape == (3072, 256) and p["layer_1"]["moe"]["w1"].shape == (8, 3072, 1024)
    assert p["layer_1"]["attn"]["q_proj"]["kernel"].shape == (3072, 36 * 128) and p["layer_0"]["attn"]["q_proj"]["kernel"].shape == (3072, 24 * 128)
    assert p["layer_1"]["attn"]["k_proj"]["kernel"].shape == (3072, 4 * 128) and p["layer_1"]["attn"]["g_proj"]["kernel"].shape == (3072, 36)
    assert p["layer_0"]["dense_mlp"]["w1"]["kernel"].shape == (3072, 12288)


# ------------------------------------------------------------- the manifest
def test_the_manifest_gained_the_cell_and_its_metrics_and_lost_nothing():
    cell, qwen = cells.load("workloads", CELL), cells.load("workloads", "qwen3_next_train_b2s8k")
    assert cell["per_layer"][:36] == qwen["per_layer"][:36] and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(NEW_METRICS) <= set(cell["per_layer"]) and "mfu_pct" in cell["per_layer"]
    setup = {n[:-5] for n in os.listdir(os.path.join(REPO, "benchmark", "layer_metrics")) if n.startswith("setup_")}
    assert len(setup) >= 13 and setup <= set(cell["per_layer"])                     # a new cell lists them itself
    assert {"lm_dense_mlp_ms", "lm_moe_shared_ms", "lm_moe_route_ms", "lm_moe_experts_ms", "lm_unnamed_ms"} <= set(cell["per_layer"])
    # the whole layer's name is no part of this model: its kernels and its projections are read apart
    assert not {"lm_attention_ms", "lm_short_conv_ms", "lm_ssm_scan_ms", "lm_mla_core_ms", "lm_gdn_scan_ms", "moe_experts_roofline_pct",
                "mla_core_roofline_pct"} & set(cell["per_layer"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    by_name = lambda group: {e["name"]: e for e in m[group]}
    assert by_name("workloads")[CELL] == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
                                          "why": cell["why"]}
    mine = by_name("configs")[CONFIG]
    assert mine["reduced"] == cells.load("configs", CONFIG)["reduced"] and mine["file"] == f"benchmark/configs/{CONFIG}.json"
    assert mine["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    for name in NEW_METRICS:
        e = by_name("per_layer")[name]
        assert CELL in e["workloads"] and e["moves"] == "train_frames_per_s" and e["layer"] == "Jitted step", name
        assert not set(e["workloads"]) & {"lfm2_train_b4s8k", "nemotron_twotower_train_b2s8k", "kimi_vl_train_b2s8k",
                                          "qwen3_next_train_b2s8k"}
        assert e["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert {e["name"] for e in m["per_layer"] if CELL in e["workloads"]} == set(cell["per_layer"])
    # every data file has its entry and what was there is there: supersets, so that a later cell breaks nothing here
    assert set(by_name("workloads")) >= set(cells.names("workloads")) >= {
        "sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64", "lfm2_train_b4s8k", "nemotron_twotower_train_b2s8k",
        "kimi_vl_train_b2s8k", "qwen3_next_train_b2s8k", CELL}
    assert {"ssm_scan_roofline_pct", "moe_experts_roofline_pct", "mla_core_roofline_pct", "gdn_scan_roofline_pct", "mfu_pct",
            "lm_attention_ms", "setup_first_step_s"} <= set(by_name("per_layer"))
    assert {"setup_s", "train_frames_per_s"} <= set(by_name("end_to_end"))
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(len(m["workloads"]) // 4, 1)
    from benchmark.tools import manifest

    assert manifest.build(m) == m


# ------------------------------------------------ scopes and roofline shares
def op(start, end, scope):
    return Op("x", float(start), float(end), scope, "")


def test_the_new_scopes_partition_a_step_with_the_old_ones():
    head = "jit(lm_train_step)/jvp(Laguna)/"
    back = "jit(lm_train_step)/transpose(jvp(Laguna))/"
    ops = [op(0, 40, head + "layer_0/checkpoint/attn_proj/operator_norm/mul"),
           op(40, 100, head + "layer_0/checkpoint/attn/attn_core/cond/branch_0_fun/jit(flash_attention)/pallas_call"),
           op(100, 110, head + "layer_0/checkpoint/attn/attn_proj/o_proj/dot_general"),
           op(110, 130, head + "layer_1/checkpoint/attn/swa_core/cond/branch_0_fun/vmap(jit(_splash_attention))/pallas_call"),
           op(130, 150, head + "layer_1/checkpoint/moe/moe_shared/dot_general"),
           op(150, 190, back + "layer_1/rematted_computation/attn/swa_core/cond/branch_0_fun/pallas_call"),
           op(190, 260, back + "layer_1/attn/swa_core/cond/branch_0_fun/transpose/pallas_call"),
           op(260, 270, back + "layer_1/attn/attn_proj/g_proj/dot_general"),
           op(270, 280, "")]
    got = trace_scope_lm.self_times(ops, 0.0, 300.0)
    assert got == {("attn_proj", "forward"): 50.0, ("attn_core", "forward"): 60.0, ("swa_core", "forward"): 20.0,
                   ("moe_shared", "forward"): 20.0, ("swa_core", "recompute"): 40.0, ("swa_core", "backward"): 70.0,
                   ("attn_proj", "backward"): 10.0, ("unnamed", "forward"): 10.0}
    # the cell's scope metrics cover every scope this model has once: over one step they sum to all of it
    step = {**got, ("loss", "forward"): 5.0, ("optimizer", "forward"): 7.0, ("embed", "forward"): 1.0,
            ("lm_head", "backward"): 6.0, ("moe_router", "forward"): 2.0, ("moe_dispatch", "forward"): 1.5,
            ("moe_combine", "backward"): 2.5, ("moe_experts", "backward"): 4.0, ("diagnostics", "forward"): 3.0,
            ("dense_mlp", "backward"): 9.0}
    files = [cells.load("layer_metrics", n) for n in cells.load("workloads", CELL)["per_layer"]]
    scope_files = [m for m in files if m["reader"] == "trace_scope_lm" and "passes" not in m["params"]]
    import unittest.mock as mock

    with mock.patch.object(trace_scope_lm, "steps_of", lambda result: [step]):
        total = sum(trace_scope_lm.read(None, scale=1.0, **m["params"]) for m in scope_files)
        assert total == pytest.approx(sum(step.values()))
        band = cells.load("layer_metrics", "swa_core_roofline_pct")["params"]
        # the roofline share's time: the banded kernel without the layer's replay
        assert trace_scope_lm.read(None, scale=1.0, scopes=band["scopes"], passes=band["passes"]) == 90.0
    covered = [s for m in scope_files for s in m["params"].get("scopes", [])]
    assert len(covered) == len(set(covered))                                      # no scope is counted twice
    from distar_tpu import obs

    mine = {"embed", "attn_proj", "attn_core", "swa_core", "dense_mlp", "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "moe_shared", "lm_head", "loss", "optimizer", "diagnostics"}
    assert mine <= set(obs.LM_STEP_SCOPES) and mine <= set(covered) and "attention" not in covered
    assert not (set(covered) - set(obs.LM_STEP_SCOPES) - {"unnamed"})


def test_a_model_whose_layer_is_under_attention_as_a_whole_is_still_read_by_that_name():
    """The kernel's and the projections' names stay on the path of ``lfm2``,
    ``nemotron_h`` and ``qwen3_next``; the reader gives an operation to the
    first name on its path, so ``lm_attention_ms`` reads what it read."""
    path = "jit(lm_train_step)/jvp(LFM2)/layer_1/checkpoint/attention/attention/attn_core/cond/branch_0_fun/pallas_call"
    got = trace_scope_lm.self_times([op(0, 10, path), op(10, 15, path.replace("attn_core/cond/branch_0_fun/pallas_call",
                                                                              "attn_proj/q_proj/dot_general"))], 0.0, 20.0)
    assert got == {("attention", "forward"): 15.0}


def test_the_steps_scopes_on_the_lowered_program_are_the_ones_the_cell_covers():
    """The tiny preset's lowered ``lm_train_step`` carries ``attn_proj``,
    ``attn_core``, ``swa_core`` and ``dense_mlp`` and none of the other mixers' names."""
    import jax
    import jax.numpy as jnp
    import optax

    from distar_tpu.learner.lm_learner import make_lm_train_step
    from distar_tpu.model import Laguna, default_laguna_config
    from distar_tpu.obs import LM_STEP_SCOPES, tree_spec
    from distar_tpu.utils import deep_merge_dicts

    tiny = dict(cells.load("configs", CONFIG)["tiny"]["model"], hidden_size=64)
    model = Laguna(deep_merge_dicts(default_laguna_config(), tiny))
    tokens = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    optimizer = optax.adam(1e-3)
    step = jax.jit(make_lm_train_step(model, optimizer, dynamics=tree_spec({}, {"type": "none"})))
    text = step.lower(variables, jax.eval_shape(optimizer.init, variables["params"]),
                      {"tokens": tokens, "labels": tokens}).as_text(debug_info=True)
    there = {name for name in LM_STEP_SCOPES if f"/{name}" in text or f"({name})" in text}
    assert {"attn_proj", "attn_core", "swa_core", "dense_mlp", "moe_shared", "moe_router", "moe_experts", "embed", "lm_head", "loss",
            "optimizer"} <= there
    assert not there & {"attention", "short_conv", "ssm_proj", "ssm_scan", "mla_proj", "mla_core", "gdn_proj", "gdn_scan"}


def test_band_roofline_is_required_time_over_scope_time_at_the_published_sizes(monkeypatch):
    params = cells.load("layer_metrics", "swa_core_roofline_pct")["params"]
    shape = params["shape"]
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"], shape["window"], shape["layers"]) == (36, 4, 128, 512, 3)
    assert shape["positions"] == shape["seq_len"] == 16384
    need = kernels_swa.banded_core(**shape)
    band = 512 - 512 * 511 / 32768
    assert need["flops"] == pytest.approx(16384 * 3 * 3 * 4 * 36 * 128 * band) and need["flops"] == pytest.approx(1.370e12, rel=1e-3)
    assert need["bytes"] == 16384 * 3 * 2 * 6 * 128 * (36 + 4) == pytest.approx(3.02e9, rel=1e-3)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9                                       # bound by its products
    assert need["flops"] / 197e12 == pytest.approx(6.95e-3, rel=1e-2)
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 35.0)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline_swa.read(result, **params)
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.035) and 19 < share < 21
    # a kernel that computes every key block of the causal triangle does not raise the required work
    assert kernels_swa.banded_core(**dict(shape, window=16384))["flops"] == pytest.approx(need["flops"] * 8192.5 / band)
    assert kernels_swa.banded_core(**dict(shape, layers=6))["flops"] == 2 * need["flops"]
    # nothing to read without a trace, off the chip, or from a program without the scope (the parent commit)
    assert kernel_roofline_swa.read({"device": {"platform": "cpu", "kind": "cpu"}}, **params) is None
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: None)
    assert kernel_roofline_swa.read(result, **params) is None


def test_full_core_roofline_reads_the_latent_cores_function_at_this_cells_heads(monkeypatch):
    metric = cells.load("layer_metrics", "attn_core_roofline_pct")
    params = metric["params"]
    assert metric["reader"] == "kernel_roofline_mla" and params["kernel"] == "causal_core" and params["scopes"] == ["attn_core"]
    assert params["shape"] == {"positions": 16384, "seq_len": 16384, "heads": 24, "qk_dim": 128, "v_dim": 128, "layers": 2,
                               "bytes_per_value": 2}
    need = kernels_mla.causal_core(**params["shape"])
    assert need["flops"] == 16384 * 2 * 3 * 2 * 24 * 256 * 8192 == pytest.approx(9.896e12, rel=1e-3)
    assert need["flops"] / 197e12 == pytest.approx(50.2e-3, rel=1e-2) and need["flops"] / 197e12 > 10 * need["bytes"] / 819e9
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 140.0)
    share = kernel_roofline_mla.read({"device": {"platform": "tpu", "kind": "TPU v5 lite"}}, **params)
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.140) and 35 < share < 37


def test_expert_roofline_reads_the_existing_reader_at_this_cells_shape(monkeypatch):
    metric = cells.load("layer_metrics", "laguna_moe_experts_roofline_pct")
    params = metric["params"]
    assert metric["reader"] == "kernel_roofline" and params["kernel"] == "grouped_swiglu"
    assert params["shape"] == {"d": 3072, "width": 1024, "experts": 8, "layers": 4, "bytes_per_value": 2}
    rows = 4 * 5120.0                                                              # the expected rows of a step
    need = kernels_lm.grouped_swiglu(rows, **params["shape"])
    assert need["flops"] == 18.0 * rows * 3072 * 1024
    # 640 rows an expert: the products (5.9 ms) at twice the bytes (3.0 ms)
    assert need["flops"] / 197e12 == pytest.approx(5.89e-3, rel=1e-2) and need["bytes"] / 819e9 == pytest.approx(2.98e-3, rel=1e-2)
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 15.0)
    from benchmark.readers import histogram_window

    monkeypatch.setattr(histogram_window, "read", lambda result, **kw: rows)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline.read(result, **params)
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.015) and 38 < share < 41

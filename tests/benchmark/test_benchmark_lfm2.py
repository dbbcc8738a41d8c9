"""The token-sequence cell: its rehearsal end to end, its generator, its FLOP
count, its scope reader and roofline share, its configuration against the
published one, and ``correct`` turning false when the run's expert layer
parts from the reference's."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, flops, flops_lm, kernels_lm, run  # noqa: E402
from benchmark.gen import lm_pool  # noqa: E402
from benchmark.readers import kernel_roofline, trace_scope_lm  # noqa: E402
from benchmark.trace_meta import Op  # noqa: E402

CELL = "lfm2_train_b4s8k"
CONFIG = "lfm2_24b_a2b_ep8_l5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rehearse(capsys, trace=0):
    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "2.5",
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
    # what the registry and the hooks give exists on the CPU too; the trace's metrics do not
    assert {"moe_rows_here", "moe_load_max_over_mean", "data_wait_ms", "device_step_ms", "feed_put_ms",
            "loop_dispatch_ms", "retraces_in_window", "program_hbm_gb"} <= set(line["rehearsed"])
    assert not [n for n in line["rehearsed"] if n.startswith(("lm_", "idle_", "scope_"))]
    assert "moe_experts_roofline_pct" not in line["rehearsed"]
    reference = json.load(open(tmp_path / (CELL + "_rehearsal") / "reference.json"))
    first = reference["first_step"]
    assert reference["seed"] == 3000000019 and first["moe_overflow_rows"] == 0.0
    # loss, 4 expert layers x 4 held experts, 5 layers x 2 RMS, and at this size the gradient norms
    assert len([k for k in first if k.startswith("moe_rows/")]) == 16
    assert len([k for k in first if k.startswith(("residual_rms/", "ff_rms/"))]) == 10
    assert {"dyn/grad_norm/embedding", "dyn/grad_norm/layer_0", "dyn/grad_norm/layer_4",
            "dyn/grad_norm/final_norm"} <= set(first)
    steps = json.load(open(tmp_path / (CELL + "_rehearsal") / "steps.json"))
    assert steps["scalars"][0]["moe_overflow_rows"] == 0.0
    assert steps["scalars"][0]["moe_rows/layer_2/expert_1"] == first["moe_rows/layer_2/expert_1"]


def _drop_expert_0(monkeypatch):
    from distar_tpu.ops import moe

    whole = moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul",
                        lambda x, w, sizes: whole(x, w.at[0].set(0.0), sizes))


def _route_without_bias(monkeypatch):
    from distar_tpu.ops import moe

    whole = moe.route
    monkeypatch.setattr(moe, "route", lambda logits, bias, k, scaling=1.0: whole(logits, 0.0, k, scaling))


def _route_without_renormalisation(monkeypatch):
    import jax
    import jax.numpy as jnp

    from distar_tpu.ops import moe

    def route(logits, bias, k, scaling=1.0):
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        sel = jax.lax.top_k(s + bias, k)[1]
        return sel, scaling * jnp.take_along_axis(s, sel, axis=-1)

    monkeypatch.setattr(moe, "route", route)


@pytest.mark.parametrize("fault", [_drop_expert_0, _route_without_bias, _route_without_renormalisation],
                         ids=["dropped_expert", "no_bias", "no_renormalisation"])
def test_correct_turns_false_when_the_runs_expert_layer_parts_from_the_references(
        capsys, tmp_path, monkeypatch, fault):
    """In this process only: the reference is a process of its own and
    computes the whole layer. The run still trains (its loss falls, its
    parameters change); what fails is the first step against the reference,
    by the component that sees this fault."""
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    fault(monkeypatch)
    line, notes = rehearse(capsys)
    assert line["correct"] is False and not notes["checks"]["first_step_matches_reference"]
    assert notes["checks"]["loss_went_down"] and notes["checks"]["params_changed"]
    assert notes["checks"]["ran_to_its_end"] and line["failed"] == 0


def test_gradient_check_passes_the_program_and_fails_the_control_in_float8(capsys, tmp_path, monkeypatch):
    """``tools/gradients_on_chip`` at the tiny preset: the program's loss,
    statistics and every gradient leaf agree with the reference on one
    sequence, and the reference with its products' operands rounded to
    float8, fed through the same comparison at the cell's own limits, does
    not, by its first step and by its gradients."""
    from benchmark.tools import gradients_on_chip

    monkeypatch.setattr(gradients_on_chip, "ROOT", str(tmp_path))
    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.chdir(REPO)
    child = gradients_on_chip.subprocess.run
    monkeypatch.setattr(gradients_on_chip.subprocess, "run", lambda cmd, cwd, **kw: child(cmd, cwd=REPO, **kw))
    assert gradients_on_chip.main(["--workload", CELL, "--seed", "3000000023", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    assert out["ok"] and out["positions"] == 32 and len(out["program"]["gradient_off_by_leaf"]) >= 40
    assert out["program"]["correct"] and out["program"]["gradient_off_largest"] < 1e-4  # float32 both, at this size
    control = out["control"]
    assert not control["correct"] and control["first_step_off"] and control["gradient_leaves_off"]
    assert any(k.startswith("total_loss") for k in control["first_step_off"])
    assert control["gradient_off_largest"] > 2 * out["gradients_rtol"]
    # a backward kernel that is wrong in one place is one leaf off, and that is enough
    whole = gradients_on_chip.off_by_leaf
    monkeypatch.setattr(gradients_on_chip, "off_by_leaf", lambda got, want: whole(
        dict(got, **{"layer_1/attention/q_proj/kernel": 1.2 * got["layer_1/attention/q_proj/kernel"]}), want))
    assert gradients_on_chip.main(["--workload", CELL, "--seed", "3000000023", "--rehearse"]) == 1
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    assert out["program"]["gradient_leaves_off"] == ["layer_1/attention/q_proj/kernel"]


# ------------------------------------------------------------ the generator
PARAMS = dict(cells.load("traffic", "lm_zipf_pool4_b4s8192")["params"], pool=2, batch_size=2, unroll_len=4096)


def test_pool_repeats_from_a_seed_and_differs_across_seeds():
    a, b, c = (lm_pool.build(s, PARAMS) for s in (2 ** 31 + 11, 2 ** 31 + 11, 7))
    assert len(a) == 2 and all(set(x) == {"tokens", "labels"} for x in a)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) and x[k].dtype == np.int32 for k in x)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    assert a[0]["tokens"].shape == (2, 4096) and a[0]["tokens"].min() >= 0 and a[0]["tokens"].max() < 8192
    # labels are the next id
    assert np.array_equal(a[0]["tokens"][:, 1:], a[0]["labels"][:, :-1])
    served = lm_pool.cycle(a)
    first = [next(served) for _ in range(3)]
    assert first[0] is not a[0] and np.array_equal(first[2]["tokens"], a[0]["tokens"])


def test_ids_are_zipf_over_a_shuffled_vocabulary_with_packed_documents():
    big = dict(PARAMS, pool=1, batch_size=8, unroll_len=8192)
    ids = lm_pool.build(11, big)[0]["tokens"].reshape(-1)
    sep = 8191
    text = ids[ids != sep]
    counts = np.sort(np.bincount(text, minlength=8191))[::-1].astype(float)
    # p(rank) ~ 1/rank: the slope of log count over log rank, on the ranks that have enough draws
    slope = np.polyfit(np.log(np.arange(1, 101)), np.log(counts[:100]), 1)[0]
    assert -1.1 < slope < -0.9, slope
    assert counts[0] / len(text) == pytest.approx(1.0 / np.sum(1.0 / np.arange(1, 8191)), rel=0.1)
    # which id is frequent is the seed's choice
    other = lm_pool.build(12, big)[0]["tokens"].reshape(-1)
    assert np.bincount(text).argmax() != np.bincount(other[other != sep]).argmax()
    # documents: the separator comes about once in mean-length + 1 ids, none longer than a sequence
    gaps = np.diff(np.flatnonzero(ids == sep))
    assert 300 < np.median(gaps) < 1000 and gaps.max() <= 8192 + 1
    # a model with another vocabulary gets ids of its own slice
    tiny = lm_pool.build(3, dict(PARAMS, unroll_len=64), model_cfg={"vocab_size": 128})
    assert tiny[0]["tokens"].max() < 128


# --------------------------------------------------------------- the count
def test_flops_lm_is_the_walkers_count_on_the_dense_parts():
    """``flops.py`` walks the traced forward pass; on a model without expert
    layers it sees every product this module counts. Attention through the
    program's XLA path multiplies each query against all S keys, which is
    this module's count at twice the sequence length (S/2 keys a query)."""
    import jax
    import jax.numpy as jnp

    from distar_tpu.model import LFM2, default_lfm2_config
    from distar_tpu.utils import deep_merge_dicts

    B, S = 2, 64
    m = deep_merge_dicts(default_lfm2_config(), {
        "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "vocab_size": 128, "layer_types": ["conv", "full_attention", "conv"],
        "num_dense_layers": 3, "remat": False})
    model = LFM2(m)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    walked = flops.forward_flops(lambda v, t: model.apply(v, t)[0], variables, tokens) / (B * S)
    parts = flops_lm.forward_parts(m, 2 * S)
    assert parts["moe_experts"] == 0 and parts["moe_router"] == 0
    assert walked == pytest.approx(sum(parts.values()), rel=1e-9)
    assert flops_lm.forward_parts(m, S)["attention"] < parts["attention"]


def test_recorded_count_is_what_the_module_gives_for_the_program_file():
    cfg = cells.load("configs", CONFIG)
    model = cells.program_config(cfg)["model"]
    got = flops_lm.required_per_frame(model, cfg["as_run"]["learner"]["unroll_len"])
    assert got["step"] == cfg["required_flops_per_frame"] == pytest.approx(1.2174e9, rel=1e-3)
    parts = flops_lm.forward_parts(model, 8192)
    # 0.5 expected rows a position and expert layer; the dense layer is the largest part
    assert parts["moe_experts"] == 4 * 0.5 * 6 * 2048 * 1536
    assert max(parts, key=parts.get) == "dense_mlp"


# -------------------------------------------------- the configuration file
def test_configuration_holds_every_published_number_and_lists_what_it_cut():
    cfg = cells.load("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8, "vocab_size": 8192}
    assert cfg["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
                                "vocab_size": 65536}
    # no width among the cuts, and the widths the program runs are the published ones
    run_model = cfg["as_run"]["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok", "conv_L_cache", "norm_eps"):
        assert run_model[key] == cfg[key], key
    assert run_model["num_experts"] == cfg["num_experts_routed_over"] == 64  # the router's width
    assert run_model["experts_held"] == {"offset": 0, "count": cfg["num_experts"]}
    assert [cfg["layer_types"][i] for i in cfg["layers_held"]] == run_model["layer_types"]
    assert len(run_model["layer_types"]) == cfg["num_hidden_layers"]
    assert run_model["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert cfg["parameters"]["state_bytes"] >= 0.25 * 16e9  # a quarter of the chip before any activation
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if '"LFM2-24B-A2B"' in ln)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert sorted(differs) == sorted(cfg["reduced"])


def test_manifest_gained_the_cell_and_its_metrics_at_the_end():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [w["name"] for w in m["workloads"]][:3] == ["sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "lm_zipf_pool4_b4s8192", "chips": 1,
                    "why": cells.load("workloads", CELL)["why"]}
    assert [c["name"] for c in m["configs"]] == ["distar_sl_flagship", "distar_rl_flagship", CONFIG]
    mine = [e for e in m["per_layer"] if e["workloads"] == [CELL]]
    assert {e["name"] for e in mine} == {
        "lm_short_conv_ms", "lm_attention_ms", "lm_dense_mlp_ms", "lm_moe_route_ms", "lm_moe_experts_ms",
        "lm_embed_head_ms", "lm_optimizer_ms", "lm_unnamed_ms", "lm_forward_ms", "lm_backward_ms",
        "lm_recompute_ms", "moe_rows_here", "moe_load_max_over_mean", "moe_experts_roofline_pct"}
    assert m["per_layer"][-len(mine):] == mine  # appended, nothing moved
    # the policy's vocabulary is not this cell's
    assert not [e["name"] for e in m["per_layer"]
                if CELL in e["workloads"] and e["name"].startswith(("scope_", "step_forward", "step_backward",
                                                                    "step_recompute"))]


# ------------------------------------------------ scopes and roofline share
def op(start, end, scope):
    return Op("x", float(start), float(end), scope, "")


def test_lm_scopes_partition_a_step_by_the_token_vocabulary():
    from distar_tpu import obs

    assert trace_scope_lm.VOCABULARY is obs.LM_STEP_SCOPES
    head = "jit(lm_train_step)/jvp(LFM2)/"
    back = "jit(lm_train_step)/transpose(jvp(LFM2))/"
    ops = [op(0, 100, head + "layer_1/checkpoint/attention/attention/while"),     # container
           op(10, 60, head + "layer_1/checkpoint/attention/attention/while/body/dot"),
           op(100, 130, back + "layer_2/rematted_computation/moe/moe_experts/gmm"),
           op(120, 150, ""),                                                         # the compiler's own
           op(150, 160, "jit(lm_train_step)/jvp(LFM2)/layer_0/short_conv/short_conv/in_proj/dot_general"),
           op(160, 200, back + "layer_2/moe/moe_experts/tgmm"),
           op(300, 320, "jit(lm_train_step)/optimizer/add"),
           op(400, 410, head + "lm_head/final_norm/mul")]                            # outside the run
    got = trace_scope_lm.self_times(ops, 0.0, 350.0)
    # the compiler's own operation starts while the grouped product runs: the later start is innermost
    assert got == {("attention", "forward"): 100.0, ("moe_experts", "recompute"): 20.0,
                   ("unnamed", "forward"): 30.0, ("short_conv", "forward"): 10.0,
                   ("moe_experts", "backward"): 40.0, ("optimizer", "forward"): 20.0}
    assert sum(got.values()) == 220.0  # the union of the intervals inside the run
    # the policy's names mean nothing here, and the reverse
    assert trace_scope_lm.self_times([op(0, 5, "jit(s)/jvp(M)/core_lstm/while")], 0.0, 10.0) == {
        ("unnamed", "forward"): 5.0}


def test_lm_pass_metrics_split_what_the_scope_metrics_sum(monkeypatch):
    """Over one step: the eight lm_*_ms of the scopes partition it, and
    lm_forward/backward/recompute_ms split the part under a model scope; the
    roofline share's time is the kernel's without its recompute."""
    step = {("attention", "forward"): 30e6, ("attention", "backward"): 60e6, ("attention", "recompute"): 30e6,
            ("moe_experts", "forward"): 20e6, ("moe_experts", "backward"): 40e6,
            ("moe_experts", "recompute"): 20e6, ("loss", "forward"): 5e6, ("optimizer", "forward"): 7e6,
            ("unnamed", "forward"): 3e6}
    monkeypatch.setattr(trace_scope_lm, "steps_of", lambda result: [step])
    files = {n: cells.load("layer_metrics", n) for n in cells.names("layer_metrics") if n.startswith("lm_")}
    ms = {n: trace_scope_lm.read(None, **m["params"]) for n, m in files.items()}
    passes = ("lm_forward_ms", "lm_backward_ms", "lm_recompute_ms")
    assert sum(v for n, v in ms.items() if n not in passes) == pytest.approx(215.0)
    assert [ms[n] for n in passes] == [pytest.approx(50.0), pytest.approx(100.0), pytest.approx(50.0)]
    roofline = cells.load("layer_metrics", "moe_experts_roofline_pct")["params"]
    assert trace_scope_lm.read(None, scopes=roofline["scopes"], passes=roofline["passes"]) == pytest.approx(60.0)


def test_readers_give_nothing_without_a_trace_or_without_the_vocabulary(monkeypatch):
    result = {"events": None, "values": {}, "device": {"platform": "cpu", "kind": "cpu"}, "tap": None}
    assert trace_scope_lm.read(result, scopes=["attention"]) is None
    assert kernel_roofline.read(result, "grouped_swiglu", ["moe_experts"],
                                {"metric": "distar_moe_rows_here"}, {}) is None
    # a program from before the vocabulary was written: nothing is read, nothing raises
    monkeypatch.setattr(trace_scope_lm, "VOCABULARY", ())
    assert trace_scope_lm.steps_of({"events": [("a", 0, 1)]}) is None


def test_roofline_share_is_required_time_over_scope_time(monkeypatch):
    shape = cells.load("layer_metrics", "moe_experts_roofline_pct")["params"]["shape"]
    need = kernels_lm.grouped_swiglu(65536.0, **shape)
    assert need["flops"] == 18.0 * 65536 * 2048 * 1536
    weights = 3 * 8 * 2048 * 1536 * 4
    assert need["bytes"] == 2 * (5 * 65536 * 2048 + 3 * weights)
    # compute-bound at the deployment's load: 18.9 ms of products at the chip's 197 TFLOP/s
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    monkeypatch.setattr(trace_scope_lm, "read", lambda result, scopes=None, passes=None: 50.0)
    from benchmark.readers import histogram_window

    monkeypatch.setattr(histogram_window, "read", lambda result, **kw: 65536.0)
    result = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = kernel_roofline.read(result, "grouped_swiglu", ["moe_experts"], {"metric": "x"}, shape)
    assert share == pytest.approx(100.0 * (need["flops"] / 197e12) / 0.050)
    assert 30 < share < 45

"""``deepseek_v3`` (``model/deepseek_v3.py``, ``ops/sequence.py``'s latent
attention, ``ops/moe.py``) against its plain reference
(``benchmark/references/deepseek_v3_plain.py``, which imports none of them
and writes latent attention out literally) at a tiny size on seeded weights,
float32, on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import deepseek_v3_plain as plain  # noqa: E402
from distar_tpu.model import TOKEN_MODELS, DeepseekV3, default_deepseek_v3_config  # noqa: E402
from distar_tpu.ops import moe  # noqa: E402
from distar_tpu.ops import sequence  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

TINY = {"hidden_size": 64, "num_hidden_layers": 3, "intermediate_size": 96, "moe_intermediate_size": 24,
        "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rope_theta": 100.0, "n_routed_experts": 16, "num_experts_per_tok": 3,
        "experts_held": {"offset": 4, "count": 4}, "vocab_size": 128}
B, S = 2, 20


def build(seed=0, scale=5.0, **over):
    """The tiny model with seeded weights, its matrices widened by ``scale``
    so that each part moves the logits and a fault in any of them shows (see
    ``tests/test_lfm2.py``)."""
    cfg = deep_merge_dicts(default_deepseek_v3_config(), dict(TINY, **over))
    model = DeepseekV3(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    params = jax.tree.map(lambda x: x * scale if x.ndim >= 2 else x, variables["params"])
    # the embedding is drawn at 1.0: at a tenth, attention is as large a part of the stream as the tokens
    params["embedding"] = variables["params"]["embedding"] * 0.1
    return cfg, model, {"params": params, "buffers": variables["buffers"]}, tokens, labels


def system_loss(model, variables, params, tokens, labels):
    from distar_tpu.losses import compute_lm_loss

    logits, stats = model.apply({**variables, "params": params}, tokens)
    return compute_lm_loss(logits, labels)[0], (logits, stats)


def leaves(tree):
    return dict(jax.tree_util.tree_flatten_with_path(tree)[0])


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_logits_loss_statistics_and_every_gradient_leaf_match_the_plain_reference(remat):
    cfg, model, variables, tokens, labels = build(remat=remat)
    cut = plain.plain_config(cfg)
    (loss, (logits, stats)), grads = jax.value_and_grad(
        lambda p: system_loss(model, variables, p, tokens, labels), has_aux=True)(variables["params"])
    with jax.default_matmul_precision("highest"):
        (ref_loss, (ref_logits, ref_stats)), ref_grads = jax.value_and_grad(
            lambda p: plain.loss(p, variables, cut, tokens, labels), has_aux=True)(variables["params"])
    # float32 against float32 on one backend: what differs is the order of the sums (a rolled rotation
    # against a stack of pairs, a sorted buffer against a masked loop over experts, 1e-6 against 1e-20)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_array_equal(stats["rows"], np.stack(ref_stats["rows"]))
    for name in ("rms", "attn_rms", "ff_rms"):
        np.testing.assert_allclose(stats[name], np.stack(ref_stats[name]), rtol=1e-4, err_msg=name)
    assert stats["rows"].shape == (2, 4) and int(stats["overflow"]) == 0
    flat, ref_flat = leaves(grads), leaves(ref_grads)
    # a layer: 2 norms, 5 latent-attention leaves; dense 3 matrices; experts router + 3 + 3 shared; ends 3
    assert flat.keys() == ref_flat.keys() and len(flat) == 3 * 7 + 3 + 2 * 7 + 3
    for path, g in flat.items():
        # every leaf, against its own size: sum order moves it by 1e-6 of its largest entry, a wrong term by O(1)
        bound = 1e-3 * float(jnp.abs(ref_flat[path]).max()) + 1e-9
        np.testing.assert_allclose(g, ref_flat[path], atol=bound, rtol=0, err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(ref_flat[path]).max()) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("without", plain.OMISSIONS)
def test_the_reference_without_one_term_is_another_model(without):
    """Each omission the cell's limits have to see moves the reference's own
    loss and the statistic of the layer it sits in."""
    cfg, _, variables, tokens, labels = build()
    cut = plain.plain_config(cfg)
    with jax.default_matmul_precision("highest"):
        whole, (_, stats) = plain.loss(variables["params"], variables, cut, tokens, labels)
        less, (_, less_stats) = plain.loss(variables["params"], variables, cut, tokens, labels, None, None, (without,))
    assert abs(float(less) - float(whole)) > 2e-4 * float(whole)
    # the statistic that sees it: the first layer's attention output, the first expert layer's feed-forward output
    name, at = ("ff_rms", 1) if without in ("shared", "scaling") else ("attn_rms", 0)
    assert abs(float(less_stats[name][at]) / float(stats[name][at]) - 1) > 0.01


def literal_attention(q, k, v, scale):
    """``softmax_causal(q k^T * scale) v`` with the whole score matrix: ``q``, ``k`` [B, S, H, D], ``v`` [B, S, H, Dv]."""
    S = q.shape[1]
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    score = jnp.where(jnp.tril(jnp.ones((S, S), bool)), score, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(score, axis=-1), v)


@pytest.mark.parametrize("S", (16, 128, 1024), ids=("s16", "s128_kernel_shaped", "two_query_blocks"))
@pytest.mark.parametrize("grad", (False, True), ids=("value", "grad"))
def test_causal_attention_takes_a_value_head_of_its_own_size(S, grad):
    """24-wide scores and 16-wide values through the loop over query blocks
    (what a CPU lowers), value and gradient, against the whole score matrix."""
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S), 4)
    q, k = jax.random.normal(k0, (2, S, 3, 24)), jax.random.normal(k1, (2, S, 3, 24))
    v, weight = jax.random.normal(k2, (2, S, 3, 16)), jax.random.normal(k3, (2, S, 3, 16))
    ours = lambda q, k, v: sequence.causal_attention(q[:, :, :, None, :], k, v, 24 ** -0.5)[:, :, :, 0, :]
    with jax.default_matmul_precision("highest"):
        if not grad:
            out = ours(q, k, v)
            assert out.shape == (2, S, 3, 16)
            np.testing.assert_allclose(out, literal_attention(q, k, v, 24 ** -0.5), atol=2e-5, rtol=1e-4)
            return
        got = jax.grad(lambda *a: (ours(*a) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (literal_attention(*a, 24 ** -0.5) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()), rtol=0, err_msg=name)


@pytest.mark.parametrize("S", (12, 128), ids=("s12", "s128_kernel_shaped"))
def test_latent_attention_is_the_literal_form_at_unequal_head_sizes(S):
    """Head sizes 24 (16 + a rotary part of 8) against 16, a latent of 20."""
    att = sequence.LatentAttention(heads=3, kv_rank=20, nope_dim=16, rope_dim=8, v_dim=16, rope_theta=50.0)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, S, 32))
    v = att.init(jax.random.PRNGKey(1), u)
    p = jax.tree.map(lambda x: x * 8.0 if x.ndim >= 2 else x, v["params"])
    assert {k: p[k]["kernel"].shape for k in ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj")} == {
        "q_proj": (32, 3 * 24), "kv_a_proj": (32, 20 + 8), "kv_b_proj": (20, 3 * 32), "o_proj": (3 * 16, 32)}
    assert p["kv_norm"]["scale"].shape == (20,) and len(p) == 5
    cut = {"num_attention_heads": 3, "kv_lora_rank": 20, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "rope_theta": 50.0, "rms_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        out = att.apply({"params": p}, u)
        np.testing.assert_allclose(out, plain.latent_attention(p, u, cut, None), atol=2e-5, rtol=1e-4)
        for without in ("rope", "k_pe", "latent_norm", "scale"):
            assert not np.allclose(out, plain.latent_attention(p, u, cut, None, (without,)), atol=1e-3), without
    later = u.at[:, 7:].add(1.0)
    np.testing.assert_allclose(out[:, :7], att.apply({"params": p}, later)[:, :7], atol=1e-5)


@pytest.mark.parametrize("R", (2, 8, 64))
def test_interleaved_rope_is_a_complex_rotation_of_each_pair(R):
    x = jax.random.normal(jax.random.PRNGKey(R), (2, 9, 3, R))
    theta = 800000.0
    z = np.asarray(x[..., 0::2], np.float64) + 1j * np.asarray(x[..., 1::2], np.float64)
    angle = np.arange(9)[:, None] * theta ** (-np.arange(0, R, 2) / R)[None, :]
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(sequence.rope_interleaved(x, theta), want, atol=1e-5)
    np.testing.assert_allclose(jnp.stack([plain.rotary_interleaved(x[b], theta) for b in range(2)]), want, atol=1e-5)
    # not the rotate-half convention that ``rope`` keeps for LFM2, and position 0 is left alone
    assert R == 2 or not np.allclose(sequence.rope(x, theta), want, atol=1e-3)
    np.testing.assert_array_equal(sequence.rope_interleaved(x, theta)[:, 0], x[:, 0])


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """64 experts, top-6, scale 2.446, two shared experts as one SwiGLU, tiny
    widths: the routed part as each of the eight members of an expert-parallel
    group computes it (experts 0-7, 8-15, ..., offsets 0..56), summed, plus the
    shared experts that each of them computes alike COUNTED ONCE, is the
    reference's layer over all 64 experts."""
    d, width, E, k = 32, 16, 64, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, d))
    whole = moe.ExpertsHeldMoE(E, k, width, 0, E, scaling=2.446, body="swiglu", shared_width=2 * width)
    variables = whole.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    cut = {"num_experts_per_tok": k, "experts_held": {"offset": 0, "count": E}, "use_expert_bias": True,
           "routed_scaling_factor": 2.446}
    u = plain.rms_norm(x, p["norm"]["scale"], 1e-5).reshape(-1, d)
    bias = variables["buffers"]["expert_bias"]
    with jax.default_matmul_precision("highest"):
        want, want_rows, _ = plain.experts_held(p, bias, u, cut, None)
        shared_part = plain.swiglu(u, p["shared_w1"], p["shared_w2"], p["shared_w3"], None)
    total, rows = 0.0, []
    for member in range(8):
        held = slice(8 * member, 8 * member + 8)
        share = moe.ExpertsHeldMoE(E, k, width, 8 * member, 8, scaling=2.446, body="swiglu", shared_width=2 * width)
        mine = {"params": {**p, **{n: p[n][held] for n in ("w1", "w2", "w3")}}, "buffers": variables["buffers"]}
        y, stats = share.apply(mine, x)
        total = total + y.reshape(-1, d)
        rows.append(stats["rows"])
    assert float(jnp.abs(shared_part).max()) > 0.1
    np.testing.assert_allclose(total - 7 * shared_part, want, atol=2e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.concatenate(rows), want_rows)
    assert int(np.concatenate(rows).sum()) == 2 * 24 * k  # every pick is somebody's
    assert p["shared_w1"].shape == (d, 2 * width)  # the two shared experts are one SwiGLU of twice the width


def test_the_learner_finds_the_model_by_its_model_type_and_the_default_is_the_published_cut():
    assert TOKEN_MODELS["deepseek_v3"][0] is DeepseekV3
    cfg = default_deepseek_v3_config()
    assert DeepseekV3.moe_layers(cfg) == [1, 2, 3, 4, 5]
    shapes = jax.eval_shape(DeepseekV3(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    params = shapes["params"]
    assert count(params["layer_0"]["mla"]) == 13_763_072 and count(params["layer_0"]) == 82_973_184
    assert count(params["layer_1"]) == 100_405_760 and count(params["embedding"]) + count(params["lm_head"]) == 83_886_080
    assert count(params) == 668_890_112
    assert count(shapes["buffers"]) == 5 * 64


@pytest.mark.parametrize("stat", ("rms", "attn_rms", "ff_rms"))
def test_the_learners_log_names_the_per_layer_statistics(stat):
    from distar_tpu.learner.lm_learner import _flat_log

    log = _flat_log({stat: np.asarray([1.0, 2.0, 3.0])}, [1, 2])
    name = "residual_rms" if stat == "rms" else stat
    assert log == {f"{name}/layer_0": 1.0, f"{name}/layer_1": 2.0, f"{name}/layer_2": 3.0}

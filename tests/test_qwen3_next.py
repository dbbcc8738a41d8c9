"""``qwen3_next`` (``model/qwen3_next.py``, ``ops/delta.py``'s gated delta
rule, ``ops/sequence.py``'s gated attention, ``ops/moe.py``'s softmax router
and gated shared expert) against its plain reference
(``benchmark/references/qwen3_next_plain.py``, which imports none of them and
writes the delta rule as the literal recurrence) at a tiny size on seeded
weights, float32, on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import qwen3_next_plain as plain  # noqa: E402
from distar_tpu.model import TOKEN_MODELS, Qwen3Next, default_qwen3_next_config  # noqa: E402
from distar_tpu.ops import delta, moe, sequence  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

TINY = {"hidden_size": 64, "num_hidden_layers": 4, "full_attention_interval": 2, "linear_num_key_heads": 2,
        "linear_key_head_dim": 8, "linear_num_value_heads": 4, "linear_value_head_dim": 12, "gdn_chunk_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 100.0,
        "num_experts": 16, "num_experts_per_tok": 3, "moe_intermediate_size": 24,
        "shared_expert_intermediate_size": 20, "experts_held": {"offset": 4, "count": 4}, "vocab_size": 128}
B, S = 2, 20  # two chunks of 8 and a part of one


def build(seed=0, scale=5.0, **over):
    """The tiny model with seeded weights, its matrices widened by ``scale``
    so that each part moves the logits and a fault in any of them shows (see
    ``tests/test_lfm2.py``); the norms' ``w`` moved off zero, so that ``1 + w``
    is not ``1``."""
    cfg = deep_merge_dicts(default_qwen3_next_config(), dict(TINY, **over))
    model = Qwen3Next(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 3), 64))
    off_zero = lambda path, x: 0.3 * jax.random.normal(next(keys), x.shape) if path[-1].key == "w" else x
    params = jax.tree.map(lambda x: x * scale if x.ndim >= 2 else x, variables["params"])
    params = jax.tree_util.tree_map_with_path(off_zero, params)
    # the embedding is drawn at 1.0: at a tenth, the mixers are as large a part of the stream as the tokens
    params["embedding"] = variables["params"]["embedding"] * 0.1
    return cfg, model, {"params": params, "buffers": variables["buffers"]}, tokens, labels


def system_loss(model, variables, params, tokens, labels):
    from distar_tpu.losses import compute_lm_loss

    logits, stats = model.apply({**variables, "params": params}, tokens)
    return compute_lm_loss(logits, labels)[0], (logits, stats)


def leaves(tree):
    return dict(jax.tree_util.tree_flatten_with_path(tree)[0])


# ------------------------------------------------------ the chunked delta rule
def rule_inputs(S, b=2, Hk=2, H=4, K=8, V=6, seed=0, decay=-2.0, write=0.0):
    """``decay``: the mean of log(-g); ``write``: the mean of beta's logit."""
    ks = jax.random.split(jax.random.PRNGKey(seed + S), 5)
    q, k = (jax.random.normal(key, (b, S, Hk, K)) for key in ks[:2])
    q, k = (t / jnp.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = jax.random.normal(ks[2], (b, S, H, V))
    g = -jnp.exp(jax.random.normal(ks[3], (b, S, H)) + decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, S, H)) + write)
    return q, k, v, g, beta


def literal(q, k, v, g, beta, reset_every=0):
    """The reference's recurrence, a sequence at a time."""
    out = [plain.recurrence(q[i], k[i], v[i], g[i], beta[i], reset_every) for i in range(q.shape[0])]
    return jnp.stack([o for o, _ in out]), jnp.stack([last for _, last in out])


RULE_CASES = {"chunk_divides": dict(S=32, chunk=8), "chunk_does_not_divide": dict(S=21, chunk=8),
              "shorter_than_a_chunk": dict(S=5, chunk=8), "published_chunk": dict(S=130, chunk=64),
              "strong_decay": dict(S=24, chunk=8, decay=1.5), "no_decay_to_speak_of": dict(S=24, chunk=8, decay=-9.0),
              "beta_near_0": dict(S=24, chunk=8, write=-6.0), "beta_near_1": dict(S=24, chunk=8, write=6.0),
              "one_group_of_heads": dict(S=24, chunk=8, groups=1), "eight_groups_asked_of_two_key_heads": dict(S=24, chunk=8, groups=8)}


@pytest.mark.parametrize("case", RULE_CASES)
def test_chunked_delta_rule_is_the_literal_recurrence_forward_and_backward(case):
    kw = dict(RULE_CASES[case])
    S, chunk = kw.pop("S"), kw.pop("chunk")
    groups = kw.pop("groups", 2)
    args = rule_inputs(S, **kw)
    ours = lambda *a: delta.chunked_delta_rule(*a, chunk, groups=groups)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    score = lambda fn: lambda *a: (fn(*a)[0] * weight).sum() + jnp.square(fn(*a)[1]).sum()
    with jax.default_matmul_precision("highest"):
        (o, last), (want_o, want_last) = ours(*args), literal(*args)
        np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(last, want_last, atol=2e-5, rtol=1e-4)
        got = jax.grad(score(ours), argnums=range(5))(*args)
        want = jax.grad(score(literal), argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-9, rtol=0, err_msg=name)
    assert o.shape == args[2].shape and last.shape == (2, 4, 8, 6) and float(jnp.abs(want_last).max()) > 0


# the same cases at shapes the kernel's tiles take: heads of 128, chunks of whole 16-blocks (64 as published, and 16
# where the case's length is a few dozen positions, so that it still crosses chunk boundaries); the two cases that
# differ only in ``groups`` are the XLA form's (the kernel reads no ``groups``), two longer ones cross grid steps
KERNEL_CASES = {**{name: dict(kw, chunk=64 if kw["chunk"] == 64 else 16) for name, kw in RULE_CASES.items()
                   if "groups" not in kw},
                "three_grid_steps_of_chunks_of_16": dict(S=130, chunk=16),
                "two_grid_steps_of_chunks_of_64": dict(S=300, chunk=64, decay=-4.0),
                "chunks_of_three_blocks": dict(S=100, chunk=48)}     # the inverse merges blocks (0, 1), then 2 alone


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernel_in_interpret_mode_is_the_literal_recurrence_forward_and_backward(case, executables_dropped):
    """``ops.delta._rule_kernel`` (the forward and the backward Pallas kernel, interpreted on the CPU) on two
    value heads a key head: ``o``, the last state and all five gradients, to the tolerances the XLA form is held to."""
    kw = dict(KERNEL_CASES[case])
    S, chunk = kw.pop("S"), kw.pop("chunk")
    args = rule_inputs(S, K=128, V=128, **kw)
    ours = lambda *a: delta._rule_kernel(*a, chunk, True)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    score = lambda fn: lambda *a: (lambda o, last: (o * weight).sum() + jnp.square(last).sum())(*fn(*a))
    with jax.default_matmul_precision("highest"):     # under jit: eagerly the interpreter dispatches a kernel op by op
        (o, last), (want_o, want_last) = jax.jit(ours)(*args), literal(*args)
        np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(last, want_last, atol=2e-5, rtol=1e-4)
        got = jax.jit(jax.grad(score(ours), argnums=range(5)))(*args)
        want = jax.grad(score(literal), argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-9, rtol=0, err_msg=name)
    assert o.shape == args[2].shape and last.shape == (2, 4, 128, 128) and float(jnp.abs(want_last).max()) > 0


def test_the_kernel_keeps_its_operands_dtype_and_pads_to_whole_grid_steps(executables_dropped):
    """bfloat16 operands: the products round where the XLA form's do, so the two agree to about bfloat16's own
    step (2^-8: a sum taken in another order moves a rounding by one), outputs float32, gradients in the operands'
    dtypes; 70 positions are padded to one grid step."""
    bf = jnp.bfloat16
    q, k, v, g, beta = rule_inputs(70, K=128, V=128)
    args = (q.astype(bf), k.astype(bf), v.astype(bf), g, beta)
    score = lambda fn: lambda *a: jnp.square(fn(*a)[0]).sum() + jnp.square(fn(*a)[1]).sum()
    kernel, xla = lambda *a: delta._rule_kernel(*a, 64, True), lambda *a: delta._rule_xla(*a, 64, bf, 2)
    (o, last), (want_o, want_last) = jax.jit(kernel)(*args), xla(*args)
    assert o.dtype == last.dtype == jnp.float32 and o.shape == (2, 70, 4, 128)
    np.testing.assert_allclose(o, want_o, atol=1e-2 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(last, want_last, atol=1e-2 * float(jnp.abs(want_last).max()))
    got, want = (jax.jit(jax.grad(score(fn), argnums=range(5)))(*args) for fn in (kernel, xla))
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                                   atol=3e-2 * float(jnp.abs(b.astype(jnp.float32)).max()), err_msg=name)


@pytest.mark.parametrize("refused", ("key_head_of_8", "value_head_of_6", "chunk_of_8", "chunk_of_128"))
def test_a_shape_the_kernels_tiles_refuse_takes_the_xla_form_and_agrees(refused, monkeypatch):
    shape = dict(K=128, V=128, chunk=16)
    shape.update({"key_head_of_8": dict(K=8), "value_head_of_6": dict(V=6), "chunk_of_8": dict(chunk=8),
                  "chunk_of_128": dict(chunk=128)}[refused])
    chunk = shape.pop("chunk")
    assert not delta.kernel_takes(shape["K"], shape["V"], chunk) and delta.kernel_takes(128, 256, 64)

    def never(*a):
        raise AssertionError("the kernel was asked")

    monkeypatch.setattr(delta, "_rule_kernel", never)
    args = rule_inputs(24, **shape)
    with jax.default_matmul_precision("highest"):
        (o, last), (want_o, want_last) = delta.chunked_delta_rule(*args, chunk), literal(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(last, want_last, atol=2e-5, rtol=1e-4)
    # and a shape the tiles take does ask it: under jit the branch for a TPU is traced whatever the platform
    with pytest.raises(AssertionError, match="the kernel was asked"):
        jax.jit(lambda *a: delta.chunked_delta_rule(*a, 16))(*rule_inputs(24, K=128, V=128))


def test_off_the_tpu_a_shape_the_tiles_take_runs_the_xla_form_under_both_branches():
    """``jax.lax.platform_dependent`` traces the kernel's branch and the XLA form's and lowers, here, the second:
    value and gradients through the pair are the literal recurrence's, and no ``pallas_call`` is interpreted."""
    args = rule_inputs(40, K=128, V=128)
    ours = lambda *a: delta.chunked_delta_rule(*a, 16)
    score = lambda fn: lambda *a: jnp.square(fn(*a)[0]).sum() + jnp.square(fn(*a)[1]).sum()
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(jax.jit(ours)(*args)[0], literal(*args)[0], atol=2e-5, rtol=1e-4)
        got = jax.jit(jax.grad(score(ours), argnums=range(5)))(*args)
        want = jax.grad(score(literal), argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-9, rtol=0, err_msg=name)
    lowered = jax.jit(ours).lower(*args).as_text()
    assert "tpu_custom_call" not in lowered and "while" in lowered     # the XLA form's loop over groups of heads


def test_the_carry_across_a_chunk_boundary_is_what_the_later_chunks_read():
    """A rule that starts every chunk from nothing agrees with the recurrence
    up to the first boundary and nowhere after it."""
    args = rule_inputs(24, decay=-4.0)
    with jax.default_matmul_precision("highest"):
        whole, _ = delta.chunked_delta_rule(*args, 8)
        dropped, _ = literal(*args, reset_every=8)
    np.testing.assert_allclose(whole[:, :8], dropped[:, :8], atol=2e-5)
    assert float(jnp.abs(whole[:, 8:] - dropped[:, 8:]).max()) > 0.05
    # and a write at position 3 reaches position 20 through two boundaries
    later = list(args)
    later[2] = args[2].at[:, 3].add(1.0)
    with jax.default_matmul_precision("highest"):
        moved, _ = delta.chunked_delta_rule(*later, 8)
    assert float(jnp.abs(moved[:, 20] - whole[:, 20]).max()) > 1e-3
    np.testing.assert_allclose(moved[:, :3], whole[:, :3], atol=1e-6)


@pytest.mark.parametrize("C", (1, 2, 16, 64))
def test_the_inverse_inverts_a_unit_lower_triangular_matrix_and_the_nilpotent_series_agrees(C):
    L = jnp.tril(jax.random.normal(jax.random.PRNGKey(C), (3, C, C)) * 0.3, -1)
    eye = jnp.broadcast_to(jnp.eye(C), L.shape)
    with jax.default_matmul_precision("highest"):
        T = delta._inverse(L)
        np.testing.assert_allclose(T @ (eye + L), eye, atol=2e-5)
        series, power = eye - L, L            # (I + L)^-1 = (I - L)(I + L^2)(I + L^4)...: L^C = 0
        for _ in range(6):
            power = power @ power
            series = series + series @ power
    np.testing.assert_allclose(T, series, atol=2e-5)


@pytest.mark.parametrize("S", (12, 40), ids=("s12", "s40_five_chunks"))
def test_gated_delta_net_layer_is_the_reference_layer(S):
    layer = delta.GatedDeltaNet(key_heads=2, value_heads=4, key_dim=8, value_dim=12, chunk=8, groups=2)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, S, 32))
    v = layer.init(jax.random.PRNGKey(1), u)
    p = jax.tree.map(lambda x: x * 8.0 if x.ndim >= 2 else x, v["params"])
    p["out_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (12,))
    assert {k: x.shape for k, x in leaves(p).items()} and p["in_proj_qkvz"]["kernel"].shape == (32, 2 * 16 + 2 * 48)
    assert p["in_proj_ba"]["kernel"].shape == (32, 8) and p["conv_kernel"].shape == (4, 2 * 16 + 48)
    assert p["out_proj"]["kernel"].shape == (48, 32) and len(leaves(p)) == 7
    cut = {"linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 8,
           "linear_value_head_dim": 12, "rms_norm_eps": 1e-6, "gdn_chunk_size": 8}
    with jax.default_matmul_precision("highest"):
        out, stats = layer.apply({"params": p}, u)
        want, last_ms, decay = plain.gated_delta_net(p, u, cut, None)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(stats["state_rms"], plain.state_rms(last_ms), rtol=1e-4)
        np.testing.assert_allclose(stats["decay_mean"], decay, rtol=1e-5)
        for without in ("decay", "beta", "carry", "z_gate", "l2norm"):
            other = plain.gated_delta_net(p, u, cut, None, (without,))[0]
            assert (S <= 8 and without == "carry") or not np.allclose(out, other, atol=1e-3), without
    later = u.at[:, 7:].add(1.0)
    np.testing.assert_allclose(out[:, :7], layer.apply({"params": p}, later)[0][:, :7], atol=1e-5)
    # the drawn decays spread: some heads forget in a few positions, some hold for hundreds
    big = delta.GatedDeltaNet(16, 32, 8, 8).init(jax.random.PRNGKey(3), jnp.zeros((1, 4, 32)))["params"]
    alpha = np.exp(-np.exp(big["A_log"]) * np.log1p(np.exp(big["dt_bias"])))
    assert alpha.min() < 0.9 and alpha.max() > 0.995 and np.all((alpha > 0) & (alpha < 1))


# ------------------------------------------------------------ gated attention
def attention_and_reference(S, **over):
    kw = dict(heads=4, kv_heads=2, head_dim=16, rope_theta=100.0, eps=1e-6, rotary_dim=4, zero_centred=True, gate=True)
    att = sequence.CausalGQAttention(**dict(kw, **over))
    u = jax.random.normal(jax.random.PRNGKey(0), (2, S, 32))
    v = att.init(jax.random.PRNGKey(1), u)
    p = jax.tree.map(lambda x: x * 8.0 if x.ndim >= 2 else x, v["params"])
    for i, name in enumerate(("q_norm", "k_norm")):
        p[name] = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(5 + i), (16,))}
    cut = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
           "rope_theta": 100.0, "rms_norm_eps": 1e-6}
    return att, p, u, cut


@pytest.mark.parametrize("S", (12, 128), ids=("s12", "s128_kernel_shaped"))
def test_gated_attention_is_the_reference_and_each_of_its_terms_counts(S):
    att, p, u, cut = attention_and_reference(S)
    assert p["q_proj"]["kernel"].shape == (32, 4 * 2 * 16) and p["q_norm"]["w"].shape == (16,)
    with jax.default_matmul_precision("highest"):
        out, opened = att.apply({"params": p}, u)
        want, want_opened = plain.gated_attention(p, u, cut, None)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(opened, want_opened, rtol=1e-5)
        for without in ("attn_gate", "rope", "rope_whole", "one_plus_w"):
            assert not np.allclose(out, plain.gated_attention(p, u, cut, None, (without,))[0], atol=1e-3), without
        # and the program's own switches are those terms: each, turned off, is the reference without it
        plain_norm = {**p, "q_norm": {"scale": p["q_norm"]["w"]}, "k_norm": {"scale": p["k_norm"]["w"]}}
        for over, params, without in ((dict(rotary_dim=None), p, "rope_whole"), (dict(positions=False), p, None),
                                      (dict(zero_centred=False), plain_norm, "one_plus_w")):
            got = attention_and_reference(S, **over)[0].apply({"params": params}, u)[0]
            if without:
                np.testing.assert_allclose(got, plain.gated_attention(p, u, cut, None, (without,))[0], atol=2e-5, rtol=1e-4)
            assert not np.allclose(got, out, atol=1e-3), over
    later = u.at[:, 7:].add(1.0)
    np.testing.assert_allclose(out[:, :7], att.apply({"params": p}, later)[0][:, :7], atol=1e-5)


def test_the_attention_other_models_run_has_none_of_it():
    """Without the three arguments the layer is what LFM2 and ``nemotron_h``
    run: one output, ``scale`` norms, a ``q_proj`` of the heads' width."""
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 32))
    att = sequence.CausalGQAttention(heads=4, kv_heads=2, head_dim=16)
    v = att.init(jax.random.PRNGKey(1), u)
    assert v["params"]["q_proj"]["kernel"].shape == (32, 64) and set(v["params"]["q_norm"]) == {"scale"}
    assert att.apply(v, u).shape == (1, 8, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, 16))
    np.testing.assert_array_equal(sequence.rope_first(x, 1e4, 16), sequence.rope(x, 1e4))
    np.testing.assert_array_equal(sequence.rope_first(x, 1e4, 4)[..., 4:], x[..., 4:])
    np.testing.assert_allclose(jnp.stack([plain.rotary_first(x[0], 1e4, 4)]), sequence.rope_first(x, 1e4, 4), atol=1e-6)


@pytest.mark.parametrize("zero_centred", (False, True))
def test_rms_norm_is_one_plus_w_only_when_asked(zero_centred):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    layer = sequence.RMSNorm(1e-6, zero_centred)
    v = layer.init(jax.random.PRNGKey(1), x)
    name, at_init = ("w", 0.0) if zero_centred else ("scale", 1.0)
    assert set(v["params"]) == {name} and float(v["params"][name].mean()) == at_init
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1.0 + w if zero_centred else w)
    np.testing.assert_allclose(layer.apply({"params": {name: w}}, x), want, atol=1e-6)
    if zero_centred:
        np.testing.assert_allclose(plain.norm(x, w, 1e-6), want, atol=1e-6)


# ------------------------------------------------------- the router and experts
def test_route_with_softmax_is_the_written_out_form_and_the_sigmoid_path_is_as_it_was():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (40, 32))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    sel, w = moe.route(logits, bias, 5, scoring="softmax")
    prob = np.exp(np.asarray(logits, np.float64))
    prob /= prob.sum(-1, keepdims=True)
    order = np.argsort(-prob, axis=-1)[:, :5]
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(order, -1))
    picked = np.take_along_axis(prob, np.asarray(sel), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(moe.route(logits, 0.0, 5, scoring="softmax")[0], sel)  # the bias is read by nothing
    np.testing.assert_allclose(moe.route(logits, bias, 5, 2.0, scoring="softmax")[1], 2.0 * w, rtol=1e-6)
    # sigmoid, as every other model routes: the bias moves the picks and not the weights
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    sel_s, w_s = moe.route(logits, bias, 5, 2.5)
    np.testing.assert_array_equal(np.sort(sel_s, -1), np.sort(np.argsort(-(s + np.asarray(bias)), -1)[:, :5], -1))
    picked = np.take_along_axis(s, np.asarray(sel_s), -1)
    np.testing.assert_allclose(w_s, 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    assert not np.array_equal(np.sort(sel_s, -1), np.sort(sel, -1))
    with pytest.raises(ValueError, match="'sigmoid' or 'softmax'"):
        moe.route(logits, bias, 5, scoring="tanh")


def layer_and_params(count, offset, d=32, width=16, E=64, k=10, shared=12):
    layer = moe.ExpertsHeldMoE(E, k, width, offset, count, use_bias=False, eps=1e-6, body="swiglu", shared_width=shared,
                               scoring="softmax", gated_shared=True, zero_centred=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, d))
    return layer, x


def test_the_shared_experts_gate_opens_it_a_position_at_a_time():
    layer, x = layer_and_params(8, 0)
    variables = layer.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    p["shared_gate"] = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    assert p["shared_gate"].shape == (32,) and set(p["norm"]) == {"w"}
    mine = {"params": p, "buffers": variables["buffers"]}
    u = plain.norm(x, p["norm"]["w"], 1e-6).reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        y, _ = layer.apply(mine, x)
        gated = plain.gated_shared(u, p["shared_gate"], p["shared_w1"], p["shared_w2"], p["shared_w3"], None)
        ungated = plain.gated_shared(u, p["shared_gate"], p["shared_w1"], p["shared_w2"], p["shared_w3"], None, gated=False)
        cut = {"num_experts_per_tok": 10, "experts_held": {"offset": 0, "count": 8}}
        want, rows, _ = plain.experts_held(p, None, u, cut, None)
        np.testing.assert_allclose(y.reshape(-1, 32), want, atol=2e-4, rtol=1e-4)
        routed_only, _, _ = plain.experts_held(p, None, u, cut, None, without=("shared",))
    gate = jax.nn.sigmoid(u @ p["shared_gate"])[:, None]
    np.testing.assert_allclose(gated, gate * ungated, rtol=1e-5, atol=1e-6)
    assert 0.02 < float(gate.min()) and float(gate.max()) < 0.98 and float(jnp.abs(gate - 0.5).mean()) > 0.1
    np.testing.assert_allclose(y.reshape(-1, 32) - routed_only, gated, atol=2e-4)
    assert int(rows.sum()) > 0


def test_a_buffer_of_more_than_six_chunks_is_walked_in_pieces_to_the_same_sum(monkeypatch):
    """Every pick held (16 experts, top-8): the load asks for the whole
    buffer of 8 chunks, which is walked two chunks at a time; value and every
    gradient are what one pass over the whole buffer gives."""
    layer = moe.ExpertsHeldMoE(16, 8, 8, 0, 16, use_bias=False, scoring="softmax")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    v = layer.init(jax.random.PRNGKey(1), x)
    params = jax.tree.map(lambda a: a * 8 if a.ndim >= 2 else a, v["params"])

    def run(p):
        y, stats = layer.apply({"params": p, "buffers": v["buffers"]}, x)
        return jnp.sum(y ** 2), (y, stats)

    with jax.default_matmul_precision("highest"):
        (_, (y, stats)), g = jax.value_and_grad(run, has_aux=True)(params)
        monkeypatch.setattr(moe, "WHOLE_AT_ONCE_UP_TO", 100)
        (_, (whole, _)), g_whole = jax.value_and_grad(run, has_aux=True)(params)
    assert int(stats["buffer_rows"]) == 24 * 8 and int(stats["overflow"]) == 0
    np.testing.assert_allclose(y, whole, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_whole)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-9)


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """64 experts, top-10 by a softmax over all of them, a gated shared
    expert, tiny widths: the routed part as each of the sixteen members of an
    expert-parallel group computes it (experts 0-3, 4-7, ..., offsets 0..60),
    summed, plus the shared expert that each of them computes alike COUNTED
    ONCE, is the reference's layer over all 64 experts."""
    d, E, k = 32, 64, 10
    whole, x = layer_and_params(E, 0)
    variables = whole.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    p["shared_gate"] = jax.random.normal(jax.random.PRNGKey(2), (d,))
    p["norm"] = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(3), (d,))}
    cut = {"num_experts_per_tok": k, "experts_held": {"offset": 0, "count": E}}
    u = plain.norm(x, p["norm"]["w"], 1e-6).reshape(-1, d)
    with jax.default_matmul_precision("highest"):
        want, want_rows, _ = plain.experts_held(p, None, u, cut, None)
        shared_part = plain.gated_shared(u, p["shared_gate"], p["shared_w1"], p["shared_w2"], p["shared_w3"], None)
        total, rows = 0.0, []
        for member in range(16):
            held = slice(4 * member, 4 * member + 4)
            share, _ = layer_and_params(4, 4 * member)
            mine = {"params": {**p, **{n: p[n][held] for n in ("w1", "w2", "w3")}}, "buffers": variables["buffers"]}
            y, stats = share.apply(mine, x)
            total = total + y.reshape(-1, d)
            rows.append(stats["rows"])
            assert int(stats["overflow"]) == 0
    assert float(jnp.abs(shared_part).max()) > 0.1
    np.testing.assert_allclose(total - 15 * shared_part, want, atol=3e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.concatenate(rows), want_rows)
    assert int(np.concatenate(rows).sum()) == 2 * 24 * k  # every pick is somebody's


# --------------------------------------------------------------- the whole model
@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_logits_loss_statistics_and_every_gradient_leaf_match_the_plain_reference(remat):
    cfg, model, variables, tokens, labels = build(remat=remat)
    cut = plain.plain_config(cfg)
    (loss, (logits, stats)), grads = jax.value_and_grad(
        lambda p: system_loss(model, variables, p, tokens, labels), has_aux=True)(variables["params"])
    with jax.default_matmul_precision("highest"):
        (ref_loss, (ref_logits, ref_stats)), ref_grads = jax.value_and_grad(
            lambda p: plain.loss(p, variables, cut, tokens, labels), has_aux=True)(variables["params"])
    # float32 against float32 on one backend: what differs is the order of the sums (chunks against a step a
    # position, a sorted buffer against a masked loop over experts)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_array_equal(stats["rows"], np.stack(ref_stats["rows"]))
    for name in ("rms", "mixer_rms", "ff_rms"):
        np.testing.assert_allclose(stats[name], np.stack(ref_stats[name]), rtol=1e-4, err_msg=name)
    assert sorted(stats["gdn_state_rms"]) == sorted(stats["gdn_decay_mean"]) == ["layer_0", "layer_2"]
    assert sorted(stats["attn_gate_mean"]) == ["layer_1", "layer_3"]
    for name in ("gdn_state_rms", "gdn_decay_mean", "attn_gate_mean"):
        np.testing.assert_allclose([stats[name][k] for k in sorted(stats[name])], np.stack(ref_stats[name]),
                                   rtol=1e-4, err_msg=name)
    assert stats["rows"].shape == (4, 4) and int(stats["overflow"]) == 0 and int(stats["row_indexed"]) == 4
    flat, ref_flat = leaves(grads), leaves(ref_grads)
    # a Gated DeltaNet layer: 1 norm + 7; an attention layer: 1 + 6; every layer's experts: norm, router, 3 + 3 shared, the gate
    assert flat.keys() == ref_flat.keys() and len(flat) == 2 * 8 + 2 * 7 + 4 * 9 + 3
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
    assert abs(float(less) - float(whole)) > 1e-4 * float(whole)
    # the statistic that sees it: the first such layer's mixer output, the first layer's feed-forward output
    name, at = {"shared": ("ff_rms", 0), "shared_gate": ("ff_rms", 0), "attn_gate": ("mixer_rms", 1),
                "rope": ("mixer_rms", 1), "rope_whole": ("mixer_rms", 1)}.get(without, ("mixer_rms", 0))
    assert abs(float(less_stats[name][at]) / float(stats[name][at]) - 1) > 0.005, (without, name)


def test_one_learner_step_reports_what_the_reference_computes(tmp_path):
    """``LMLearner`` finds the model by ``model.model_type`` alone and its
    first step's log is the reference's loss vector on the untrained weights."""
    from distar_tpu.learner.lm_learner import LMLearner, fake_token_batch

    learner = LMLearner({"common": {"experiment_name": "qwen3_next_test", "save_path": str(tmp_path)},
                         "learner": {"batch_size": B, "unroll_len": S, "save_freq": 10 ** 9},
                         "model": dict(TINY, model_type="qwen3_next")})
    assert type(learner.model) is Qwen3Next and learner._moe_layers == [0, 1, 2, 3]
    batch = fake_token_batch(B, S, 128, np.random.default_rng(3))
    before = jax.tree.map(np.asarray, learner.state["params"])
    state = learner._state
    want = plain.first_step(learner, batch)     # it drops the learner's Adam moments, which it does not read
    learner._state = state
    log = learner._train(batch)
    for key, ref in want.items():
        if key != "forward_seconds":
            assert log[key] == pytest.approx(ref, rel=2e-3, abs=1e-6), key
    assert {"gdn_state_rms/layer_0", "gdn_decay_mean/layer_2", "attn_gate_mean/layer_1", "mixer_rms/layer_3",
            "ff_rms/layer_0", "residual_rms/layer_3", "moe_rows_sum/layer_0", "moe_rows_max/layer_3",
            "dyn/grad_norm/layer_0"} <= set(want) <= set(log) | {"forward_seconds"}
    assert 0.0 < log["gdn_decay_mean/layer_0"] < 1.0 and log["moe_overflow_rows"] == 0.0
    after = learner.state["params"]["params"]
    assert not np.array_equal(before["params"]["layer_0"]["gdn"]["A_log"], after["layer_0"]["gdn"]["A_log"])


def test_the_learner_finds_the_model_by_its_model_type_and_the_default_is_the_published_cut():
    assert TOKEN_MODELS["qwen3_next"][0] is Qwen3Next
    cfg = default_qwen3_next_config()
    assert Qwen3Next.moe_layers(cfg) == [0, 1, 2, 3]
    shapes = jax.eval_shape(Qwen3Next(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    params = shapes["params"]
    assert count(params["layer_0"]["gdn"]) == 33_718_464 and count(params["layer_3"]["attention"]) == 27_263_488
    assert count(params["layer_0"]["moe"]) + count(params["layer_0"]["operator_norm"]) == 4_200_448 + 100_663_296
    assert count(params["embedding"]) + count(params["lm_head"]) + count(params["final_norm"]) == 77_793_280
    assert count(params) == 625_667_136
    assert [k for k in sorted(params) if k.startswith("layer_") and "gdn" in params[k]] == ["layer_0", "layer_1", "layer_2"]
    assert params["layer_3"]["attention"]["q_proj"]["kernel"].shape == (2048, 16 * 512)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        Qwen3Next(deep_merge_dicts(cfg, dict(TINY, norm_topk_prob=False))).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("stat", ("gdn_state_rms", "gdn_decay_mean", "attn_gate_mean"))
def test_the_learners_log_names_the_statistics_only_some_layers_have(stat):
    from distar_tpu.learner.lm_learner import _flat_log

    log = _flat_log({stat: {"layer_0": np.float32(1.0), "layer_2": np.float32(3.0)}}, [0, 1, 2, 3])
    assert log == {f"{stat}/layer_0": 1.0, f"{stat}/layer_2": 3.0}

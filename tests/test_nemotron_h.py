"""``nemotron_h`` (``model/nemotron_h.py``, ``ops/ssm.py``, ``ops/moe.py``,
``ops/sequence.py``) against its plain reference
(``benchmark/references/nemotron_h_plain.py``, which imports none of them and
runs the state-space layer as the literal recurrence) at a tiny size on seeded
weights, float32, on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import nemotron_h_plain as plain  # noqa: E402
from distar_tpu.model import TOKEN_MODELS, NemotronH, default_nemotron_h_config  # noqa: E402
from distar_tpu.ops import moe, ssm  # noqa: E402
from distar_tpu.ops.sequence import CausalGQAttention, causal_conv  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

TINY = {"hidden_size": 64, "hybrid_override_pattern": "MEM*E", "mamba_num_heads": 8, "mamba_head_dim": 8,
        "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 16, "num_experts_per_tok": 3,
        "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
        "experts_held": {"offset": 4, "count": 4}, "vocab_size": 128}
B, S = 2, 20  # two chunks of 8 and a part of a third


def build(seed=0, scale=5.0, **over):
    """The tiny model with seeded weights, its matrices widened by ``scale``
    so that each part moves the logits and a fault in any of them shows (see
    ``tests/test_lfm2.py``)."""
    cfg = deep_merge_dicts(default_nemotron_h_config(), dict(TINY, **over))
    model = NemotronH(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    params = jax.tree.map(lambda x: x * scale if x.ndim >= 2 else x, variables["params"])
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
    # float32 against float32 on one backend: what differs is the order of the sums (products by
    # chunk against a step a position, a sorted buffer against a masked loop over experts)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_array_equal(stats["rows"], np.stack(ref_stats["rows"]))
    np.testing.assert_allclose(stats["rms"], np.stack(ref_stats["rms"]), rtol=1e-5)
    np.testing.assert_allclose(stats["mixer_rms"], np.stack(ref_stats["mixer_rms"]), rtol=1e-4)
    assert list(stats["ssm_state_rms"]) == ["layer_0", "layer_2"]       # by layer: the pattern's M
    np.testing.assert_allclose(list(stats["ssm_state_rms"].values()), np.stack(ref_stats["ssm_state_rms"]), rtol=1e-4)
    assert stats["rows"].shape == (2, 4) and int(stats["overflow"]) == 0
    flat, ref_flat = leaves(grads), leaves(ref_grads)
    assert flat.keys() == ref_flat.keys() and len(flat) == 2 * 9 + 5 + 2 * 6 + 3
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
    # the statistic that sees it: of the first M layer (its output, or its last state), of the first E layer
    name, at = {"skip": ("mixer_rms", 0), "dt_bias": ("ssm_state_rms", 0), "carry": ("ssm_state_rms", 0)}.get(
        without, ("mixer_rms", 1))
    assert abs(float(less_stats[name][at]) / float(stats[name][at]) - 1) > 0.01
    if at == 1:  # the layer's input is the whole model's: the same rows, another output
        np.testing.assert_array_equal(np.stack(less_stats["rows"])[0], np.stack(stats["rows"])[0])


def scan_inputs(S, seed=0, b=2, H=4, P=8, G=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, S, H, P)), jax.nn.softplus(jax.random.normal(k[1], (b, S, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))), jax.random.normal(k[3], (b, S, G, N)),
            jax.random.normal(k[4], (b, S, G, N)))


def literal(x, dt, A, B, C):
    """``plain.recurrence``, a step a position, over the batch."""
    ys, lasts = zip(*(plain.recurrence(x[i], dt[i], A, B[i], C[i]) for i in range(x.shape[0])))
    return jnp.stack(ys), jnp.stack(lasts)


@pytest.mark.parametrize("S", (5, 16, 37, 64), ids=("part_of_a_chunk", "one_chunk", "chunks_and_a_part", "four_chunks"))
@pytest.mark.parametrize("grad", (False, True), ids=("value", "grad"))
def test_chunked_scan_is_the_literal_recurrence(S, grad):
    args = scan_inputs(S)
    if not grad:
        (y, last), (want, want_last) = ssm.chunked_scan(*args, 16), literal(*args)
        assert y.shape == (2, S, 4, 8) and last.shape == (2, 4, 8, 16)
        np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(last, want_last, atol=2e-5, rtol=1e-5)
        return
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, S, 4, 8))
    scalar = lambda f: lambda *a: (f(*a)[0] * weight).sum() + jnp.square(f(*a)[1]).sum()
    got = jax.grad(scalar(lambda *a: ssm.chunked_scan(*a, 16)), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(scalar(literal), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()), rtol=0, err_msg=name)


def test_chunked_scan_holds_through_decays_that_underflow():
    """``dt A`` of -80 a position: ``exp`` of a chunk's sum is 0 in float32,
    and no product of a large and a small factor is formed on the way."""
    x, dt, A, B, C = scan_inputs(32)
    A = A.at[0].set(-80.0).at[1].set(-1e-4)
    y, last = ssm.chunked_scan(x, dt, A, B, C, 16)
    want, want_last = literal(x, dt, A, B, C)
    assert np.isfinite(y).all() and np.isfinite(last).all()
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    g = jax.grad(lambda dt: ssm.chunked_scan(x, dt, A, B, C, 16)[0].sum())(dt)
    assert np.isfinite(g).all()


# the scan's two Pallas kernels, interpreted: heads of 64 two to a lane tile and a state of 128 as published, chunks
# of 16 (a grid step is four of them, 64 positions) where the case's length is a few dozen positions
KERNEL_CASES = {"two_whole_grid_steps": dict(S=128), "steps_and_a_part_padded_with_dt_0": dict(S=70),
                "one_chunk": dict(S=16), "eight_heads_a_group": dict(S=40, H=8, G=1),
                "decays_that_underflow_and_that_hardly_decay": dict(S=40, A=(-80.0, -1e-4)),
                "the_published_chunk": dict(S=130, chunk=128, b=1, H=2, G=1)}


def kernel_scan(chunk):
    return lambda x, dt, A, B, C: ssm._scan_kernel(x, dt, dt * A, B, C, chunk, True)


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_in_interpret_mode_are_the_literal_recurrence_forward_and_backward(case, executables_dropped):
    """``ops.ssm._scan_kernel`` (the forward and the backward Pallas kernel, interpreted on the CPU): ``y``, the
    last state and all five gradients (``A``'s by the chain rule through ``dt A``)."""
    kw = dict(KERNEL_CASES[case])
    S, chunk, A_of = kw.pop("S"), kw.pop("chunk", 16), kw.pop("A", ())
    x, dt, A, B, C = scan_inputs(S, **{"H": 4, "P": 64, "G": 2, "N": 128, **kw})
    args = (x, dt, A.at[:len(A_of)].set(jnp.asarray(A_of)) if A_of else A, B, C)
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    score = lambda f: lambda *a: (lambda y, last: ((y * weight).sum() + jnp.square(last).sum(), (y, last)))(*f(*a))
    with jax.default_matmul_precision("highest"):     # under jit: eagerly the interpreter dispatches a kernel op by op
        (_, (y, last)), got = jax.jit(jax.value_and_grad(score(kernel_scan(chunk)), range(5), has_aux=True))(*args)
        (_, (want, want_last)), grads = jax.value_and_grad(score(literal), range(5), has_aux=True)(*args)
    assert y.shape == x.shape and last.shape == (x.shape[0], x.shape[2], 64, 128) and np.isfinite(y).all()
    # float32 against float32: sums over a state of 128 taken in another order (the XLA form reads the same)
    np.testing.assert_allclose(y, want, atol=1e-6 * float(jnp.abs(want).max()) + 2e-5, rtol=1e-4)
    np.testing.assert_allclose(last, want_last, atol=1e-6 * float(jnp.abs(want_last).max()) + 2e-5, rtol=1e-4)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, grads):
        assert np.isfinite(g).all(), name
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()), rtol=0, err_msg=name)


def test_the_kernels_keep_their_operands_dtype_and_round_where_the_xla_form_rounds(executables_dropped):
    """bfloat16 operands, ``dt`` and ``A`` drawn as the layer draws them (steps of 1e-3 to 1e-1, ``A`` = -1, -2, ..),
    two chunks of the published 128 positions: products of bfloat16 values summed in float32, the weights rounded once, the state float32.
    Each gradient's distance from the float32 recurrence's is about the XLA form's own at bfloat16. ``A``'s is the
    one that tells: it is a sum of running sums of ``a``'s cotangent, whose row and column parts cancel only when
    both are sums of one matrix (taken from ``<dy_i, y_i>`` and the weights' column sums, which round apart, it read
    0.015 here where the XLA form reads 0.003, and was 0.9 off on the chip: PERF.md section 6, PR 40; float32 operands
    and chunks of 16 show nothing of it)."""
    bf = jnp.bfloat16
    b, S, H, P, G, N, Q = 1, 256, 4, 64, 2, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    step = jnp.exp(jax.random.uniform(ks[1], (H,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    args = (jax.random.normal(ks[0], (b, S, H, P)).astype(bf), step * jnp.exp(0.5 * jax.random.normal(ks[2], (b, S, H))),
            -jnp.arange(1.0, H + 1.0), jax.random.normal(ks[3], (b, S, G, N)).astype(bf),
            jax.random.normal(ks[4], (b, S, G, N)).astype(bf))
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    score = lambda f: lambda *a: (lambda y, last: ((y * weight).sum() + 1e-3 * jnp.square(last).sum(), (y, last)))(*f(*a))
    (_, (y, last)), got = jax.jit(jax.value_and_grad(score(kernel_scan(Q)), range(5), has_aux=True))(*args)
    (_, (xla_y, _)), xla = jax.value_and_grad(score(lambda *a: ssm._scan_xla(*a, Q, bf)), range(5), has_aux=True)(*args)
    with jax.default_matmul_precision("highest"):
        (_, (want, want_last)), grads = jax.value_and_grad(
            score(lambda x, dt, A, B, C: ssm._scan_xla(x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
                                                       C.astype(jnp.float32), Q, jnp.float32)), range(5), has_aux=True)(*args)
    assert y.dtype == last.dtype == jnp.float32 and y.shape == (b, S, H, P)
    off = lambda g, w: float(jnp.linalg.norm(g.astype(jnp.float32) - w) / jnp.linalg.norm(w))
    assert off(y, want) < 2 * off(xla_y, want) + 1e-3 and off(last, want_last) < 1e-2
    for name, g, x, w in zip(("x", "dt", "A", "B", "C"), got, xla, grads):
        assert g.dtype == x.dtype and g.shape == x.shape, name
        assert off(g, w) < 2 * off(x, w) + 2e-3, (name, off(g, w), off(x, w))


@pytest.mark.parametrize("refused", ("head_of_8", "state_of_16"))
def test_a_shape_the_kernels_tiles_refuse_takes_the_xla_form(refused, monkeypatch):
    shape = dict({"H": 4, "P": 64, "G": 2, "N": 128}, **{"head_of_8": dict(P=8), "state_of_16": dict(N=16)}[refused])
    assert not ssm.kernel_takes(shape["P"], shape["N"], shape["G"], shape["H"], 16)
    assert ssm.kernel_takes(64, 128, 8, 64, 128) and not ssm.kernel_takes(64, 128, 8, 64, 8)   # published; tiny's chunk
    assert not ssm.kernel_takes(64, 128, 4, 12, 128)                                           # three heads a group
    assert not ssm.kernel_takes(128, 128, 8, 32, 128)                                          # a head a whole lane tile

    def never(*a):
        raise AssertionError("the kernel was asked")

    monkeypatch.setattr(ssm, "_scan_kernel", never)
    args = scan_inputs(24, **shape)
    (y, last), (want, want_last) = jax.jit(lambda *a: ssm.chunked_scan(*a, 16))(*args), literal(*args)
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(last, want_last, atol=1e-4, rtol=1e-4)
    # and a shape the tiles take does ask it: under jit the branch for a TPU is traced whatever the platform
    with pytest.raises(AssertionError, match="the kernel was asked"):
        jax.jit(lambda *a: ssm.chunked_scan(*a, 16))(*scan_inputs(24, H=4, P=64, G=2, N=128))


def test_off_the_tpu_a_shape_the_tiles_take_runs_the_xla_form_under_both_branches():
    """``jax.lax.platform_dependent`` traces the kernels' branch and the XLA form's and lowers, here, the second:
    value and gradients through the pair are the literal recurrence's, and no ``pallas_call`` is interpreted."""
    args = scan_inputs(40, H=4, P=64, G=2, N=128)
    ours = lambda *a: ssm.chunked_scan(*a, 16)
    score = lambda f: lambda *a: jnp.square(f(*a)[0]).sum() + jnp.square(f(*a)[1]).sum()
    with jax.default_matmul_precision("highest"):
        want = literal(*args)[0]
        np.testing.assert_allclose(jax.jit(ours)(*args)[0], want, atol=1e-6 * float(jnp.abs(want).max()) + 2e-5, rtol=1e-4)
        got = jax.jit(jax.grad(score(ours), argnums=range(5)))(*args)
        grads = jax.grad(score(literal), argnums=range(5))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, grads):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()), rtol=0, err_msg=name)
    lowered = jax.jit(ours).lower(*args).as_text()
    assert "tpu_custom_call" not in lowered and "while" in lowered     # the XLA form's loop over groups of heads


def test_mixer_is_causal_and_has_the_published_parameters():
    mixer = ssm.Mamba2Mixer(heads=8, head_dim=8, groups=2, state=16, conv_kernel=4, chunk=8)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32))
    v = mixer.init(jax.random.PRNGKey(1), u)
    p = v["params"]
    assert p["in_proj"]["kernel"].shape == (32, 2 * 64 + 2 * 32 + 8) and p["out_proj"]["kernel"].shape == (64, 32)
    assert p["conv_kernel"].shape == (4, 64 + 2 * 32) and p["conv_bias"].shape == (128,)
    assert p["gated_norm"]["scale"].shape == (64,) and {k: p[k].shape for k in ("A_log", "D", "dt_bias")} == {
        "A_log": (8,), "D": (8,), "dt_bias": (8,)}
    np.testing.assert_allclose(jnp.exp(p["A_log"]), np.arange(1.0, 9.0), rtol=1e-6)
    np.testing.assert_array_equal(p["D"], 1.0)
    dt0 = jax.nn.softplus(p["dt_bias"])          # drawn log-uniform in [time_step_min, time_step_max]
    assert float(dt0.min()) >= 1e-3 * (1 - 1e-4) and float(dt0.max()) <= 0.1 * (1 + 1e-4)
    later = u.at[:, 13:].add(1.0)
    out, state_rms = mixer.apply(v, u)
    np.testing.assert_allclose(out[:, :13], mixer.apply(v, later)[0][:, :13], atol=1e-6)
    assert not np.allclose(out[:, 13:], mixer.apply(v, later)[0][:, 13:]) and float(state_rms) > 0


def test_causal_convolution_takes_four_taps_and_a_bias():
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    c = causal_conv(z, w, b)
    for t in range(10):
        want = sum(w[k] * (z[:, t - 3 + k] if t - 3 + k >= 0 else 0.0) for k in range(4)) + b
        np.testing.assert_allclose(c[:, t], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(causal_conv(z, w), c - b, rtol=1e-6, atol=1e-6)


def test_gated_norm_is_over_groups_of_channels():
    y, z = (jax.random.normal(jax.random.PRNGKey(i), (3, 5, 24)) for i in (0, 1))
    norm = ssm.GroupedRMSNormGated(group=8)
    v = norm.init(jax.random.PRNGKey(2), y, z)
    t = (y * jax.nn.silu(z)).reshape(3, 5, 3, 8)
    want = (t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5)).reshape(3, 5, 24)
    np.testing.assert_allclose(norm.apply(v, y, z), want, rtol=1e-5, atol=1e-6)


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """128 experts, top-6, scale 2.5, tiny widths: the routed part as each of
    the sixteen members of an expert-parallel group computes it (experts 0-7,
    8-15, ...), summed, plus the shared expert that each of them computes
    alike COUNTED ONCE, is the reference's layer over all 128 experts."""
    d, width, shared, E, k = 32, 16, 24, 128, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, d))
    whole = moe.ExpertsHeldMoE(E, k, width, 0, E, scaling=2.5, body="relu2", shared_width=shared)
    variables = whole.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    cut = {"num_experts_per_tok": k, "experts_held": {"offset": 0, "count": E}, "use_expert_bias": True,
           "routed_scaling_factor": 2.5}
    u = plain.rms_norm(x, p["norm"]["scale"], 1e-5).reshape(-1, d)
    bias = variables["buffers"]["expert_bias"]
    with jax.default_matmul_precision("highest"):
        want, want_rows, _ = plain.experts_held(p, bias, u, cut, None)
        shared_part = plain.relu2_expert(u, p["shared_w1"], p["shared_w2"], None)
    total, rows = 0.0, []
    for member in range(16):
        held = slice(8 * member, 8 * member + 8)
        share = moe.ExpertsHeldMoE(E, k, width, 8 * member, 8, scaling=2.5, body="relu2", shared_width=shared)
        mine = {"params": {**p, "w1": p["w1"][held], "w2": p["w2"][held]}, "buffers": variables["buffers"]}
        y, stats = share.apply(mine, x)
        total = total + y.reshape(-1, d)
        rows.append(stats["rows"])
    assert float(jnp.abs(shared_part).max()) > 0.1
    np.testing.assert_allclose(total - 15 * shared_part, want, atol=2e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.concatenate(rows), want_rows)
    assert int(np.concatenate(rows).sum()) == 2 * 24 * k  # every pick is somebody's
    assert "w3" not in p  # two matrices an expert


def test_the_bias_moves_the_selection_and_not_the_weights_which_carry_the_scale():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0]])
    s = jax.nn.sigmoid(logits[0])
    sel, w = moe.route(logits, jnp.zeros((5,)), 3, 2.5)
    assert sorted(sel[0].tolist()) == [0, 1, 2] and float(w.sum()) == pytest.approx(2.5, rel=1e-5)
    sel, w = moe.route(logits, jnp.asarray([0.0, 0.0, 0.0, 0.0, 5.0]), 3, 2.5)   # the bias picks expert 4...
    assert sorted(sel[0].tolist()) == [0, 1, 4]
    picked = s[jnp.asarray([0, 1, 4])]                                               # ...at its own score's weight
    np.testing.assert_allclose(sorted(w[0].tolist()), sorted((2.5 * picked / (picked.sum() + 1e-6)).tolist()),
                               rtol=1e-6)


@pytest.mark.parametrize("S", (16, 128), ids=("s16", "s128_flash_shaped"))
def test_attention_without_positions_is_blind_to_rope_theta_and_has_no_head_norms(S):
    u = jax.random.normal(jax.random.PRNGKey(0), (1, S, 32))
    att = CausalGQAttention(heads=4, kv_heads=2, head_dim=8, rope_theta=1e4, positions=False)
    v = att.init(jax.random.PRNGKey(1), u)
    assert sorted(v["params"]) == ["k_proj", "o_proj", "q_proj", "v_proj"]
    other = CausalGQAttention(heads=4, kv_heads=2, head_dim=8, rope_theta=1e6, positions=False)
    np.testing.assert_array_equal(att.apply(v, u), other.apply(v, u))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(att.apply(v, u), plain.attention(v["params"], u, 4, 2, 8, None),
                                   atol=1e-5, rtol=1e-4)
    later = u.at[:, 9:].add(1.0)
    np.testing.assert_allclose(att.apply(v, u)[:, :9], att.apply(v, later)[:, :9], atol=1e-6)
    # with positions (LFM2's attention) theta matters and the head norms are there
    rotary = CausalGQAttention(heads=4, kv_heads=2, head_dim=8, rope_theta=1e4)
    vr = rotary.init(jax.random.PRNGKey(1), u)
    assert {"q_norm", "k_norm"} <= set(vr["params"])
    assert not np.allclose(rotary.apply(vr, u), CausalGQAttention(4, 2, 8, rope_theta=1e6).apply(vr, u))
    # and the model's config: a change of rope_theta moves nothing
    cfg, model, variables, tokens, _ = build()
    moved = NemotronH(deep_merge_dicts(cfg, {"rope_theta": 123.0}))
    np.testing.assert_array_equal(model.apply(variables, tokens)[0], moved.apply(variables, tokens)[0])


def test_a_buffer_has_a_program_for_one_chunk_two_and_the_whole_and_runs_the_first_that_fits():
    sel = jnp.zeros((8, 6), jnp.int32)
    lengths = lambda k, count: moe._lengths(8, moe.dispatch(sel[:, :k], 0, count))
    assert lengths(6, 8) == [8, 16, 48] and lengths(4, 8) == [8, 16, 32]
    assert lengths(3, 8) == [8, 16, 24] and lengths(2, 1) == [8] and lengths(6, 2) == [8, 16]
    plan = moe.dispatch(sel, 0, 8)
    for chunks, program in ((1, 0), (2, 1), (3, 2), (4, 2), (6, 2)):
        assert int(moe._program(8, plan._replace(chunks=jnp.asarray(chunks)))) == program


def test_the_learner_finds_both_models_by_their_published_model_type():
    assert set(TOKEN_MODELS) >= {"lfm2_moe", "nemotron_h", "deepseek_v3"}   # a superset: later models only add
    assert TOKEN_MODELS["nemotron_h"][0] is NemotronH
    assert NemotronH.moe_layers({"hybrid_override_pattern": "MEMEM*EME"}) == [1, 3, 6, 8]
    assert TOKEN_MODELS["lfm2_moe"][0].moe_layers({"num_dense_layers": 1, "layer_types": ["conv"] * 5}) == [1, 2, 3, 4]
    assert default_nemotron_h_config().hybrid_override_pattern == "MEMEM*EME"
    with pytest.raises(ValueError, match="'M', '\\*' or 'E'"):
        cfg = deep_merge_dicts(default_nemotron_h_config(), dict(TINY, hybrid_override_pattern="M-"))
        NemotronH(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

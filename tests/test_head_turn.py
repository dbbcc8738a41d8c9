"""``ops/head_turn.py``: from a projection's result to the attention core's operand in one pass (head norm,
rotation, scale, the kernel's layout), its two Pallas kernels in interpret mode on the CPU against the XLA form
(``ops.sequence.head_turn_xla``), value and gradients; the rule that hands ``CausalGQAttention``'s q/k
preparations to it; and the counter of that rule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distar_tpu.model import TOKEN_MODELS, default_laguna_config
from distar_tpu.ops import head_turn, sequence
from distar_tpu.utils import deep_merge_dicts

# the published full layer's YaRN: its table's keys, and the factor on cos and sin
FULL = default_laguna_config()["rope_parameters"]["full_attention"]
YARN = {k: FULL[k] for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow")}
yarn_table = lambda: jnp.asarray(sequence.yarn_inv_freq(64, FULL["rope_theta"], **YARN), jnp.float32)
# positions, heads, head size, rotary dimensions, the table's frequencies, the factor on cos and sin, the norm's
# weight (None: no norm; "1+w": a zero-centred norm's sum), whether the core's scale is multiplied in (queries)
CASES = {
    "laguna_sliding_queries_whole_head_of_128": (256, 3, 128, 128, lambda: sequence.rope_inv_freq(128, 1e4), None, "w", True),
    "laguna_sliding_keys_no_scale": (128, 4, 128, 128, lambda: sequence.rope_inv_freq(128, 1e4), None, "w", False),
    "laguna_full_yarn_first_64_of_128_with_the_factor": (
        1024, 2, 128, 64, yarn_table, FULL["attention_factor"], "w", True),
    "qwen3_next_rope_first_64_of_256_zero_centred": (128, 5, 256, 64, lambda: sequence.rope_inv_freq(64, 1e7), None, "1+w", True),
    "no_norm_weight": (128, 2, 128, 128, lambda: sequence.rope_inv_freq(128, 1e6), None, None, True),
    "no_norm_no_rotation_scale_and_layout_alone": (128, 6, 128, None, lambda: None, None, None, True),
}


@pytest.mark.parametrize("case", CASES)
def test_the_fused_pass_is_the_xla_form_on_value_and_both_gradients(case, rng):
    S, H, D, R, table, factor, weight, scaled = CASES[case]
    assert head_turn.takes(S, D, R)
    B, eps, scale, inv_freq = 2, 1e-6, D ** -0.5 if scaled else None, table()
    x = jnp.asarray(3.0 * rng.standard_normal((B, S, H * D)), jnp.bfloat16)
    w = None if weight is None else jnp.asarray(0.3 * rng.standard_normal(D), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    scale_of = lambda w: None if w is None else 1.0 + w            # both weights are a sum here: the gradient passes through
    tables = None if inv_freq is None else head_turn.turn_tables(S, D, inv_freq, factor)
    fused = lambda x, w: head_turn.head_turn(x, H, scale_of(w), tables, R, scale, eps, interpret=True)
    xla = lambda x, w: sequence.head_turn_xla(x, H, scale_of(w), inv_freq, factor, scale, eps)
    (got, got_vjp), (want, want_vjp) = jax.vjp(fused, x, w), jax.vjp(xla, x, w)
    assert got.shape == (B, H, S, D) and got.dtype == jnp.bfloat16
    f32 = lambda t: np.asarray(t, np.float32)
    # a bfloat16 keeps 8 bits: one step of the last is 2^-8 of the value's power of two
    np.testing.assert_allclose(f32(got), f32(want), rtol=2 ** -7, atol=2 ** -7 * float(jnp.max(jnp.abs(want))) / 64)
    assert np.mean(f32(got) == f32(want)) > 0.99 and float(jnp.std(want.astype(jnp.float32))) > 0.05
    (dx, dw), (want_dx, want_dw) = got_vjp(dy), want_vjp(dy)
    assert dx.shape == x.shape and dx.dtype == x.dtype
    np.testing.assert_allclose(f32(dx), f32(want_dx), rtol=2 ** -6, atol=2 ** -7 * float(jnp.max(jnp.abs(want_dx))) / 16)
    assert float(jnp.std(want_dx.astype(jnp.float32))) > 1e-3
    if w is not None:
        assert dw.shape == (D,) and dw.dtype == jnp.float32 and float(jnp.std(want_dw)) > 0.1
        np.testing.assert_allclose(dw, want_dw, rtol=1e-3, atol=1e-3 * float(jnp.max(jnp.abs(want_dw))))
    else:
        assert dw is None and want_dw is None


def test_the_tables_are_turns_cos_and_sin_with_the_sign_and_ones_and_zeros_beyond_the_rotation():
    inv_freq = yarn_table()
    cos, sin = head_turn.turn_tables(40, 128, inv_freq, 1.5)
    angle = np.arange(40, dtype=np.float32)[:, None] * np.asarray(inv_freq)[None, :]
    np.testing.assert_allclose(cos[:, :64], 1.5 * np.concatenate([np.cos(angle)] * 2, axis=-1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin[:, :64], 1.5 * np.concatenate([-np.sin(angle), np.sin(angle)], axis=-1), rtol=1e-5, atol=1e-4)
    assert np.all(np.asarray(cos[:, 64:]) == 1.0) and np.all(np.asarray(sin[:, 64:]) == 0.0) and cos.dtype == jnp.float32


@pytest.mark.parametrize("S,D,R,taken", [(16384, 128, 128, True), (16384, 128, 64, True), (8192, 256, 64, True),
                                         (8192, 64, 64, False), (8192, 192, 64, False), (200, 128, 128, False),
                                         (128, 128, None, True), (128, 128, 130, False), (128, 128, 63, False)])
def test_the_rule_takes_heads_of_whole_lane_tiles_over_whole_blocks_of_positions(S, D, R, taken):
    assert head_turn.takes(S, D, R) is taken


def attention_layer_as_it_was(layer, params, u):
    """``CausalGQAttention`` with positions, no gate: the projections, RMSNorm over each head, ``rope`` over the whole
    head, ``causal_attention``, ``o_proj``, written out."""
    B, S, _ = u.shape
    H, Hkv, D = layer.heads, layer.kv_heads, layer.head_dim
    product = lambda name: (u.astype(layer.dtype) @ params[name]["kernel"].astype(layer.dtype))

    def norm(x, scale):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + layer.eps)
        return (y * scale).astype(x.dtype)

    q = sequence.rope(norm(product("q_proj").reshape(B, S, H, D), params["q_norm"]["scale"]), layer.rope_theta)
    k = sequence.rope(norm(product("k_proj").reshape(B, S, Hkv, D), params["k_norm"]["scale"]), layer.rope_theta)
    out = sequence.causal_attention(q.reshape(B, S, Hkv, H // Hkv, D), k, product("v_proj").reshape(B, S, Hkv, D), D ** -0.5)
    return out.reshape(B, S, H * D) @ params["o_proj"]["kernel"].astype(layer.dtype)


@pytest.mark.parametrize("D,S,fused", [(64, 128, False), (128, 96, False), (128, 128, True)],
                         ids=["lfm2_head_of_64_refused", "positions_no_block_divides_refused", "head_of_128_taken"])
def test_a_refused_shape_and_every_platform_but_a_tpu_get_bit_for_bit_what_they_got(D, S, fused):
    """A shape the rule refuses holds no trace of the fused function; a shape it takes holds it for a TPU and, anywhere
    else, computes what the layer computed before, bit for bit."""
    layer = sequence.CausalGQAttention(4, 2, D, rope_theta=1e6, dtype=jnp.bfloat16)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, S, 48), jnp.bfloat16)
    variables = layer.init(jax.random.PRNGKey(1), u)
    params = jax.tree.map(lambda x: x * 4.0 + 0.1, variables["params"])
    with sequence.heads_fused() as log:
        jaxpr = str(jax.make_jaxpr(lambda p, u: layer.apply({"params": p}, u))(params, u))
    assert log == [fused] * 2 and ("head_turn_fwd" in jaxpr) is fused
    got = jax.jit(lambda p, u: layer.apply({"params": p}, u))(params, u)
    want = jax.jit(lambda p, u: attention_layer_as_it_was(layer, p, u))(params, u)
    assert float(jnp.std(want.astype(jnp.float32))) > 0.1 and np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    grads = jax.jit(jax.grad(lambda p, u: jnp.sum(layer.apply({"params": p}, u).astype(jnp.float32) ** 2)))(params, u)
    want_grads = jax.jit(jax.grad(lambda p, u: jnp.sum(attention_layer_as_it_was(layer, p, u).astype(jnp.float32) ** 2)))(params, u)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        assert np.array_equal(a, b) and np.any(np.asarray(a) != 0), jax.tree_util.keystr(path)


# what the learner's gauge reads: (fused, xla) q/k preparations of a model's trace, two an attention layer with positions
COUNTS = {"laguna": ({}, 64, (10, 0)),
          "laguna_heads_of_16": ({"head_dim": 16, "hidden_size": 64, "num_attention_heads_per_layer": [4, 8, 8, 8, 4],
                                  "num_key_value_heads": 4, "kv_heads_held": {"count": 2}}, 64, (0, 10)),
          "phi4flash": ({}, 64, (0, 0)),
          "qwen3_next": ({}, 128, (2, 0)),
          "lfm2_moe": ({}, 128, (0, 2))}


@pytest.mark.parametrize("which", COUNTS)
def test_the_counter_reads_which_form_a_models_qk_preparations_take(which):
    """``laguna``'s published file: ten preparations (five layers, q and k), all fused; ``phi4flash``'s differential
    attention has none; a head of 16 or 64 stays with the XLA form."""
    over, S, (fused, xla) = COUNTS[which]
    model_cls, defaults = TOKEN_MODELS[which.split("_heads")[0]]
    # the published widths, few rows: only shapes are traced
    small = {"vocab_size": 256, "num_experts": 16, "experts_held": {"offset": 0, "count": 2}} if which.startswith("laguna") else {}
    model = model_cls(deep_merge_dicts(defaults(), dict(small, **over)))
    with sequence.heads_fused() as log:
        jax.eval_shape(model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, S * 2), jnp.int32))
    assert (sum(log), len(log) - sum(log)) == (fused, xla)
    with sequence.heads_fused() as again:
        pass
    assert again == []

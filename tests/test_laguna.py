"""``laguna`` (``model/laguna.py``; ``ops/sequence.py``'s banded attention,
YaRN table and per-head gate; ``ops/moe.py``'s softmax router with a scale and
an ungated shared expert) against its plain reference
(``benchmark/references/laguna_plain.py``, which imports none of them) at a
tiny size on seeded weights, float32, on the CPU."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import laguna_plain as plain  # noqa: E402
from distar_tpu.model import TOKEN_MODELS, Laguna, default_laguna_config, laguna  # noqa: E402
from distar_tpu.ops import moe, sequence  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 128.0, "original_max_position_embeddings": 8192,
        "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads_per_layer": [4, 8, 8, 8, 4],
        "num_key_value_heads": 4, "kv_heads_held": {"count": 2}, "head_dim": 16, "sliding_window": 5,
        # YaRN from 16 positions: at 20 positions the interpolated pairs have turned far enough to show
        "rope_parameters": {"full_attention": dict(YARN, original_max_position_embeddings=16, rope_theta=100.0)},
        "num_experts": 16, "num_experts_per_tok": 3, "moe_intermediate_size": 24,
        "shared_expert_intermediate_size": 20, "experts_held": {"offset": 4, "count": 4}, "vocab_size": 128}
B, S = 2, 20


def build(seed=0, scale=5.0, **over):
    """The tiny model with seeded weights, its matrices widened by ``scale``
    so that each part moves the logits and a fault in any of them shows (see
    ``tests/test_lfm2.py``)."""
    cfg = deep_merge_dicts(default_laguna_config(), dict(TINY, **over))
    model = Laguna(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    params = jax.tree.map(lambda x: x * scale if x.ndim >= 2 else x, variables["params"])
    # the embedding is drawn at 1.0: at a tenth, the mixers are as large a part of the stream as the tokens
    params["embedding"] = variables["params"]["embedding"] * 0.1
    return cfg, model, {"params": params, "buffers": variables["buffers"]}, tokens, labels


def system_loss(model, variables, params, tokens, labels):
    from distar_tpu.losses import compute_lm_loss

    logits, stats = model.apply({**variables, "params": params}, tokens)
    return compute_lm_loss(logits, labels)[0], (logits, stats)


def leaves(tree):
    return dict(jax.tree_util.tree_flatten_with_path(tree)[0])


# ------------------------------------------------------------ the whole model
def test_the_model_is_the_plain_reference_on_loss_logits_and_every_statistic():
    cfg, model, variables, tokens, labels = build()
    with jax.default_matmul_precision("highest"):
        total, (logits, stats) = system_loss(model, variables, variables["params"], tokens, labels)
        want, (want_logits, want_stats) = plain.loss(variables["params"], variables, plain.plain_config(cfg), tokens, labels)
    assert float(jnp.std(want_logits)) > 0.3                     # the parts move the logits
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    assert float(total) == pytest.approx(float(want), rel=1e-6)
    for name in ("rms", "mixer_rms", "ff_rms"):
        np.testing.assert_allclose(stats[name], jnp.stack(want_stats[name]), rtol=1e-5, err_msg=name)
    assert sorted(stats["attn_gate_mean"]) == [f"layer_{i}" for i in range(5)]   # every layer has the gate
    np.testing.assert_allclose([stats["attn_gate_mean"][f"layer_{i}"] for i in range(5)],
                               jnp.stack(want_stats["attn_gate_mean"]), rtol=1e-6)
    assert np.array_equal(stats["rows"], jnp.stack(want_stats["rows"])) and stats["rows"].shape == (4, 4)
    assert int(stats["overflow"]) == 0 and int(stats["row_indexed"]) == 4
    gates = np.asarray([stats["attn_gate_mean"][f"layer_{i}"] for i in range(5)])
    assert np.all((gates > 0.3) & (gates < 0.7)) and np.std(gates) > 1e-3


def test_the_gradients_are_the_plain_references_for_every_leaf(monkeypatch):
    # two blocks of queries a sequence and two groups of rows a block: the reference's sum over blocks by hand,
    # its rows at a time and its layer at a time are all in this backward pass
    for name, value in (("QUERY_BLOCK", 10), ("KEY_STEP", 10), ("ROWS_AT_ONCE", 5)):
        monkeypatch.setattr(plain, name, value)
    cfg, model, variables, tokens, labels = build()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: system_loss(model, variables, p, tokens, labels)[0])(variables["params"])
        want = plain.gradients(variables, plain.plain_config(cfg), tokens, labels)
    got, want = leaves(got), leaves(want)
    assert set(got) == set(want) and len(got) == 1 * (7 + 3 + 2) + 4 * (7 + 8 + 1) + 3
    for path, g in want.items():
        assert float(jnp.linalg.norm(g)) > 0, path
        assert float(jnp.linalg.norm(got[path] - g) / jnp.linalg.norm(g)) < 2e-4, path


@pytest.mark.parametrize("without", plain.OMISSIONS)
def test_the_reference_without_a_term_is_another_model(without):
    """Each term the cell's limits are argued against moves what the tiny
    model reports: the statistic of the layer it belongs to, by far more than
    the program and the reference differ."""
    cfg, model, variables, tokens, labels = build()
    pc = plain.plain_config(cfg)
    with jax.default_matmul_precision("highest"):
        _, (logits, stats) = plain.loss(variables["params"], variables, pc, tokens, labels)
        _, (less, less_stats) = plain.loss(variables["params"], variables, pc, tokens, labels, without=(without,))
    assert float(jnp.max(jnp.abs(less - logits))) > 1e-3
    # the kind of layer the term belongs to: a sliding one (1-3), a full one (0, 4), an expert block (1-4)
    name, at = {"window": ("mixer_rms", (1, 2, 3)), "window_1024": ("mixer_rms", (1, 2, 3)),
                "window_minus_1": ("mixer_rms", (1, 2, 3)), "yarn": ("mixer_rms", (0, 4)), "yarn_factor": ("mixer_rms", (0, 4)),
                "rope_whole": ("mixer_rms", (0, 4)), "thetas": ("mixer_rms", range(5)), "gate": ("mixer_rms", range(5)),
                "scaling": ("ff_rms", (1, 2, 3, 4)), "shared": ("ff_rms", (1, 2, 3, 4))}[without]
    moved = max(abs(float(less_stats[name][i]) / float(stats[name][i]) - 1) for i in at)
    assert moved > 1e-3, (without, name, moved)


def test_the_references_slice_of_keys_is_every_key(monkeypatch):
    """A query block against the keys its band can reach, and against all of
    them: the same numbers (the reference's one departure in what it multiplies)."""
    cfg, model, variables, tokens, labels = build(sliding_window=3)
    pc = plain.plain_config(cfg)
    big = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0, 128)
    monkeypatch.setattr(plain, "QUERY_BLOCK", 4)                       # six blocks, a band of three keys
    monkeypatch.setattr(plain, "ROWS_AT_ONCE", 2)                      # two groups of rows a block
    with jax.default_matmul_precision("highest"):
        sliced = plain.forward(variables, pc, big)[0]
        whole = plain.forward(variables, pc, big, keys="all")[0]
    np.testing.assert_allclose(sliced, whole, atol=1e-6)


# --------------------------------------------------------- banded attention
def literal_attention(q, k, v, scale, window=None):
    """Every query against every key, the mask as the comparison of positions."""
    S = q.shape[1]
    score = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (i - j >= 0) if window is None else (i - j >= 0) & (i - j < window)
    return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1), v)


def qkv(S, Hkv=2, G=3, D=16, Dv=None, seed=0, b=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, S, Hkv, G, D)), jax.random.normal(ks[1], (b, S, Hkv, D)),
            jax.random.normal(ks[2], (b, S, Hkv, Dv or D)))


BANDS = {"three_query_blocks_a_window_that_does_not_divide_them": (1536, 700),
         "a_window_shorter_than_a_block": (1024, 100),
         "one_block_of_a_length_the_kernels_do_not_take": (200, 37),
         "a_window_of_one_block_and_one": (1536, 513),
         "the_published_window_over_four_blocks": (2048, 512)}


@pytest.mark.parametrize("case", BANDS)
def test_banded_attention_is_the_literal_mask_and_not_a_window_one_off(case):
    S, W = BANDS[case]
    q, k, v = qkv(S, Hkv=1, G=2, D=8)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: sequence.causal_attention(*a, 0.25, window=W))(q, k, v)
        want = literal_attention(q, k, v, 0.25, W)
        np.testing.assert_allclose(got, want, atol=1e-5)
        for other in (W - 1, W + 1):                   # the off-by-one the chip's tolerance cannot see
            assert float(jnp.max(jnp.abs(got - literal_attention(q, k, v, 0.25, other)))) > 1e-3, other
        if S > 1536:
            return
        # and its gradients, through the checkpointed loop over blocks
        f = lambda fn: jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
        for g, g_want in zip(f(lambda *a: sequence.causal_attention(*a, 0.25, window=W)),
                             f(lambda *a: literal_attention(*a, 0.25, W))):
            np.testing.assert_allclose(g, g_want, atol=2e-4)


def test_a_window_as_long_as_the_sequence_is_causal_attention():
    q, k, v = qkv(256)
    for W in (256, 1000):
        assert np.array_equal(sequence.causal_attention(q, k, v, 0.25, window=W), sequence.causal_attention(q, k, v, 0.25))


@pytest.mark.parametrize("S,W", [(64, 5), (1024, 512), (640, 129)])
def test_the_kernels_mask_is_the_literal_comparison(S, W):
    """What the TPU's kernel is told (splash attention's local mask, which its
    grid is cut from) is the band and not a window one off."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    literal = lambda w: (i - j >= 0) & (i - j < w)
    mask = np.asarray(sequence.band_mask(S, W)[:, :])
    assert mask.shape == (S, S) and np.array_equal(mask, literal(W))
    assert not np.array_equal(mask, literal(W - 1)) and not np.array_equal(mask, literal(W + 1))
    assert mask.sum(axis=1).max() == W and mask.sum(axis=1)[0] == 1


def attention_xla_as_it_was(q, k, v, scale: float):
    """``ops.sequence._attention_xla`` as it stood before ``causal_attention`` took a window (PR 37), verbatim."""
    B, S, Hkv, G, _ = q.shape
    block = min(S, sequence.XLA_QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        score = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                           preferred_element_type=jnp.float32) * scale
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(S)[None, :]
        prob = jax.nn.softmax(jnp.where(visible, score, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", prob.astype(v.dtype), v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hkv, G, v.shape[-1])


# one shape of each other model (a quarter of its key/value heads): key/value heads, group, head size, value head size
OTHERS = {"lfm2": (2, 4, 64, None), "nemotron_h": (1, 16, 128, None), "qwen3_next": (1, 8, 256, None),
          "deepseek_v3": (4, 1, 192, 128), "laguna_full_layer": (1, 6, 128, None)}


@pytest.mark.parametrize("which", OTHERS)
def test_without_a_window_every_caller_gets_bit_for_bit_what_it_got(which):
    Hkv, G, D, Dv = OTHERS[which]
    q, k, v = qkv(1024, Hkv, G, D, Dv, seed=3)
    before = jax.jit(lambda *a: attention_xla_as_it_was(*a, D ** -0.5))
    for now in (lambda *a: sequence.causal_attention(*a, D ** -0.5),
                lambda *a: sequence.causal_attention(*a, D ** -0.5, window=None)):
        assert np.array_equal(jax.jit(now)(q, k, v), before(q, k, v))
    # and the program is the same program: the same operations in the same order
    text = lambda fn: jax.jit(fn).lower(q, k, v).as_text()
    same = lambda t: t.replace("attention_xla_as_it_was", "_attention_xla")
    assert same(text(lambda *a: attention_xla_as_it_was(*a, D ** -0.5)).split("\n", 1)[1]) == \
        text(lambda *a: sequence._attention_xla(*a, D ** -0.5)).split("\n", 1)[1]


# what ``core_plan``'s table says the chip measured: (S, D, Dv, window) -> (query block, key block, the keys of it
# multiplied at a time, dQ in the fused backward kernel)
PLANS = {"lfm2_train_b4s8k": ((8192, 64, 64, None), (1024, 2048, 512, True)),
         "nemotron_twotower_train_b2s8k": ((8192, 128, 128, None), (1024, 2048, 512, True)),
         "kimi_vl_train_b2s8k": ((8192, 192, 128, None), (1024, 1024, 1024, True)),
         "qwen3_next_train_b2s8k": ((8192, 256, 256, None), (512, 512, 512, True)),
         "laguna_train_b1s16k_full_layer": ((16384, 128, 128, None), (1024, 2048, 512, True)),
         "laguna_train_b1s16k_banded_layer": ((16384, 128, 128, 512), (512, 512, 512, False))}


@pytest.mark.parametrize("cell", PLANS)
def test_the_kernel_rule_returns_what_the_chip_measured_at_every_cells_shape(cell):
    shape, plan = PLANS[cell]
    assert sequence.core_plan(*shape) == plan


@pytest.mark.parametrize("S,D,Dv,window", [(4096, 96, 96, None), (1536, 128, 128, None), (384, 64, 64, None), (640, 64, 64, None),
                                           (640, 128, 128, 512), (1536, 256, 256, None), (128, 192, 128, None)],
                         ids=lambda x: str(x))
def test_the_kernel_rule_between_the_measured_shapes_returns_tiles_the_kernel_accepts(S, D, Dv, window):
    """Splash attention takes tiles that are multiples of 128 positions and divide the sequence, and multiplies a
    key block in pieces that divide it; the rule hands it the largest such tiles under the measured ones."""
    q, kv, compute, fused = sequence.core_plan(S, D, Dv, window)
    q_most, kv_most, compute_most, fused_most = sequence.core_plan(1 << 20, D, Dv, window)   # every measured tile divides it
    for tile, most, whole in ((q, q_most, S), (kv, kv_most, S), (compute, compute_most, kv)):
        assert tile % 128 == 0 and whole % tile == 0 and tile <= most
        assert tile == whole or 2 * tile > most or whole % (2 * tile)  # and no smaller than the sequence forces
    assert fused == fused_most == (window is None)


def test_rope_is_bit_for_bit_what_it_was_and_partial_rope_with_it():
    """``rope`` now goes through the table-driven rotation that YaRN shares."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 16))

    def rope_as_it_was(x, theta):
        S, D = x.shape[1], x.shape[-1]
        inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
        x32 = x.astype(jnp.float32)
        a, b = jnp.split(x32, 2, axis=-1)
        return (x32 * cos + jnp.concatenate([-b, a], axis=-1) * sin).astype(x.dtype)

    assert np.array_equal(jax.jit(lambda x: sequence.rope(x, 1e6))(x), jax.jit(lambda x: rope_as_it_was(x, 1e6))(x))
    first = sequence.rope_first(x, 1e4, 8)
    assert np.array_equal(first[..., :8], rope_as_it_was(x[..., :8], 1e4)) and np.array_equal(first[..., 8:], x[..., 8:])


# ------------------------------------------------------------------- YaRN
def test_yarn_table_is_the_formula_with_9_and_18_as_the_ramps_ends():
    p = {k: YARN[k] for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow")}
    table = sequence.yarn_inv_freq(64, YARN["rope_theta"], **p)
    j = np.arange(32)
    f = 500000.0 ** (-2.0 * j / 64)
    edge = lambda beta: 64 * math.log(8192 / (2 * math.pi * beta)) / (2 * math.log(500000.0))
    assert (math.floor(edge(32)), math.ceil(edge(1))) == (9, 18)
    ramp = np.clip((j - 9) / (18 - 9), 0.0, 1.0)
    np.testing.assert_allclose(table, f * (1 - ramp) + f / 128 * ramp, rtol=1e-12)
    assert np.array_equal(table[:10], f[:10]) and np.allclose(table[18:], f[18:] / 128, rtol=1e-12)
    assert np.all((table[10:18] < f[10:18]) & (table[10:18] > f[10:18] / 128))
    assert YARN["attention_factor"] == pytest.approx(0.1 * math.log(128) + 1, rel=1e-12)   # the convention of the factor
    np.testing.assert_allclose(plain.inv_freq(YARN, 64), table, rtol=2e-6)                  # the reference writes it again


def test_yarn_rotates_the_first_half_of_a_head_and_scales_cos_and_sin():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 50, 2, 16))
    p = {k: YARN[k] for k in laguna.YARN_KEYS}
    got = sequence.yarn_first(x, 8, 500000.0, **p)
    assert np.array_equal(got[..., 8:], x[..., 8:])
    want = jnp.stack([plain.rotated(x[0, :, h][:, None], plain.inv_freq(YARN, 8), YARN["attention_factor"])[:, 0]
                      for h in range(2)], axis=1)[None]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # position 0 is not turned: there the factor alone shows
    np.testing.assert_allclose(got[0, 0, :, :8], x[0, 0, :, :8] * YARN["attention_factor"], rtol=1e-6)


# ----------------------------------------------------------- the attention layer
def test_the_per_head_gate_is_one_number_a_head_from_a_projection_of_its_own():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    layer = sequence.CausalGQAttention(6, 2, 8, 1e4, gate="head", window=5)
    variables = layer.init(jax.random.PRNGKey(1), u)
    shapes = jax.tree.map(lambda x: x.shape, variables["params"])
    assert shapes["g_proj"]["kernel"] == (32, 6) and shapes["q_proj"]["kernel"] == (32, 6 * 8)    # q_proj is not doubled
    params = jax.tree.map(lambda x: x * 20 if x.ndim == 2 else x, variables["params"])
    out, opened = layer.apply({"params": params}, u)
    gate = jax.nn.sigmoid(u @ params["g_proj"]["kernel"])                                       # [2, 24, 6]
    assert float(opened) == pytest.approx(float(jnp.mean(gate)), rel=1e-6) and float(jnp.std(gate)) > 0.1
    # a gate held open gives the heads' outputs as they are: the two differ by the gate, head by head
    shut = dict(params, g_proj={"kernel": jnp.zeros((32, 6))})
    half, opened_half = layer.apply({"params": shut}, u)
    assert float(opened_half) == 0.5
    ungated = sequence.CausalGQAttention(6, 2, 8, 1e4, window=5).apply(
        {"params": {k: v for k, v in params.items() if k != "g_proj"}}, u)
    np.testing.assert_allclose(half, 0.5 * ungated, atol=1e-5)
    with pytest.raises(ValueError, match="gate"):
        sequence.CausalGQAttention(6, 2, 8, gate="row").init(jax.random.PRNGKey(1), u)


def test_the_gates_other_models_pass_keep_their_parameters():
    """``gate=True`` (``qwen3_next``) still doubles ``q_proj`` and has no ``g_proj``; no gate, neither."""
    u = jnp.zeros((1, 8, 32))
    shapes = lambda **kw: jax.tree.map(lambda x: x.shape, jax.eval_shape(
        sequence.CausalGQAttention(4, 2, 8, **kw).init, jax.random.PRNGKey(0), u)["params"])
    assert shapes(gate=True)["q_proj"]["kernel"] == (32, 64) and "g_proj" not in shapes(gate=True)
    assert shapes()["q_proj"]["kernel"] == (32, 32) and "g_proj" not in shapes()
    assert sorted(shapes(positions=False)) == ["k_proj", "o_proj", "q_proj", "v_proj"]


# --------------------------------------------------------- the shares add up
def test_two_head_shares_and_all_expert_shares_add_up_to_the_uncut_layer():
    """One expert layer (a sliding one) of a model that holds everything, and
    its parts as the chips of a group would hold them: each of two chips' four
    key/value heads with their query heads, gates and rows of ``o_proj``, each
    of four chips' four experts. The shares' attention outputs add up to the
    uncut reference's; on that sum, the shares' routed outputs, with the
    shared expert and the router's weights counted once, add up to its
    feed-forward."""
    whole = {"num_key_value_heads": 8, "kv_heads_held": {"count": 8},
             "num_attention_heads_per_layer": [16, 24, 24, 24, 16], "experts_held": {"offset": 0, "count": 16}}
    cfg, model, variables, tokens, _ = build(**whole)
    pc, p = plain.plain_config(cfg), variables["params"]["layer_1"]
    D, d, eps = cfg.head_dim, cfg.hidden_size, cfg.rms_norm_eps
    x = jax.random.normal(jax.random.PRNGKey(7), (B, S, d))
    with jax.default_matmul_precision("highest"):
        u = plain.rms_norm(x, p["operator_norm"]["scale"], eps)
        want_attn, _ = plain.attention(p["attn"], u, pc, 1, None)
        attn = 0.0
        for share in range(2):
            part = deep_merge_dicts(cfg, {"kv_heads_held": {"count": 4}})
            H, Hkv = laguna.heads_here(part, 1)
            assert (H, Hkv) == (12, 4)
            cols = lambda w, heads: w.reshape(d, -1, D)[:, heads * share:heads * (share + 1)].reshape(d, -1)
            a = p["attn"]
            held = {"q_proj": {"kernel": cols(a["q_proj"]["kernel"], H)}, "k_proj": {"kernel": cols(a["k_proj"]["kernel"], Hkv)},
                    "v_proj": {"kernel": cols(a["v_proj"]["kernel"], Hkv)},
                    "g_proj": {"kernel": a["g_proj"]["kernel"][:, H * share:H * (share + 1)]},
                    "o_proj": {"kernel": a["o_proj"]["kernel"].reshape(-1, D, d)[H * share:H * (share + 1)].reshape(-1, d)},
                    "q_norm": a["q_norm"], "k_norm": a["k_norm"]}
            turn = cfg.rope_parameters["sliding_attention"]
            out, _ = sequence.CausalGQAttention(H, Hkv, D, turn["rope_theta"], eps, rotary_dim=D, gate="head",
                                                window=cfg.sliding_window).apply({"params": held}, u)
            attn = attn + out
        np.testing.assert_allclose(attn, want_attn, atol=2e-5)
        assert float(jnp.std(want_attn)) > 0.05
        h = x + attn
        m = p["moe"]
        un = plain.rms_norm(h, m["norm"]["scale"], eps).reshape(B * S, d)
        want_ff, rows, _ = plain.experts_held(m, None, un, pc, None)
        shared = plain.swiglu(un, m["shared_w1"], m["shared_w2"], m["shared_w3"], None)
        ff, routed_rows = 0.0, 0
        for share in range(4):
            mine = slice(4 * share, 4 * share + 4)
            held = dict(m, **{n: m[n][mine] for n in ("w1", "w2", "w3")})
            out, stats = moe.ExpertsHeldMoE(16, 3, 24, 4 * share, 4, cfg.moe_routed_scaling_factor, use_bias=False, eps=eps,
                                            shared_width=20, scoring="softmax").apply(
                {"params": held, "buffers": {"expert_bias": jnp.zeros((16,))}}, h)
            ff = ff + out.reshape(B * S, d) - shared                  # every member computes the shared expert alike
            routed_rows += int(stats["rows"].sum())
        np.testing.assert_allclose(ff + shared, want_ff, atol=2e-5)
        assert routed_rows == B * S * 3 == int(rows.sum())            # every pick is some member's row, once
        # the scale 2.5 is in the sum: without it the routed part is 2.5 times smaller
        assert float(jnp.std(want_ff - shared)) > 0.05


# -------------------------------------------------------- learner and scopes
def test_one_learner_step_reports_what_the_reference_computes(tmp_path):
    """``LMLearner`` finds the model by ``model.model_type`` alone and its
    first step's log is the reference's loss vector on the untrained weights."""
    from distar_tpu.learner.lm_learner import LMLearner, fake_token_batch

    learner = LMLearner({"common": {"experiment_name": "laguna_test", "save_path": str(tmp_path)},
                         "learner": {"batch_size": B, "unroll_len": S, "save_freq": 10 ** 9},
                         "model": dict(TINY, model_type="laguna")})
    assert type(learner.model) is Laguna and learner._moe_layers == [1, 2, 3, 4]
    batch = fake_token_batch(B, S, 128, np.random.default_rng(3))
    before = jax.tree.map(np.asarray, learner.state["params"])
    state = learner._state
    want = plain.first_step(learner, batch)     # it drops the learner's Adam moments, which it does not read
    learner._state = state
    log = learner._train(batch)
    for key, ref in want.items():
        if key != "forward_seconds":
            assert log[key] == pytest.approx(ref, rel=2e-3, abs=1e-6), key
    assert {"attn_gate_mean/layer_0", "attn_gate_mean/layer_4", "mixer_rms/layer_3", "ff_rms/layer_0", "residual_rms/layer_4",
            "moe_rows_sum/layer_1", "moe_rows_max/layer_4", "dyn/grad_norm/layer_0"} <= set(want) <= set(log) | {"forward_seconds"}
    assert "moe_rows_sum/layer_0" not in log and log["moe_overflow_rows"] == 0.0 and log["moe_row_indexed_layers"] == 4.0
    after = learner.state["params"]["params"]
    assert not np.array_equal(before["params"]["layer_1"]["attn"]["g_proj"]["kernel"], after["layer_1"]["attn"]["g_proj"]["kernel"])


def test_the_learner_finds_the_model_by_its_model_type_and_the_default_is_the_published_cut():
    assert TOKEN_MODELS["laguna"][0] is Laguna
    cfg = default_laguna_config()
    assert Laguna.moe_layers(cfg) == [1, 2, 3, 4]
    assert [laguna.heads_here(cfg, i) for i in range(5)] == [(24, 4), (36, 4), (36, 4), (36, 4), (24, 4)]
    shapes = jax.eval_shape(Laguna(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    params = shapes["params"]
    assert count(params["layer_0"]["attn"]) == 22_094_080 and count(params["layer_1"]["attn"]) == 31_568_128
    assert count(params["layer_0"]) == 135_346_432 and count(params["layer_1"]) == 117_295_360
    assert count(params["layer_4"]) == 107_821_312
    assert count(params["embedding"]) + count(params["lm_head"]) + count(params["final_norm"]) == 77_073_408
    assert count(params) == 672_127_232 and 10.7e9 < 16 * count(params) < 10.8e9
    assert params["layer_1"]["attn"]["g_proj"]["kernel"].shape == (3072, 36)
    assert params["layer_1"]["attn"]["o_proj"]["kernel"].shape == (36 * 128, 3072)
    assert params["layer_1"]["moe"]["router"].shape == (3072, 256) and "shared_gate" not in params["layer_1"]["moe"]
    for wrong, match in ((dict(norm_topk_prob=False), "norm_topk_prob"), (dict(gating=True), "gating"),
                         (dict(mlp_layer_types=["dense"]), "list the same layers")):
        with pytest.raises(ValueError, match=match):
            Laguna(deep_merge_dicts(cfg, dict(TINY, **wrong))).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_every_new_scope_is_on_the_lowered_steps_op_names():
    """``attn_proj``, ``attn_core`` and ``swa_core`` are on the op_name paths
    of the lowered ``lm_train_step`` beside the names every token model has,
    and no operation of an attention layer is under ``attention`` as a whole."""
    import optax

    from distar_tpu.learner.lm_learner import make_lm_train_step
    from distar_tpu.obs import LM_STEP_SCOPES, tree_spec

    cfg, model, variables, tokens, labels = build()
    optimizer = optax.adam(1e-3)
    step = jax.jit(make_lm_train_step(model, optimizer, dynamics=tree_spec({}, {"type": "none"})))
    text = step.lower(variables, optimizer.init(variables["params"]),
                      {"tokens": tokens, "labels": labels}).as_text(debug_info=True)
    there = {name for name in LM_STEP_SCOPES if f"/{name}" in text or f"({name})" in text}
    assert {"attn_proj", "attn_core", "swa_core", "dense_mlp", "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "moe_shared", "embed", "lm_head", "loss", "optimizer"} <= there
    assert not there & {"attention", "short_conv", "ssm_proj", "ssm_scan", "mla_proj", "mla_core", "gdn_proj", "gdn_scan"}
    # the kernels alone: no projection is under a core's name, and the projections are under ``attn_proj``
    lines = text.split("\n")
    assert not [ln for ln in lines if "_core/" in ln and "_proj" in ln.split("_core/")[1]]
    for name in ("q_proj", "k_proj", "v_proj", "g_proj", "o_proj", "q_norm"):
        assert [ln for ln in lines if f"/attn/attn_proj/{name}/" in ln], name
    assert [ln for ln in lines if "/layer_1/attn/swa_core/" in ln] and [ln for ln in lines if "/layer_0/attn/attn_core/" in ln]
    assert not [ln for ln in lines if "/layer_0/attn/swa_core/" in ln or "/layer_1/attn/attn_core/" in ln]

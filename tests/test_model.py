"""Model forward-mode tests on reduced shapes (full field schema, smaller
spatial map via config override) — mirrors the reference's fake_step_data
warmup contract (agent.py:120-127)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from conftest import gather_and_add_embed  # the entity embedding's plain reference
from distar_tpu.lib import features as F
from distar_tpu.model import Model, default_model_config, encoders, ref_convert

B = 2


@pytest.fixture(scope="module")
def small_cfg():
    cfg = default_model_config()
    # shrink heavy dims for test speed; field schema stays complete
    cfg.encoder.entity.layer_num = 1
    cfg.encoder.entity.hidden_dim = 64
    cfg.encoder.entity.output_dim = 32
    cfg.encoder.entity.head_dim = 16
    cfg.encoder.spatial.down_channels = [8, 8, 16]
    cfg.encoder.spatial.project_dim = 8
    cfg.encoder.spatial.resblock_num = 1
    cfg.encoder.spatial.fc_dim = 32
    cfg.encoder.scatter.output_dim = 8
    cfg.encoder.core_lstm.hidden_size = 64
    cfg.encoder.core_lstm.num_layers = 2
    cfg.policy.action_type_head.res_dim = 32
    cfg.policy.action_type_head.res_num = 1
    cfg.policy.action_type_head.gate_dim = 64
    cfg.policy.delay_head.decode_dim = 32
    cfg.policy.queued_head.decode_dim = 32
    cfg.policy.selected_units_head.func_dim = 32
    cfg.policy.location_head.res_dim = 16
    cfg.policy.location_head.res_num = 1
    cfg.policy.location_head.upsample_dims = [8, 8, 1]
    cfg.policy.location_head.map_skip_dim = 16
    cfg.value.res_dim = 16
    cfg.value.res_num = 1
    cfg.use_value_network = True
    return cfg


def _batch_obs(n, train=False):
    obs = [F.fake_step_data(train=train, rng=np.random.default_rng(i)) for i in range(n)]
    batched = F.batch_tree(obs)
    return jax.tree.map(jnp.asarray, batched)


def _hidden(cfg, batch):
    H = cfg.encoder.core_lstm.hidden_size
    z = jnp.zeros((batch, H))
    return tuple((z, z) for _ in range(cfg.encoder.core_lstm.num_layers))


@pytest.fixture(scope="module")
def model_and_params(small_cfg):
    model = Model(small_cfg)
    # init through rl_forward: it traces encoder + teacher-forced policy +
    # every value tower, creating the complete parameter tree (the sampling
    # path shares all its params with the train path)
    T = 1
    data = _batch_obs((T + 1) * B)
    action_info = {
        "action_type": jnp.zeros((T, B), jnp.int32),
        "delay": jnp.zeros((T, B), jnp.int32),
        "queued": jnp.zeros((T, B), jnp.int32),
        "selected_units": jnp.zeros((T, B, F.MAX_SELECTED_UNITS_NUM), jnp.int32),
        "target_unit": jnp.zeros((T, B), jnp.int32),
        "target_location": jnp.zeros((T, B), jnp.int32),
    }
    sun = jnp.ones((T, B), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(0),
        data["spatial_info"], data["entity_info"], data["scalar_info"], data["entity_num"],
        _hidden(small_cfg, B), action_info, sun, B, T,
        method=model.rl_forward,
    )
    return model, params


def test_sample_action_shapes(small_cfg, model_and_params):
    model, params = model_and_params
    data = _batch_obs(B)
    out = jax.jit(
        lambda p, d, h, r: model.apply(
            p, d["spatial_info"], d["entity_info"], d["scalar_info"], d["entity_num"], h, r,
            method=model.sample_action)
    )(params, data, _hidden(small_cfg, B), jax.random.PRNGKey(2))
    a = out["action_info"]
    assert a["action_type"].shape == (B,)
    assert a["selected_units"].shape == (B, F.MAX_SELECTED_UNITS_NUM)
    assert out["logit"]["selected_units"].shape == (B, 64, F.MAX_ENTITY_NUM + 1)
    assert out["logit"]["target_location"].shape == (B, 152 * 160)
    assert out["action_logp"]["selected_units"].shape == (B, 64)
    assert len(out["hidden_state"]) == small_cfg.encoder.core_lstm.num_layers
    # delays are in range
    assert int(a["delay"].max()) <= F.MAX_DELAY
    # selected_units_num <= 64
    assert int(out["selected_units_num"].max()) <= 64


def test_selected_units_respects_su_mask(small_cfg, model_and_params):
    """Sampled action types that don't select units must yield num == 0."""
    model, params = model_and_params
    data = _batch_obs(B)
    out = model.apply(
        params, data["spatial_info"], data["entity_info"], data["scalar_info"],
        data["entity_num"], _hidden(small_cfg, B), jax.random.PRNGKey(3),
        method=model.sample_action,
    )
    from distar_tpu.lib.actions import SELECTED_UNITS_MASK

    su = np.asarray(SELECTED_UNITS_MASK)[np.asarray(out["action_info"]["action_type"])]
    num = np.asarray(out["selected_units_num"])
    assert (num[~su] == 0).all()


def test_rl_forward_shapes(small_cfg, model_and_params):
    model, params = model_and_params
    T = 3
    n = (T + 1) * B
    data = _batch_obs(n, train=False)
    action_info = {
        "action_type": jnp.zeros((T, B), jnp.int32),
        "delay": jnp.zeros((T, B), jnp.int32),
        "queued": jnp.zeros((T, B), jnp.int32),
        "selected_units": jnp.zeros((T, B, F.MAX_SELECTED_UNITS_NUM), jnp.int32),
        "target_unit": jnp.zeros((T, B), jnp.int32),
        "target_location": jnp.zeros((T, B), jnp.int32),
    }
    sun = jnp.full((T, B), 2, jnp.int32)
    out = model.apply(
        params,
        data["spatial_info"], data["entity_info"], data["scalar_info"], data["entity_num"],
        _hidden(small_cfg, B), action_info, sun, B, T,
        method=model.rl_forward,
    )
    assert out["target_logit"]["action_type"].shape == (T, B, 327)
    assert out["target_logit"]["selected_units"].shape == (T, B, 64, 513)
    for k, v in out["value"].items():
        assert v.shape == (T + 1, B), k
    # winloss squashed into (-1, 1)
    assert np.abs(np.asarray(out["value"]["winloss"])).max() < 1.0


def test_teacher_and_sl_forward(small_cfg, model_and_params):
    model, params = model_and_params
    data = _batch_obs(B)
    action_info = {
        "action_type": jnp.zeros((B,), jnp.int32),
        "delay": jnp.zeros((B,), jnp.int32),
        "queued": jnp.zeros((B,), jnp.int32),
        "selected_units": jnp.zeros((B, F.MAX_SELECTED_UNITS_NUM), jnp.int32),
        "target_unit": jnp.zeros((B,), jnp.int32),
        "target_location": jnp.zeros((B,), jnp.int32),
    }
    sun = jnp.ones((B,), jnp.int32)
    out = model.apply(
        params, data["spatial_info"], data["entity_info"], data["scalar_info"],
        data["entity_num"], _hidden(small_cfg, B), action_info, sun,
        method=model.teacher_logits,
    )
    assert out["logit"]["action_type"].shape == (B, 327)

    # SL: batch of 1 trajectory x T=2 steps
    T = 2
    data2 = _batch_obs(T)  # B=1 trajectory of len 2 flat
    logits, state = model.apply(
        params, data2["spatial_info"], data2["entity_info"], data2["scalar_info"],
        data2["entity_num"],
        {k: jnp.repeat(v, 1, axis=0) for k, v in {
            "action_type": jnp.zeros((T,), jnp.int32),
            "delay": jnp.zeros((T,), jnp.int32),
            "queued": jnp.zeros((T,), jnp.int32),
            "selected_units": jnp.zeros((T, F.MAX_SELECTED_UNITS_NUM), jnp.int32),
            "target_unit": jnp.zeros((T,), jnp.int32),
            "target_location": jnp.zeros((T,), jnp.int32),
        }.items()},
        jnp.full((T,), 1, jnp.int32),
        _hidden(small_cfg, 1), 1,
        method=model.sl_forward,
    )
    assert logits["action_type"].shape == (T, 327)
    assert len(state) == small_cfg.encoder.core_lstm.num_layers


def test_bfloat16_compute_dtype(small_cfg, model_and_params):
    """cfg.dtype='bfloat16' must produce finite float32 outputs (params stay
    f32; matmuls/convs compute in bf16 on the MXU)."""
    model, params = model_and_params
    from distar_tpu.utils import deep_merge_dicts

    bf_cfg = deep_merge_dicts(small_cfg, {"dtype": "bfloat16"})
    bf_model = Model(bf_cfg)
    data = _batch_obs(B)
    out = bf_model.apply(
        params, data["spatial_info"], data["entity_info"], data["scalar_info"],
        data["entity_num"], _hidden(small_cfg, B), jax.random.PRNGKey(5),
        method=bf_model.sample_action,
    )
    for k, v in out["logit"].items():
        assert np.isfinite(np.asarray(v, dtype=np.float32)).all(), k
    # params remain float32
    assert jax.tree.leaves(params)[0].dtype == jnp.float32


def test_su_head_parallel_matches_scan(small_cfg, model_and_params):
    """The batched teacher-forced SelectedUnits path must equal the scan path
    bit-for-bit in semantics: same logits on real steps, same downstream
    embeddings (checked via target_unit/location logits)."""
    from distar_tpu.utils import deep_merge_dicts

    model, params = model_and_params
    scan_cfg = deep_merge_dicts(
        small_cfg, {"policy": {"selected_units_head": {"train_impl": "scan"}}}
    )
    scan_model = Model(scan_cfg)
    data = _batch_obs(B)
    rng = np.random.default_rng(7)
    labels = np.zeros((B, F.MAX_SELECTED_UNITS_NUM), np.int64)
    sun = np.array([3, 5])
    for b in range(B):
        labels[b, : sun[b] - 1] = rng.permutation(6)[: sun[b] - 1]
        labels[b, sun[b] - 1] = int(data["entity_num"][b])  # end token
    action_info = {
        "action_type": jnp.zeros((B,), jnp.int32),
        "delay": jnp.zeros((B,), jnp.int32),
        "queued": jnp.zeros((B,), jnp.int32),
        "selected_units": jnp.asarray(labels),
        "target_unit": jnp.zeros((B,), jnp.int32),
        "target_location": jnp.zeros((B,), jnp.int32),
    }
    outs = {}
    for name, m in (("parallel", model), ("scan", scan_model)):
        outs[name] = m.apply(
            params, data["spatial_info"], data["entity_info"], data["scalar_info"],
            data["entity_num"], _hidden(small_cfg, B), action_info, jnp.asarray(sun),
            method=m.teacher_logits,
        )
    su_p = np.asarray(outs["parallel"]["logit"]["selected_units"])
    su_s = np.asarray(outs["scan"]["logit"]["selected_units"])
    # compare real steps only (post-end steps diverge in masking, loss-masked)
    for b in range(B):
        np.testing.assert_allclose(
            su_p[b, : sun[b]], su_s[b, : sun[b]], rtol=2e-4, atol=2e-4
        )
    # downstream heads see the same autoregressive embedding
    for head in ("target_unit", "target_location"):
        np.testing.assert_allclose(
            np.asarray(outs["parallel"]["logit"][head]),
            np.asarray(outs["scan"]["logit"][head]),
            rtol=2e-4, atol=2e-4,
        )


def test_remat_preserves_numerics(rng, monkeypatch):
    """cfg.remat wraps the activation-heavy blocks in jax.checkpoint: the
    HBM-for-FLOPs knob must not change forward or gradient numerics. The two
    float32 programs fuse differently (remat keeps no residual), so they part
    by an ulp in a LayerNorm and neither is "the" answer: each is held to the
    same program in float64."""
    import jax
    import jax.numpy as jnp

    from distar_tpu.lib import features as F
    from distar_tpu.model import Model, default_model_config
    from distar_tpu.model import core, encoders, heads
    from distar_tpu.utils import deep_merge_dicts

    small = {
        "encoder": {
            "entity": {"layer_num": 1, "hidden_dim": 32, "output_dim": 16, "head_dim": 8},
            "spatial": {"down_channels": [4, 4, 8], "project_dim": 4, "resblock_num": 1, "fc_dim": 16},
            "scatter": {"output_dim": 4},
            "core_lstm": {"hidden_size": 32, "num_layers": 1},
        },
        "policy": {
            "action_type_head": {"res_dim": 16, "res_num": 1, "gate_dim": 32},
            "delay_head": {"decode_dim": 16},
            "queued_head": {"decode_dim": 16},
            "selected_units_head": {"func_dim": 16},
            "target_unit_head": {"func_dim": 16},
            "location_head": {"res_dim": 8, "res_num": 1, "upsample_dims": [4, 4, 1], "map_skip_dim": 8},
        },
        "value": {"res_dim": 8, "res_num": 1},
    }
    B, SUN, H = 2, 3, 32
    obs = F.batch_tree([F.fake_step_data(train=False, rng=rng) for _ in range(B)])
    # forced labels (the learner's path): a sampled action would make the
    # float64 program another function, its noise is drawn in float64
    labels = np.zeros((B, F.MAX_SELECTED_UNITS_NUM), np.int64)
    labels[:, : SUN - 1] = np.arange(SUN - 1)
    labels[:, SUN - 1] = obs["entity_num"]  # end token
    action = {k: np.zeros((B,), np.int32) for k in
              ("action_type", "delay", "queued", "target_unit", "target_location")}
    action["selected_units"] = labels

    def inputs(ft):
        o = jax.tree.map(
            lambda x: jnp.asarray(x, ft if np.issubdtype(x.dtype, np.floating) else None), obs)
        hidden = ((jnp.zeros((B, H), ft), jnp.zeros((B, H), ft)),)
        return o["spatial_info"], o["entity_info"], o["scalar_info"], o["entity_num"], hidden

    def value_and_grad(remat, params, ft):
        model = Model(deep_merge_dicts(default_model_config(), dict(small, remat=remat)))

        def loss(p):
            out = model.apply(
                p, *inputs(ft), jax.tree.map(jnp.asarray, action),
                jnp.full((B,), SUN, jnp.int32), method=model.teacher_logits)
            # a loss of order 1: the -1e9 of masked logits stays out of the sum
            # (squared it is 1e18 a logit and every gradient is its rounding)
            total = 0.0
            for logit in jax.tree.leaves(out["logit"]):
                logit, live = logit.astype(ft), logit > -1e8
                total += jnp.sum(jnp.where(live, logit, 0.0) ** 2) / jnp.maximum(live.sum(), 1)
            return total

        return jax.jit(jax.value_and_grad(loss))(params)

    plain = Model(deep_merge_dicts(default_model_config(), small))
    params = plain.init(
        jax.random.PRNGKey(0), *inputs(jnp.float32), jax.random.PRNGKey(1),
        method=plain.sample_action)
    programs = {remat: value_and_grad(remat, params, jnp.float32) for remat in (False, True)}
    with jax.enable_x64(True):
        for module in (core, encoders, heads):
            monkeypatch.setattr(module, "cdtype", lambda cfg: jnp.float64)
        witness_value, witness = value_and_grad(
            False, jax.tree.map(lambda x: x.astype(jnp.float64), params), jnp.float64)
        witness = [np.asarray(w, np.float64) for w in jax.tree.leaves(witness)]
        witness_value = float(witness_value)
    assert 0.1 < witness_value < 10.0

    # Float32 rounds once an operation (eps = 1.2e-7) along paths a few dozen
    # operations deep. Measured at B = 2, 3, 4: both programs 20-27 eps from
    # the witness over the whole gradient, the loss 1-18 eps, and the worst
    # leaf (the spatial encoder's first FC, the deepest) 650-870 eps of its
    # largest entry, in both programs alike. A recompute that drops or
    # doubles a term moves a leaf by order 1: 8 million eps.
    eps = float(np.finfo(np.float32).eps)
    norm = np.sqrt(sum(np.sum(w ** 2) for w in witness))
    for remat, (value, grad) in programs.items():
        assert abs(float(value) - witness_value) <= 64 * eps * witness_value, remat
        errors = [np.asarray(g, np.float64) - w for g, w in zip(jax.tree.leaves(grad), witness)]
        assert np.sqrt(sum(np.sum(e ** 2) for e in errors)) <= 64 * eps * norm, remat
        for path, e, w in zip(jax.tree_util.tree_flatten_with_path(grad)[0], errors, witness):
            assert np.abs(e).max() <= 4096 * eps * np.abs(w).max(), (remat, path[0])


# ------------------------------------------ the entity embedding as one product
# `encoders._field_sum_embed` multiplies each entity's 997-wide row by the
# fields' leaves stacked into one matrix; `conftest.gather_and_add_embed` is the
# form it replaced (a table a field, a gather each, 35 adds), kept as the plain
# reference: same leaves, same seeded values, same function of them.
ENT_WIDTH, ENT_SLOTS = 32, 24


class _FieldEmbed(nn.Module):
    form: object
    fields: tuple
    dtype: object

    @nn.compact
    def __call__(self, x):
        return self.form("ent", self.fields, x, ENT_WIDTH, self.dtype)


def _entity_fields(cfg):
    return tuple(tuple(f) for f in cfg.encoder.entity.fields)


def _entity_inputs(fields, ids, rng, frames=3):
    """One array a field; ``ids``: 'in_range', 'out_of_range' (below 0 and past
    the last class: the clamp) or 'zeros' (what the benchmark's traffic holds)."""
    shape, x = (frames, ENT_SLOTS), {}
    for key, arc, n in fields:
        if ids == "zeros":
            x[key] = np.zeros(shape, np.float32 if arc == "float" else np.int64)
        elif arc == "float":
            x[key] = rng.normal(size=shape).astype(np.float32)
        elif arc == "binary":
            x[key] = rng.integers(0, 2 ** n, size=shape)
        elif ids == "out_of_range":
            x[key] = rng.integers(-3, n + 4, size=shape)
        else:
            x[key] = rng.integers(0, n, size=shape)
    return jax.tree.map(jnp.asarray, x)


@pytest.mark.parametrize("ids", ("in_range", "out_of_range", "zeros"))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("f32", "bf16"))
def test_entity_embedding_product_matches_gather_and_add(small_cfg, dtype, ids):
    """Value and every leaf's gradient. float32: the same numbers to 1e-5 of
    the largest. bfloat16: both forms against the float32 reference; the product
    rounds its 36-term sum once (the reference form 35 times) and accumulates
    the tables' gradients in float32, so it must come no further from float32
    than one bf16 rounding of operands and result allows (2^-7 of the largest)
    and not further than the form it replaced."""
    fields = _entity_fields(small_cfg)
    assert {arc for _, arc, _ in fields} == {"one_hot", "binary", "float"}
    rng = np.random.default_rng(26)
    x = _entity_inputs(fields, ids, rng)
    ref32 = _FieldEmbed(gather_and_add_embed, fields, jnp.float32)
    params = ref32.init(jax.random.PRNGKey(1), x)
    cot = jnp.asarray(rng.normal(size=(3, ENT_SLOTS, ENT_WIDTH)).astype(np.float32))

    def value_and_grads(form, dt):
        m = _FieldEmbed(form, fields, dt)
        out = m.apply(params, x)
        assert out.dtype == dt and out.shape == cot.shape
        grads = jax.grad(lambda p: jnp.sum(m.apply(p, x).astype(jnp.float32) * cot))(params)
        assert jax.tree.structure(grads) == jax.tree.structure(params)
        return [np.asarray(out, np.float32)] + [
            np.asarray(g) for g in jax.tree.leaves(grads)]

    def worst(got, want):  # largest error over value and leaves, each by its own scale
        return max(np.abs(g - w).max() / max(np.abs(w).max(), 1e-6)
                   for g, w in zip(got, want))

    want = value_and_grads(gather_and_add_embed, jnp.float32)
    got = value_and_grads(encoders._field_sum_embed, dtype)
    if dtype == jnp.float32:
        assert worst(got, want) < 1e-5
    else:
        replaced = value_and_grads(gather_and_add_embed, dtype)
        assert worst(got, want) < 2.0 ** -7
        assert worst(got, want) <= worst(replaced, want) + 1e-6


def test_entity_embedding_gradient_reaches_the_float_observations(small_cfg):
    """The backward rule is written by hand: a ``float`` field's cotangent is
    what ``nn.Dense`` gave it in the gather-and-add form (``g`` times the
    field's one row), not a silent zero; the integer fields have none."""
    fields = _entity_fields(small_cfg)
    rng = np.random.default_rng(27)
    x = _entity_inputs(fields, "in_range", rng)
    floats = {key: x[key] for key, arc, _ in fields if arc == "float"}
    assert floats
    ref = _FieldEmbed(gather_and_add_embed, fields, jnp.float32)
    params = ref.init(jax.random.PRNGKey(1), x)
    cot = jnp.asarray(rng.normal(size=(3, ENT_SLOTS, ENT_WIDTH)).astype(np.float32))

    def d_floats(form):
        m = _FieldEmbed(form, fields, jnp.float32)
        return jax.grad(lambda f: jnp.sum(m.apply(params, {**x, **f}) * cot))(floats)

    got, want = d_floats(encoders._field_sum_embed), d_floats(gather_and_add_embed)
    for key in floats:
        assert np.abs(np.asarray(want[key])).max() > 0.1
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                                   rtol=1e-5, atol=1e-6, err_msg=key)


def _reference_entity_state_dict(cfg, rng):
    """A state_dict with the reference EntityEncoder's keys and shapes (torch
    Linear weights are [out, in]) for ``ref_convert.convert_entity_encoder``."""
    ent = cfg.encoder.entity
    cols = sum(1 if arc == "float" else n for _, arc, n in ent.fields)
    w, hd = ent.output_dim, ent.head_num * ent.head_dim
    sd = {}

    def linear(prefix, out_dim, in_dim):
        sd[f"{prefix}.0.weight"] = rng.normal(size=(out_dim, in_dim)).astype(np.float32)
        sd[f"{prefix}.0.bias"] = np.zeros(out_dim, np.float32)

    def layernorm(prefix):
        sd[f"{prefix}.weight"], sd[f"{prefix}.bias"] = np.ones(w, np.float32), np.zeros(w, np.float32)

    linear("transformer.embedding", w, cols)
    for i in range(ent.layer_num):
        p = f"transformer.layers.{i}"
        linear(f"{p}.attention.attention_pre", 3 * hd, w)
        linear(f"{p}.attention.project", w, hd)
        layernorm(f"{p}.layernorm1")
        layernorm(f"{p}.layernorm2")
        dims = [w] + [ent.hidden_dim] * (ent.mlp_num - 1) + [w]
        for j in range(ent.mlp_num):
            linear(f"{p}.mlp.{j}", dims[j + 1], dims[j])
    linear("entity_fc", w, w)
    linear("embed_fc", w, w)
    return sd


def test_entity_encoder_parameter_tree_is_pinned(small_cfg):
    """Checkpoints, `ref_convert` and the benchmark's reference process (which
    draws its weights from the same seed) see the tree the gather form had: the
    leaf paths, shapes and dtypes `convert_entity_encoder` writes, and in every
    `ent_*` leaf the values `nn.Embed` / `nn.Dense` of that name draw."""
    fields = _entity_fields(small_cfg)
    x = _entity_inputs(fields, "in_range", np.random.default_rng(0), frames=2)
    key = jax.random.PRNGKey(7)
    params = encoders.EntityEncoder(small_cfg).init(
        key, x, jnp.asarray([ENT_SLOTS, 5], jnp.int32))

    converted = ref_convert.convert_entity_encoder(
        _reference_entity_state_dict(small_cfg, np.random.default_rng(1)), small_cfg)
    spec = lambda tree: {
        jax.tree_util.keystr(path): (np.shape(leaf), np.asarray(leaf).dtype)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    assert spec(params) == spec(converted)

    assert small_cfg.encoder.entity.output_dim == ENT_WIDTH
    drawn = _FieldEmbed(gather_and_add_embed, fields, jnp.float32).init(key, x)["params"]
    assert sorted(drawn) == sorted(k for k in params["params"] if k != "ent_embed_bias"
                                   and k.startswith("ent_"))
    for name, leaves in drawn.items():
        for leaf, value in leaves.items():
            np.testing.assert_array_equal(
                np.asarray(params["params"][name][leaf]), np.asarray(value), err_msg=name)


def test_entity_encoder_gradient_has_no_gather_and_no_scatter(small_cfg):
    """The lowered program of the encoder's gradient, whatever the platform:
    every table is reached through the one product, none by rows."""
    fields = _entity_fields(small_cfg)
    x = _entity_inputs(fields, "in_range", np.random.default_rng(0), frames=2)
    num = jnp.asarray([ENT_SLOTS, 5], jnp.int32)
    enc = encoders.EntityEncoder(small_cfg)
    params = enc.init(jax.random.PRNGKey(0), x, num)

    def loss(p, x, num):
        per_entity, pooled, _mask = enc.apply(p, x, num)
        return jnp.sum(per_entity.astype(jnp.float32) ** 2) + jnp.sum(pooled.astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(params, x, num).as_text()
    assert "stablehlo.dot_general" in text
    assert "stablehlo.gather" not in text and "stablehlo.scatter" not in text

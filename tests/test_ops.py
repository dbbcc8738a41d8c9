import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distar_tpu.ops import (
    AttentionPool,
    FCBlock,
    GLU,
    LayerNormLSTMCell,
    ResBlock,
    ResFCBlock,
    StackedLSTM,
    Transformer,
    binary_encode,
    one_hot,
    scatter_connection,
    sequence_mask,
)


def test_one_hot_clamps():
    x = jnp.array([0, 5, 99])
    out = one_hot(x, 6)
    assert out.shape == (3, 6)
    assert out[2, 5] == 1.0  # out-of-range clamps to last class


def test_binary_encode():
    out = np.asarray(binary_encode(jnp.array([5]), 4))
    np.testing.assert_array_equal(out[0], [0, 1, 0, 1])


def test_sequence_mask():
    m = np.asarray(sequence_mask(jnp.array([0, 2, 4]), 4))
    assert m.sum() == 6
    assert m[1, 1] and not m[1, 2]


def test_fc_res_blocks():
    x = jnp.ones((2, 16))
    for mod in (FCBlock(32), ResFCBlock(16, norm="LN")):
        params = mod.init(jax.random.PRNGKey(0), x)
        y = mod.apply(params, x)
        assert y.shape[0] == 2


def test_conv_res_block():
    x = jnp.ones((2, 8, 8, 4))
    mod = ResBlock(4)
    y = mod.apply(mod.init(jax.random.PRNGKey(0), x), x)
    assert y.shape == (2, 8, 8, 4)


def test_glu():
    x, ctx = jnp.ones((2, 16)), jnp.ones((2, 8))
    mod = GLU(32)
    y = mod.apply(mod.init(jax.random.PRNGKey(0), x, ctx), x, ctx)
    assert y.shape == (2, 32)


def test_transformer_masked_invariance():
    """Padded entity slots must not influence valid entity outputs."""
    B, N, D = 2, 8, 12
    rng = np.random.default_rng(0)
    x = rng.standard_normal((B, N, D)).astype(np.float32)
    lengths = jnp.array([5, 8])
    mask = sequence_mask(lengths, N)
    mod = Transformer(head_dim=8, hidden_dim=16, output_dim=16, layer_num=2)
    params = mod.init(jax.random.PRNGKey(0), jnp.asarray(x), mask)
    y1 = mod.apply(params, jnp.asarray(x), mask)
    # perturb padding slots of batch 0 (idx >= 5)
    x2 = x.copy()
    x2[0, 5:] += 100.0
    y2 = mod.apply(params, jnp.asarray(x2), mask)
    np.testing.assert_allclose(np.asarray(y1[0, :5]), np.asarray(y2[0, :5]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y1[1]), np.asarray(y2[1]), atol=1e-4)


def test_attention_pool():
    B, N, C = 2, 6, 8
    x = jnp.ones((B, N, C))
    mask = sequence_mask(jnp.array([3, 6]), N)[..., None]
    mod = AttentionPool(head_num=2, output_dim=16, max_num=7)
    params = mod.init(jax.random.PRNGKey(0), x, jnp.array([3, 6]), mask)
    y = mod.apply(params, x, jnp.array([3, 6]), mask)
    assert y.shape == (2, 16)


def test_lstm_cell_and_stack():
    T, B, D, H = 5, 2, 12, 16
    xs = jnp.asarray(np.random.default_rng(0).standard_normal((T, B, D)), dtype=jnp.float32)
    mod = StackedLSTM(hidden_size=H, num_layers=3)
    params = mod.init(jax.random.PRNGKey(0), xs)
    ys, final = mod.apply(params, xs)
    assert ys.shape == (T, B, H)
    assert len(final) == 3 and final[0][0].shape == (B, H)
    # carrying state: running [T] then [T:] from the carried state == running all at once
    ys_a, st = mod.apply(params, xs[:3])
    ys_b, _ = mod.apply(params, xs[3:], st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([ys_a, ys_b], 0)), np.asarray(ys), atol=1e-5)


def _time_major_reference(mod, params, xs):
    """The step-per-layer formulation: a Python loop over T through
    ``StackedLSTM._step``, all layer states in one carry."""
    states = mod.init_state(xs.shape[1])
    ys = []
    for x in xs:
        states, y = mod.apply(params, states, x, method=StackedLSTM._step)
        ys.append(y)
    return jnp.stack(ys), states


def test_lstm_layer_major_matches_time_major():
    """Layer-major execution (hoisted input projection) must be numerically
    identical to the time-major loop on the same params, for both cell
    types, including carried-state restarts."""
    T, B, D, H = 6, 3, 10, 16
    xs = jnp.asarray(np.random.default_rng(2).standard_normal((T, B, D)), dtype=jnp.float32)
    for norm in ("LN", "none"):
        lm = StackedLSTM(hidden_size=H, num_layers=3, norm=norm)
        params = lm.init(jax.random.PRNGKey(0), xs)
        ys_lm, fin_lm = lm.apply(params, xs)
        ys_tm, fin_tm = _time_major_reference(lm, params, xs)
        np.testing.assert_allclose(np.asarray(ys_lm), np.asarray(ys_tm), atol=1e-5)
        for a, b in zip(fin_lm, fin_tm):
            np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]), atol=1e-5)
        # carried state across a split run
        ys_a, st = lm.apply(params, xs[:2])
        ys_b, _ = lm.apply(params, xs[2:], st)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([ys_a, ys_b], 0)), np.asarray(ys_lm), atol=1e-5
        )


def test_scatter_connection_add():
    B, N, D, H, W = 2, 4, 3, 5, 6
    emb = jnp.ones((B, N, D))
    # two entities share a cell in batch 0 -> embeddings add
    loc = jnp.array(
        [[[1, 2], [1, 2], [0, 0], [5, 4]], [[3, 1], [2, 2], [0, 4], [9, 9]]]
    )
    out = np.asarray(scatter_connection(emb, loc, (H, W), "add"))
    assert out.shape == (B, H, W, D)
    np.testing.assert_array_equal(out[0, 2, 1], [2, 2, 2])  # (x=1,y=2) doubled
    np.testing.assert_array_equal(out[0, 0, 0], [1, 1, 1])
    # out-of-range location clamps into the map
    np.testing.assert_array_equal(out[1, 4, 5], [1, 1, 1])


def _drawn_scatter_case(rng, B, N, H, W):
    """Locations (x, y) with what a frame's entities do to a map: several
    entities in one cell, cells on all four borders and in the corners,
    coordinates outside the map (clipped into it), and masked rows (the
    encoder zeroes their embeddings and leaves their location at cell 0)."""
    loc = np.stack([rng.integers(-2, W + 3, size=(B, N)), rng.integers(-2, H + 3, size=(B, N))], -1)
    loc[:, 1] = loc[:, 0]  # a shared cell in every frame
    loc[:, 2] = loc[:, 0]
    loc[:, 3:7] = [(0, 1), (W - 1, H - 2), (2, 0), (1, H - 1)]  # left, right, top, bottom
    loc[:, 7] = (W - 1, H - 1)
    loc[:, 8] = (W + 5, H + 7)  # clipped onto the same corner
    masked = np.zeros((B, N), bool)
    masked[:, N - 3:] = True
    loc[masked] = 0
    return loc, masked


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("f32", "bf16"))
@pytest.mark.parametrize("B", (1, 3, 5, 16))
@pytest.mark.parametrize("mode,impl", (("add", "product"), ("add", "xla"), ("cover", "product")),
                         ids=("add", "add_xla", "cover"))
def test_scatter_connection_against_a_numpy_loop(mode, impl, B, dtype):
    """out[b, y, x] += emb[b, n], with shared cells, border cells, masked
    rows and out-of-range locations (clipped into the map); forward exactly,
    and in add mode the gradient of a sum of squares exactly (2 * out gathered
    at each entity's cell), for the product (the default) and for the plain
    scatter alike. Embeddings are small integers, so bf16 sums are exact too; in
    cover mode a cell takes one writer's value and no (b, cell) is shared."""
    N, D, H, W = 12, 3, 5, 6
    rng = np.random.default_rng(7 + B)
    emb = rng.integers(-4, 5, size=(B, N, D)).astype(np.float32)
    if mode == "add":
        loc, masked = _drawn_scatter_case(rng, B, N, H, W)
        emb[masked] = 0
    else:
        # distinct cells, none the last one: entity 0 is clipped into that
        cells = np.stack([rng.permutation(H * W - 1)[:N] for _ in range(B)])
        loc = np.stack([cells % W, cells // W], axis=-1)
        loc[:, 0] = (W + 3, H + 4)
    xs, ys = np.clip(loc[..., 0], 0, W - 1), np.clip(loc[..., 1], 0, H - 1)
    want = np.zeros((B, H, W, D), np.float32)
    for b in range(B):
        for n in range(N):
            if mode == "add":
                want[b, ys[b, n], xs[b, n]] += emb[b, n]
            else:
                want[b, ys[b, n], xs[b, n]] = emb[b, n]

    kwargs = {} if impl == "product" else {"impl": impl}  # the default is the product
    fn = lambda e: scatter_connection(e, jnp.asarray(loc), (H, W), mode, **kwargs)
    out = fn(jnp.asarray(emb, dtype))
    assert out.shape == (B, H, W, D) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32), want)
    if mode == "add":
        grad = jax.grad(lambda e: jnp.sum(fn(e) ** 2))(jnp.asarray(emb, dtype))
        want_grad = 2.0 * want[np.arange(B)[:, None], ys, xs]
        np.testing.assert_array_equal(np.asarray(grad, np.float32), want_grad)


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("f32", "bf16"))
@pytest.mark.parametrize("B", (1, 3, 16))
@pytest.mark.parametrize("geometry", ((5, 6, 3), (4, 160, 32), (7, 24, 16)), ids=lambda g: "x".join(map(str, g)))
def test_scatter_connection_product_against_the_scatter(geometry, B, dtype):
    """Drawn real-valued embeddings with a dozen entities in some cells, on
    maps whose width splits differently. float32: the product and the scatter
    add the same addends in another order (1e-6). bfloat16: the product is
    the float32 sum rounded ONCE (one ulp of slack for the order of the float32
    adds), and so at least as close to it as the scatter, which rounds after
    every add. The gradient is the same gather of the same cotangent: equal."""
    H, W, D = geometry
    N = 40
    rng = np.random.default_rng(35 + B + W)
    loc, masked = _drawn_scatter_case(rng, B, N, H, W)
    loc[:, 20:32] = loc[:, :1]  # twelve more writers of the shared cell
    emb = rng.standard_normal((B, N, D)).astype(np.float32)
    emb[masked] = 0
    emb = np.asarray(jnp.asarray(emb, dtype), np.float32)  # what the dtype holds
    xs, ys = np.clip(loc[..., 0], 0, W - 1), np.clip(loc[..., 1], 0, H - 1)
    want = np.zeros((B, H, W, D), np.float64)
    np.add.at(want, (np.arange(B)[:, None], ys, xs), emb)

    product = lambda e: scatter_connection(e, jnp.asarray(loc), (H, W), "add")
    scatter = lambda e: scatter_connection(e, jnp.asarray(loc), (H, W), "add", impl="xla")
    e = jnp.asarray(emb, dtype)
    got, plain = np.asarray(product(e), np.float64), np.asarray(scatter(e), np.float64)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got, plain, rtol=1e-6, atol=1e-6)
    else:
        once = np.asarray(jnp.asarray(want, jnp.float32).astype(jnp.bfloat16), np.float64)
        ulp = np.maximum(np.abs(once), 2.0 ** -126) * 2.0 ** -7
        assert np.all(np.abs(got - once) <= ulp)
        assert np.abs(got - want).max() <= np.abs(plain - want).max() + 1e-12
    # the cotangent is drawn, not a function of the map: both rules gather it
    g = jnp.asarray(rng.standard_normal((B, H, W, D)), dtype)
    grads = [jax.vjp(f, e)[1](g)[0] for f in (product, scatter)]
    assert grads[0].dtype == dtype
    np.testing.assert_array_equal(np.asarray(grads[0], np.float32), np.asarray(grads[1], np.float32))
    np.testing.assert_array_equal(
        np.asarray(grads[0], np.float32), np.asarray(g, np.float32)[np.arange(B)[:, None], ys, xs])


def test_scatter_connection_cover():
    B, N, D, H, W = 1, 2, 2, 3, 3
    emb = jnp.array([[[1.0, 1.0], [5.0, 5.0]]])
    loc = jnp.array([[[1, 1], [1, 1]]])
    out = np.asarray(scatter_connection(emb, loc, (H, W), "cover"))
    # cover: one of the writes wins (scatter, not add)
    assert out[0, 1, 1, 0] in (1.0, 5.0)

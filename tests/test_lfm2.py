"""LFM2 (``model/lfm2.py``, ``ops/moe.py``, ``ops/sequence.py``) against its
plain reference (``benchmark/references/lfm2_plain.py``, which imports none
of them) at a tiny size on seeded weights, float32, on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import lfm2_plain, nemotron_h_plain  # noqa: E402
from distar_tpu.model import LFM2, default_lfm2_config  # noqa: E402
from distar_tpu.ops import moe  # noqa: E402
from distar_tpu.ops.sequence import ShortConv, causal_conv  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "experts_held": {"offset": 2, "count": 4},
        "vocab_size": 128}
B, S = 2, 32


def build(seed=0, scale=5.0, **over):
    """The tiny model with seeded weights. The program draws N(0, 0.02^2),
    which at width 64 leaves every layer's output far below the residual
    stream; ``scale`` widens the matrices so that each part moves the logits
    and a fault in any of them shows."""
    cfg = deep_merge_dicts(default_lfm2_config(), dict(TINY, **over))
    model = LFM2(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    params = jax.tree.map(lambda x: x * scale if x.ndim >= 2 else x, variables["params"])
    return cfg, model, {"params": params, "buffers": variables["buffers"]}, tokens, labels


def system_loss(model, variables, params, tokens, labels):
    from distar_tpu.losses import compute_lm_loss

    logits, stats = model.apply({**variables, "params": params}, tokens)
    return compute_lm_loss(logits, labels)[0], (logits, stats)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_logits_loss_and_every_gradient_leaf_match_the_plain_reference(remat):
    cfg, model, variables, tokens, labels = build(remat=remat)
    plain = lfm2_plain.plain_config(cfg)
    (loss, (logits, stats)), grads = jax.value_and_grad(
        lambda p: system_loss(model, variables, p, tokens, labels), has_aux=True)(variables["params"])
    with jax.default_matmul_precision("highest"):
        (ref_loss, (ref_logits, ref_stats)), ref_grads = jax.value_and_grad(
            lambda p: lfm2_plain.loss(p, variables, plain, tokens, labels), has_aux=True)(variables["params"])
    # float32 against float32 on one backend: what differs is the order of the sums (a sorted
    # buffer and grouped products against a masked loop over experts, blocks of queries against
    # whole rows). Logits are O(1): 2e-4 absolute is ~100 ulp of headroom over the 1e-6 seen
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    # the routing is discrete: the same picks, or the comparison above means nothing
    np.testing.assert_array_equal(stats["rows"], np.stack(ref_stats["rows"]))
    np.testing.assert_allclose(stats["rms"], np.stack(ref_stats["rms"]), rtol=1e-5)
    np.testing.assert_allclose(stats["ff_rms"], np.stack(ref_stats["ff_rms"]), rtol=1e-4)
    assert int(stats["overflow"]) == 0
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    assert flat.keys() == ref_flat.keys() and len(flat) >= 40
    for path, g in flat.items():
        # every leaf, against its own size: a leaf is off when it parts by more than 1e-3 of its
        # largest entry (sum order again; a wrong term is off by O(1) of it)
        bound = 1e-3 * float(jnp.abs(ref_flat[path]).max()) + 1e-9
        np.testing.assert_allclose(g, ref_flat[path], atol=bound, rtol=0, err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(ref_flat[path]).max()) > 0, jax.tree_util.keystr(path)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """64 experts, top-4, tiny width: the layer as each of the eight members
    of an expert-parallel group computes it (experts 0-7, 8-15, ...), summed,
    is the reference's layer over all 64 experts."""
    d, width, E, k = 32, 16, 64, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, d))
    whole = moe.ExpertsHeldMoE(E, k, width, 0, E)
    variables = whole.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    plain_cfg = {"num_experts_per_tok": k, "experts_held": {"offset": 0, "count": E},
                 "use_expert_bias": True, "routed_scaling_factor": 1.0}
    u = lfm2_plain.rms_norm(x, p["norm"]["scale"], 1e-5).reshape(-1, d)
    with jax.default_matmul_precision("highest"):
        want, want_rows, _ = lfm2_plain.experts_held(
            {k_: p[k_] for k_ in ("router", "w1", "w2", "w3")},
            variables["buffers"]["expert_bias"], u, plain_cfg, None)
    total, rows = 0.0, []
    for member in range(8):
        held = slice(8 * member, 8 * member + 8)
        share = moe.ExpertsHeldMoE(E, k, width, 8 * member, 8)
        mine = {"params": {**p, **{w: p[w][held] for w in ("w1", "w2", "w3")}},
                "buffers": variables["buffers"]}
        y, stats = share.apply(mine, x)
        total = total + y
        rows.append(stats["rows"])
    np.testing.assert_allclose(total.reshape(-1, d), want, atol=1e-5, rtol=1e-4)
    np.testing.assert_array_equal(np.concatenate(rows), want_rows)
    assert int(np.concatenate(rows).sum()) == 2 * 24 * k  # every pick is somebody's


def test_short_convolution_is_causal_and_is_the_per_position_formula():
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 6))
    c = causal_conv(z, w)
    for t in range(10):
        want = sum(w[k] * (z[:, t - 2 + k] if t - 2 + k >= 0 else 0.0) for k in range(3))
        np.testing.assert_allclose(c[:, t], want, rtol=1e-6, atol=1e-6)
    # the whole operator: what comes after position t does not reach position t
    op = ShortConv(3)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 8))
    v = op.init(jax.random.PRNGKey(3), u)
    later = u.at[:, 7:].set(jax.random.normal(jax.random.PRNGKey(4), (1, 5, 8)))
    np.testing.assert_array_equal(op.apply(v, u)[:, :7], op.apply(v, later)[:, :7])
    assert not np.allclose(op.apply(v, u)[:, 7:], op.apply(v, later)[:, 7:])


@pytest.mark.parametrize("S", (16, 128), ids=("s16", "s128_flash_shaped"))
def test_attention_is_causal_and_groups_four_query_heads_to_a_key_head(S):
    """At 128 positions the flash kernel's tiles divide the sequence, so the
    choice is the platform's: on the CPU that is the query-block loop."""
    from distar_tpu.ops.sequence import CausalGQAttention

    att = CausalGQAttention(heads=4, kv_heads=2, head_dim=8)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, S, 32))
    v = att.init(jax.random.PRNGKey(1), u)
    later = u.at[:, 9:].add(1.0)
    np.testing.assert_allclose(att.apply(v, u)[:, :9], att.apply(v, later)[:, :9], atol=1e-6)
    np.testing.assert_allclose(att.apply(v, u), lfm2_plain.attention(v["params"], u, 4, 2, 8, 1e-5, 1e6, None),
                               atol=1e-5, rtol=1e-4)


def test_no_row_is_lost_when_every_position_picks_the_same_held_expert():
    """The router's bias sends every position to expert 5 (held) first: its
    group is the whole batch, the others share the second pick, and the
    layer is still the reference's."""
    d, width, E, k, N = 16, 8, 8, 2, 40
    layer = moe.ExpertsHeldMoE(E, k, width, 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, N, d))
    variables = layer.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    bias = jnp.zeros((E,)).at[5].set(10.0)
    y, stats = layer.apply({"params": p, "buffers": {"expert_bias": bias}}, x)
    assert int(stats["rows"][1]) == N and int(stats["overflow"]) == 0
    plain_cfg = {"num_experts_per_tok": k, "experts_held": {"offset": 4, "count": 4},
                 "use_expert_bias": True, "routed_scaling_factor": 1.0}
    u = lfm2_plain.rms_norm(x, p["norm"]["scale"], 1e-5).reshape(-1, d)
    want, rows, _ = lfm2_plain.experts_held(p, bias, u, plain_cfg, None)
    np.testing.assert_allclose(y.reshape(-1, d), want, atol=1e-5, rtol=1e-4)
    np.testing.assert_array_equal(stats["rows"], rows)


def test_dispatch_sorts_the_picks_by_expert_into_the_provable_bound():
    sel = jnp.asarray([[0, 1], [0, 1], [0, 2], [1, 0], [3, 0]], jnp.int32)  # expert 0 five times
    plan = moe.dispatch(sel, 0, 2)                                           # 5 positions x min(2, 2) rows
    assert plan.rows.tolist() == [5, 3] and int(plan.overflow) == 0
    # two chunks of five rows: expert 0 fills the first, expert 1 starts the second
    assert plan.group_sizes.tolist() == [5, 3] and int(plan.chunks) == 2
    # each present row copies the position that picked it, sorted by expert
    assert plan.token[:8].tolist() == [0, 1, 2, 3, 4, 0, 1, 3]
    assert plan.slot[:8].tolist() == [0, 2, 4, 7, 9, 1, 3, 6] and plan.slot[8:].tolist() == [10, 10]
    # each row serves a pick of the position it copies, and that pick is of its expert
    assert all(s // 2 == t and int(sel.reshape(-1)[s]) == e for s, t, e in
               zip(plan.slot[:8].tolist(), plan.token[:8].tolist(), [0] * 5 + [1] * 3))
    # one expert held: a position can send it one row at most, and the buffer is that long
    one = moe.dispatch(sel, 0, 1)
    assert one.token.shape == (5,) and one.rows.tolist() == [5] and int(one.overflow) == 0
    assert one.group_sizes.tolist() == [5] and int(one.chunks) == 1
    # no row here: the first chunk is still walked
    assert int(moe.dispatch(sel, 4, 2).chunks) == 1


# 40 positions, top-3 of 8, experts 4-7 held: three chunks of 40 rows
LOADS = {
    "first_chunk": ({6: -10.0, 7: -10.0}, 1),          # experts 6 and 7 are never picked: under 40 rows
    "second_chunk": ({5: 10.0}, 2),                    # every position picks expert 5, some the others
    "whole_bound": ({4: 10.0, 5: 10.0, 6: 10.0}, 3),   # every pick of every position is held: 120 rows
}


# the layer as each token model builds it, and the plain reference of each
BODIES = {"swiglu": (dict(), lfm2_plain, 6),                                  # norm, router, w1, w2, w3, input
          "relu2_shared": (dict(body="relu2", shared_width=12, scaling=2.5), nemotron_h_plain, 7)}  # no w3, 2 shared


@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("load", LOADS)
def test_the_layer_is_the_reference_at_every_load_and_walks_the_chunks_that_hold_rows(load, body):
    """Value and every gradient leaf (parameters and input) against the plain
    ``experts_held`` of the model the body belongs to, with the rows in the
    first chunk only, spilling into the second, and filling the provable
    bound; ``buffer_rows`` says how many chunks ran."""
    d, width, E, k, N = 16, 8, 8, 3, 40
    push, chunks = LOADS[load]
    options, plain, n_leaves = BODIES[body]
    layer = moe.ExpertsHeldMoE(E, k, width, 4, 4, **options)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, N, d))
    variables = layer.init(jax.random.PRNGKey(1), x)
    p = jax.tree.map(lambda a: a * 8.0 if a.ndim >= 2 else a, variables["params"])
    bias = jnp.zeros((E,)).at[jnp.asarray(list(push))].set(jnp.asarray(list(push.values())))
    weight = jax.random.normal(jax.random.PRNGKey(2), (N, d))
    plain_cfg = {"num_experts_per_tok": k, "experts_held": {"offset": 4, "count": 4},
                 "use_expert_bias": True, "routed_scaling_factor": options.get("scaling", 1.0)}

    def system(p, x):
        y, stats = layer.apply({"params": p, "buffers": {"expert_bias": bias}}, x)
        return (y.reshape(N, d) * weight).sum(), (y, stats)

    def reference(p, x):
        u = plain.rms_norm(x, p["norm"]["scale"], 1e-5).reshape(-1, d)
        y, rows, _ = plain.experts_held(p, bias, u, plain_cfg, None)
        return (y * weight).sum(), (y, rows)

    (_, (y, stats)), grads = jax.value_and_grad(system, argnums=(0, 1), has_aux=True)(p, x)
    with jax.default_matmul_precision("highest"):
        (_, (want, rows)), ref_grads = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(p, x)
    np.testing.assert_array_equal(stats["rows"], rows)
    held = int(rows.sum())
    assert (chunks - 1) * N < held <= chunks * N and (load != "whole_bound" or held == N * k)
    assert int(stats["buffer_rows"]) == chunks * N and int(stats["overflow"]) == 0
    np.testing.assert_allclose(y.reshape(N, d), want, atol=1e-5, rtol=1e-4)
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    assert flat.keys() == ref_flat.keys() and len(flat) == n_leaves
    for path, g in flat.items():
        bound = 1e-3 * float(jnp.abs(ref_flat[path]).max()) + 1e-9
        np.testing.assert_allclose(g, ref_flat[path], atol=bound, rtol=0, err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(ref_flat[path]).max()) > 0, jax.tree_util.keystr(path)


def test_every_row_move_belongs_to_the_length_the_load_chose():
    """The lowered value-and-gradient of the layer (CPU, 24 positions, top-3,
    4 held: a buffer of 3 x 24 rows for 72 picks): outside the choice between
    the buffer's lengths no row of ``d`` or ``width`` numbers is gathered or
    scattered, and each length's program moves ``length`` of them at a time,
    by buffer row, forward and backward: one chunk never ``N * k``."""
    import re

    d, width, E, k, N = 16, 12, 8, 3, 24
    layer = moe.ExpertsHeldMoE(E, k, width, 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, N, d))
    variables = layer.init(jax.random.PRNGKey(1), x)
    f = lambda p, x: layer.apply({"params": p, "buffers": variables["buffers"]}, x)[0].sum()
    text = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(variables["params"], x).as_text()

    def choices(text):
        """The text outside every ``stablehlo.case`` and, for each outermost
        one, the text of its branches (``platform_dependent``'s are nested)."""
        outside, cases, depth, top = [], [], 0, None
        for line in text.splitlines():
            if top is None and "stablehlo.case" in line:
                top = depth
                cases.append([[]])
            elif top is not None and depth == top + 1 and line.strip().startswith("}, {"):
                cases[-1].append([])
            elif top is not None:
                cases[-1][-1].append(line)
            else:
                outside.append(line)
            depth += line.count("{") - line.count("}")
            if top is not None and depth <= top:
                top = None
        return "\n".join(outside), [["\n".join(b) for b in c] for c in cases]

    def rows(found):
        """Rows moved at a time by the operations whose moved type is ``found``,
        of those that move rows of ``d`` or ``width`` numbers."""
        return sorted({int(np.prod([int(n) for n in dims.split("x")[:-1]])) for dims in found
                       if dims.endswith(f"x{d}") or dims.endswith(f"x{width}")})

    dims = r"((?:\d+x)+\d+)xf32>"
    gathered = lambda t: rows(re.findall(r'"stablehlo\.gather".*-> tensor<' + dims, t))
    # a scatter's types close its region: (operand, indices, updates) -> result
    scattered = lambda t: rows(re.findall(r"^\s*\}\) : \(tensor<[^>]*>, tensor<[^>]*>, tensor<" + dims + r"\) ->", t, re.M))
    outside, cases = choices(text)
    assert gathered(outside) == [] and scattered(outside) == []
    assert [len(c) for c in cases] == [3, 3]                       # forward and backward, three lengths each
    for programs in cases:
        for chunks, program in enumerate(programs, start=1):
            assert (gathered(program), scattered(program)) == ([chunks * N], [chunks * N])
    # backward, one chunk: the rows of u again and the cotangent by position; then rows added to d_u
    assert len(re.findall(r'"stablehlo\.gather".*-> tensor<' + f"{N}x{d}xf32>", cases[1][0])) == 2


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_megablox_as_the_layer_calls_it_is_the_ragged_product(grad):
    """The TPU branch of ``grouped_matmul`` (its tiles, its argument order) in
    interpret mode against the branch every other platform runs, on uneven
    groups with an empty one and an unused tail."""
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))
    sizes = jnp.asarray([50, 0, 7, 40], jnp.int32)
    present = (jnp.arange(128) < 97)[:, None]
    out = lambda f: lambda x, w: jnp.where(present, f(x, w, sizes), 0.0)
    kernel, plain = out(lambda *a: moe.megablox(*a, interpret=True)), out(jax.lax.ragged_dot)
    if grad:
        weight = jax.random.normal(jax.random.PRNGKey(2), (128, 32))
        kernel, plain = (jax.grad(lambda x, w, f=f: (f(x, w) * weight).sum(), argnums=(0, 1))
                         for f in (kernel, plain))
    for got, want in zip(jax.tree.leaves(kernel(x, w)), jax.tree.leaves(plain(x, w))):
        if got.shape[0] == 128:  # rows beyond the groups are undefined, values and gradients alike
            got, want = got[:97], want[:97]
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # off a TPU the layer's product is the plain one: nothing is interpreted
    np.testing.assert_array_equal(moe.grouped_matmul(x, w, sizes), jax.lax.ragged_dot(x, w, sizes))


def test_route_is_sigmoid_top_k_with_a_bias_on_the_selection_only():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    sel, w = moe.route(logits, jnp.zeros((4,)), 2)
    s = jax.nn.sigmoid(logits[0])
    assert sel.tolist() == [[0, 1]]
    np.testing.assert_allclose(w[0], s[:2] / (s[:2].sum() + 1e-6), rtol=1e-6)
    sel, w = moe.route(logits, jnp.asarray([0.0, 0.0, 0.0, 5.0]), 2)   # the bias picks expert 3...
    assert sorted(sel[0].tolist()) == [0, 3]
    picked = s[jnp.asarray(sorted(sel[0].tolist()))]                   # ...and its weight is its score
    np.testing.assert_allclose(sorted(w[0].tolist()), sorted((picked / (picked.sum() + 1e-6)).tolist()),
                               rtol=1e-6)


# five positions, top-3, eight buffer rows: position 4 is copied three times, 0
# twice, 2 once, positions 1 and 3 have no pick held; the last two rows are
# beyond the rows present (they copy position 0 and serve no pick)
ROW_TOKEN = jnp.asarray([4, 0, 0, 2, 4, 4, 0, 0])
ROW_SLOT = jnp.asarray([12, 0, 2, 7, 13, 14, 15, 15])   # the pick n*k+j each row serves, 15 = none


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("float32", "bfloat16"))
def test_copy_rows_gradient_is_the_row_adds_of_plain_indexing(dtype):
    """``copy_rows`` against autodiff of ``src[idx]`` masked to the rows
    present: duplicate positions add up (in float32, rounded once), a row
    beyond those present adds nothing whatever its cotangent holds, and a
    position no row copies gets zero."""
    src = jax.random.normal(jax.random.PRNGKey(0), (5, 3)).astype(dtype)
    present = ROW_SLOT < 15
    weight = jax.random.normal(jax.random.PRNGKey(1), (8, 3)).astype(dtype)
    np.testing.assert_array_equal(moe.copy_rows(src, ROW_TOKEN, present), src[ROW_TOKEN])
    # the grouped product leaves anything in the rows it does not own
    junk = jnp.where(present[:, None], 1.0, jnp.nan).astype(dtype)
    got = jax.grad(lambda s: (moe.copy_rows(s, ROW_TOKEN, present).astype(jnp.float32) * weight * junk).sum())(src)
    plain = lambda s: (jnp.where(present[:, None], s[ROW_TOKEN], 0) * weight).sum()
    want = jax.grad(plain)(src.astype(jnp.float32))
    assert got.dtype == dtype and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(dtype).astype(jnp.float32), rtol=1e-6)
    assert not got[1].any() and not got[3].any() and bool(got[4].any())


def test_add_rows_is_the_weighted_sum_over_the_picks_held_and_so_are_its_gradients():
    """``add_rows`` (rows added by position, each times the weight of the pick
    it serves) against the sum written pick by pick, value and both
    gradients: the weights travel by ``slot`` both ways, a pick that no row
    serves gets no gradient, a position without rows gets a zero row."""
    N, k, d = 5, 3, 4
    out = jax.random.normal(jax.random.PRNGKey(0), (8, d))
    # as ``_buffer`` calls it: the mask zeroes the rows beyond those present, and their cotangents
    by_row = lambda out, w: moe.add_rows(jnp.where((ROW_SLOT < N * k)[:, None], out, 0), w, ROW_TOKEN, ROW_SLOT)
    w = jax.random.uniform(jax.random.PRNGKey(1), (N, k)) + 0.5
    weight = jax.random.normal(jax.random.PRNGKey(2), (N, d))
    # the buffer row of each pick, 8 where none serves it
    row = jnp.full((N * k,), 8).at[ROW_SLOT[:6]].set(jnp.arange(6)).reshape(N, k)

    def by_pick(out, w):
        padded = jnp.concatenate([out[:6], jnp.zeros((3, d))])
        return (padded[row] * w[..., None]).sum(1)

    got = by_row(out, w)
    assert got.dtype == jnp.float32 and not got[1].any() and not got[3].any()
    np.testing.assert_allclose(got, by_pick(out, w), rtol=1e-6)
    grads = [jax.grad(lambda out, w, f=f: (f(out, w) * weight).sum(), argnums=(0, 1))(out, w)
             for f in (by_row, by_pick)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    # positions 1 and 3, and the picks of the others that no row serves
    assert np.flatnonzero(np.asarray(grads[0][1]).reshape(-1)).tolist() == sorted(ROW_SLOT[:6].tolist())


# (N, top_k, experts held) of the three token cells
CELL_LAYERS = {"lfm2_train_b4s8k": (32768, 4, 8), "nemotron_twotower_train_b2s8k": (16384, 6, 8),
               "kimi_vl_train_b2s8k": (16384, 6, 8)}


@pytest.mark.parametrize("cell", CELL_LAYERS)
def test_each_program_of_a_cells_layer_moves_rows_by_buffer_row(cell):
    """One chunk, two and the whole buffer at the cell's ``(N, k, count)``:
    the chip measured the moves by buffer row ahead of those by pick at all
    three (PERF.md section 5, "PR 32"), so there is one form and no constant:
    each program of the lowered layer adds its ``length`` rows to their
    positions, and ``row_indexed`` in ``stats`` says so at every load
    (the layer at the cell's ``k`` and ``count``, 32 positions)."""
    import re

    N, k, count = CELL_LAYERS[cell]
    plan = jax.eval_shape(lambda sel: moe.dispatch(sel, 0, count), jax.ShapeDtypeStruct((N, k), jnp.int32))
    assert moe._lengths(N, plan) == [N, 2 * N, N * min(k, count)]
    n, d, E = 32, 8, 16
    layer = moe.ExpertsHeldMoE(E, k, 4, 0, count)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n, d))
    variables = layer.init(jax.random.PRNGKey(1), x)
    apply = jax.jit(lambda bias: layer.apply({"params": variables["params"], "buffers": {"expert_bias": bias}}, x))
    text = apply.lower(jnp.zeros((E,))).as_text()
    moved = re.findall(r"^\s*\}\) : \(tensor<[^>]*>, tensor<[^>]*>, tensor<(\d+)x" + f"{d}xf32>" + r"\) ->", text, re.M)
    assert sorted(int(m) for m in moved) == [n, 2 * n, n * k]
    for pushed in (0, 1, k):   # the first ``pushed`` held experts take every position
        bias = jnp.where(jnp.arange(E) < pushed, 10.0, jnp.where(jnp.arange(E) < count, -10.0, 0.0))
        stats = apply(bias)[1]
        assert int(stats["buffer_rows"]) == n * (pushed or 1) and int(stats["row_indexed"]) == 1


def _tiny_nemotron_h():
    from distar_tpu.model import NemotronH, default_nemotron_h_config

    cfg = deep_merge_dicts(default_nemotron_h_config(), {
        "hidden_size": 64, "hybrid_override_pattern": "M*E", "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 48, "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128})
    model = NemotronH(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens, tokens


def _tiny_deepseek_v3(B=2, S=32):
    from distar_tpu.model import DeepseekV3, default_deepseek_v3_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = deep_merge_dicts(default_deepseek_v3_config(), {
        "hidden_size": 64, "num_hidden_layers": 2, "intermediate_size": 96, "moe_intermediate_size": 24,
        "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
        "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128})
    model = DeepseekV3(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens, tokens


# the names of ``LM_STEP_SCOPES`` that each model's layers have, beside those every token model has
def _tiny_qwen3_next(B=2, S=32):
    from distar_tpu.model import Qwen3Next, default_qwen3_next_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = deep_merge_dicts(default_qwen3_next_config(), {
        "hidden_size": 64, "num_hidden_layers": 2, "full_attention_interval": 2, "linear_num_key_heads": 2,
        "linear_key_head_dim": 8, "linear_num_value_heads": 4, "linear_value_head_dim": 8, "gdn_chunk_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
        "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128})
    model = Qwen3Next(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens, tokens


def _tiny_laguna(B=2, S=32):
    from distar_tpu.model import Laguna, default_laguna_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = deep_merge_dicts(default_laguna_config(), {
        "hidden_size": 64, "intermediate_size": 96, "layer_types": ["full_attention", "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse"], "num_attention_heads_per_layer": [4, 8], "num_key_value_heads": 4,
        "kv_heads_held": {"count": 2}, "head_dim": 16, "sliding_window": 5, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
        "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128})
    model = Laguna(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens, tokens


def _tiny_phi4flash(B=2, S=32):
    from distar_tpu.model import Phi4Flash, default_phi4flash_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = deep_merge_dicts(default_phi4flash_config(), {
        "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8, "num_key_value_heads": 4,
        "sliding_window": 5, "mamba_dt_rank": 4, "vocab_size": 128})
    model = Phi4Flash(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    return model, jax.jit(model.init)(jax.random.PRNGKey(0), tokens), tokens, tokens


# grouped-query attention names its kernel and the rest of the layer itself (``attn_core`` / ``attn_proj``); under a
# model's ``attention`` they stay on the path and the trace reader gives the operation to ``attention``, the first
ATTENTION = {"attention", "attn_proj", "attn_core"}
EXPERTS = {"moe_router", "moe_dispatch", "moe_experts", "moe_combine"}        # every model but ``phi4flash`` has them
HAS = {"lfm2": {"short_conv", "dense_mlp"} | ATTENTION | EXPERTS,
       "nemotron_h": {"ssm_proj", "ssm_scan", "moe_shared"} | ATTENTION | EXPERTS,
       "deepseek_v3": {"mla_proj", "mla_core", "dense_mlp", "moe_shared"} | EXPERTS,
       "qwen3_next": {"gdn_proj", "gdn_scan", "moe_shared"} | ATTENTION | EXPERTS,
       "laguna": {"attn_proj", "attn_core", "swa_core", "dense_mlp", "moe_shared"} | EXPERTS,
       "phi4flash": {"mamba1_proj", "mamba1_scan", "gmu", "cross_core", "attn_proj", "attn_core", "swa_core", "dense_mlp"}}


@pytest.mark.parametrize("which", HAS)
def test_the_steps_scopes_are_on_the_compiled_program(tmp_path, which):
    """Every name of ``LM_STEP_SCOPES`` that the model has a part for is on
    the op_name paths of the lowered ``lm_train_step``, and no name of the
    parts only other models have: what the benchmark's trace reader looks for."""
    import optax

    from distar_tpu.learner.lm_learner import make_lm_train_step
    from distar_tpu.obs import LM_STEP_SCOPES, tree_spec

    if which == "lfm2":
        _, model, variables, tokens, labels = build()
    else:
        model, variables, tokens, labels = {"nemotron_h": _tiny_nemotron_h, "deepseek_v3": _tiny_deepseek_v3,
                                            "qwen3_next": _tiny_qwen3_next, "laguna": _tiny_laguna,
                                            "phi4flash": _tiny_phi4flash}[which]()
    optimizer = optax.adam(1e-3)
    step = jax.jit(make_lm_train_step(model, optimizer, dynamics=tree_spec({}, {"type": "none"})))
    text = step.lower(variables, optimizer.init(variables["params"]),
                      {"tokens": tokens, "labels": labels}).as_text(debug_info=True)
    assert "lm_train_step" in text
    there = {name for name in LM_STEP_SCOPES if f"/{name}" in text or f"({name})" in text}
    others = set().union(*HAS.values()) - HAS[which]
    assert there == set(LM_STEP_SCOPES) - others, (set(LM_STEP_SCOPES) - others) ^ there

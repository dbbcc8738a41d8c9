"""``phi4flash`` (``model/phi4flash.py``; ``ops/ssm.py``'s Mamba-1 selective scan, mixer and gated memory unit;
``ops/sequence.py``'s differential attention and LayerNorm; the frame's hand-over in ``model/token_decoder.py``)
against its plain reference (``benchmark/references/phi4flash_plain.py``, which imports none of them) at a tiny size
on seeded weights, float32, on the CPU. One tiny model a module, built once."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import phi4flash_plain as plain  # noqa: E402
from distar_tpu.model import Phi4Flash, default_phi4flash_config, phi4flash  # noqa: E402
from distar_tpu.ops import sequence, ssm  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

# the default's six published layers 14-19 at a hidden size of 64: four query pairs over two key/value pairs
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8, "num_key_value_heads": 4,
        "sliding_window": 5, "mamba_dt_rank": 4, "vocab_size": 128}
B, S = 2, 20


def system_loss(model, variables, params, tokens, labels):
    from distar_tpu.losses import compute_lm_loss

    logits, stats = model.apply({**variables, "params": params}, tokens)
    return compute_lm_loss(logits, labels)[0], (logits, stats)


def leaves(tree):
    return {"/".join(str(k.key) for k in path): x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def tiny():
    """The tiny model with seeded weights: its matrices widened five times so that each part moves the logits, its
    biases and norm offsets (drawn zero) and ``D`` moved off their draw so that leaving one out shows; the program's
    and the reference's loss, logits, statistics and gradients, each computed once."""
    cfg = deep_merge_dicts(default_phi4flash_config(), TINY)
    model = Phi4Flash(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    drawn = leaves(jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"])
    keys = jax.random.split(jax.random.PRNGKey(3), len(drawn))
    moved = {path: x * 5.0 if x.ndim >= 2 and not path.endswith(("A_log", "conv_kernel"))
             else x + 0.1 * jax.random.normal(k, x.shape) if path.endswith(("bias", "/D")) else x
             for (path, x), k in zip(drawn.items(), keys)}
    params = {}
    for path, x in moved.items():
        at = params
        for part in path.split("/")[:-1]:
            at = at.setdefault(part, {})
        at[path.split("/")[-1]] = x
    variables, pc = {"params": params}, plain.plain_config(cfg)
    # each compiled as one function, once, and called again by the tests that change a weight
    program = jax.jit(jax.value_and_grad(lambda p: system_loss(model, {"params": p}, p, tokens, labels), has_aux=True))
    reference = jax.jit(lambda p: (plain.loss(p, {"params": p}, pc, tokens, labels),
                                   plain.gradients({"params": p}, pc, tokens, labels)))
    with jax.default_matmul_precision("highest"):
        (total, (logits, stats)), grads = program(params)
        (want, (want_logits, want_stats)), want_grads = reference(params)
    return dict(cfg=cfg, pc=pc, model=model, variables=variables, tokens=tokens, labels=labels, total=total,
                logits=logits, stats=stats, grads=grads, want=want, want_logits=want_logits, want_stats=want_stats,
                want_grads=want_grads, program=program, reference=reference)


# ------------------------------------------------------------ the whole model
def test_the_model_is_the_plain_reference_on_loss_logits_and_every_statistic(tiny):
    stats, want = tiny["stats"], tiny["want_stats"]
    assert float(jnp.std(tiny["want_logits"])) > 0.3                     # the parts move the logits
    np.testing.assert_allclose(tiny["logits"], tiny["want_logits"], atol=2e-5)
    assert float(tiny["total"]) == pytest.approx(float(tiny["want"]), rel=1e-6)
    for name in ("rms", "mixer_rms", "ff_rms"):
        np.testing.assert_allclose(stats[name], jnp.stack(want[name]), rtol=2e-5, err_msg=name)
    # layers 14 and 16 carry a state, 15, 17 and 19 a lambda, 16 hands the memory on
    assert sorted(stats["ssm_state_rms"]) == ["layer_0", "layer_2"] and sorted(want["ssm_state_ms"]) == [0, 2]
    for i in (0, 2):
        assert float(stats["ssm_state_rms"][f"layer_{i}"]) == pytest.approx(
            float(plain.state_rms(want["ssm_state_ms"][i])), rel=1e-5)
    assert sorted(stats["diff_lambda"]) == ["layer_1", "layer_3", "layer_5"]
    for i, published in ((1, 15), (3, 17), (5, 19)):
        assert float(stats["diff_lambda"][f"layer_{i}"]) == pytest.approx(float(want["diff_lambda"][i]), rel=1e-6)
        # the published index, not the place in layers_held: 0.8 - 0.6 exp(-0.3 i) within the vectors' 0.1 draw
        assert abs(float(want["diff_lambda"][i]) - (0.8 - 0.6 * math.exp(-0.3 * published))) < 0.2
    assert float(stats["memory_rms"]) == pytest.approx(float(jnp.sqrt(want["memory_ms"][2])), rel=1e-5)
    assert stats["rows"].shape == (0, 1) and int(stats["overflow"]) == 0      # no experts


def test_the_gradients_are_the_plain_references_for_every_leaf(tiny):
    got, want = leaves(tiny["grads"]), leaves(tiny["want_grads"])
    # embedding (tied), final norm 2; Mamba 9, attention 13 | 9 (cross), memory unit 2; two norms and a SwiGLU 7 a layer
    assert set(got) == set(want) and len(got) == 3 + 2 * 9 + 2 * 13 + 9 + 2 + 6 * 7
    for path, g in want.items():
        if path.endswith("k_proj/bias"):
            # one number added to every score of a row moves no softmax: the gradient is zero up to rounding
            assert float(jnp.linalg.norm(g)) < 1e-6 > float(jnp.linalg.norm(got[path])), path
            continue
        assert float(jnp.linalg.norm(g)) > 0, path
        assert float(jnp.linalg.norm(got[path] - g) / jnp.linalg.norm(g)) < 2e-4, path


def test_a_lower_precision_is_outside_what_the_program_is_held_to(tiny):
    """The control of THIS file's limits: the reference with every product's operands rounded to bfloat16, the
    precision below the float32 these tests run in, parts from the float32 reference by far more than the program
    may (logits 2e-5, statistics 2e-5 above). The float8 control of the cell's limits, at the published widths, is
    ``tests/benchmark/test_benchmark_phi4flash.py``'s."""
    with jax.default_matmul_precision("highest"):
        _, (logits, stats) = jax.jit(lambda p: plain.loss(p, {"params": p}, tiny["pc"], tiny["tokens"], tiny["labels"],
                                                          products_in="bfloat16"))(tiny["variables"]["params"])
    assert float(jnp.max(jnp.abs(logits - tiny["want_logits"]))) > 5e-4
    off = np.abs(np.asarray(stats["mixer_rms"]) / np.asarray(tiny["want_stats"]["mixer_rms"]) - 1.0)
    assert off.max() > 1e-4


@pytest.mark.parametrize("without", plain.OMISSIONS)
def test_the_reference_without_a_term_is_another_model(tiny, without, monkeypatch):
    """Each term the cell's limits are argued against moves the statistic of the layer it belongs to."""
    monkeypatch.setattr(plain, "REMAT_BLOCK", 8)                           # the carry is dropped inside 20 positions
    with jax.default_matmul_precision("highest"):
        _, (less, less_stats) = plain.loss(tiny["variables"]["params"], tiny["variables"], tiny["pc"], tiny["tokens"],
                                           tiny["labels"], without=(without,))
    assert float(jnp.max(jnp.abs(less - tiny["want_logits"]))) > 1e-3
    at = {"window": (1,), "lambda": (1, 3, 5), "pair_norm": (1, 3, 5), "lambda_scale": (1, 3, 5), "layer_index": (1, 3, 5),
          "gated_memory": (4,), "skip": (0, 2, 4), "dt_bias": (0, 2), "carry": (0, 2)}[without]
    off = np.abs(np.asarray(less_stats["mixer_rms"]) / np.asarray(tiny["want_stats"]["mixer_rms"]) - 1.0)
    if without == "carry":                                                 # the state itself, which the slow channels live off
        off = {i: abs(float(plain.state_rms(less_stats["ssm_state_ms"][i]) / plain.state_rms(tiny["want_stats"]["ssm_state_ms"][i])) - 1.0)
               for i in at}
    assert all(off[i] > 1e-3 for i in at), off
    assert without == "carry" or all(off[i] < 1e-5 for i in range(min(at))), off   # the layers before it are as they were


def test_the_reference_takes_the_keys_a_block_can_see_or_all_of_them(tiny, monkeypatch):
    # four blocks of queries a sequence, side by side on threads, each a pair and five rows at a time: how the
    # published widths are computed; the fixture's reading took a sequence's 20 queries as one block, whole
    for name, value in (("QUERY_BLOCK", 5), ("KEY_STEP", 10), ("ROWS_AT_ONCE", 5)):
        monkeypatch.setattr(plain, name, value)
    with jax.default_matmul_precision("highest"):
        banded = plain.loss(tiny["variables"]["params"], tiny["variables"], tiny["pc"], tiny["tokens"], tiny["labels"])
        every = plain.loss(tiny["variables"]["params"], tiny["variables"], tiny["pc"], tiny["tokens"], tiny["labels"],
                           keys="all")
    np.testing.assert_allclose(banded[1][0], every[1][0], atol=1e-5)
    np.testing.assert_allclose(banded[1][0], tiny["want_logits"], atol=1e-5)
    assert plain.spans(20, 5, "band")[2] == (10, 15, 5, 15) and plain.spans(20, None, "band")[2] == (10, 15, 0, 20)


# ------------------------------------------------------------ the hand-over
def test_the_cross_decoder_holds_no_scan_and_no_key_or_value_projection(tiny):
    params = tiny["variables"]["params"]
    assert sorted(params["layer_5"]["attn"]) == ["lambda_k1", "lambda_k2", "lambda_q1", "lambda_q2", "o_proj",
                                                 "pair_norm", "q_proj"]
    assert sorted(params["layer_3"]["attn"]) == sorted(list(params["layer_5"]["attn"]) + ["k_proj", "v_proj"])
    assert sorted(params["layer_4"]["gmu"]) == ["in_proj", "out_proj"] and "mamba" not in params["layer_4"]
    assert "lm_head" not in params                                         # tied


@pytest.mark.parametrize("readers", (True, False), ids=("through_18_and_19", "readers_closed"))
def test_a_gradient_reaches_the_makers_through_the_cross_decoder_alone(tiny, readers):
    """With layer 16's ``out_proj`` and layer 17's ``o_proj`` at zero their mixers add nothing of their own to the
    stream: what the scan and the key/value projections do to the loss goes through the memory into layer 18 and
    through ``k, v`` into layer 19, under the frame's remat, and is the reference's. With those two readers'
    output projections at zero as well, nothing is left."""
    params = jax.tree.map(lambda x: x, tiny["variables"]["params"])
    shut = [("layer_2", "mamba", "out_proj"), ("layer_3", "attn", "o_proj")]
    shut += [] if readers else [("layer_4", "gmu", "out_proj"), ("layer_5", "attn", "o_proj")]
    for layer, module, name in shut:
        params[layer][module][name] = jax.tree.map(jnp.zeros_like, params[layer][module][name])
    with jax.default_matmul_precision("highest"):
        got = leaves(tiny["program"](params)[1])
        want = leaves(tiny["reference"](params)[1]) if readers else None
    for path in ("layer_2/mamba/A_log", "layer_2/mamba/x_proj/kernel", "layer_2/mamba/D", "layer_3/attn/k_proj/kernel",
                 "layer_3/attn/v_proj/kernel", "layer_3/attn/v_proj/bias"):
        if readers:
            assert float(jnp.linalg.norm(want[path])) > 0, path
            assert float(jnp.linalg.norm(got[path] - want[path]) / jnp.linalg.norm(want[path])) < 2e-4, path
        else:
            assert float(jnp.linalg.norm(got[path])) == 0.0, path


def test_the_memory_is_the_scans_output_before_the_gate():
    mixer = ssm.Mamba1Mixer(inner=128, state=16, dt_rank=4)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64))
    params, apply = jax.jit(mixer.init)(jax.random.PRNGKey(1), u), jax.jit(mixer.apply)
    out, memory, _ = apply(params, u)
    kernel = params["params"]["in_proj"]["kernel"]
    other = {"params": dict(params["params"], in_proj={"kernel": kernel.at[:, 128:].multiply(-2.0)})}   # z alone
    other_out, other_memory, _ = apply(other, u)
    np.testing.assert_array_equal(memory, other_memory)
    assert float(jnp.max(jnp.abs(out - other_out))) > 1e-4 and memory.shape == (2, 12, 128)
    # ... and holds the D x skip: with D at zero it is the scan alone
    no_skip = {"params": dict(params["params"], D=jnp.zeros((128,)))}
    assert float(jnp.max(jnp.abs(apply(no_skip, u)[1] - memory))) > 1e-3


def test_layers_follow_their_published_index_and_a_reader_needs_its_maker(tiny):
    cfg = tiny["cfg"]
    kinds = [phi4flash.layer_kind(cfg, i) for i in range(32)]
    assert [kinds.count(k) for k in ("mamba", "sliding", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == ["mamba", "sliding", "mamba", "full", "gmu", "cross"]
    assert kinds == [plain.layer_kind(tiny["pc"], i) for i in range(32)]
    for held in ([14, 15, 17, 18], [16, 18, 19]):                          # no memory for 18; no k, v for 19
        with pytest.raises(ValueError, match="hands on"):
            Phi4Flash(dict(cfg, layers_held=held)).init(jax.random.PRNGKey(0), tiny["tokens"])


# ------------------------------------------------------------ the selective scan
def literal_scan(x, dt, A, B, C):
    def step(h, at):
        x_t, dt_t, B_t, C_t = at
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], -1)

    last, y = jax.lax.scan(step, jnp.zeros((x.shape[0], x.shape[2], A.shape[1])),
                           tuple(t.swapaxes(0, 1) for t in (x, dt, B, C)))
    return y.swapaxes(0, 1), last


SCANS = {"xla": lambda *a: ssm._s6_xla(*a, 32), "kernel_interpreted": lambda *a: ssm._s6_kernel(*a, 8, True),
         "as_dispatched": ssm.selective_scan}


@pytest.fixture(scope="module")
def scanned():
    """Operands at 75 positions, weights of a loss over ``y`` and the last state, and the literal recurrence's values,
    last state and five gradients."""
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    b, S, c, N = 2, 75, 256, 16
    operands = (jax.random.normal(k[0], (b, S, c)), jax.nn.softplus(jax.random.normal(k[1], (b, S, c)) - 2),
                -jnp.exp(jax.random.normal(k[2], (c, N)) * 0.5), jax.random.normal(k[3], (b, S, N)),
                jax.random.normal(k[4], (b, S, N)))
    wy, wl = jax.random.normal(k[5], (b, S, c)), jax.random.normal(k[6], (b, c, N))

    def all_of(f):
        loss = lambda *a: (lambda y, last: jnp.sum(y * wy) + jnp.sum(last * wl))(*f(*a))
        return jax.jit(lambda *a: f(*a) + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a))(*operands)

    return all_of, all_of(literal_scan)


@pytest.mark.parametrize("form", SCANS)
def test_selective_scan_is_the_literal_recurrence_at_a_length_the_chunk_does_not_divide(scanned, form, monkeypatch):
    """Values, the last state and all five gradients; 75 positions are two chunks of 32 and a part of one in the XLA
    form, and in the kernel (interpreted; chunks of 8, two blocks of 128 channels) two grid steps of ``CHUNKS_A_STEP``
    chunks and a part of one, padded with ``dt = 0``, which leaves the state as it is."""
    monkeypatch.setattr(ssm, "S6_LANES", 128)
    all_of, want = scanned
    for name, g, w in zip(("y", "last", "dx", "ddt", "dA", "dB", "dC"), all_of(SCANS[form]), want):
        assert g.shape == w.shape and g.dtype == jnp.float32, name
        assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < 2e-6, name


def test_the_kernel_takes_whole_tiles_and_the_rest_goes_to_xla():
    assert ssm.s6_kernel_takes(5120, 16) and ssm.s6_kernel_takes(128, 16)
    assert not ssm.s6_kernel_takes(96, 16) and not ssm.s6_kernel_takes(512, 12)
    assert [ssm._s6_lanes(c) for c in (128, 640, 1536, 5120)] == [128, 640, 768, 1024]     # the widest block that divides
    x = jnp.ones((1, 8, 96))
    text = jax.jit(ssm.selective_scan).lower(x, x, -jnp.ones((96, 16)), jnp.ones((1, 8, 16)), jnp.ones((1, 8, 16))).as_text()
    assert "platform_index" not in text and "tpu_custom_call" not in text


# ------------------------------------------------------------ differential attention
def written_out(p, u, kv, H, Hkv, lambda_init, window, eps=1e-5):
    """Every pair's two softmaxes, one query at a time, in numpy float64."""
    p = jax.tree.map(lambda t: np.asarray(t, np.float64), p)
    u = np.asarray(u, np.float64)
    Bt, S, d = u.shape
    D = d // H
    q = (u @ p["q_proj"]["kernel"] + p["q_proj"]["bias"]).reshape(Bt, S, H, D)
    if kv is None:
        k, v = ((u @ p[n]["kernel"] + p[n]["bias"]).reshape(Bt, S, Hkv, D) for n in ("k_proj", "v_proj"))
    else:
        k, v = (np.asarray(t, np.float64) for t in kv)
    lam = np.exp(p["lambda_q1"] @ p["lambda_k1"]) - np.exp(p["lambda_q2"] @ p["lambda_k2"]) + lambda_init
    out = np.zeros((Bt, S, H // 2, 2 * D))
    for b in range(Bt):
        for j in range(H // 2):
            pair = j // ((H // 2) // (Hkv // 2))
            V = np.concatenate([v[b, :, 2 * pair], v[b, :, 2 * pair + 1]], axis=-1)
            for i in range(S):
                first = 0 if window is None else max(0, i - window + 1)
                maps = []
                for r in (0, 1):
                    score = k[b, first:i + 1, 2 * pair + r] @ q[b, i, 2 * j + r] / math.sqrt(D)
                    weight = np.exp(score - score.max())
                    maps.append(weight / weight.sum() @ V[first:i + 1])
                o = maps[0] - lam * maps[1]
                out[b, i, j] = o / np.sqrt(np.mean(o * o) + eps) * p["pair_norm"]["scale"] * (1.0 - lambda_init)
    return out.reshape(Bt, S, d) @ p["o_proj"]["kernel"] + p["o_proj"]["bias"], lam


@pytest.mark.parametrize("window,handed", [(None, False), (5, False), (None, True)], ids=("full", "window", "cross"))
def test_differential_attention_is_two_softmaxes_a_pair_written_out(window, handed):
    H, Hkv, D, lambda_init = 8, 4, 8, 0.8 - 0.6 * math.exp(-0.3 * 17)
    layer = sequence.DifferentialAttention(H, Hkv, D, lambda_init, window=window)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(k[0], (2, 12, 64))
    kv = (jax.random.normal(k[1], (2, 12, Hkv, D)), jax.random.normal(k[2], (2, 12, Hkv, D))) if handed else None
    params = jax.jit(layer.init)(k[3], u, kv)["params"]
    assert ("k_proj" in params) == ("v_proj" in params) == (not handed)
    keys = jax.random.split(k[4], len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        x * 8.0 if x.ndim == 2 else x + 0.3 * jax.random.normal(key, x.shape) for x, key in zip(jax.tree.leaves(params), keys)])
    with jax.default_matmul_precision("highest"):
        out, (k_out, v_out), lam = jax.jit(layer.apply)({"params": params}, u, kv)
    want, want_lam = written_out(params, u, kv, H, Hkv, lambda_init, window)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert float(lam) == pytest.approx(float(want_lam), abs=1e-5) and k_out.shape == v_out.shape == (2, 12, Hkv, D)
    if handed:
        np.testing.assert_array_equal(k_out, kv[0])


def test_layer_norm_is_written_out():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64)) * 3.0 + 1.0
    norm = sequence.LayerNorm(1e-5)
    params = {"params": {"scale": jnp.linspace(0.5, 1.5, 64), "bias": jnp.linspace(-1.0, 1.0, 64)}}
    x64 = np.asarray(x, np.float64)
    want = (x64 - x64.mean(-1, keepdims=True)) / np.sqrt(x64.var(-1, keepdims=True) + 1e-5)
    want = want * np.asarray(params["params"]["scale"]) + np.asarray(params["params"]["bias"])
    np.testing.assert_allclose(norm.apply(params, x), want, atol=1e-5)
    assert sorted(norm.init(jax.random.PRNGKey(0), x)["params"]) == ["bias", "scale"]
    assert norm.apply(params, x.astype(jnp.bfloat16)).dtype == jnp.bfloat16

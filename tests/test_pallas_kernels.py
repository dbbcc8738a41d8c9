"""Pallas kernel correctness (interpret mode on CPU) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distar_tpu.ops import pallas_kernels
from distar_tpu.ops.pallas_kernels import (
    masked_attention,
    masked_attention_reference,
    scatter_add_onehot,
    scatter_add_reference,
)
from distar_tpu.ops import scatter_connection, sequence_mask


def test_masked_attention_matches_reference(rng):
    B, H, N, Dh = 2, 2, 64, 32
    q = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    mask = sequence_mask(jnp.array([10, 64]), N)
    got = masked_attention(q, k, v, mask, interpret=True)
    want = masked_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_masked_attention_padding_invariance(rng):
    """Garbage in masked key slots must not change valid outputs."""
    B, H, N, Dh = 1, 2, 32, 16
    q = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    mask = sequence_mask(jnp.array([7]), N)
    out1 = masked_attention(q, k, v, mask, interpret=True)
    k2 = k.at[:, :, 7:].add(100.0)
    v2 = v.at[:, :, 7:].add(-50.0)
    out2 = masked_attention(q, k2, v2, mask, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-4)


def test_masked_attention_bf16_out_dtype(rng):
    """bf16 inputs produce a bf16 output (matching the XLA path's einsum
    dtype under mixed precision) with f32 accumulation inside."""
    B, H, N, Dh = 1, 2, 16, 8
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, N, Dh)), jnp.bfloat16)
        for _ in range(3)
    )
    mask = jnp.ones((B, N), bool)
    out = masked_attention(q, k, v, mask, interpret=True)
    assert out.dtype == jnp.bfloat16
    want = masked_attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), mask
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=0.05, atol=0.05
    )


def test_scatter_add_matches_jnp(rng):
    B, N, D, H, W = 2, 16, 8, 8, 8
    emb = jnp.asarray(rng.standard_normal((B, N, D)).astype(np.float32))
    x = jnp.asarray(rng.integers(0, W, (B, N)))
    y = jnp.asarray(rng.integers(0, H, (B, N)))
    flat = (y * W + x).astype(jnp.int32)
    got = scatter_add_onehot(emb, flat, H * W, interpret=True)
    want = scatter_connection(emb, jnp.stack([x, y], -1), (H, W), "add").reshape(B, H * W, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_scatter_add_collisions(rng):
    """Multiple entities on one cell must sum."""
    B, N, D = 1, 4, 2
    emb = jnp.ones((B, N, D))
    flat = jnp.zeros((B, N), jnp.int32)  # all collide on cell 0
    out = scatter_add_onehot(emb, flat, 9, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 0]), [4.0, 4.0])
    assert float(jnp.abs(out[0, 1:]).sum()) == 0.0


def test_scatter_onehot_matches_reference_unaligned_hw(rng):
    """MXU one-hot formulation == jnp reference (incl. collisions), fwd and
    grad, at an hw that does NOT divide the cell chunk."""
    B, N, D, H, W = 2, 16, 8, 9, 7  # hw=63: exercises the padded last chunk
    emb = jnp.asarray(rng.standard_normal((B, N, D)).astype(np.float32))
    flat = jnp.asarray(rng.integers(0, H * W, (B, N))).astype(jnp.int32)
    flat = flat.at[0, :4].set(0)  # forced collisions
    want = scatter_add_reference(emb, flat, H * W)
    got = scatter_add_onehot(emb, flat, H * W, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    g1 = jax.grad(lambda e: jnp.sum(scatter_add_onehot(e, flat, H * W, True) ** 2))(emb)
    g2 = jax.grad(lambda e: jnp.sum(scatter_add_reference(e, flat, H * W) ** 2))(emb)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-5)


def test_scatter_oob_clipped_like_the_reference(rng):
    """Out-of-range indices are clipped to [0, hw-1] in the public wrapper,
    exactly as the jnp reference (and ops.scatter_connection) clips them:
    switching impl strings can never silently change forward or gradient
    semantics (the raw one-hot kernel would drop them)."""
    B, N, D, hw = 1, 4, 2, 8
    emb = jnp.asarray(rng.standard_normal((B, N, D)).astype(np.float32))
    flat = jnp.asarray([[0, 3, -2, hw + 5]], jnp.int32)  # last two OOB
    out_ref = scatter_add_reference(emb, flat, hw)
    out_onehot = scatter_add_onehot(emb, flat, hw, interpret=True)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_onehot),
                               rtol=1e-5, atol=1e-5)
    # clamp semantics: the OOB entities landed on cells 0 and hw-1
    np.testing.assert_allclose(np.asarray(out_onehot[0, 0]),
                               np.asarray(emb[0, 0] + emb[0, 2]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_onehot[0, hw - 1]),
                               np.asarray(emb[0, 3]), rtol=1e-5)
    # gradients agree too, and flow THROUGH the clamped cells (not zeroed)
    g1 = jax.grad(lambda e: jnp.sum(scatter_add_onehot(e, flat, hw, True) ** 2))(emb)
    g2 = jax.grad(lambda e: jnp.sum(scatter_add_reference(e, flat, hw) ** 2))(emb)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(g1[0, 2:]).sum()) > 0.0  # clamped, so grads flow


def test_scatter_impl_switch_onehot(rng):
    """scatter_connection(impl='pallas_onehot') routes and matches XLA."""
    B, N, D, H, W = 2, 12, 4, 8, 8
    emb = jnp.asarray(rng.standard_normal((B, N, D)).astype(np.float32))
    x = jnp.asarray(rng.integers(0, W, (B, N)))
    y = jnp.asarray(rng.integers(0, H, (B, N)))
    want = scatter_connection(emb, jnp.stack([x, y], -1), (H, W), "add")
    got = scatter_connection(emb, jnp.stack([x, y], -1), (H, W), "add",
                             impl="pallas_onehot")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_masked_attention_vjp_matches_reference(rng):
    """Trainable kernel: pallas forward, XLA-recompute backward — gradients
    must match the dense reference's exactly."""
    B, H, N, Dh = 2, 2, 32, 16
    q = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, H, N, Dh)).astype(np.float32))
    mask = sequence_mask(jnp.array([9, 32]), N)
    g1 = jax.grad(
        lambda q, k, v: jnp.sum(masked_attention(q, k, v, mask, True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(masked_attention_reference(q, k, v, mask) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_scatter_add_vjp_is_gather(rng):
    B, N, D, HW = 2, 24, 4, 40
    emb = jnp.asarray(rng.standard_normal((B, N, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, HW, (B, N)), jnp.int32)

    ga = jax.grad(lambda e: jnp.sum(scatter_add_onehot(e, idx, HW, True) ** 2))(emb)
    gb = jax.grad(lambda e: jnp.sum(scatter_add_reference(e, idx, HW) ** 2))(emb)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=1e-4, atol=1e-4)


def test_interpret_choice_is_one_logged_counted_place(monkeypatch, caplog):
    """``interpret=None`` resolves in ONE helper: on the CPU backend it picks
    interpret mode, logs once at warning level and counts every fallback; an
    explicit argument is returned untouched and never counted; every kernel
    entry goes through the helper."""
    import inspect
    import logging

    from distar_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()  # fresh: the "once" is per process registry
    monkeypatch.setattr(pallas_kernels, "get_registry", lambda: reg)
    counted = lambda: pallas_kernels.interpret_fallbacks().value
    with caplog.at_level(logging.WARNING, logger=pallas_kernels.__name__):
        assert pallas_kernels.resolve_interpret(None) is True
        assert pallas_kernels.resolve_interpret(None) is True
    assert counted() == 2
    assert [r.levelno for r in caplog.records] == [logging.WARNING]  # once
    assert "INTERPRET" in caplog.records[0].getMessage()
    for explicit in (True, False):
        assert pallas_kernels.resolve_interpret(explicit) is explicit
    assert counted() == 2
    # a test that needs the native branch steers the backend query itself
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_kernels.resolve_interpret(None) is False
    assert counted() == 2
    src = inspect.getsource(pallas_kernels)
    assert src.count("default_backend()") == 2  # the test + its log argument
    assert src.count("pl.pallas_call(") == src.count(
        "interpret = resolve_interpret(interpret)")


@pytest.mark.slow
def test_small_model_trains_with_pallas_ops():
    """Full small-model SL train step with BOTH pallas hot-ops enabled
    (attention_impl='pallas', scatter impl='pallas_onehot', interpret on
    CPU): the A/B the bench runs on silicon must be a real training path.

    Runs in a SUBPROCESS: pallas interpret mode at train-step scale leaves
    native state behind that can segfault unrelated later jit compiles in
    the same process (reproduced at suite scale), so its lifetime is scoped
    to a child interpreter."""
    import os
    import subprocess
    import sys

    code = """
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from distar_tpu.learner import SLLearner

model = {
    "encoder": {
        "entity": {"layer_num": 1, "hidden_dim": 32, "output_dim": 16,
                   "head_dim": 8, "attention_impl": "pallas"},
        "spatial": {"down_channels": [4, 4, 8], "project_dim": 4,
                    "resblock_num": 1, "fc_dim": 16},
        "scatter": {"output_dim": 4, "impl": "pallas_onehot"},
        "core_lstm": {"hidden_size": 32, "num_layers": 1},
    },
    "policy": {
        "action_type_head": {"res_dim": 16, "res_num": 1, "gate_dim": 32},
        "delay_head": {"decode_dim": 16},
        "queued_head": {"decode_dim": 16},
        "selected_units_head": {"func_dim": 16},
        "target_unit_head": {"func_dim": 16},
        "location_head": {"res_dim": 8, "res_num": 1,
                          "upsample_dims": [4, 4, 1], "map_skip_dim": 8},
    },
    "value": {"res_dim": 8, "res_num": 1},
}
learner = SLLearner(
    {
        "common": {"experiment_name": "pallas_sl_smoke"},
        "learner": {"batch_size": 2, "unroll_len": 2,
                    "save_freq": 10 ** 9, "log_freq": 10 ** 9},
        "model": model,
    }
)
learner.run(max_iterations=2)
assert learner.last_iter.val == 2
assert np.isfinite(learner.variable_record.get("total_loss").avg)
print("PALLAS-TRAIN-OK")
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=repo,
        capture_output=True, text=True, timeout=1800,
    )
    assert out.returncode == 0, f"child failed:\n{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
    assert "PALLAS-TRAIN-OK" in out.stdout

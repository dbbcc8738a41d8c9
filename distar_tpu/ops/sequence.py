"""Layers of a causal token-sequence model: RMSNorm, rotary positions, the
depthwise causal convolution, the gated short convolution and causal
grouped-query attention.

These are the operators the token models share (``model/lfm2.py``,
``model/nemotron_h.py``; the state-space mixer is ``ops/ssm.py``). Parameters
are float32; ``dtype`` is the compute dtype of the matrix products. No
projection has a bias. Sequences are ``[B, S, d]``, position 0 first.

Attention over ``S`` positions never holds an ``S x S`` score tensor per
head. Which code computes it follows from the platform the program is being
compiled for (``jax.lax.platform_dependent``) and the shapes: on a TPU, at a
sequence length its tiles divide (``FLASH_MIN_BLOCK``), JAX's own flash
attention (``jax.experimental.pallas.ops.tpu.flash_attention``: online
softmax, blocks above the diagonal skipped, its own backward kernels; it has
no interpret mode); anywhere else a loop over query blocks under
``jax.checkpoint`` (every block multiplies against all keys and masks, so it
does twice the causal work).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any

XLA_QUERY_BLOCK = 512
# flash attention's tiles at 8k positions: 512 x 512 score tiles keep the
# MXU fed; the library's default of 128 is a placeholder ("select better
# parameters", its own TODO)
FLASH_BLOCK = 512
FLASH_MIN_BLOCK = 128  # the kernel's tiles are multiples of this many positions


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def dense(features: int, dtype: Dtype, name: str) -> nn.Dense:
    """A bias-free projection with float32 parameters and ``dtype`` products."""
    return nn.Dense(features, use_bias=False, dtype=dtype, param_dtype=jnp.float32,
                    kernel_init=nn.initializers.normal(0.02), name=name)


def rope(x, theta: float):
    """Rotary positions over the whole head, rotate-half convention:
    ``x * cos + rotate_half(x) * sin`` with ``rotate_half([a, b]) = [-b, a]``
    and angle ``t * theta^(-2i/D)`` for pair ``i``. ``x`` is ``[B, S, H, D]``."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]      # [S, D/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-b, a], axis=-1) * sin).astype(x.dtype)


def causal_conv(z, kernel, bias=None):
    """Depthwise causal convolution over time: ``c_t = sum_k kernel[k] *
    z[t - (L-1) + k] (+ bias)`` with zeros before position 0. ``z`` is
    ``[B, S, d]``, ``kernel`` ``[L, d]``, ``bias`` ``[d]``; L shifted
    multiply-adds, no convolution primitive."""
    L, S = kernel.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    c = sum(padded[:, k:k + S] * kernel[k].astype(z.dtype) for k in range(L))
    return c if bias is None else c + bias.astype(z.dtype)


def conv_kernel_init(L: int):
    """A depthwise kernel's fan-in is its ``L`` taps, as torch's Conv1d draws it: U(+-1/sqrt(L))."""
    bound = L ** -0.5
    return lambda key, shape: jax.random.uniform(key, shape, jnp.float32, -bound, bound)


class ShortConv(nn.Module):
    """The gated short convolution: ``[B, C, X] = split3(W_in u)``, ``Op(u) =
    W_out (C * conv(B * X))`` with a depthwise causal kernel of ``L`` taps."""

    L: int = 3
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        d = u.shape[-1]
        gate_b, gate_c, x = jnp.split(dense(3 * d, self.dtype, "in_proj")(u), 3, axis=-1)
        kernel = self.param("conv_kernel", conv_kernel_init(self.L), (self.L, d))
        return dense(d, self.dtype, "out_proj")(gate_c * causal_conv(gate_b * x, kernel))


def _attention_xla(q, k, v, scale: float):
    """``q`` [B, S, Hkv, G, D], ``k``/``v`` [B, S, Hkv, D] -> [B, S, Hkv, G, D].
    One query block at a time against all keys, float32 softmax."""
    B, S, Hkv, G, D = q.shape
    block = min(S, XLA_QUERY_BLOCK)
    if S % block:
        raise ValueError(f"sequence length {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        score = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                           preferred_element_type=jnp.float32) * scale
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(S)[None, :]
        prob = jax.nn.softmax(jnp.where(visible, score, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", prob.astype(v.dtype), v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))          # [S/block, B, block, ...]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hkv, G, D)


def _attention_flash(q, k, v, scale: float):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes, flash_attention

    B, S, Hkv, G, D = q.shape
    b = min(S, FLASH_BLOCK)
    heads = lambda t: t.reshape(B, S, Hkv * G, D).transpose(0, 2, 1, 3)
    # the kernel wants one key/value head per query head: each is repeated for its group
    rep = lambda t: heads(jnp.broadcast_to(t[:, :, :, None, :], (B, S, Hkv, G, D)))
    out = flash_attention(
        heads(q), rep(k), rep(v), causal=True, sm_scale=scale,
        block_sizes=BlockSizes(
            block_q=b, block_k_major=b, block_k=b, block_b=1,
            block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b, block_q_dkv=b,
            block_k_major_dq=b, block_k_dq=b, block_q_dq=b))
    return out.transpose(0, 2, 1, 3).reshape(B, S, Hkv, G, D)


def causal_attention(q, k, v, scale: float):
    """``q`` [B, S, Hkv, G, D], ``k``/``v`` [B, S, Hkv, D] -> [B, S, Hkv, G, D]."""
    if q.shape[1] % FLASH_MIN_BLOCK:
        return _attention_xla(q, k, v, scale)
    return jax.lax.platform_dependent(
        q, k, v, tpu=lambda *qkv: _attention_flash(*qkv, scale),
        default=lambda *qkv: _attention_xla(*qkv, scale))


class CausalGQAttention(nn.Module):
    """Causal grouped-query attention: ``heads`` query heads over ``kv_heads``
    key/value heads, ``softmax(q k^T / sqrt(head_dim)) v``. With ``positions``
    (LFM2): RMSNorm over each head of q and k (one learned scale of
    ``head_dim``), then rotary positions. Without (``nemotron_h``: the
    state-space layers carry the order): q and k as projected, and
    ``rope_theta`` is read by nothing."""

    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: Dtype = jnp.float32
    positions: bool = True

    @nn.compact
    def __call__(self, u):
        B, S, d = u.shape
        H, Hkv, D = self.heads, self.kv_heads, self.head_dim
        q = dense(H * D, self.dtype, "q_proj")(u).reshape(B, S, H, D)
        k = dense(Hkv * D, self.dtype, "k_proj")(u).reshape(B, S, Hkv, D)
        v = dense(Hkv * D, self.dtype, "v_proj")(u).reshape(B, S, Hkv, D)
        if self.positions:
            q = rope(RMSNorm(self.eps, name="q_norm")(q), self.rope_theta)
            k = rope(RMSNorm(self.eps, name="k_norm")(k), self.rope_theta)
        out = causal_attention(q.reshape(B, S, Hkv, H // Hkv, D), k, v, D ** -0.5)
        return dense(d, self.dtype, "o_proj")(out.reshape(B, S, H * D))


class SwiGLU(nn.Module):
    """``W_2 (silu(W_1 u) * W_3 u)``."""

    width: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        gate = nn.silu(dense(self.width, self.dtype, "w1")(u))
        return dense(u.shape[-1], self.dtype, "w2")(gate * dense(self.width, self.dtype, "w3")(u))

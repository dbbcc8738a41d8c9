"""Gated DeltaNet: the gated delta rule in chunks, and the layer around it.

One value head carries a state ``S`` [K, V] (``K`` = key head size, ``V`` =
value head size) along the sequence, zero before position 0:

  S'_t = alpha_t S_{t-1}
  S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T          o_t = S_t^T q_t

with ``alpha_t = exp(g_t)`` in (0, 1] the decay and ``beta_t`` in (0, 1) the
strength of the write, a number a head and position each. The transition
``alpha_t (I - beta_t k_t k_t^T)`` is a matrix that depends on the data, so
unlike Mamba-2's diagonal decay (``ops/ssm.py``) the state a chunk adds is not
one product: inside a chunk of ``C`` positions the rows are coupled through a
unit lower-triangular system. With ``gamma_i`` the sum of ``g`` inside the
chunk up to ``i`` and ``Gamma_ij = exp(gamma_i - gamma_j)`` for ``i >= j``
(every exponent <= 0, so nothing overflows):

  L_ij = beta_i (k_i . k_j) Gamma_ij  for i > j,      T = (I + L)^-1
  U = T (beta * V),        W = T (beta * exp(gamma) * K)
  per chunk in order, ``S`` the state it starts from:
    V' = U - W S
    O  = (exp(gamma) * Q) S + tril(Q K^T * Gamma) V'
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) * K)^T V'

``chunked_delta_rule`` computes that: ``T`` for every chunk at once, in
float32, by XLA's batched triangular solve against the identity (the other
way to it, ``L`` being nilpotent, is ``(I - L)(I + L^2)(I + L^4)...``, ``log2
C`` squarings and as many products: on the chip it lost, 72.7 ms a layer
forward and backward against 53.5, and went; PERF.md section 6, PR 36),
``U`` and ``W`` as
products in ``dtype``, then ``S / C`` dependent steps of two products each that
carry ``S`` in float32 and emit each chunk's ``V'`` and starting state, and
``O`` from those for all chunks at once. The backward pass is JAX's own
derivative of these products and of the solve, a group of heads at a time
(``lax.map``), each group's intermediates computed again in its backward pass,
as ``chunked_scan`` bounds its memory. A sequence whose length ``C`` does not
divide is padded with positions of ``g = 0``, ``beta = 0``, which leave the
state as it is and are cut off.

``GatedDeltaNet`` is the layer, as ``qwen3_next`` publishes it: ``[q k v z] =
W_qkvz u``, ``[b a] = W_ba u``; ``[q k v] <- silu(causal_conv([q k v]))``;
``q <- q / |q| / sqrt(K)``, ``k <- k / |k|`` per head, each q/k head serving
``value_heads / key_heads`` value heads in a row (repeated, not tiled);
``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the rule;
``y = RMSNorm_V(o) * w_o * silu(z)`` per head (the norm first, then the gate:
not ``ops.ssm.GroupedRMSNormGated``, which gates first); ``W_out y``. Its
operations sit under two scopes: ``gdn_scan`` (``chunked_delta_rule`` alone,
the triangular system included) and ``gdn_proj`` (everything else).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .sequence import causal_conv, conv_kernel_init, dense
from .ssm import _dt_bias_init, state_rms

Dtype = Any
F32 = jnp.float32


def _inverse(L):
    """``(I + L)^-1`` of strictly lower-triangular ``L`` [..., C, C], in its own dtype: XLA's batched triangular
    solve against the identity."""
    eye = jnp.broadcast_to(jnp.eye(L.shape[-1], dtype=L.dtype), L.shape)
    return jax.lax.linalg.triangular_solve(eye + L, eye, left_side=True, lower=True, unit_diagonal=True)



def _group_rule(q, k, v, g, beta, dtype: Dtype):
    """One group's heads: ``q``/``k`` [b, c, hk, C, K], ``v`` [b, c, h, C, V],
    ``g``/``beta`` [b, c, h, C] float32 -> (``o`` [b, c, h, C, V] float32, the
    last state [b, h, K, V] float32). Value head ``j`` reads key head
    ``j // (h / hk)``."""
    C, r = q.shape[3], v.shape[2] // k.shape[2]
    per_value_head = lambda t: jnp.repeat(t, r, axis=2)
    gamma = jnp.cumsum(g, axis=-1)
    seen = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    Gamma = jnp.exp(jnp.where(seen, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))   # [b, c, h, i, j]
    kk = per_value_head(jnp.einsum("bchid,bchjd->bchij", k, k, preferred_element_type=F32))
    qk = per_value_head(jnp.einsum("bchid,bchjd->bchij", q, k, preferred_element_type=F32))
    T = _inverse(jnp.tril(beta[..., None] * kk * Gamma, -1)).astype(dtype)
    q, k = per_value_head(q), per_value_head(k)
    decay = jnp.exp(gamma)
    U = jnp.einsum("bchij,bchjd->bchid", T, v * beta[..., None].astype(dtype), preferred_element_type=F32)
    W = jnp.einsum("bchij,bchjd->bchid", T, k * (beta * decay)[..., None].astype(dtype),
                   preferred_element_type=F32).astype(dtype)
    k_to_end = k * jnp.exp(gamma[..., -1:] - gamma)[..., None].astype(dtype)   # exp(gamma_C - gamma_j) k_j
    through = jnp.exp(gamma[..., -1])                                           # [b, c, h]

    def carry(S, chunk_of):
        U_c, W_c, k_c, through_c = chunk_of
        new = U_c - jnp.einsum("bhik,bhkv->bhiv", W_c, S.astype(dtype), preferred_element_type=F32)
        added = jnp.einsum("bhik,bhiv->bhkv", k_c, new.astype(dtype), preferred_element_type=F32)
        return through_c[..., None, None] * S + added, (S, new)

    by_chunk = lambda t: t.swapaxes(0, 1)
    start = jnp.zeros((q.shape[0], v.shape[2], q.shape[-1], v.shape[-1]), F32)
    last, (S_in, new) = jax.lax.scan(carry, start, tuple(map(by_chunk, (U, W, k_to_end, through))))
    o = jnp.einsum("bchik,cbhkv->bchiv", q * decay[..., None].astype(dtype), S_in.astype(dtype),
                   preferred_element_type=F32)
    o = o + jnp.einsum("bchij,cbhjv->bchiv", (qk * Gamma).astype(dtype), new.astype(dtype),
                       preferred_element_type=F32)
    return o, last


def chunked_delta_rule(q, k, v, g, beta, chunk: int = 64, dtype: Dtype = jnp.float32,
                       groups: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``q``/``k`` [b, S, Hk, K] (normalised and scaled by the caller), ``v``
    [b, S, H, V], ``g``/``beta`` [b, S, H] float32 -> (``o`` [b, S, H, V]
    float32, the state after the last position [b, H, K, V] float32).
    ``groups`` groups of ``H / groups`` value heads (and their ``Hk / groups``
    key heads) one after another, each group's intermediates computed again in
    its backward pass: the ``C x C`` matrices, ``U``, ``W`` and every chunk's
    starting state of all 32 heads at once are 1.5 GB a layer at 16,384
    positions, and on the chip eight groups of four were also the fastest
    (PERF.md section 6, PR 36)."""
    b, S, H, V = v.shape
    Hk, K = q.shape[2:]
    groups = math.gcd(groups, Hk)
    C = min(chunk, S)
    pad = -S % C
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v, g, beta))
    nc = (S + pad) // C

    def by_group(t, heads, *rest):   # [b, S, heads, ...] -> [groups, b, nc, heads / groups, C, ...]
        t = t.reshape(b, nc, C, groups, heads // groups, *rest)
        return jnp.moveaxis(jnp.moveaxis(t, 3, 0), 3, 4)

    o, last = jax.lax.map(
        lambda group: jax.checkpoint(functools.partial(_group_rule, dtype=dtype))(*group),
        (by_group(q.astype(dtype), Hk, K), by_group(k.astype(dtype), Hk, K), by_group(v.astype(dtype), H, V),
         by_group(g.astype(F32), H), by_group(beta.astype(F32), H)))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 4), 0, 3).reshape(b, nc * C, H, V)[:, :S]   # [g, b, c, h, C, V] -> [b, S, H, V]
    return o, jnp.moveaxis(last, 0, 1).reshape(b, H, K, V)


def _l2_normalised(x, eps: float = 1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def output_gate(o, z, scale, eps: float):
    """``RMSNorm(o) * scale * silu(z)`` over the last axis (a head), float32: the norm first, then the gate."""
    o, z = o.astype(F32), z.astype(F32)
    return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * scale * nn.silu(z)


class GatedDeltaNet(nn.Module):
    """``u`` [B, S, d] -> (the mixer's output [B, S, d], stats: ``state_rms``
    (``ops.ssm.state_rms`` over the value heads' last states) and
    ``decay_mean`` (the mean ``alpha`` over heads and positions: a run that
    drops the decay reads 1)). Parameters: ``in_proj_qkvz`` [d, 2 Hk K + 2 H V],
    ``in_proj_ba`` [d, 2 H], ``conv_kernel`` [L, 2 Hk K + H V] (no bias),
    ``dt_bias`` and ``A_log`` one a value head, ``out_norm`` [V] (ones),
    ``out_proj`` [H V, d]. ``A = exp(A_log)`` is drawn uniform in (0, 16) and
    ``softplus(dt_bias)`` log-uniform in ``dt_range``, as the linear-attention
    library the published code follows draws them: the decays then spread from
    a few positions to thousands (docs/token_models.md)."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: Dtype = jnp.float32
    dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)   # softplus(dt_bias): least, largest, floor
    groups: int = 8

    @nn.compact
    def __call__(self, u):
        Bt, S, d = u.shape
        Hk, H, K, V = self.key_heads, self.value_heads, self.key_dim, self.value_dim
        qk_width, v_width = Hk * K, H * V
        with jax.named_scope("gdn_proj"):
            qkv, z = jnp.split(dense(2 * qk_width + 2 * v_width, self.dtype, "in_proj_qkvz")(u),
                               [2 * qk_width + v_width], axis=-1)
            b, a = jnp.split(dense(2 * H, self.dtype, "in_proj_ba")(u).astype(F32), 2, axis=-1)
            kernel = self.param("conv_kernel", conv_kernel_init(self.conv_kernel),
                                (self.conv_kernel, 2 * qk_width + v_width))
            q, k, v = jnp.split(nn.silu(causal_conv(qkv, kernel)), [qk_width, 2 * qk_width], axis=-1)
            q = _l2_normalised(q.reshape(Bt, S, Hk, K)) * K ** -0.5
            k = _l2_normalised(k.reshape(Bt, S, Hk, K))
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_range), (H,))
            A_log = self.param("A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, F32, 1e-4, 16.0)),
                               (H,))
            g = -jnp.exp(A_log) * nn.softplus(a + dt_bias)
            beta = jax.nn.sigmoid(b)
        with jax.named_scope("gdn_scan"):
            o, last = chunked_delta_rule(q, k, v.reshape(Bt, S, H, V), g, beta, self.chunk, self.dtype, self.groups)
        with jax.named_scope("gdn_proj"):
            scale = self.param("out_norm", nn.initializers.ones, (V,), F32)
            y = output_gate(o, z.reshape(Bt, S, H, V), scale, self.eps).astype(self.dtype)
            out = dense(d, self.dtype, "out_proj")(y.reshape(Bt, S, v_width))
        return out, {"state_rms": state_rms(jnp.mean(jnp.square(last), axis=(0, 2, 3))),
                     "decay_mean": jnp.mean(jnp.exp(g))}

"""The Mamba-2 state-space mixer and its chunked scan.

One head of the layer carries a state ``h`` [P, N] (``P`` = head size, ``N``
= state size) along the sequence:

  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

with ``A < 0`` and ``D`` one number a head, ``dt_t > 0`` a number a head and
position, ``x_t`` [P] the head's input and ``B_t``, ``C_t`` [N] shared by the
``H / G`` heads of a group. Written as is, that is ``S`` dependent steps of
elementwise work. ``chunked_scan`` computes the same ``y`` in chunks of ``Q``
positions (the state-space duality of the Mamba-2 paper): inside a chunk the
recurrence unrolls into matrix products,

  y_i = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j  +  exp(a_i) C_i h_in,
  h_out = exp(a_Q) h_in + sum_j exp(a_Q - a_j) dt_j x_j B_j^T,   a_i = sum_{l <= i} dt_l A

(``Q x Q``, ``Q x N`` and ``Q x P`` products on the MXU in ``dtype``, sums in
float32), and only ``h_in -> h_out`` is carried from chunk to chunk, in
float32: ``S / Q`` dependent steps. Every exponent is a sum of ``dt A <= 0``
over a span, so nothing overflows. The backward pass is JAX's own derivative
of these products, a group of heads at a time. A sequence whose length ``Q``
does not divide is padded with ``dt = 0`` positions, which leave the state as
it is and are cut off.

``Mamba2Mixer`` is the layer around it, as ``nemotron_h`` publishes it:
``[z, xBC, dt] = W_in u``; ``xBC <- silu(causal_conv(xBC) + b)``; ``x, B, C``
from ``xBC``; ``dt <- softplus(dt + dt_bias)``; the scan; ``y <- RMSNorm over
groups of d_inner / G (y * silu(z)) * g``; ``W_out y``. Its operations sit
under two scopes: ``ssm_scan`` (``chunked_scan`` alone) and ``ssm_proj``
(everything else).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .sequence import causal_conv, conv_kernel_init, dense

Dtype = Any


def _group_scan(x, dt, A, B, C, dtype: Dtype):
    """One group's heads: ``x`` [b, c, Q, h, P], ``dt`` [b, c, Q, h] float32,
    ``A`` [h], ``B``/``C`` [b, c, Q, N] -> (``y`` [b, c, Q, h, P] float32, the
    last state [b, h, P, N] float32)."""
    Q = x.shape[2]
    a = jnp.cumsum(dt * A, axis=2)                                          # [b, c, Q, h]
    # inside a chunk: position i reads j <= i
    cb = jnp.einsum("bcin,bcjn->bcij", C, B, preferred_element_type=jnp.float32)
    span = a[:, :, :, None] - a[:, :, None, :]                              # [b, c, i, j, h]
    seen = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[:, :, None]
    weight = jnp.exp(jnp.where(seen, span, -jnp.inf)) * dt[:, :, None] * cb[..., None]
    y = jnp.einsum("bcijh,bcjhp->bcihp", weight.astype(dtype), x, preferred_element_type=jnp.float32)

    # what a chunk adds to the state, and the state each chunk starts from
    to_end = jnp.exp(a[:, :, -1:] - a) * dt                                 # exp(a_Q - a_j) dt_j
    added = jnp.einsum("bcjhp,bcjn->bchpn", x * to_end[..., None].astype(dtype), B,
                       preferred_element_type=jnp.float32)
    through = jnp.exp(a[:, :, -1])                                          # [b, c, h]

    def carry(h, chunk_of):
        add, decay = chunk_of
        return decay[..., None, None] * h + add, h

    last, h_in = jax.lax.scan(carry, jnp.zeros_like(added[:, 0]), (added.swapaxes(0, 1), through.swapaxes(0, 1)))
    y = y + jnp.exp(a)[..., None] * jnp.einsum(
        "bcin,cbhpn->bcihp", C, h_in.astype(dtype), preferred_element_type=jnp.float32)
    return y, last


def chunked_scan(x, dt, A, B, C, chunk: int, dtype: Dtype = jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``x`` [b, S, H, P], ``dt`` [b, S, H] float32, ``A`` [H] float32,
    ``B``/``C`` [b, S, G, N] -> (``y`` [b, S, H, P] float32 without the
    ``D x`` skip, the state after the last position [b, H, P, N] float32).
    One group of ``H / G`` heads at a time (``lax.map``), each group's
    intermediates computed again in its backward pass: the ``Q x Q`` decay
    matrices of every head at once are 0.5 GB a layer at 16,384 positions."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, B, C))
    nc = (S + pad) // Q
    by_group = lambda t, *rest: jnp.moveaxis(t.reshape(b, nc, Q, G, *rest), 3, 0)
    y, last = jax.lax.map(
        lambda group: jax.checkpoint(functools.partial(_group_scan, dtype=dtype))(*group),
        (by_group(x.astype(dtype), H // G, P), by_group(dt.astype(jnp.float32), H // G),
         A.reshape(G, H // G), by_group(B.astype(dtype), N), by_group(C.astype(dtype), N)))
    y = jnp.moveaxis(y, 0, 3).reshape(b, nc * Q, H, P)[:, :S]               # [G, b, c, Q, h, P] -> [b, S, H, P]
    return y, jnp.moveaxis(last, 0, 1).reshape(b, H, P, N)


def state_rms(per_head):
    """One number for a layer's carried state, from the mean square of each
    head's ``[P, N]`` state: the geometric mean over the heads of their RMS.
    The plain RMS is the few heads with the largest ``dt`` (they decay within
    a few positions, so it moves by half a percent with bfloat16's rounding of
    a few hundred numbers and hardly at all when the carry across chunks is
    lost); here every head counts alike, the slow ones that live off the carry
    among them."""
    return jnp.exp(0.5 * jnp.mean(jnp.log(per_head + 1e-30)))


def _dt_bias_init(lo: float, hi: float, floor: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in [lo, hi], not below ``floor``."""
    def init(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus

    return init


class GroupedRMSNormGated(nn.Module):
    """``RMSNorm(y * silu(z))`` over groups of ``group`` channels, times a learned scale; float32."""

    group: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],), jnp.float32)
        t = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        g = t.reshape(*t.shape[:-1], -1, self.group)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + self.eps)
        return (g.reshape(t.shape) * scale).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    """``u`` [B, S, d] -> (the mixer's output [B, S, d], ``state_rms`` of the
    state after the last position). Parameters: ``in_proj`` [d, 2 H P + 2 G N + H],
    ``conv_kernel`` [L, H P + 2 G N] and ``conv_bias``, ``dt_bias``, ``A_log``
    (``A = -exp(A_log)``, drawn as log(1..H)) and ``D`` (ones), one a head,
    ``gated_norm`` [H P], ``out_proj`` [H P, d]."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    conv_bias: bool = True
    dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)   # time_step_min, _max, _floor
    eps: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        Bt, S, d = u.shape
        H, P, G, N = self.heads, self.head_dim, self.groups, self.state
        inner, bc = H * P, G * N
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = jnp.split(dense(2 * inner + 2 * bc + H, self.dtype, "in_proj")(u),
                                   [inner, 2 * inner + 2 * bc], axis=-1)
            kernel = self.param("conv_kernel", conv_kernel_init(self.conv_kernel),
                                (self.conv_kernel, inner + 2 * bc))
            bias = self.param("conv_bias", conv_kernel_init(self.conv_kernel),
                              (inner + 2 * bc,)) if self.conv_bias else None
            x, B, C = jnp.split(nn.silu(causal_conv(xbc, kernel, bias)), [inner, inner + bc], axis=-1)
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_range), (H,))
            A = -jnp.exp(self.param("A_log", lambda key, shape: jnp.log(jnp.arange(1.0, H + 1.0)), (H,)))
            D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
            dt = nn.softplus(dt.astype(jnp.float32) + dt_bias)
            x = x.reshape(Bt, S, H, P)
        with jax.named_scope("ssm_scan"):
            y, last = chunked_scan(x, dt, A, B.reshape(Bt, S, G, N), C.reshape(Bt, S, G, N),
                                   self.chunk, self.dtype)
        with jax.named_scope("ssm_proj"):
            y = (y + D[:, None] * x.astype(jnp.float32)).astype(self.dtype).reshape(Bt, S, inner)
            y = GroupedRMSNormGated(inner // G, self.eps, name="gated_norm")(y, z)
            out = dense(d, self.dtype, "out_proj")(y)
        return out, state_rms(jnp.mean(jnp.square(last), axis=(0, 2, 3)))

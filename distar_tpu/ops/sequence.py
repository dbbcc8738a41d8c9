"""Layers of a causal token-sequence model: RMSNorm and LayerNorm, rotary
positions, the depthwise causal convolution, the gated short convolution,
causal grouped-query attention, differential attention and multi-head latent
attention.

These are the operators the token models share (``model/lfm2.py``,
``model/nemotron_h.py``, ``model/deepseek_v3.py``, ``model/qwen3_next.py``,
``model/laguna.py``, ``model/phi4flash.py``; the state-space mixers are
``ops/ssm.py``, the gated delta rule ``ops/delta.py``). Parameters
are float32; ``dtype`` is the compute dtype of the matrix products. No
projection has a bias but ``phi4flash``'s attention projections (``dense(...,
bias=True)``), and every norm is an RMSNorm but that model's (``LayerNorm``,
with a bias). Sequences are ``[B, S, d]``, position 0 first.

Attention over ``S`` positions never holds an ``S x S`` score tensor per
head. Which code computes it follows from the platform the program is being
compiled for (``jax.lax.platform_dependent``) and the shapes: on a TPU, at a
sequence length its tiles divide (``KERNEL_MIN_BLOCK``), JAX's splash
attention (``jax.experimental.pallas.ops.tpu.splash_attention``): an online
softmax, the blocks above the diagonal skipped, a key/value head shared by
the query heads of its group, a value head of its own size where the model
has one (latent attention: 192 against 128), and backward kernels of its
own. Its tiles, and whether dQ is computed beside dK and dV or by a kernel of
its own, follow from the sequence length and the head sizes by the table the
chip measured (``core_plan``). Anywhere else a loop over query blocks under
``jax.checkpoint`` (every block multiplies against all keys and masks, so it
does twice the causal work).

With a ``window`` (``laguna``'s sliding layers and ``phi4flash``'s: query ``i``
sees the keys ``i - window < j <= i``) the work is the band's: on a TPU the same kernel with a
local mask, which visits only the key blocks that meet the band, forward and
backward; anywhere else the same loop over query blocks with the band in its mask.

What stands between ``q_proj``'s and ``k_proj``'s results and that kernel in
``CausalGQAttention`` (the head norm, the rotation, the queries' scale and the
move to the kernel's ``[B, heads, S, D]``) is, lowered for a TPU at shapes its
tiles take (``head_turn.takes``: a head of whole lane tiles), ONE pass each way
(``ops/head_turn.py``, two Pallas kernels); anywhere else, and at the other
shapes, the XLA form it always was (``rms_norm``, ``turn_first``).

``DifferentialAttention`` (``phi4flash``) is two softmaxes a pair of heads over
a value twice a head wide, ``a1 - lambda a2`` under an RMSNorm over the pair;
it hands ``causal_attention`` ONE call a layer (a key head with the two query
heads of its parity in its pair, the pair's value as its value head: the core
takes a value head of its own size), with its keys and values projected or,
in a cross-attention layer, handed in from the layer that made them.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from . import head_turn

Dtype = Any

XLA_QUERY_BLOCK = 512
KERNEL_MIN_BLOCK = 128  # the kernel's tiles are multiples of this many positions
# the name a full-causal core's forward results go by (``jax.ad_checkpoint.checkpoint_name``): a layer's
# remat that saves this name (``model/token_decoder.py::decode``) does not run the kernel again in its replay
CORE_KEPT = "attn_core_kept"
_kept_log: contextvars.ContextVar = contextvars.ContextVar("cores_kept", default=None)
_fused_log: contextvars.ContextVar = contextvars.ContextVar("heads_fused", default=None)


class CorePlan(NamedTuple):
    """Splash attention's tiles, in positions, the same in the forward and the backward kernels: a query
    block, a key block, the keys of it that are multiplied at a time, and whether dQ is computed beside dK
    and dV in one backward kernel (``fused_bwd``) or by a kernel of its own."""

    block_q: int
    block_kv: int
    block_kv_compute: int
    fused_bwd: bool


def core_plan(S: int, D: int, Dv: int, window: Optional[int] = None) -> CorePlan:
    """The tiles of the attention core over ``S`` positions with score heads of ``D`` and value heads of
    ``Dv``, from what the chip measured (TPU v5e; ms a layer, forward + the forward replayed under
    ``jax.checkpoint`` + backward, bf16, the kernel alone; tiles as query/key positions, ``c`` the keys
    multiplied at a time; PERF.md section 5, the PR named):

    ============================  ===============  ===============  ===============
    query heads x head size,      24 x 128 over 4  32 x 128 over 2  32 x 64 over 8
    sequences x positions (PR 39) 1 x 16,384       2 x 8,192        4 x 8,192
    ============================  ===============  ===============  ===============
    flash attention, 512-tiles         88.6             67.9            137.5
    splash 512/512, own dQ kernel      80.1             57.1            121.4
    splash 512/512, fused              72.3             51.0            105.4
    splash 1,024/1,024, own dQ         66.3             50.1            100.1
    splash 1,024/1,024, fused          58.9             43.9             87.0
    splash 1,024/1,024 c 512, fused    56.4             42.2             84.1
    **splash 1,024/2,048 c 512**       **54.2**         42.7           **83.7**
    splash 1,024/2,048 c 1,024         55.5             43.8             85.5
    splash 2,048/1,024 c 512           56.9             44.2            (VMEM)
    ============================  ===============  ===============  ===============

    The flash kernel (which ``causal_attention`` ran at these three shapes until PR 39, handed a copy of
    each key/value head for every query head of its group) is behind every splash row at every shape, so
    no shape keeps it. The fused backward kernel writes dQ once for every key block of the sequence
    (``[S / block_kv, heads, S, D]``) and adds the copies up afterwards: key blocks of 2,048 halve those
    copies (0.8 GB at 16,384 positions where 1,024 holds 1.6), which is what they win at 16,384 and why
    they cost nothing at 8,192; 2,048 keys fit the chip's vector memory only when multiplied 512 at a time.
    The number of query heads a key/value head (4, 6 and 16 above) never changed the order: the kernel
    shares the key/value head itself.

    What earlier PRs measured stays (their ms are forward + backward with no replay): a head of 256
    (``qwen3_next``, 16 over 2, 2 x 8,192) 512-tiles, 34.6 ms against the flash kernel's 43.5, 1,024-tiles
    refused for vector memory when multiplied whole (PR 36); a value head of its own size (latent attention,
    16 heads of 192/128, 2 x 8,192) 1,024-tiles fused, 26.7 ms against 34.2 at 512 with its own dQ kernel
    (PR 31); a ``window`` (``laguna``, 36 x 128 over 4, a band of 512 in 16,384) 512-tiles and a dQ kernel of
    its own, 14.2 ms against 29.0 fused, since the fused kernel's dQ copies are the whole sequence's whatever
    the mask (4.8 GB there), and 17-21 at every other tile (PR 38).

    A score head NARROWER than its value head (``phi4flash``'s differential attention: 20 key heads of 64, two
    query heads each, the pair's value of 128; 2 x 8,192; ms a layer as in the table, PR 42) goes by the
    table's own row: 1,024/2,048 c 512 fused 38.4, 1,024/1,024 c 512 38.6, 1,024/2,048 c 1,024 39.0, 1,024/1,024
    whole (latent attention's row) 39.3, 1,024/1,024 with its own dQ kernel 47.9, 512-tiles 48.7, query blocks of
    2,048 refused for vector memory. Under the band of 512 the window's row: 512-tiles with a dQ kernel of its
    own 14.6, multiplied 256 keys at a time 15.4, 256/512 18.5, 1,024/512 18.8, 512/1,024 18.9, 1,024-tiles 20.7,
    256-tiles 21.6, 512-tiles fused 23.6. Between the measured shapes the nearest
    row serves, cut to tiles that divide ``S`` (128 divides it: ``causal_attention`` sees to that)."""
    if window is not None:
        q, kv, compute, fused = 512, 512, 512, False
    elif max(D, Dv) >= 256:
        q, kv, compute, fused = 512, 512, 512, True
    elif D > Dv:
        q, kv, compute, fused = 1024, 1024, 1024, True
    else:
        q, kv, compute, fused = 1024, 2048, 512, True

    def dividing(n, tile):
        tile = min(n, tile)
        while n % tile:
            tile //= 2
        return tile

    q, kv = dividing(S, q), dividing(S, kv)
    return CorePlan(q, kv, dividing(kv, compute), fused)


def rms_norm(x, scale, eps: float):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32, rounded to ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


class RMSNorm(nn.Module):
    """``rms_norm`` with a learned scale of the last axis' size.
    ``zero_centred`` (``qwen3_next``): ``... * (1 + w)`` with ``w`` zero at
    init, so that weight decay pulls the layer towards the plain norm and not
    towards nothing. ``weight(D)`` is the scale alone, for a caller that norms
    by a function of its own (``CausalGQAttention``: ``ops/head_turn.py``)."""

    eps: float = 1e-5
    zero_centred: bool = False

    @nn.compact
    def weight(self, D: int):
        if self.zero_centred:
            return 1.0 + self.param("w", nn.initializers.zeros, (D,), jnp.float32)
        return self.param("scale", nn.initializers.ones, (D,), jnp.float32)

    def __call__(self, x):
        return rms_norm(x, self.weight(x.shape[-1]), self.eps)


class LayerNorm(nn.Module):
    """``(x - mean(x)) / sqrt(var(x) + eps) * scale + bias`` over the last axis, in float32 (``phi4flash``:
    every norm of that model)."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        y = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + self.eps)
        return (y * scale + bias).astype(x.dtype)


def dense(features: int, dtype: Dtype, name: str, bias: bool = False) -> nn.Dense:
    """A projection with float32 parameters and ``dtype`` products, without a bias unless ``bias`` (zero at init)."""
    return nn.Dense(features, use_bias=bias, dtype=dtype, param_dtype=jnp.float32,
                    kernel_init=nn.initializers.normal(0.02), name=name)


def _turn(x, inv_freq, factor=None):
    """``x * cos + rotate_half(x) * sin`` with ``rotate_half([a, b]) = [-b, a]``
    and the angle ``t * inv_freq[i]`` for pair ``i`` (dimension ``i`` with ``i
    + D/2``); with a ``factor``, cos and sin times it. ``x`` is ``[B, S, H, D]``."""
    S = x.shape[1]
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]      # [S, D/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-b, a], axis=-1) * sin).astype(x.dtype)


def rope_inv_freq(rotary_dim: int, theta: float):
    """The angle a position turns pair ``i`` of ``rotary_dim`` dimensions by: ``theta^(-2i/rotary_dim)``."""
    return theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)


def turn_first(x, inv_freq, factor=None):
    """``_turn`` over the first ``2 len(inv_freq)`` dimensions of each head, the others as they are."""
    R = 2 * inv_freq.shape[0]
    if R == x.shape[-1]:
        return _turn(x, inv_freq, factor)
    return jnp.concatenate([_turn(x[..., :R], inv_freq, factor), x[..., R:]], axis=-1)


def head_turn_xla(x, heads: int, weight=None, inv_freq=None, factor=None, scale: Optional[float] = None, eps: float = 1e-5):
    """``head_turn.head_turn``'s result, ``x`` [B, S, heads D] -> [B, heads, S, D], as the XLA form computes it
    (``rms_norm``, ``turn_first``, the scale as ``_attention_splash`` multiplies by it, the kernel's layout): what the
    kernels' tests and ``tools/bench_kernels.py --ops head_turn`` hold them against."""
    x = x.reshape(*x.shape[:2], heads, -1)
    if weight is not None:
        x = rms_norm(x, weight, eps)
    if inv_freq is not None:
        x = turn_first(x, inv_freq, factor)
    if scale is not None:
        x = x * jnp.asarray(scale, x.dtype)
    return x.transpose(0, 2, 1, 3)


def rope(x, theta: float):
    """Rotary positions over the whole head, rotate-half convention:
    ``x * cos + rotate_half(x) * sin`` with ``rotate_half([a, b]) = [-b, a]``
    and angle ``t * theta^(-2i/D)`` for pair ``i``. ``x`` is ``[B, S, H, D]``."""
    return _turn(x, rope_inv_freq(x.shape[-1], theta))


def rope_first(x, theta: float, rotary_dim: int):
    """``rope`` over the first ``rotary_dim`` dimensions of each head (angles
    ``t * theta^(-2i/rotary_dim)``), the others as they are."""
    return turn_first(x, rope_inv_freq(rotary_dim, theta))


def yarn_inv_freq(rotary_dim: int, theta: float, factor: float, original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's frequency table [rotary_dim / 2] (numpy, float64): pair ``j`` of
    ``f_j = theta^(-2j/rotary_dim)`` keeps its frequency where it turns more
    than ``beta_fast`` times within the original length, takes ``f_j / factor``
    where it turns less than ``beta_slow`` times, and a linear ramp between:
    ``f_j (1 - ramp_j) + (f_j / factor) ramp_j`` with ``ramp_j = clip((j - low)
    / (high - low), 0, 1)``, ``low`` and ``high`` the floor and the ceiling of
    ``rotary_dim ln(original / (2 pi beta)) / (2 ln theta)`` at ``beta_fast``
    and ``beta_slow`` (9 and 18 at ``laguna``'s published numbers)."""
    j = np.arange(rotary_dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / rotary_dim)
    turns = lambda beta: rotary_dim * math.log(original_max_position_embeddings / (2 * math.pi * beta)) / (2 * math.log(theta))
    low, high = max(math.floor(turns(beta_fast)), 0), min(math.ceil(turns(beta_slow)), rotary_dim - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def yarn_first(x, rotary_dim: int, theta: float, attention_factor: float, **yarn):
    """The rotation of the first ``rotary_dim`` dimensions of each head by
    ``yarn_inv_freq``'s table, cos and sin times ``attention_factor``; the
    others as they are."""
    return turn_first(x, jnp.asarray(yarn_inv_freq(rotary_dim, theta, **yarn), jnp.float32), attention_factor)


def rope_interleaved(x, theta: float):
    """Rotary positions over interleaved pairs: ``(x_2j, x_2j+1)`` is turned
    by the angle ``t * theta^(-2j/R)``, as a complex number ``x_2j + i x_2j+1``
    times ``e^(i angle)``. ``x`` is ``[B, S, H, R]``: the rotary part of a
    head, not the whole of it."""
    S, R = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]      # [S, R/2]
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[None, :, None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    even = jnp.arange(R) % 2 == 0
    # the other member of each pair, with the sign it takes: (-x_2j+1, x_2j)
    other = jnp.where(even, -jnp.roll(x32, -1, axis=-1), jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + other * sin).astype(x.dtype)


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


def _attention_xla(q, k, v, scale: float, window: Optional[int] = None):
    """``q`` [B, S, Hkv, G, D], ``k`` [B, S, Hkv, D], ``v`` [B, S, Hkv, Dv] ->
    [B, S, Hkv, G, Dv]. One query block at a time against all keys, float32 softmax;
    with a ``window`` the mask is the band's (query ``i`` sees the keys ``i - window < j <= i``)."""
    B, S, Hkv, G, _ = q.shape
    block = min(S, XLA_QUERY_BLOCK)
    if S % block:
        raise ValueError(f"sequence length {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        score = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                           preferred_element_type=jnp.float32) * scale
        at, key_at = (start + jnp.arange(block))[:, None], jnp.arange(S)[None, :]
        visible = at >= key_at
        if window is not None:
            visible &= at - key_at < window
        prob = jax.nn.softmax(jnp.where(visible, score, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", prob.astype(v.dtype), v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))          # [S/block, B, block, ...]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hkv, G, v.shape[-1])


def band_mask(S: int, window: int):
    """Splash attention's mask of the band: query ``i`` sees the keys ``i - window < j <= i``
    (``window - 1`` keys before its own and none after)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    return masks.LocalMask((S, S), (window - 1, 0), 0)


@contextlib.contextmanager
def heads_fused():
    """The q/k preparations (a head norm and a rotation: two a ``CausalGQAttention`` layer with ``positions``) of
    a trace made inside the ``with``: a list with one entry each, True where ``head_turn.takes`` handed it to the
    fused function (for a program lowered for a TPU), False where the rule left it with the XLA form."""
    yield from _logged(_fused_log)


@contextlib.contextmanager
def cores_kept():
    """The cores that were given the name ``CORE_KEPT`` by a trace made inside the ``with``: a list with one
    entry a core, the bytes of its forward results that go by the name (``out`` [B, heads, S, Dv] in the
    operands' dtype and ``logsumexp`` [B, heads, S] in float32)."""
    yield from _logged(_kept_log)


def _logged(log: contextvars.ContextVar):
    entries = []
    token = log.set(entries)
    try:
        yield entries
    finally:
        log.reset(token)


def _attention_splash(q, k, v, scale: float, window: Optional[int] = None, head_major: bool = False):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    if head_major:
        (B, _, S, D), Hkv = q.shape, k.shape[1]
        G = q.shape[1] // Hkv
    else:
        B, S, Hkv, G, D = q.shape
    bq, bkv, compute, fused = core_plan(S, D, v.shape[-1], window)
    # under a band the kernel's grid holds the key blocks that meet it and no others, in all three passes
    mask = masks.CausalMask((S, S)) if window is None else band_mask(S, window)
    heads = lambda t: t.reshape(B, S, -1, t.shape[-1]).transpose(0, 2, 1, 3)
    sizes = dict(use_fused_bwd_kernel=True) if fused else dict(use_fused_bwd_kernel=False, block_q_dq=bq, block_kv_dq=bkv)
    # the backward kernels need ``out`` and ``logsumexp``, which only the forward kernel can make: over the whole
    # triangle that kernel is quadratic in ``S`` and its results go by a name, so a remat that saves the name
    # replays the layer up to the kernel and not through it; under a band it is linear, and replayed
    kept = CORE_KEPT if window is None else None
    if kept and _kept_log.get() is not None:
        _kept_log.get().append(B * Hkv * G * S * (v.shape[-1] * q.dtype.itemsize + 4))
    # the library keeps the tables it makes of a mask on the host (1.2 s for 24 heads at 16,384 positions),
    # so the layers of one shape, and a layer's replay, make them once
    kernel = splash.make_splash_mha(
        masks.MultiHeadMask([mask] * (Hkv * G)), head_shards=1, q_seq_shards=1, residual_checkpoint_name=kept,
        block_sizes=splash.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=compute, block_q_dkv=bq, block_kv_dkv=bkv,
            block_kv_dkv_compute=compute, **sizes))
    # the kernel has no scale of its own, takes one sequence ([heads, S, .]) and shares a
    # key/value head among the query heads of its group itself
    if not head_major:
        q, k, v = heads(q * jnp.asarray(scale, q.dtype)), heads(k), heads(v)
    out = jax.vmap(kernel)(q, k, v)
    return out.transpose(0, 2, 1, 3).reshape(B, S, Hkv, G, v.shape[-1])


def causal_attention(q, k, v, scale: float, window: Optional[int] = None, head_major: bool = False):
    """``q`` [B, S, Hkv, G, D], ``k`` [B, S, Hkv, D], ``v`` [B, S, Hkv, Dv] ->
    [B, S, Hkv, G, Dv]: the value head has a size of its own. With a
    ``window``, query ``i`` sees the keys ``i - window < j <= i`` and no others.
    ``head_major`` is for a caller that is itself the ``tpu`` branch of a
    ``platform_dependent`` and has made the kernel's operands (``CausalGQAttention``,
    by ``ops/head_turn.py``): ``q`` [B, Hkv G, S, D] already times ``scale``, ``k``
    [B, Hkv, S, D], ``v`` [B, Hkv, S, Dv], a sequence the kernel's tiles divide;
    they go to the kernel as they are.

    Over the whole triangle (no ``window``, or one that covers the sequence) the kernel's forward results, ``out``
    and ``logsumexp``, go by the name ``CORE_KEPT``: under a ``jax.checkpoint`` that saves the name
    (``model/token_decoder.py::decode``) the backward pass takes them from memory and the replay does not run the
    forward kernel again, which is quadratic in ``S`` and the only thing that can make them. Under a band the
    kernel is linear in ``S`` (3 ms for 0.15-0.3 GB at 16,384 positions) and is replayed with the rest of the
    layer, so it is given no name. Outside such a checkpoint the name does nothing."""
    if window is not None and window >= q.shape[2 if head_major else 1]:
        window = None                                               # the band is the whole triangle
    if head_major:
        return _attention_splash(q, k, v, scale, window, head_major=True)
    plain = functools.partial(_attention_xla, scale=scale, window=window)
    if q.shape[1] % KERNEL_MIN_BLOCK:
        return plain(q, k, v)
    kernel = functools.partial(_attention_splash, scale=scale, window=window)
    return jax.lax.platform_dependent(q, k, v, tpu=kernel, default=plain)


def open_gate(out, gate):
    """The heads' outputs times ``sigmoid(gate)``, in float32, and the mean of ``sigmoid(gate)``."""
    opened = jax.nn.sigmoid(gate.astype(jnp.float32))
    return (out.astype(jnp.float32) * opened).astype(out.dtype), jnp.mean(opened)


class CausalGQAttention(nn.Module):
    """Causal grouped-query attention: ``heads`` query heads over ``kv_heads``
    key/value heads, ``softmax(q k^T / sqrt(head_dim)) v``. With ``positions``
    (LFM2): RMSNorm over each head of q and k (one learned scale of
    ``head_dim``), then rotary positions. Without (``nemotron_h``: the
    state-space layers carry the order): q and k as projected, and
    ``rope_theta`` is read by nothing. ``qwen3_next``'s gated attention is
    three more arguments: ``rotary_dim`` (the rotation takes the first that
    many dimensions of a head and leaves the others), ``zero_centred`` (the
    q/k norms are ``1 + w``) and ``gate`` (``q_proj`` is twice as wide, each
    head's ``2 head_dim`` split into q and a gate; the heads' outputs are
    multiplied by ``sigmoid(gate)`` before ``o_proj``, and the call returns
    ``(output, the mean of sigmoid(gate))``). ``laguna``'s layers are three
    more: ``window`` (a query sees the ``window`` keys up to its own),
    ``yarn`` (the rotation's table is ``yarn_inv_freq``'s and cos and sin are
    scaled: the keys ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow`` and ``attention_factor`` of the published
    ``rope_parameters``) and ``gate="head"`` (one number a head and position
    from a projection of its own, ``g_proj`` [d, heads], opens the head's
    whole output).

    The kernel alone runs under the scope ``attn_core`` (``swa_core`` with a
    ``window``), everything else under ``attn_proj``; a model that puts the
    whole layer under ``attention`` is read by that name, the first on the path.

    With ``positions``, a program lowered for a TPU prepares q and k for the
    kernel by ``head_turn.head_turn`` where ``head_turn.takes`` the shapes
    (counted by ``heads_fused``), and hands ``causal_attention`` the operands
    head-major; every other platform, and every other shape, runs ``plain``."""

    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: Dtype = jnp.float32
    positions: bool = True
    rotary_dim: Optional[int] = None
    zero_centred: bool = False
    gate: Union[bool, str] = False
    window: Optional[int] = None
    yarn: Optional[Dict[str, float]] = None

    @nn.compact
    def __call__(self, u):
        B, S, d = u.shape
        H, Hkv, D = self.heads, self.kv_heads, self.head_dim
        if self.gate not in (False, True, "head"):
            raise ValueError(f"gate {self.gate!r}: False, True (an element) or 'head'")
        G, scale, core = H // Hkv, D ** -0.5, "attn_core" if self.window is None else "swa_core"
        with jax.named_scope("attn_proj"):
            q = dense(H * D * (2 if self.gate is True else 1), self.dtype, "q_proj")(u).reshape(B, S, H, -1)
            if self.gate is True:
                q, gate = jnp.split(q, 2, axis=-1)
            elif self.gate:
                gate = dense(H, self.dtype, "g_proj")(u)[..., None]
            k = dense(Hkv * D, self.dtype, "k_proj")(u).reshape(B, S, Hkv, D)
            v = dense(Hkv * D, self.dtype, "v_proj")(u).reshape(B, S, Hkv, D)
            weights, fused = (), False
            if self.positions:
                R = self.rotary_dim or D
                if self.yarn is not None:
                    turn = functools.partial(yarn_first, rotary_dim=R, theta=self.rope_theta, **self.yarn)
                elif self.rotary_dim is None:
                    turn = functools.partial(rope, theta=self.rope_theta)
                else:
                    turn = functools.partial(rope_first, theta=self.rope_theta, rotary_dim=self.rotary_dim)
                weights = tuple(RMSNorm(self.eps, self.zero_centred, name=name).weight(D) for name in ("q_norm", "k_norm"))
                fused = head_turn.takes(S, D, R)
                if _fused_log.get() is not None:
                    _fused_log.get().extend([fused] * 2)

        def normed(x, weight, name):
            with jax.named_scope(name):
                return rms_norm(x, weight, self.eps)

        def plain(q, k, v, *weights, attend=causal_attention):
            with jax.named_scope("attn_proj"):
                if weights:
                    q, k = (turn(normed(x, w, name)) for x, w, name in zip((q, k), weights, ("q_norm", "k_norm")))
            with jax.named_scope(core):
                return attend(q.reshape(B, S, Hkv, G, D), k, v, scale, window=self.window)

        def one_pass(q, k, v, wq, wk):
            with jax.named_scope("attn_proj"):
                if self.yarn is not None:
                    yarn = dict(self.yarn)
                    factor = yarn.pop("attention_factor")
                    inv_freq = jnp.asarray(yarn_inv_freq(R, self.rope_theta, **yarn), jnp.float32)
                else:
                    inv_freq, factor = rope_inv_freq(R, self.rope_theta), None
                tables = head_turn.turn_tables(S, D, inv_freq, factor)
                q = head_turn.head_turn(q.reshape(B, S, H * D), H, wq, tables, R, scale, self.eps)
                k = head_turn.head_turn(k.reshape(B, S, Hkv * D), Hkv, wk, tables, R, None, self.eps)
                v = v.transpose(0, 2, 1, 3)
            with jax.named_scope(core):
                return causal_attention(q, k, v, scale, window=self.window, head_major=True)

        if fused:
            # the default is what every platform ran before: the XLA form, then the loop over query blocks
            out = jax.lax.platform_dependent(q, k, v, *weights, tpu=one_pass,
                                             default=functools.partial(plain, attend=_attention_xla))
        else:
            out = plain(q, k, v, *weights)
        with jax.named_scope("attn_proj"):
            if not self.gate:
                return dense(d, self.dtype, "o_proj")(out.reshape(B, S, H * D))
            out, opened = open_gate(out.reshape(B, S, H, D), gate)
            return dense(d, self.dtype, "o_proj")(out.reshape(B, S, H * D)), opened


class DifferentialAttention(nn.Module):
    """Differential attention (``phi4flash``): the heads pair up, query pair ``j`` = query heads ``(2j, 2j + 1)``
    reads key/value pair ``j // (heads / kv_heads)`` = key heads ``(k1, k2)`` and the value ``V = [v1, v2]``,
    ``2 head_dim`` wide, and

      a1 = softmax(q1 k1^T / sqrt(head_dim)) V,   a2 = softmax(q2 k2^T / sqrt(head_dim)) V
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init      (four learned vectors of ``head_dim`` a layer)
      o_j = (1 - lambda_init) RMSNorm(a1 - lambda a2)               (``pair_norm``: one learned scale of ``2 head_dim``)

    under the layer's mask (every key up to the query's own; with a ``window``, the keys ``i - window < j <= i``);
    the output is ``W_o [o_j of every pair] + b``. q, k, v and o have biases. ``causal_attention`` is handed ONE
    call a layer: ``kv_heads`` key heads of ``head_dim``, each read by the ``heads / kv_heads`` query heads of its
    parity in its pair, with the pair's ``V`` as its value head (the kernel takes a value head of its own size).
    With ``kv`` (``(k, v)`` [B, S, kv_heads, head_dim] as an earlier layer projected them: cross-attention) the
    layer has no key/value projection of its own. Returns ``(output, (k, v), lambda)``.

    The kernel alone runs under the scope ``attn_core`` (``swa_core`` with a ``window``, ``cross_core`` with
    ``kv`` handed in), everything else under ``attn_proj``."""

    heads: int
    kv_heads: int
    head_dim: int
    lambda_init: float
    eps: float = 1e-5
    dtype: Dtype = jnp.float32
    window: Optional[int] = None

    @nn.compact
    def __call__(self, u, kv=None):
        B, S, d = u.shape
        H, Hkv, D = self.heads, self.kv_heads, self.head_dim
        G, pairs = H // Hkv, Hkv // 2
        core = "cross_core" if kv is not None else "attn_core" if self.window is None else "swa_core"
        with jax.named_scope("attn_proj"):
            q = dense(H * D, self.dtype, "q_proj", bias=True)(u)
            if kv is None:
                kv = tuple(dense(Hkv * D, self.dtype, name, bias=True)(u).reshape(B, S, Hkv, D)
                           for name in ("k_proj", "v_proj"))
            k, v = kv
            # key head 2p + r is read by the query heads of parity r in the G query pairs of key/value pair p
            q = q.reshape(B, S, pairs, G, 2, D).swapaxes(3, 4).reshape(B, S, Hkv, G, D)
            wide = jnp.repeat(v.reshape(B, S, pairs, 2 * D), 2, axis=2)
            vector = lambda name: self.param(name, nn.initializers.normal(0.1), (D,), jnp.float32)
            lam = (jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
                   - jnp.exp(jnp.sum(vector("lambda_q2") * vector("lambda_k2"))) + self.lambda_init)
        with jax.named_scope(core):
            out = causal_attention(q, k, wide, D ** -0.5, window=self.window)       # [B, S, (p, r), G, 2 D]
        with jax.named_scope("attn_proj"):
            out = out.reshape(B, S, pairs, 2, G, 2 * D).astype(jnp.float32)
            o = RMSNorm(self.eps, name="pair_norm")(out[:, :, :, 0] - lam * out[:, :, :, 1]) * (1.0 - self.lambda_init)
            return dense(d, self.dtype, "o_proj", bias=True)(o.astype(self.dtype).reshape(B, S, H * D)), kv, lam


class LatentAttention(nn.Module):
    """Multi-head latent attention with a decoupled rotary part, as trained
    (keys and values expanded from the latent; the absorbed form that a
    decoder caches the latent for is another program, docs/token_models.md):

      q          = W_q u                        [heads x (nope_dim | rope_dim)]
      [c | k_pe] = W_kva u                      [kv_rank | rope_dim]
      [k_nope_h | v_h] = W_kvb RMSNorm(c)       [heads x (nope_dim | v_dim)]
      q_pe, k_pe <- ``rope_interleaved``; k_h = [k_nope_h | k_pe]: ONE rotary
      key for all heads; ``softmax(q_h . k_h / sqrt(nope_dim + rope_dim)) v_h``;
      W_o over the heads' ``v_dim`` outputs.

    The projections, the latent norm, the rotation and the broadcast of
    ``k_pe`` run under the scope ``mla_proj``, the attention itself under
    ``mla_core``."""

    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 1e4
    eps: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        B, S, d = u.shape
        H, N, R, V = self.heads, self.nope_dim, self.rope_dim, self.v_dim
        with jax.named_scope("mla_proj"):
            q = dense(H * (N + R), self.dtype, "q_proj")(u).reshape(B, S, H, N + R)
            c, k_pe = jnp.split(dense(self.kv_rank + R, self.dtype, "kv_a_proj")(u), [self.kv_rank], axis=-1)
            kv = dense(H * (N + V), self.dtype, "kv_b_proj")(RMSNorm(self.eps, name="kv_norm")(c))
            k_nope, v = jnp.split(kv.reshape(B, S, H, N + V), [N], axis=-1)
            q = jnp.concatenate([q[..., :N], rope_interleaved(q[..., N:], self.rope_theta)], axis=-1)
            k_pe = rope_interleaved(k_pe[:, :, None, :], self.rope_theta)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, R))], axis=-1)
        with jax.named_scope("mla_core"):
            out = causal_attention(q[:, :, :, None, :], k, v, (N + R) ** -0.5)
        with jax.named_scope("mla_proj"):
            return dense(d, self.dtype, "o_proj")(out.reshape(B, S, H * V))


class SwiGLU(nn.Module):
    """``W_2 (silu(W_1 u) * W_3 u)``."""

    width: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        gate = nn.silu(dense(self.width, self.dtype, "w1")(u))
        return dense(u.shape[-1], self.dtype, "w2")(gate * dense(self.width, self.dtype, "w3")(u))

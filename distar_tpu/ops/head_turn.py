"""From a projection's result to the attention core's operand in one pass.

``CausalGQAttention`` (``ops/sequence.py``) hands the core its queries and keys a
head at a time: RMSNorm over each head (one learned weight of ``D``), rounded to
the products' dtype; the rotation of the first ``rotary_dim`` dimensions
(``x cos + rotate_half(x) sin``, float32), rounded; the queries times the core's
scale, rounded; and, for a TPU's kernel, the move from ``[B, S, heads, D]`` to
``[B, heads, S, D]``. As XLA compiles that for a TPU it is five passes over the
head tensor, three of them in float32, since ``rotate_half`` cuts the 128 lanes
of a head in half and the compiler does not fuse across that cut (PERF.md
section 5, PR 45). ``head_turn`` is the same arithmetic as two Pallas kernels under
one ``jax.custom_vjp`` (``head_turn_fwd`` and ``head_turn_bwd`` in a trace): a
program instance reads a block of ``positions x heads`` of the projection's
result where the product wrote it (``[B, S, heads D]``: a head is a lane-aligned
column block), norms, turns and scales it in float32 on the chip, rounding where
the XLA form rounds, and writes each head's block of ``[B, heads, S, D]``, so the
transpose is where a block is written. ``rotate_half`` is a lane roll by half the
rotary width against a ``sin`` table that carries the sign (under a partial
rotation two rolls and a select on the lane index, ``sin`` 0 and ``cos`` 1 beyond
``rotary_dim``). The backward kernel reads the cotangent in the core's layout and
the projection's result again, walks the same arithmetic the other way, rounding
where JAX's derivative of the XLA form rounds, writes the projection's cotangent
``[B, S, heads D]`` and accumulates the norm weight's gradient in float32 across
the grid. The tables are ``[S, D]`` float32, made outside the kernel
(``turn_tables``).

What it does follows from what it is handed: no weight, no norm; no tables, no
rotation; no scale, none. ``takes`` is the rule of the shapes its tiles take;
anything else stays with the XLA form.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .chunked_kernel import F32

LANES = 128
POSITIONS = (512, 256, 128)   # a block's positions: the largest of these that divides the sequence
HEADS = (4, 3, 2, 1)          # a block's heads: the largest of these that divides their number
ROWS = 8                      # the norm weight's gradient accumulates a float32 sublane tile at a time


def takes(S: int, D: int, rotary_dim: Optional[int] = None) -> bool:
    """The kernels' tiles: a head whole lane tiles, the sequence whole blocks of positions, the rotation (where
    there is one) the whole head or pairs that lie inside it."""
    turned = rotary_dim is None or (0 < rotary_dim <= D and rotary_dim % 2 == 0)
    return D % LANES == 0 and S % POSITIONS[-1] == 0 and turned


def turn_tables(S: int, D: int, inv_freq, factor=None):
    """``cos`` and ``sin`` [S, D] float32 of the rotation ``ops.sequence._turn`` makes of the first ``2
    len(inv_freq)`` dimensions of a head: pair ``i`` (dimension ``i`` with ``i + R/2``) by the angle ``t
    inv_freq[i]``, both times ``factor`` where there is one. ``sin`` carries ``rotate_half``'s sign (minus on the
    first half of the pairs), and beyond the rotation ``cos`` is 1 and ``sin`` 0."""
    angle = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]          # [S, R/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    rest = D - 2 * inv_freq.shape[0]
    return (jnp.concatenate([cos, cos, jnp.ones((S, rest), F32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros((S, rest), F32)], axis=-1))


def _partner(t, half: int):
    """``t`` [positions, D] float32 -> the other member of each rotary pair, lane for lane: ``t[i + half]`` on the
    first ``half`` lanes and ``t[i - half]`` on the others (what lies beyond the rotation meets a zero)."""
    D = t.shape[-1]
    back = pltpu.roll(t, half, 1)
    if 2 * half == D:
        return back
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.where(lane < half, pltpu.roll(t, D - half, 1), back)


def _normed(x, eps: float):
    """``x`` [positions, D] -> (the normed head in float32 before its weight, ``rsqrt(mean(x^2) + eps)``)."""
    x32 = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return x32 * r, r


def _forward_kernel(*refs, hb, D, eps, half, scale, normed, turned):
    """One batch row, a block of positions, ``hb`` heads: ``x_ref`` [1, positions, hb D] as the product wrote it,
    ``o_ref`` [1, hb, positions, D] as the core reads it."""
    refs = list(refs)
    x_ref, o_ref = refs.pop(0), refs.pop()
    w = refs.pop(0)[...] if normed else None
    cos, sin = (refs.pop(0)[...], refs.pop(0)[...]) if turned else (None, None)
    dtype = o_ref.dtype
    for h in range(hb):
        t = x_ref[0, :, h * D:(h + 1) * D]
        if normed:
            t = (_normed(t, eps)[0] * w).astype(dtype)
        if turned:
            t32 = t.astype(F32)
            t = (t32 * cos + _partner(t32, half) * sin).astype(dtype)
        if scale is not None:
            t = (t.astype(F32) * scale).astype(dtype)
        o_ref[0, h] = t


def _backward_kernel(*refs, hb, D, eps, half, scale, normed, turned):
    """The same block as the forward kernel's, the other way: ``dy_ref`` [1, hb, positions, D] and ``x_ref`` in,
    ``dx_ref`` [1, positions, hb D] out, and ``dw_ref`` [ROWS, D] float32, the one block every grid step adds to."""
    refs = list(refs)
    dy_ref, x_ref = refs.pop(0), refs.pop(0)
    w = refs.pop(0)[...] if normed else None
    cos, sin = (refs.pop(0)[...], refs.pop(0)[...]) if turned else (None, None)
    dx_ref = refs.pop(0)
    dtype = dx_ref.dtype
    if normed:
        dw_ref = refs.pop(0)

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (pl.program_id(2) == 0))
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

    for h in range(hb):
        g = dy_ref[0, h]
        if scale is not None:
            g = (g.astype(F32) * scale).astype(dtype)
        if turned:
            g32 = g.astype(F32)
            moved = _partner(g32 * sin, half)
            if 2 * half < D:
                moved = jnp.where(jax.lax.broadcasted_iota(jnp.int32, moved.shape, 1) < 2 * half, moved, 0.0)
            g = (g32 * cos + moved).astype(dtype)
        if normed:
            n, r = _normed(x_ref[0, :, h * D:(h + 1) * D], eps)
            g32 = g.astype(F32)
            on_n = g32 * n
            dw_ref[...] += jnp.sum(on_n.reshape(-1, ROWS, D), axis=0)
            g = (r * (g32 * w - n * jnp.mean(on_n * w, axis=-1, keepdims=True))).astype(dtype)
        dx_ref[0, :, h * D:(h + 1) * D] = g


def _call(kernel, operands, results, B, S, H, D, scale, eps, half, interpret, name):
    """``pallas_call`` over (batch rows, blocks of positions, blocks of heads), the heads innermost so that a
    block of the tables is fetched once for all of them. Operands and results are named by kind: ``flat`` [B, S, H
    D], ``heads`` [B, H, S, D], ``weight`` [1, D], ``table`` [S, D], ``sum`` [ROWS, D]."""
    bs = next(n for n in POSITIONS if S % n == 0)
    hb = next(n for n in HEADS if H % n == 0)
    specs = {"flat": pl.BlockSpec((1, bs, hb * D), lambda b, s, h: (b, s, h)),
             "heads": pl.BlockSpec((1, hb, bs, D), lambda b, s, h: (b, h, s, 0)),
             "weight": pl.BlockSpec((1, D), lambda b, s, h: (0, 0)),
             "table": pl.BlockSpec((bs, D), lambda b, s, h: (s, 0)),
             "sum": pl.BlockSpec((ROWS, D), lambda b, s, h: (0, 0))}
    kinds = [kind for kind, _ in operands]
    dtype = operands[0][1].dtype
    # the scale as the XLA form multiplies by it: rounded to the operands' dtype
    scale = None if scale is None else float(np.asarray(scale, dtype).astype(np.float32))
    return pl.pallas_call(
        functools.partial(kernel, hb=hb, D=D, eps=eps, half=half, scale=scale, normed="weight" in kinds,
                          turned="table" in kinds),
        grid=(B, S // bs, H // hb), in_specs=[specs[kind] for kind in kinds],
        out_specs=[specs[kind] for kind, _ in results], out_shape=[shape for _, shape in results],
        interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3),
    )(*(x for _, x in operands))


def _operands(x, w, cos, sin):
    given = [("flat", x)]
    if w is not None:
        given.append(("weight", w.astype(F32)[None]))
    if cos is not None:
        given += [("table", cos), ("table", sin)]
    return given


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _forward(x, w, cos, sin, heads, scale, eps, half, interpret):
    B, S, D = x.shape[0], x.shape[1], x.shape[2] // heads
    out, = _call(_forward_kernel, _operands(x, w, cos, sin), [("heads", jax.ShapeDtypeStruct((B, heads, S, D), x.dtype))],
                 B, S, heads, D, scale, eps, half, interpret, "head_turn_fwd")
    return out


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _backward(dy, x, w, cos, sin, heads, scale, eps, half, interpret):
    B, S, D = x.shape[0], x.shape[1], x.shape[2] // heads
    results = [("flat", jax.ShapeDtypeStruct(x.shape, x.dtype))]
    if w is not None:
        results.append(("sum", jax.ShapeDtypeStruct((ROWS, D), F32)))
    dx, *dw = _call(_backward_kernel, [("heads", dy)] + _operands(x, w, cos, sin), results, B, S, heads, D, scale, eps,
                    half, interpret, "head_turn_bwd")
    return dx, (jnp.sum(dw[0], axis=0).astype(w.dtype) if dw else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _head_turn(x, w, cos, sin, heads, scale, eps, half, interpret):
    return _forward(x, w, cos, sin, heads, scale, eps, half, interpret)


def _head_turn_fwd(x, w, cos, sin, heads, scale, eps, half, interpret):
    return _forward(x, w, cos, sin, heads, scale, eps, half, interpret), (x, w, cos, sin)


def _head_turn_bwd(heads, scale, eps, half, interpret, kept, dy):
    x, w, cos, sin = kept
    dx, dw = _backward(dy, x, w, cos, sin, heads, scale, eps, half, interpret)
    return dx, dw, None, None          # the tables are made of positions and constants: they have no cotangent


_head_turn.defvjp(_head_turn_fwd, _head_turn_bwd)


def head_turn(x, heads: int, weight=None, tables=None, rotary_dim: Optional[int] = None, scale: Optional[float] = None,
              eps: float = 1e-5, interpret: bool = False):
    """``x`` [B, S, heads D] (a projection's result) -> [B, heads, S, D] in ``x``'s dtype: each head under RMSNorm
    times ``weight`` [D] (where there is one; ``1 + w`` of a zero-centred norm is the caller's sum), rounded; turned
    by ``tables`` (``turn_tables``'s ``(cos, sin)`` over ``rotary_dim`` dimensions, where there are any), rounded;
    times ``scale`` (where there is one), rounded. Differentiable in ``x`` and ``weight``."""
    cos, sin = tables if tables is not None else (None, None)
    return _head_turn(x, weight, cos, sin, heads, scale, eps, (rotary_dim or 0) // 2, interpret)

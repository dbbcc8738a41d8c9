"""The walk that the chunked recurrences' Pallas kernels share.

A recurrence that carries a state along the sequence and is computed a chunk
at a time (``ops/delta.py``: the gated delta rule; ``ops/ssm.py``: Mamba-2's
scan) runs on a TPU as two kernels over the same grid: a program instance
owns a batch row and a group of heads, and the grid's last axis walks the
sequence ``CHUNKS_A_STEP`` chunks a step, the group's states on the chip from
the first chunk to the last. The forward kernel walks it in order; the
backward kernel keeps the operands as the forward one was handed them and the
states each grid step started from, walks from the end, computes a step's
chunks again on the chip and carries the states' cotangent. What a chunk
computes, the kernels' bodies and their layouts are the recurrence's own; the
``pallas_call`` of such a walk (``walk``), the padding to whole grid steps
(``padded``), the ``jax.custom_vjp`` that ties the two kernels (``kept_starts``)
and the products' few helpers are here, once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))   # a b, a b^T, a^T b
# chunks one grid step walks: a step's fixed cost is shared, and the compiler sees that many chunks' work at once
CHUNKS_A_STEP = 4


def mm(a, b, dims=NN, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=F32, precision=precision)


def rounded(scale, dtype):
    """``scale`` as the XLA form multiplies by it: rounded to ``dtype``, held in float32."""
    return scale.astype(dtype).astype(F32)


def padded(t, multiple: int):
    """``t`` [b, S, ...] with zeros behind the sequence up to a whole number of ``multiple`` positions."""
    pad = -t.shape[1] % multiple
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) if pad else t


def walk(kernel, specs, operands, results, grid, reverse: bool, scratch, interpret: bool, name: str):
    """``pallas_call`` over ``grid`` = (batch rows, head groups, steps of ``CHUNKS_A_STEP`` chunks). ``operands``
    [(kind, array)] and ``results`` [(kind, ``ShapeDtypeStruct``)] are named by kind, and ``specs(at)`` is the
    recurrence's table kind -> ``BlockSpec``, whose index maps place grid step ``j`` at the sequence's block
    ``at(j)``: ``j`` in a forward walk, counted from the end in a ``reverse`` one."""
    at = (lambda j: grid[2] - 1 - j) if reverse else (lambda j: j)
    table = specs(at)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[table[kind] for kind, _ in operands],
        out_specs=[table[kind] for kind, _ in results], out_shape=[shape for _, shape in results],
        scratch_shapes=scratch, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*(x for _, x in operands))


def kept_starts(forward, backward):
    """The ``jax.custom_vjp`` of a walk with five operands: ``kernel(*operands, chunk, interpret)`` -> results.
    ``forward(*operands, chunk, interpret, keep_starts)`` -> (results, (the operands as the kernel is handed them,
    the states each grid step starts from where ``keep_starts``, else None)); ``backward(chunk, interpret, kept,
    cotangents)`` -> the operands' cotangents. Each goes under ``jit``, so that a model's layers share ONE trace of
    a kernel's long body: without it every layer traces the body again and set-up pays (PERF.md section 5, PR 37)."""
    forward = jax.jit(forward, static_argnums=(5, 6, 7))
    backward = jax.jit(backward, static_argnums=(0, 1))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
    def kernel(a, b, c, d, e, chunk: int, interpret: bool):
        return forward(a, b, c, d, e, chunk, interpret, False)[0]

    kernel.defvjp(lambda *args: forward(*args, True), backward)
    return kernel

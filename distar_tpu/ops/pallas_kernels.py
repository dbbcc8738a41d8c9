"""Pallas TPU kernels for the framework's hot ops.

The two #1 kernel candidates named in the survey (SURVEY.md §2.3:
module_utils scatter_connection; §5: entity transformer as a Pallas masked
attention):

* ``masked_attention``   — fused softmax(QK^T + mask)V over the <=512-entity
  set. One (batch, head) program: scores, mask, a numerically-stable softmax,
  and the value matmul all stay in VMEM; both matmuls hit the MXU at
  (512 x 64/128) tiles. Saves the HBM round-trips XLA's unfused
  mask->softmax->matmul chain can incur at small batch.
* ``scatter_add_onehot`` — per-batch scatter-add of entity embeddings into
  the flattened (H*W, D) map as a chunked one-hot matmul: the [N, chunk]
  one-hot tile is built in VMEM (iota-compare) and consumed by the MXU.

Both compile for a v5e at flagship shapes in bf16 and f32, forward and
grad (``tests/test_tpu_compile.py``), and run natively on a TPU; everywhere
else ``resolve_interpret`` puts them in interpret mode, says so once at
warning level and counts it (``distar_pallas_interpret_fallbacks_total``),
so a run that was meant for the chip can fail on a non-zero count. There is no loop-of-row-
updates scatter kernel: the TPU compiler refuses a dynamic single-row
update on a packed bf16 tile, and the whole (H*W, D) output tile it needs
per program does not fit VMEM at the learner's B*T.

Enable via ``attn_impl='pallas'`` on ops.Transformer (model config key
``encoder.entity.attention_impl``) and ``impl='pallas_onehot'`` on
ops.scatter_connection (``encoder.scatter.impl``). Neither is a default:
attention's is ``xla`` (the kernel lost forward + backward on the chip in PR
21's smoke), and the scatter connection's is ``product``, the same one-hot
idea factorised and written in plain ``jax.numpy`` (``ops/scatter.py``; timed
beside this kernel on the chip in PR 35: PERF.md section 5). ROADMAP D2 keeps
the list of what a ``simplicity`` PR may delete.
"""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import get_registry

NEG_INF = -1e9


def interpret_fallbacks():
    """Counter of pallas_calls traced in interpret mode because the backend
    was not a TPU (an explicit ``interpret=`` argument is the caller's choice
    and is not counted)."""
    return get_registry().counter(
        "distar_pallas_interpret_fallbacks_total",
        "pallas_calls traced in interpret mode because the backend is not a TPU",
    )


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place that picks interpret mode: an explicit argument wins;
    ``None`` means native on a TPU backend and interpret anywhere else."""
    if interpret is not None:
        return interpret
    if jax.default_backend() == "tpu":
        return False
    counter = interpret_fallbacks()
    if not counter.value:
        logging.getLogger(__name__).warning(
            "Pallas kernels run in INTERPRET mode: backend is %r, not tpu",
            jax.default_backend(),
        )
    counter.inc()
    return True


# --------------------------------------------------------------- attention
def _attention_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, *, scale: float):
    q = q_ref[0, 0]  # [N, Dh]
    k = k_ref[0, 0]  # [N, Dh]
    v = v_ref[0, 0]  # [N, Dh]
    mask = mask_ref[0, 0]  # [1, N] key validity
    score = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [N, N]
    score = jnp.where(mask.astype(jnp.bool_), score, NEG_INF)
    score = score - jnp.max(score, axis=-1, keepdims=True)
    p = jnp.exp(score)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # f32 accumulation throughout; the write narrows to the output dtype
    # (bf16 under mixed precision — halves the HBM write, matches the XLA
    # path's einsum output dtype)
    out_ref[0, 0] = jnp.dot(
        p, v, preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def masked_attention(
    q: jnp.ndarray,  # [B, H, N, Dh]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,  # [B, N] bool key validity
    interpret: Optional[bool] = None,  # None: native on TPU, interpret elsewhere
) -> jnp.ndarray:
    """Fused masked attention. Differentiable: the forward runs the Pallas
    kernel; the backward recomputes the softmax in plain XLA (flash-attention
    style pallas-fwd/recompute-bwd split — the backward is matmul-dominated
    and XLA tiles it onto the MXU fine)."""
    return _masked_attention_fwd_kernel(q, k, v, mask, interpret)


def _masked_attention_fwd_kernel(q, k, v, mask, interpret):
    interpret = resolve_interpret(interpret)
    B, H, N, Dh = q.shape
    scale = 1.0 / (Dh ** 0.5)
    mask2 = mask[:, None, None, :].astype(jnp.float32)  # [B, 1, 1, N]
    mask2 = jnp.broadcast_to(mask2, (B, H, 1, N))

    grid = (B, H)

    def idx(b, h):
        return (b, h, 0, 0)

    return pl.pallas_call(
        functools.partial(_attention_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, N, Dh), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, N, Dh), idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, N, Dh), idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, N, Dh), idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1, N), idx, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, N, Dh), idx, memory_space=pltpu.VMEM),
        interpret=interpret,
        name="masked_attention",  # the kernel's name in a device trace
    )(q, k, v, mask2)


def _masked_attention_vjp_fwd(q, k, v, mask, interpret):
    out = _masked_attention_fwd_kernel(q, k, v, mask, interpret)
    return out, (q, k, v, mask)


def _masked_attention_vjp_bwd(interpret, res, dout):
    # recompute in f32 regardless of the primal dtype: the forward kernel
    # accumulates in f32, and a bf16 softmax recompute here would
    # differentiate a visibly different p than the forward computed
    q0, k0, v0, mask = res
    q, k, v = (t.astype(jnp.float32) for t in (q0, k0, v0))
    dout = dout.astype(jnp.float32)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    score = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    score = jnp.where(mask[:, None, None, :], score, NEG_INF)
    p = jax.nn.softmax(score, axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dout)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dout, v)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q) * scale
    return dq.astype(q0.dtype), dk.astype(k0.dtype), dv.astype(v0.dtype), None


masked_attention.defvjp(_masked_attention_vjp_fwd, _masked_attention_vjp_bwd)


def masked_attention_reference(q, k, v, mask):
    """jnp oracle with identical semantics."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    score = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    score = jnp.where(mask[:, None, None, :], score, NEG_INF)
    p = jax.nn.softmax(score, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ------------------------------------------------- scatter via one-hot matmul
def _scatter_onehot_kernel(emb_ref, idx_ref, out_ref, *, chunk: int):
    # out[cells] = onehot(idx)^T @ emb for this (batch, cell-chunk) tile.
    # The one-hot tile is BUILT IN VMEM (iota-compare) and immediately
    # consumed by the MXU — it never touches HBM, which is what makes this
    # formulation beat a serial row-update loop on TPU.
    c = pl.program_id(1)
    idx = idx_ref[0, 0, :]  # [N] int32
    emb = emb_ref[0]  # [N, D]
    n = idx.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 1) + c * chunk
    onehot = (idx[:, None] == col).astype(emb.dtype)  # [N, chunk]
    out_ref[0] = jax.lax.dot_general(
        onehot, emb, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


def scatter_add_onehot(
    embeddings: jnp.ndarray,  # [B, N, D] (invalid entities must be zeroed)
    flat_idx: jnp.ndarray,  # [B, N] int cell index (clipped to [0, H*W))
    hw: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Per-batch scatter-add as a chunked one-hot matmul ([B, hw, D]).
    Out-of-range indices are CLIPPED to [0, hw-1] in this public wrapper
    (the raw one-hot kernel would DROP out-of-range rows), which is what
    ``ops.scatter_connection`` does before either ``impl``. Costs
    `2*N*hw*D` MXU FLOPs; gather backward."""
    flat_idx = jnp.clip(flat_idx.astype(jnp.int32), 0, hw - 1)
    return _scatter_add_onehot_core(embeddings, flat_idx, hw, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _scatter_add_onehot_core(embeddings, flat_idx, hw, interpret):
    return _scatter_onehot_fwd_kernel(embeddings, flat_idx, hw, interpret)


def _scatter_onehot_fwd_kernel(embeddings, flat_idx, hw, interpret):
    interpret = resolve_interpret(interpret)
    B, N, D = embeddings.shape
    # cell chunk per program: big enough to amortise the emb reload, small
    # enough that the [N, chunk] one-hot tile stays comfortably in VMEM
    # (512x2048 bf16 = 2 MiB). Lane-dim tiles want multiples of 128.
    chunk = min(hw, 2048)
    if chunk % 128:
        chunk = -(-chunk // 128) * 128  # round up: one partially-used tile
    # ...but never past hw itself: a small unaligned grid (hw=63 -> 128)
    # would otherwise hand the kernel an out-of-bounds output block and rely
    # on the backend's block padding for correctness
    chunk = min(chunk, hw)
    grid = (B, -(-hw // chunk))

    return pl.pallas_call(
        functools.partial(_scatter_onehot_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((B, hw, D), embeddings.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N, D), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, N), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, chunk, D), lambda b, c: (b, c, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="scatter_add_onehot",  # the kernel's name in a device trace
    )(embeddings, flat_idx.astype(jnp.int32)[:, None, :])


def _scatter_onehot_vjp_fwd(embeddings, flat_idx, hw, interpret):
    return _scatter_onehot_fwd_kernel(embeddings, flat_idx, hw, interpret), flat_idx


def _scatter_onehot_vjp_bwd(hw, interpret, flat_idx, dout):
    # indices reach the core pre-clipped by the public wrapper; the in_range
    # guard stays for direct core callers
    idx = flat_idx.astype(jnp.int32)
    in_range = (idx >= 0) & (idx < hw)
    demb = jnp.take_along_axis(dout, idx[..., None].clip(0, hw - 1), axis=1)
    return jnp.where(in_range[..., None], demb, 0), None


_scatter_add_onehot_core.defvjp(_scatter_onehot_vjp_fwd, _scatter_onehot_vjp_bwd)


def scatter_add_reference(embeddings, flat_idx, hw: int):
    """jnp oracle with identical semantics (the math of
    ``ops.scatter_connection``'s XLA add path on pre-flattened indices)."""
    B, N, D = embeddings.shape
    flat_idx = jnp.clip(flat_idx.astype(jnp.int32), 0, hw - 1)
    bias = jnp.arange(B, dtype=jnp.int32)[:, None] * hw
    buf = jnp.zeros((B * hw, D), embeddings.dtype)
    buf = buf.at[(flat_idx + bias).reshape(-1)].add(embeddings.reshape(B * N, D))
    return buf.reshape(B, hw, D)

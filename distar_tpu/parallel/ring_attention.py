"""Ring attention: exact attention over a sequence sharded across the mesh's
``sp`` axis.

Long-context design (SURVEY.md §5 "Long-context / sequence parallelism"):
the reference never shards sequence (its temporal context is an LSTM and its
set attention tops out at 512 entities), but this framework treats context
parallelism as first-class — the mesh declares an ``sp`` axis and this op
makes attention over sequences far beyond one chip's HBM exact and
communication-efficient.

Algorithm (Liu et al., Ring Attention, 2023): each device holds a query
shard and a K/V shard. Over ``sp_size`` steps, every device attends its
queries against the resident K/V block while the K/V blocks rotate one hop
around the ring (`jax.lax.ppermute` over ICI); a running online-softmax
(max/denominator carried per row, flash-attention style) makes the result
exactly softmax over the full sequence. Compute and the ppermute overlap
naturally under XLA's async collective scheduling.

Use inside shard_map with the sequence dim sharded over 'sp':
    out = shard_map(partial(ring_attention, axis_name="sp", axis_size=S),
                    mesh, in_specs=..., out_specs=...)(q, k, v, mask)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e9


def ring_attention(
    q: jnp.ndarray,  # [B, H, Nq_local, D]
    k: jnp.ndarray,  # [B, H, Nk_local, D]
    v: jnp.ndarray,  # [B, H, Nk_local, D]
    mask: Optional[jnp.ndarray] = None,  # [B, Nk_local] key validity
    *,
    axis_name: str = "sp",
    axis_size: int,
) -> jnp.ndarray:
    """Per-shard body (call under shard_map)."""
    B, H, Nq, D = q.shape
    scale = 1.0 / (D ** 0.5)
    if mask is None:
        mask = jnp.ones(k.shape[:1] + k.shape[2:3], bool)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, _):
        k_blk, v_blk, m_blk, acc, denom, row_max = carry
        score = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
        score = jnp.where(m_blk[:, None, None, :], score, NEG_INF)
        blk_max = score.max(axis=-1)  # [B, H, Nq]
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        p = jnp.exp(score - new_max[..., None])
        acc = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
        denom = denom * correction + p.sum(axis=-1)
        # rotate the K/V/mask block one hop around the ring
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        m_blk = jax.lax.ppermute(m_blk, axis_name, perm)
        return (k_blk, v_blk, m_blk, acc, denom, new_max), None

    # accumulators derive from q so shard_map marks them sp-varying (a bare
    # jnp.zeros would be typed replicated and fail the scan carry check)
    zero_rows = q[..., 0] * 0.0  # [B, H, Nq]
    init = (
        k,
        v,
        mask,
        q * 0.0,
        zero_rows,
        zero_rows + NEG_INF,
    )
    (k, v, mask, acc, denom, _), _ = jax.lax.scan(step, init, None, length=axis_size)
    return acc / jnp.maximum(denom, 1e-20)[..., None]


def ring_self_attention(
    q: jnp.ndarray,  # [B, H, N, D] global
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],  # [B, N]
    mesh: Mesh,
) -> jnp.ndarray:
    """Convenience wrapper: shard the sequence over the mesh's sp axis and
    run ring attention; output sharded like q."""
    sp = mesh.shape["sp"]
    assert q.shape[2] % sp == 0, f"sequence {q.shape[2]} not divisible by sp={sp}"
    spec_qkv = P(None, None, "sp", None)
    spec_mask = P(None, "sp")
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", axis_size=sp),
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask),
        out_specs=spec_qkv,
    )
    if mask is None:
        mask = jnp.ones((q.shape[0], q.shape[2]), bool)
    return fn(q, k, v, mask)

"""Scatter-connection: write per-entity embeddings onto the spatial map.

Role of the reference's scatter_connection (distar/agent/default/model/
module_utils.py:11-34): each entity's D-dim embedding is added (or written)
at its (x, y) cell of a [B, H, W, D] map.

TPU-first formulation: one flat `.at[...].add` per batch over a [B*H*W, D]
buffer — XLA lowers this to a native scatter on TPU with the embedding dim D
as the contiguous minor axis (the reference instead transposes to [D, B*H*W]
and scatters per channel). 'cover' mode uses `.set` with the reference's
same last-writer-wins-ish semantics (ties resolved by scatter order is NOT
guaranteed; use 'add' in training, as the reference default config does).

``impl='pallas_onehot'`` routes add mode through the one-hot-matmul Pallas
kernel in `pallas_kernels.py`; which of the two is faster on the chip is
not measured (ROADMAP D2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("scatter_connection")
def scatter_connection(
    embeddings: jnp.ndarray,  # [B, N, D]
    locations: jnp.ndarray,  # [B, N, 2] as (x, y) int
    spatial_size,  # (H, W)
    mode: str = "add",
    impl: str = "xla",  # 'xla' | 'pallas_onehot' (add mode only)
) -> jnp.ndarray:
    """Return [B, H, W, D] map with embeddings scattered at entity cells."""
    B, N, D = embeddings.shape
    H, W = spatial_size
    x = jnp.clip(locations[..., 0].astype(jnp.int32), 0, W - 1)
    y = jnp.clip(locations[..., 1].astype(jnp.int32), 0, H - 1)
    flat_idx = y * W + x  # [B, N] in row-major (y, x) order

    if impl == "pallas_onehot":
        assert mode == "add", "pallas scatter implements add mode"
        from .pallas_kernels import scatter_add_onehot

        return scatter_add_onehot(embeddings, flat_idx, H * W).reshape(B, H, W, D)
    if impl != "xla":
        raise ValueError(f"unknown scatter impl {impl!r} (xla|pallas_onehot)")

    batch_bias = jnp.arange(B, dtype=jnp.int32)[:, None] * (H * W)
    flat = (flat_idx + batch_bias).reshape(-1)  # [B*N]
    buf = jnp.zeros((B * H * W, D), dtype=embeddings.dtype)
    flat_emb = embeddings.reshape(B * N, D)
    if mode == "add":
        buf = buf.at[flat].add(flat_emb)
    elif mode == "cover":
        buf = buf.at[flat].set(flat_emb)
    else:
        raise NotImplementedError(mode)
    return buf.reshape(B, H, W, D)

"""Scatter-connection: write per-entity embeddings onto the spatial map.

Role of the reference's scatter_connection (distar/agent/default/model/
module_utils.py:11-34): each entity's D-dim embedding is added (or written)
at its (x, y) cell of a [B, H, W, D] map.

The map is an ``[H, W, B, D]`` operand indexed by the three components
``(y, x, b)`` with ``b = iota(B)``, and the result is its logical transpose
``[B, H, W, D]``. Why the batch is the innermost component of the cell index:

* The TPU compiler lays the scatter's operand out as ``[rows, D]{0,1}``:
  cells in lanes, the D channels in sublanes. The spatial encoder's
  convolutions want the map with the batch in lanes
  (``[B, H, W, *]{0,3,2,1}``). With rows ordered ``(y*W + x)*B + b`` and B a
  multiple of 128 the row index is lane-aligned: the way from one layout to
  the other is a bitcast and one plain copy of the map, and the gradient is
  gathered in place from what the convolution's backward writes. With x
  innermost (a flat ``b*H*W + y*W + x``; W = 160 is not a multiple of 128)
  XLA goes through a linear buffer, one channel per trip of a 32-trip
  ``while`` loop, forward and backward: 59 ms of a 321 ms flagship step
  (PERF.md section 6, PR 24). Where B is not a multiple of 128 (RL's
  (T+1)*B = 390 frames, an actor's 8-64 envs) the forward loop stays and
  only the backward one goes.
* ``b`` as an index component of its own shows the partitioner that frame b
  writes rows of frame b only, so under a ``dp`` mesh each chip scatters its
  own frames; folded into a flat index, every chip scatters the global
  batch behind all-gathers.

``tests/test_tpu_compile.py`` compiles this into a 1x1 convolution for a v5e
and fails when the loops or the all-gathers come back. 'cover' mode uses
``.set``: which writer of a shared cell wins is NOT guaranteed; use 'add' in
training, as the reference default config does.

``impl='pallas_onehot'`` routes add mode through the one-hot-matmul Pallas
kernel in `pallas_kernels.py`, whose ``[B, H*W, D]`` result is reshaped and
so keeps the forward relayout loop; which of the two is faster on the chip
is not measured (ROADMAP D2).
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
    B, _, D = embeddings.shape
    H, W = spatial_size
    x = jnp.clip(locations[..., 0].astype(jnp.int32), 0, W - 1)
    y = jnp.clip(locations[..., 1].astype(jnp.int32), 0, H - 1)

    if impl == "pallas_onehot":
        assert mode == "add", "pallas scatter implements add mode"
        from .pallas_kernels import scatter_add_onehot

        return scatter_add_onehot(embeddings, y * W + x, H * W).reshape(B, H, W, D)
    if impl != "xla":
        raise ValueError(f"unknown scatter impl {impl!r} (xla|pallas_onehot)")

    b = jnp.arange(B, dtype=jnp.int32)[:, None]  # [B, 1], broadcast over N
    buf = jnp.zeros((H, W, B, D), dtype=embeddings.dtype)
    if mode == "add":
        buf = buf.at[y, x, b].add(embeddings)
    elif mode == "cover":
        buf = buf.at[y, x, b].set(embeddings)
    else:
        raise NotImplementedError(mode)
    return buf.transpose(2, 0, 1, 3)

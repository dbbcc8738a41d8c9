"""Scatter-connection: write per-entity embeddings onto the spatial map.

Role of the reference's scatter_connection (distar/agent/default/model/
module_utils.py:11-34): each entity's D-dim embedding is added (or written)
at its (x, y) cell of a [B, H, W, D] map.

Add mode is a product, not a scatter (``impl='product'``, the default). A
cell's flat index ``y*W + x`` splits at any divisor c of W into ``q = y*(W/c)
+ x//c`` (which group of c cells of a row) and ``r = x % c`` (which cell of
the group), and ``[B, H*W/c, c*D]`` is ``[B, H, W, D]`` in memory, so::

    out[b, q, (r, :)] = sum_n [q_n == q] * ([r_n == r] * e[b, n, :])

is per frame a ``[H*W/c, N] x [N, c*D]`` matrix product: 2*N*H*W*D FLOPs
whatever c is, accumulated in float32 and rounded once; the 0/1 factors are
exact in any float dtype. The TPU compiler builds the LEFT one-hot inside the
product's own fusion and writes only the right operand ``[B, N, c*D]`` to
memory, so c is the smallest divisor of W whose ``c*D`` fills the 128 lanes of
a tile (c = 4 at D = 32: 50 MB a flagship step where c = W, the product of a
y and an x one-hot, writes 2 GB; on the chip 3.6 against 8.7 ms, the product
itself at 95% of the MXU's peak: PERF.md section 5, PR 35).

Why not the scatter it replaces: the TPU compiler turns ``buf.at[...].add(e)``
into a custom fusion that writes one row at a time, B*N = 196,608 rows of 64
bytes in a flagship step at 122-132 ns each (25.9 ms of a 188 ms step), into
an operand laid out ``[rows, D]{0,1}`` that has to be zero-filled first and
copied into the convolutions' batch-in-lanes layout afterwards, through a
32-trip ``while`` loop wherever B is not a multiple of 128 (RL's (T+1)*B = 390
frames: 21 ms more; an actor's 8-64 envs). The product's result is a plain
array that ONE copy lays out for the convolution at any B, and under a ``dp``
mesh the frame is a batch dimension of the product, so each chip computes its
own frames and nothing crosses chips.

The backward pass is NOT the product's transpose: that would spend a second
product of the same size where ``g[b, y_n, x_n]`` reads B*N rows. It is a
``jax.custom_vjp`` whose rule is a gather from the cotangent's ``[H, W, B,
D]`` transpose by the three index components ``(y, x, b)``, ``b = iota(B)``.
The batch is the innermost component for the compiler's sake: rows ordered
``(y*W + x)*B + b`` are lane-aligned with what the convolution's backward
writes (batch in lanes), so the rows are gathered in place; with x innermost
(W = 160 is not a multiple of 128) the compiler goes through a linear buffer,
one channel per trip of a ``while`` loop (PERF.md section 6, PR 24), and with
``b`` folded into a flat index every chip of a ``dp`` mesh gathers from the
global batch behind all-gathers. That indexing survives in the backward rule,
in 'cover' mode and in ``impl='xla'``, nowhere else.

``tests/test_tpu_compile.py`` compiles this into a 1x1 convolution for a v5e
and fails when a scatter, a loop or an all-gather comes back. 'cover' mode uses
``.set``: which writer of a shared cell wins is NOT guaranteed; use 'add' in
training, as the reference default config does.

``impl='xla'`` keeps the plain ``.at[y, x, b].add`` scatter: it is what the
benchmark's float32 CPU reference runs (``reference.model.encoder.scatter.
impl``), independent of the product it judges, and what the tests hold the
product to. ``impl='pallas_onehot'`` routes add mode through the one-hot-matmul
Pallas kernel in `pallas_kernels.py`: the split at c = 1, whose 32 output
columns fill a quarter of the MXU's and whose ``[B, H*W, D]`` result takes a
second copy on its way to the convolution (14.9 against 4.3 ms forward on the
chip, PR 35); no cell runs it (ROADMAP D2). Which form a traced call took is
counted: ``distar_scatter_connection_traced_total{form, mode}``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs import get_registry

IMPLS = ("product", "xla", "pallas_onehot")
LANES = 128  # the minor dimension of a TPU tile, and the MXU's output width


def _group(W: int, D: int) -> int:
    """The divisor c of W at which the product splits a cell's index: the
    narrowest right operand ``[N, c*D]`` that still fills a tile's lanes."""
    return next((c for c in range(1, W) if W % c == 0 and c * D >= LANES), W)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _add_as_product(embeddings, y, x, spatial_size):
    B, N, D = embeddings.shape
    H, W = spatial_size
    dtype = embeddings.dtype
    c = _group(W, D)
    q = y * (W // c) + x // c  # [B, N]: which group of c cells
    r = x % c  # which cell of the group
    oq = (q[..., None] == jnp.arange(H * W // c, dtype=jnp.int32)).astype(dtype)
    z = (r[..., None] == jnp.arange(c, dtype=jnp.int32)).astype(dtype)[..., None] * embeddings[:, :, None, :]
    out = jnp.einsum("bnq,bnk->bqk", oq, z.reshape(B, N, c * D), preferred_element_type=jnp.float32)
    return out.astype(dtype).reshape(B, H, W, D)


def _add_as_product_fwd(embeddings, y, x, spatial_size):
    return _add_as_product(embeddings, y, x, spatial_size), (y, x)


def _add_as_product_bwd(spatial_size, res, g):
    y, x = res
    b = jnp.arange(g.shape[0], dtype=jnp.int32)[:, None]
    return g.transpose(1, 2, 0, 3)[y, x, b], None, None


_add_as_product.defvjp(_add_as_product_fwd, _add_as_product_bwd)


@jax.named_scope("scatter_connection")
def scatter_connection(
    embeddings: jnp.ndarray,  # [B, N, D]
    locations: jnp.ndarray,  # [B, N, 2] as (x, y) int
    spatial_size,  # (H, W)
    mode: str = "add",
    impl: str = "product",  # 'product' | 'xla' | 'pallas_onehot' (add mode)
) -> jnp.ndarray:
    """Return [B, H, W, D] map with embeddings scattered at entity cells."""
    B, _, D = embeddings.shape
    H, W = spatial_size
    if impl not in IMPLS:
        raise ValueError(f"unknown scatter impl {impl!r} ({'|'.join(IMPLS)})")
    if mode not in ("add", "cover"):
        raise NotImplementedError(mode)
    x = jnp.clip(locations[..., 0].astype(jnp.int32), 0, W - 1)
    y = jnp.clip(locations[..., 1].astype(jnp.int32), 0, H - 1)

    form = impl if impl != "xla" and mode == "add" else "scatter"
    get_registry().counter(
        "distar_scatter_connection_traced_total",
        "scatter_connection calls traced, by the form the forward pass took",
        form=form, mode=mode,
    ).inc()
    if form == "pallas_onehot":
        from .pallas_kernels import scatter_add_onehot

        return scatter_add_onehot(embeddings, y * W + x, H * W).reshape(B, H, W, D)
    if form == "product":
        return _add_as_product(embeddings, y, x, (H, W))

    b = jnp.arange(B, dtype=jnp.int32)[:, None]  # [B, 1], broadcast over N
    buf = jnp.zeros((H, W, B, D), dtype=embeddings.dtype)
    if mode == "add":
        buf = buf.at[y, x, b].add(embeddings)
    else:
        buf = buf.at[y, x, b].set(embeddings)
    return buf.transpose(2, 0, 1, 3)

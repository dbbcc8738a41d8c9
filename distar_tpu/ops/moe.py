"""A mixture-of-experts feed-forward layer that is told which experts it holds.

Expert parallelism divides the experts of a layer over chips. This layer is
one member's part: the router scores ALL ``num_experts`` and picks
``top_k`` of them per position, and of those picks the block computes the
ones whose expert lives here (``experts_held``: ``offset`` and ``count``),
``FF(u) = sum_{e in sel, e held} w_e E_e(u)``. What the absent experts would
add is left out; there is no exchange with other chips in this module and
nothing that stands in for them.

No row is dropped. The rows routed here are sorted by expert into a static
buffer of ``min(top_k, count)`` rows a position, which is the provable bound
(a position picks distinct experts), and the grouped products run over the
rows that are really there (``group_sizes``), so an uneven load costs what it
holds and the empty tail costs no product. ``overflow`` counts the rows routed
here that the buffer did not take: 0 by construction, reported so that the
learner and the benchmark can hold the layer to it. The grouped product is
chosen by the platform the program is being compiled for
(``jax.lax.platform_dependent``): JAX's megablox kernel on a TPU
(``jax.experimental.pallas.ops.tpu.megablox``: tiles over the rows of each
group, grid as long as the rows present), ``jax.lax.ragged_dot`` anywhere else.

Moving rows to the sorted buffer and back is a gather in both directions,
forward and backward (``take_rows``): the transpose of a row gather is a
scatter-add, which the TPU serialises, and here every source row's takers
are known.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .sequence import RMSNorm

Dtype = Any

# megablox tiles (rows, contraction, columns) for a few thousand rows an
# expert and widths of 1536-2048
GMM_TILING = (512, 1024, 1024)


def route(logits, bias, top_k: int, scaling: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid router with a selection bias: ``s = sigmoid(logits)`` in
    float32, ``sel = top_k(s + bias)``, ``w_e = s_e / (sum_{sel} s + 1e-6)``
    times ``scaling``. The bias moves the selection only, not the weights.
    ``logits`` [N, E] -> ``sel`` [N, k] int32, ``w`` [N, k] float32."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, sel = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, scaling * w / (w.sum(-1, keepdims=True) + 1e-6)


class Dispatch(NamedTuple):
    """Where the rows of the experts held here go. ``R`` rows in the buffer,
    ``N`` positions, ``k`` picks a position."""

    token: jnp.ndarray       # [R] the position each buffer row copies (0 beyond the rows present)
    slot: jnp.ndarray        # [R] the flat pick n*k+j each buffer row serves (N*k beyond)
    row: jnp.ndarray         # [N, k] the buffer row of each pick, R where its expert is not here
    group_sizes: jnp.ndarray  # [count] rows of each held expert in the buffer
    rows: jnp.ndarray        # [count] rows routed to each held expert
    overflow: jnp.ndarray    # [] rows routed here that the buffer did not take: 0 by construction


def dispatch(sel, offset: int, count: int) -> Dispatch:
    """Sort the picks of held experts by expert into ``N * min(k, count)``
    rows: a position picks distinct experts, so no more can be routed here."""
    N, k = sel.shape
    capacity = N * min(k, count)
    local = sel - offset
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)                 # absent experts sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)         # [N*k] flat pick of sorted row r
    rows = (key[:, None] == jnp.arange(count)[None, :]).sum(0).astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(rows), capacity)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    present = ends[-1]
    # the buffer row of every flat pick: the inverse of ``order``
    where = jnp.argsort(order).astype(jnp.int32)
    row = jnp.where(held.reshape(-1) & (where < present), where, capacity).reshape(N, k)
    valid = jnp.arange(capacity) < present
    slot = jnp.where(valid, order[:capacity], N * k)
    return Dispatch(token=jnp.where(valid, order[:capacity] // k, 0), slot=slot, row=row,
                    group_sizes=group_sizes, rows=rows, overflow=rows.sum() - present)


@jax.custom_vjp
def take_rows(src, idx, takers):
    """``src[idx]``. ``takers`` [len(src), m] names, for each source row, the
    output rows that took it (``len(idx)`` = none), so the backward pass is a
    gather too: ``d_src[r] = sum_j d_out[takers[r, j]]``."""
    return src[idx]


def _take_rows_fwd(src, idx, takers):
    return src[idx], (idx, takers)


def _take_rows_bwd(res, g):
    idx, takers = res
    taken = (takers < g.shape[0])[..., None]
    return jnp.where(taken, g[jnp.minimum(takers, g.shape[0] - 1)], 0).sum(axis=1), None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def megablox(x, w, group_sizes, interpret: bool = False):
    """JAX's megablox grouped matmul with this module's tiles (``interpret``:
    how a test runs the kernel off a TPU)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    R, a, b = x.shape[0], x.shape[1], w.shape[2]
    tiling = (min(GMM_TILING[0], R), min(GMM_TILING[1], a), min(GMM_TILING[2], b))
    return gmm(x, w, group_sizes, x.dtype, tiling, None, None, False, interpret)


def grouped_matmul(x, w, group_sizes):
    """``x`` [R, a] times ``w[g]`` [a, b] for the rows of group ``g``; rows
    beyond the groups are undefined on a TPU (megablox) and zero elsewhere
    (``ragged_dot``). Only the branch of the platform compiled for is lowered."""
    return jax.lax.platform_dependent(x, w, group_sizes, tpu=megablox, default=jax.lax.ragged_dot)


class ExpertsHeldMoE(nn.Module):
    """``FF(u)`` over the experts held here, and what the step reports of it.

    Parameters: ``norm`` (the layer's feed-forward RMSNorm), ``router``
    [d, num_experts], ``w1``/``w3`` [count, d, width], ``w2`` [count, width, d].
    ``expert_bias`` [num_experts] is a buffer (collection ``buffers``): drawn
    at init, never trained. Returns ``(FF(RMSNorm(u)), stats)`` with ``stats``
    the rows routed to each held expert and the overflow."""

    num_experts: int
    top_k: int
    width: int
    offset: int
    count: int
    scaling: float = 1.0
    use_bias: bool = True
    eps: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        B, S, d = x.shape
        N = B * S
        init = nn.initializers.normal(0.02)
        w_router = self.param("router", init, (d, self.num_experts), jnp.float32)
        w1 = self.param("w1", init, (self.count, d, self.width), jnp.float32)
        w3 = self.param("w3", init, (self.count, d, self.width), jnp.float32)
        w2 = self.param("w2", init, (self.count, self.width, d), jnp.float32)
        bias = self.variable(
            "buffers", "expert_bias",
            lambda: 0.01 * jax.random.normal(self.make_rng("params"), (self.num_experts,)))

        with jax.named_scope("moe_router"):
            u = RMSNorm(self.eps, name="norm")(x).reshape(N, d)
            # 64 columns: float32 at full precision costs nothing, and the picks hang on it
            logits = jnp.dot(u.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST)
            sel, w = route(logits, bias.value if self.use_bias else 0.0, self.top_k, self.scaling)
        with jax.named_scope("moe_dispatch"):
            plan = dispatch(sel, self.offset, self.count)
            xs = take_rows(u, plan.token, plan.row)
        with jax.named_scope("moe_experts"):
            cast = lambda p: p.astype(self.dtype)
            gate = nn.silu(grouped_matmul(xs, cast(w1), plan.group_sizes))
            up = grouped_matmul(xs, cast(w3), plan.group_sizes)
            out = grouped_matmul(gate * up, cast(w2), plan.group_sizes)
        with jax.named_scope("moe_combine"):
            R = out.shape[0]
            out = jnp.where((plan.slot < N * self.top_k)[:, None], out, 0)
            # a pick whose expert is not here reads row 0 with weight 0
            here = plan.row < R
            picked = take_rows(out, jnp.where(here, plan.row, 0).reshape(-1), plan.slot[:, None])
            w = jnp.where(here, w, 0.0).astype(jnp.float32)
            y = (picked.reshape(N, self.top_k, d).astype(jnp.float32) * w[..., None]).sum(1)
        return y.astype(x.dtype).reshape(B, S, d), {"rows": plan.rows, "overflow": plan.overflow}

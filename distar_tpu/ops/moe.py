"""A mixture-of-experts feed-forward layer that is told which experts it holds.

Expert parallelism divides the experts of a layer over chips. This layer is
one member's part: the router scores ALL ``num_experts`` and picks
``top_k`` of them per position, and of those picks the block computes the
ones whose expert lives here (``experts_held``: ``offset`` and ``count``),
``FF(u) = sum_{e in sel, e held} w_e E_e(u)``. What the absent experts would
add is left out; there is no exchange with other chips in this module and
nothing that stands in for them. What an expert computes is the layer's
``body`` (``EXPERT_BODIES``: ``swiglu``, three matrices, ``W_2 (silu(W_1 u) *
W_3 u)``, LFM2's; ``relu2``, two, ``W_2 relu(W_1 u)^2``, ``nemotron_h``'s). A
shared expert (``shared_width``) is the same body as one dense product over
every position, added to the routed part: every member of the group computes
it alike, so over the group it counts once.

No row is dropped. The rows routed here are sorted by expert into a static
buffer of ``min(top_k, count)`` rows a position, which is the provable bound
(a position picks distinct experts). The buffer is walked in chunks of one
row a position, twice the expected load of ``top_k * count / num_experts``
and more: up to the end of the last chunk that holds rows, rounded up to a
length that has a program (``walk``: a program for one chunk, for two and
for the whole buffer, chosen by a count the step computes from its own
picks, forward and backward). So the gather into the buffer, the grouped products
(``group_sizes``: they skip the empty tail anyway), the masks and their
backward passes cost what the load holds, and a router that sends the bound
gets the whole buffer at the whole buffer's cost; the weighted sum gathers by
pick (``N * top_k`` indices whatever the length). ``buffer_rows`` reports the
rows walked. ``overflow`` counts the rows routed here that the buffer did not
take: 0 by construction, reported so that the learner and the benchmark can
hold the layer to it. The grouped product is chosen by the platform the
program is being compiled for (``jax.lax.platform_dependent``): JAX's
megablox kernel on a TPU (``jax.experimental.pallas.ops.tpu.megablox``: tiles
over the rows of each group, grid as long as the rows present),
``jax.lax.ragged_dot`` anywhere else.

Moving rows to the sorted buffer and back is a gather in both directions,
forward and backward (``take_rows``): the transpose of a row gather is a
scatter-add, which the TPU serialises, and here every source row's takers
are known.
"""
from __future__ import annotations

import functools
import math
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
    """Where the rows of the experts held here go. ``N`` positions, ``k``
    picks a position, a buffer of ``R = N * min(k, count)`` rows."""

    token: jnp.ndarray       # [R] the position each buffer row copies (0 beyond the rows present)
    slot: jnp.ndarray        # [R] the flat pick n*k+j each buffer row serves (N*k beyond)
    row: jnp.ndarray         # [N, k] the buffer row of each pick, R where its expert is not here
    group_sizes: jnp.ndarray  # [count] rows of each held expert in the buffer
    chunks: jnp.ndarray      # [] chunks of N rows that hold rows, counting the first, which always runs
    rows: jnp.ndarray        # [count] rows routed to each held expert
    overflow: jnp.ndarray    # [] rows routed here that the buffer did not take: 0 by construction


def dispatch(sel, offset: int, count: int) -> Dispatch:
    """Sort the picks of held experts by expert into ``N * min(k, count)``
    rows: a position picks distinct experts, so no more can be routed here."""
    N, k = sel.shape
    chunks = min(k, count)
    capacity = N * chunks
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
                    group_sizes=group_sizes, chunks=jnp.clip((present + N - 1) // N, 1, chunks),
                    rows=rows, overflow=rows.sum() - present)


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
    # the kernel wants whole row tiles: a buffer of 3 x 256 rows gets tiles of 256
    tiling = (math.gcd(GMM_TILING[0], R), min(GMM_TILING[1], a), min(GMM_TILING[2], b))
    return gmm(x, w, group_sizes, x.dtype, tiling, None, None, False, interpret)


def grouped_matmul(x, w, group_sizes):
    """``x`` [R, a] times ``w[g]`` [a, b] for the rows of group ``g``; rows
    beyond the groups are undefined on a TPU (megablox) and zero elsewhere
    (``ragged_dot``). Only the branch of the platform compiled for is lowered."""
    return jax.lax.platform_dependent(x, w, group_sizes, tpu=megablox, default=jax.lax.ragged_dot)


def _swiglu(product, x, w1, w3, w2):
    return product(nn.silu(product(x, w1)) * product(x, w3), w2)


def _relu2(product, x, w1, w2):
    return product(jnp.square(nn.relu(product(x, w1))), w2)


# what one expert computes: ``body(product, rows, *matrices)`` with ``product(x, w)``
# the grouped product over the buffer, or a dense one for a shared expert.
# ``w2`` [width, d] is the way back; the others are [d, width].
EXPERT_BODIES = {"swiglu": (_swiglu, ("w1", "w3", "w2")), "relu2": (_relu2, ("w1", "w2"))}


def shared_expert(body: str, u, ws):
    """The body over every row of ``u`` [N, d] as dense products: the expert
    that every position takes and every member of the group computes alike."""
    with jax.named_scope("moe_shared"):
        return EXPERT_BODIES[body][0](jnp.dot, u, *ws)


def _buffer(body: str, length: int, u, w, ws, plan: Dispatch):
    """``FF`` [N, d] float32 from the first ``length`` rows of the buffer,
    which hold every row present: the rows of ``u`` gathered, the body's
    grouped products, and the weighted sum over the picks served."""
    N, k = w.shape
    with jax.named_scope("moe_dispatch"):
        xs = take_rows(u, plan.token[:length], plan.row)
    with jax.named_scope("moe_experts"):
        out = EXPERT_BODIES[body][0](lambda x, m: grouped_matmul(x, m, plan.group_sizes), xs, *ws)
    with jax.named_scope("moe_combine"):
        slot = plan.slot[:length]
        out = jnp.where((slot < N * k)[:, None], out, 0)
        # a pick whose expert is not here reads row 0 with weight 0
        here = plan.row < length
        picked = take_rows(out, jnp.where(here, plan.row, 0).reshape(-1), slot[:, None])
        return (picked.reshape(N, k, -1).astype(jnp.float32) * jnp.where(here, w, 0.0)[..., None]).sum(1)


def _lengths(plan: Dispatch):
    """The buffer lengths that have a program: one chunk of ``N`` rows (twice
    the expected load and more), two, and the whole buffer. A program a chunk
    is a compile a chunk (each holds the grouped product's kernels, forward
    and backward), and a load beyond two chunks is rare enough to round up."""
    N, chunks = plan.row.shape[0], plan.token.shape[0] // plan.row.shape[0]
    return [N * c for c in sorted({1, min(2, chunks), chunks})]


def _program(plan: Dispatch):
    """The first of ``_lengths`` that takes ``plan.chunks`` chunks."""
    N = plan.row.shape[0]
    return sum((plan.chunks * N > n).astype(jnp.int32) for n in _lengths(plan)[:-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def walk(body: str, u, w, ws, plan: Dispatch):
    """``FF`` [N, d] float32 from the buffer up to the end of the last chunk
    that holds rows (``plan.chunks``, which the step computes from its own
    picks): one program a length, of which the one the load asks for runs.
    ``u`` [N, d] rows, ``w`` [N, k] float32 weights of the picks, ``ws`` the
    body's matrices, each [count, ., .]. The backward
    rule makes the same choice and computes the chosen length's forward again
    (a choice that JAX differentiates itself returns every length's residuals
    from whichever ran, zeros for the others). Under a decoder layer's remat
    that forward takes the place of the replay's, which is then dead code;
    without remat it is one forward more than a buffer of one length costs."""
    return jax.lax.switch(_program(plan), [functools.partial(_buffer, body, n) for n in _lengths(plan)],
                          u, w, ws, plan)


def _walk_fwd(body, *args):
    return walk(body, *args), args


def _walk_bwd(body, args, g):
    *rest, plan = args

    def pull(length):
        # ``checkpoint``: the forward computed here is a recompute, and reads as one in a trace
        return lambda g, *rest: jax.vjp(jax.checkpoint(lambda *a: _buffer(body, length, *a, plan)), *rest)[1](g)

    return (*jax.lax.switch(_program(plan), [pull(n) for n in _lengths(plan)], g, *rest), None)


walk.defvjp(_walk_fwd, _walk_bwd)


class ExpertsHeldMoE(nn.Module):
    """``FF(u)`` over the experts held here, and what the step reports of it.

    Parameters: ``norm`` (the layer's feed-forward RMSNorm), ``router``
    [d, num_experts], the body's matrices (``w1``/``w3`` [count, d, width],
    ``w2`` [count, width, d]; ``relu2`` has no ``w3``) and, with
    ``shared_width``, ``shared`` (the same names, [d, shared_width] and back).
    ``expert_bias`` [num_experts] is a buffer (collection ``buffers``): drawn
    at init, never trained. Returns ``(FF(RMSNorm(u)), stats)`` with ``stats``
    the ``rows`` routed to each held expert, the ``overflow`` and the
    ``buffer_rows`` walked."""

    num_experts: int
    top_k: int
    width: int
    offset: int
    count: int
    scaling: float = 1.0
    use_bias: bool = True
    eps: float = 1e-5
    dtype: Dtype = jnp.float32
    body: str = "swiglu"
    shared_width: int = 0

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        B, S, d = x.shape
        N = B * S
        init = nn.initializers.normal(0.02)
        names = EXPERT_BODIES[self.body][1]
        shape = lambda name, width: (width, d) if name == "w2" else (d, width)
        w_router = self.param("router", init, (d, self.num_experts), jnp.float32)
        ws = [self.param(n, init, (self.count, *shape(n, self.width)), jnp.float32) for n in names]
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
        with jax.named_scope("moe_experts"):
            ws = tuple(p.astype(self.dtype) for p in ws)
        y = walk(self.body, u, w, ws, plan)
        if self.shared_width:
            shared = [self.param(f"shared_{n}", init, shape(n, self.shared_width), jnp.float32) for n in names]
            with jax.named_scope("moe_shared"):
                shared = [p.astype(self.dtype) for p in shared]
            y = y + shared_expert(self.body, u, shared).astype(jnp.float32)
        with jax.named_scope("moe_combine"):
            y = y.astype(x.dtype).reshape(B, S, d)
        return y, {"rows": plan.rows, "overflow": plan.overflow, "buffer_rows": N * plan.chunks}

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
picks, forward and backward). Every row of ``d`` or ``width`` numbers that
the layer moves is indexed by buffer row, over the ``length`` rows of the
program that ran, forward and backward: the rows of ``u`` copied into the
buffer (``copy_rows``), the grouped products (``group_sizes``: they skip the
empty tail anyway), the masks, and the weighted sum, in which each buffer row
carries the weight of the pick it serves and is added to its position
(``add_rows``). So all of it costs what the load holds, and a router that
sends the bound gets the whole buffer at the whole buffer's cost (a buffer
of more than ``WHOLE_AT_ONCE_UP_TO`` chunks two chunks at a time, so that
the bound's program holds the two-chunk program's temporaries). Only
scalars are indexed by pick (a weight a pick and its gradient, through
``Dispatch.slot``). ``buffer_rows`` reports the rows walked. ``overflow``
counts the rows routed here that the buffer did not take: 0 by construction,
reported so that the learner and the benchmark can hold the layer to it. The
grouped product is chosen by the platform the program is being compiled for
(``jax.lax.platform_dependent``): JAX's megablox kernel on a TPU
(``jax.experimental.pallas.ops.tpu.megablox``: tiles over the rows of each
group, grid as long as the rows present), ``jax.lax.ragged_dot`` anywhere
else.

The way back from the buffer is a row add by position (a scatter-add), and so
is the backward pass of the way in. Until PR 32 both were gathers by pick
(``N * top_k`` indices whatever the length), because the TPU takes a
scatter-add row by row; it sorts the indices and still does, at 100-180 ns a
row, but a buffer holds a quarter or a sixth of the picks at the loads the
cells send, and on the chip the row adds were ahead at every length, the
whole buffer included (PERF.md section 5, "PR 32": 42 ms a layer for 67 at
one chunk of LFM2's shape, 128 for 133 at the whole buffer). So there is one
form, and no threshold between two.
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

# megablox tiles (rows, contraction, columns). The row tile follows the rows an
# expert can expect, which a program knows as its buffer's rows over the experts
# held: a tile that a group's rows do not fill is computed whole, once for each
# group that touches it, so a few thousand rows an expert (LFM2, ``nemotron_h``,
# Kimi-VL: 2,048-4,096 buffer rows a held expert in the first chunk's program)
# take 512-row tiles and a few hundred (``qwen3_next``: 32 held experts, 512)
# take ``GMM_FEW_ROWS_TILE``. Contraction and columns are cut to the widths.
GMM_TILING = (512, 1024, 1024)
GMM_FEW_ROWS = 1024      # buffer rows a held expert below which the row tile is the smaller one
GMM_FEW_ROWS_TILE = 128


def route(logits, bias, top_k: int, scaling: float = 1.0,
          scoring: str = "sigmoid") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``logits`` [N, E] -> ``sel`` [N, k] int32, ``w`` [N, k] float32, by the
    router's ``scoring``. ``sigmoid`` (LFM2, ``nemotron_h``, ``deepseek_v3``):
    ``s = sigmoid(logits)`` in float32, ``sel = top_k(s + bias)``, ``w_e = s_e
    / (sum_{sel} s + 1e-6)`` times ``scaling``; the bias moves the selection
    only, not the weights. ``softmax`` (``qwen3_next``): ``p =
    softmax(logits)`` over all ``E`` in float32, ``sel = top_k(p)``, ``w_e =
    p_e / sum_{sel} p`` times ``scaling``; ``bias`` is read by nothing."""
    if scoring == "softmax":
        w, sel = jax.lax.top_k(jax.nn.softmax(logits.astype(jnp.float32), axis=-1), top_k)
        return sel, scaling * w / w.sum(-1, keepdims=True)
    if scoring != "sigmoid":
        raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax'")
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, sel = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, scaling * w / (w.sum(-1, keepdims=True) + 1e-6)


class Dispatch(NamedTuple):
    """Where the rows of the experts held here go. ``N`` positions, ``k``
    picks a position, a buffer of ``R = N * min(k, count)`` rows."""

    token: jnp.ndarray       # [R] the position each buffer row copies (0 beyond the rows present)
    slot: jnp.ndarray        # [R] the flat pick n*k+j each buffer row serves (N*k beyond)
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
    valid = jnp.arange(capacity) < present
    return Dispatch(token=jnp.where(valid, order[:capacity] // k, 0), slot=jnp.where(valid, order[:capacity], N * k),
                    group_sizes=group_sizes, chunks=jnp.clip((present + N - 1) // N, 1, chunks),
                    rows=rows, overflow=rows.sum() - present)


@jax.custom_vjp
def copy_rows(src, idx, present):
    """``src[idx]``, of which the rows ``present`` count. The backward pass
    adds their cotangents back by ``idx``, ``len(idx)`` row adds (the other
    rows' are whatever the grouped product left there): in float32, as the
    sum over a row's takers is made, and rounded once."""
    return src[idx]


def _copy_rows_fwd(src, idx, present):
    return src[idx], (src, idx, present)   # ``src`` for its shape and dtype


def _copy_rows_bwd(res, g):
    src, idx, present = res
    g = jnp.where(present[:, None], g, 0).astype(jnp.float32)
    return jnp.zeros(src.shape, jnp.float32).at[idx].add(g).astype(src.dtype), None, None


copy_rows.defvjp(_copy_rows_fwd, _copy_rows_bwd)


def add_rows(out, w, token, slot):
    """``FF[n] = sum over the buffer rows r of position n of w[slot[r]] *
    out[r]`` [N, d] float32: each row carries the weight of the pick it
    serves (a gather of scalars) and is added to its position, ``len(out)``
    row adds. A row beyond those present is zero in ``out``, and adds that
    to position 0. Backward (plain autodiff): the cotangent gathered by
    ``token``, a row-wise product for the weights' gradient, scalars back by ``slot``."""
    N, k = w.shape
    w_row = w.reshape(-1)[jnp.minimum(slot, N * k - 1)]
    return jnp.zeros((N, out.shape[1]), jnp.float32).at[token].add(out.astype(jnp.float32) * w_row[:, None])


def megablox(x, w, group_sizes, interpret: bool = False):
    """JAX's megablox grouped matmul with this module's tiles (``interpret``:
    how a test runs the kernel off a TPU)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    R, a, b = x.shape[0], x.shape[1], w.shape[2]
    rows = GMM_TILING[0] if R // w.shape[0] >= GMM_FEW_ROWS else GMM_FEW_ROWS_TILE
    # the kernel wants whole row tiles: a buffer of 3 x 256 rows gets tiles of 256
    tiling = (math.gcd(rows, R), min(GMM_TILING[1], a), min(GMM_TILING[2], b))
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


def shared_gate(u, w_gate):
    """``sigmoid(u . w_gate)`` [N, 1] float32: how far a position opens the shared expert (``qwen3_next``)."""
    with jax.named_scope("moe_shared"):
        return jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), w_gate, precision=jax.lax.Precision.HIGHEST))[:, None]


def _rows(body: str, u, w, ws, token, slot, group_sizes):
    """``FF`` [N, d] float32 from the buffer rows ``token``/``slot``, of
    which each held expert has ``group_sizes`` in a row: the rows of ``u``
    copied in, the body's grouped products, and each row times its pick's
    weight added to its position."""
    present = slot < w.size
    with jax.named_scope("moe_dispatch"):
        xs = copy_rows(u, token, present)
    with jax.named_scope("moe_experts"):
        out = EXPERT_BODIES[body][0](lambda x, m: grouped_matmul(x, m, group_sizes), xs, *ws)
    with jax.named_scope("moe_combine"):
        return add_rows(jnp.where(present[:, None], out, 0), w, token, slot)


# a buffer of more chunks than this is walked two chunks at a time: the rows of ``d`` numbers that one
# pass over 10 chunks of 16,384 rows keeps (copied in, out of the products, weighted in float32, and
# their cotangents) are 5 GB, and the program of the provable bound sets the step's memory whether
# or not a load ever asks for it (PERF.md section 6, PR 36)
WHOLE_AT_ONCE_UP_TO = 6


def _buffer(body: str, length: int, u, w, ws, plan: Dispatch):
    """``FF`` [N, d] float32 from the first ``length`` rows of the buffer,
    which hold every row present."""
    N = u.shape[0]
    chunks = length // N
    if chunks <= WHOLE_AT_ONCE_UP_TO:
        return _rows(body, u, w, ws, plan.token[:length], plan.slot[:length], plan.group_sizes)
    piece = N * (1 if chunks % 2 else 2)
    ends = jnp.cumsum(plan.group_sizes)

    @jax.checkpoint
    def one(lo, u, w, ws):
        # the rows of each held expert that lie in [lo, lo + piece)
        sizes = jnp.diff(jnp.clip(ends, lo, lo + piece), prepend=lo).astype(jnp.int32)
        take = lambda t: jax.lax.dynamic_slice_in_dim(t, lo, piece)
        return _rows(body, u, w, ws, take(plan.token), take(plan.slot), sizes)

    ff, _ = jax.lax.scan(lambda ff, lo: (ff + one(lo, u, w, ws), None), jnp.zeros((N, u.shape[1]), jnp.float32),
                         jnp.arange(0, length, piece, dtype=jnp.int32))
    return ff


def _lengths(N: int, plan: Dispatch):
    """The buffer lengths that have a program: one chunk of ``N`` rows (twice
    the expected load and more), two, and the whole buffer. A program a chunk
    is a compile a chunk (each holds the grouped product's kernels, forward
    and backward), and a load beyond two chunks is rare enough to round up."""
    chunks = plan.token.shape[0] // N
    return [N * c for c in sorted({1, min(2, chunks), chunks})]


def _program(N: int, plan: Dispatch):
    """The first of ``_lengths`` that takes ``plan.chunks`` chunks."""
    return sum((plan.chunks * N > n).astype(jnp.int32) for n in _lengths(N, plan)[:-1])


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
    N = u.shape[0]
    return jax.lax.switch(_program(N, plan), [functools.partial(_buffer, body, n) for n in _lengths(N, plan)],
                          u, w, ws, plan)


def _walk_fwd(body, *args):
    return walk(body, *args), args


def _walk_bwd(body, args, g):
    *rest, plan = args
    N = rest[0].shape[0]

    def pull(length):
        # ``checkpoint``: the forward computed here is a recompute, and reads as one in a trace
        return lambda g, *rest: jax.vjp(jax.checkpoint(lambda *a: _buffer(body, length, *a, plan)), *rest)[1](g)

    return (*jax.lax.switch(_program(N, plan), [pull(n) for n in _lengths(N, plan)], g, *rest), None)


walk.defvjp(_walk_fwd, _walk_bwd)


class ExpertsHeldMoE(nn.Module):
    """``FF(u)`` over the experts held here, and what the step reports of it.

    Parameters: ``norm`` (the layer's feed-forward RMSNorm), ``router``
    [d, num_experts], the body's matrices (``w1``/``w3`` [count, d, width],
    ``w2`` [count, width, d]; ``relu2`` has no ``w3``) and, with
    ``shared_width``, ``shared`` (the same names, [d, shared_width] and back)
    and, with ``gated_shared``, ``shared_gate`` [d] (the shared expert's output
    times ``sigmoid(u . shared_gate)``). ``scoring`` is the router's (``route``);
    ``softmax`` reads no bias. ``zero_centred``: the layer's norm is ``1 + w``.
    ``expert_bias`` [num_experts] is a buffer (collection ``buffers``): drawn
    at init, never trained. Returns ``(FF(RMSNorm(u)), stats)`` with ``stats``
    the ``rows`` routed to each held expert, the ``overflow``, the
    ``buffer_rows`` walked and ``row_indexed`` (1: this layer's program moved
    its rows by buffer row, as every length's does)."""

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
    scoring: str = "sigmoid"
    gated_shared: bool = False
    zero_centred: bool = False

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
            u = RMSNorm(self.eps, self.zero_centred, name="norm")(x).reshape(N, d)
            # 64 to 512 columns of the step's thousands: float32 at full precision costs little, and the picks hang on it
            logits = jnp.dot(u.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST)
            # the sigmoid path calls ``route`` with the four arguments it always had
            scoring = {} if self.scoring == "sigmoid" else {"scoring": self.scoring}
            sel, w = route(logits, bias.value if self.use_bias else 0.0, self.top_k, self.scaling, **scoring)
        with jax.named_scope("moe_dispatch"):
            plan = dispatch(sel, self.offset, self.count)
        with jax.named_scope("moe_experts"):
            ws = tuple(p.astype(self.dtype) for p in ws)
        y = walk(self.body, u, w, ws, plan)
        if self.shared_width:
            shared = [self.param(f"shared_{n}", init, shape(n, self.shared_width), jnp.float32) for n in names]
            with jax.named_scope("moe_shared"):
                shared = [p.astype(self.dtype) for p in shared]
            from_shared = shared_expert(self.body, u, shared).astype(jnp.float32)
            if self.gated_shared:
                from_shared = from_shared * shared_gate(u, self.param("shared_gate", init, (d,), jnp.float32))
            y = y + from_shared
        with jax.named_scope("moe_combine"):
            y = y.astype(x.dtype).reshape(B, S, d)
        return y, {"rows": plan.rows, "overflow": plan.overflow, "buffer_rows": N * plan.chunks,
                   "row_indexed": jnp.ones((), jnp.int32)}

"""The state-space mixers and their scans: Mamba-2's chunked scan (``nemotron_h``)
and, further down, Mamba-1's selective scan with its gated memory unit
(``phi4flash``).

**Mamba-2.** One head of the layer carries a state ``h`` [P, N] (``P`` = head size, ``N``
= state size) along the sequence:

  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

with ``A < 0`` and ``D`` one number a head, ``dt_t > 0`` a number a head and
position, ``x_t`` [P] the head's input and ``B_t``, ``C_t`` [N] shared by the
``H / G`` heads of a group. Written as is, that is ``S`` dependent steps of
elementwise work. ``chunked_scan`` computes the same ``y`` in chunks of ``Q``
positions (the state-space duality of the Mamba-2 paper): inside a chunk the
recurrence unrolls into matrix products,

  y_i = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j  +  exp(a_i) C_i h_in,
  h_out = exp(a_Q) h_in + sum_j exp(a_Q - a_j) dt_j x_j B_j^T,   a_i = sum_{l <= i} dt_l A

(``Q x Q``, ``Q x N`` and ``Q x P`` products on the MXU in ``dtype``, sums in
float32), and only ``h_in -> h_out`` is carried from chunk to chunk, in
float32: ``S / Q`` dependent steps. Every exponent is a sum of ``dt A <= 0``
over a span, so nothing overflows. A sequence whose length ``Q`` does not
divide is padded with ``dt = 0`` positions, which leave the state as it is and
are cut off.

``chunked_scan`` computes that by one of two forms that the platform the
program is lowered for and the shapes choose (``jax.lax.platform_dependent``,
``kernel_takes``; no option). **On a TPU, two Pallas kernels**
(``_scan_kernel``, a ``jax.custom_vjp``; ``mamba2_scan_fwd`` and
``mamba2_scan_bwd`` in a trace): a program instance owns a batch row and one
group's ``H / G`` heads (they share ``B`` and ``C``, so ``C B^T`` is one product
a chunk) and walks the sequence ``CHUNKS_A_STEP`` chunks a grid step, the
group's states in VMEM from the first chunk to the last, transposed and two
heads of 64 side by side (``[N, 2 P]``: one ``128 x 128`` product reads or
writes both). ``x``, ``B``, ``C`` and ``y`` are read and written where they lie
(``[b, S, H P]``, ``[b, S, G N]``); only ``dt`` and ``dt A`` go a head a row. A
chunk's ``Q x Q`` decays and weights never leave the chip. The backward kernel
keeps the five operands and the states each grid step starts from, walks the
sequence from its end, computes a step's chunks again on the chip and carries
the states' cotangent; ``A``'s gradient is the chain rule's through ``dt A``.
**Anywhere else, and at shapes the tiles refuse, ``_scan_xla``**: a group of
heads at a time (``lax.map``), the carry a ``lax.scan`` over the chunks, the
backward pass JAX's own derivative of these products with each group's
intermediates computed again. Both forms round where the other does (products
take ``dtype`` operands and accumulate in float32, the weights are formed in
float32 and cast once, the state and ``y`` are float32).

``Mamba2Mixer`` is the layer around it, as ``nemotron_h`` publishes it:
``[z, xBC, dt] = W_in u``; ``xBC <- silu(causal_conv(xBC) + b)``; ``x, B, C``
from ``xBC``; ``dt <- softplus(dt + dt_bias)``; the scan; ``y <- RMSNorm over
groups of d_inner / G (y * silu(z)) * g``; ``W_out y``. Its operations sit
under two scopes: ``ssm_scan`` (``chunked_scan`` alone) and ``ssm_proj``
(everything else).

**Mamba-1.** A channel of the layer carries a state ``h`` [N] (``N`` = 16):

  h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t      y_t = h_t . C_t + D x_t

with ``A`` [channels, N] negative, ``dt_t`` and ``x_t`` a number a channel and
position and ``B_t``, ``C_t`` [N] shared by every channel: a decay for every
(channel, state) pair, so no span of positions unrolls into a matrix product
as Mamba-2's one decay a head lets it. ``selective_scan`` is ``S`` dependent
steps of elementwise float32 work, on a TPU as two Pallas kernels on the same
walk as the scan above (``chunked_kernel.walk`` / ``kept_starts``;
``mamba1_scan_fwd`` and ``mamba1_scan_bwd`` in a trace) with the ``[N,
channels]`` state on the chip from the first position to the last, anywhere
else ``_s6_xla``, a ``lax.scan`` over chunks; no form holds more than a grid
step's worth of the ``[positions, channels, N]`` history. ``Mamba1Mixer`` is
the layer around it as ``phi4flash`` publishes it (scopes ``mamba1_scan`` for
the scan alone and ``mamba1_proj`` for everything else) and returns the
scan's output before the gate as well: the memory that model's
``GatedMemoryUnit`` layers read.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .chunked_kernel import CHUNKS_A_STEP, F32, NN, NT, TN, kept_starts, mm, padded, rounded, walk
from .sequence import causal_conv, conv_kernel_init, dense

Dtype = Any


def _group_scan(x, dt, A, B, C, dtype: Dtype):
    """One group's heads: ``x`` [b, c, Q, h, P], ``dt`` [b, c, Q, h] float32,
    ``A`` [h], ``B``/``C`` [b, c, Q, N] -> (``y`` [b, c, Q, h, P] float32, the
    last state [b, h, P, N] float32)."""
    Q = x.shape[2]
    a = jnp.cumsum(dt * A, axis=2)                                          # [b, c, Q, h]
    # inside a chunk: position i reads j <= i
    cb = jnp.einsum("bcin,bcjn->bcij", C, B, preferred_element_type=jnp.float32)
    span = a[:, :, :, None] - a[:, :, None, :]                              # [b, c, i, j, h]
    seen = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[:, :, None]
    weight = jnp.exp(jnp.where(seen, span, -jnp.inf)) * dt[:, :, None] * cb[..., None]
    y = jnp.einsum("bcijh,bcjhp->bcihp", weight.astype(dtype), x, preferred_element_type=jnp.float32)

    # what a chunk adds to the state, and the state each chunk starts from
    to_end = jnp.exp(a[:, :, -1:] - a) * dt                                 # exp(a_Q - a_j) dt_j
    added = jnp.einsum("bcjhp,bcjn->bchpn", x * to_end[..., None].astype(dtype), B,
                       preferred_element_type=jnp.float32)
    through = jnp.exp(a[:, :, -1])                                          # [b, c, h]

    def carry(h, chunk_of):
        add, decay = chunk_of
        return decay[..., None, None] * h + add, h

    last, h_in = jax.lax.scan(carry, jnp.zeros_like(added[:, 0]), (added.swapaxes(0, 1), through.swapaxes(0, 1)))
    y = y + jnp.exp(a)[..., None] * jnp.einsum(
        "bcin,cbhpn->bcihp", C, h_in.astype(dtype), preferred_element_type=jnp.float32)
    return y, last


def _scan_xla(x, dt, A, B, C, chunk: int, dtype: Dtype):
    """``chunked_scan`` in plain XLA. One group of ``H / G`` heads at a time
    (``lax.map``), each group's intermediates computed again in its backward
    pass: the ``Q x Q`` decay matrices of every head at once are 0.5 GB a
    layer at 16,384 positions."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    Q = min(chunk, S)
    x, dt, B, C = (padded(t, Q) for t in (x, dt, B, C))
    nc = x.shape[1] // Q
    by_group = lambda t, *rest: jnp.moveaxis(t.reshape(b, nc, Q, G, *rest), 3, 0)
    y, last = jax.lax.map(
        lambda group: jax.checkpoint(functools.partial(_group_scan, dtype=dtype))(*group),
        (by_group(x.astype(dtype), H // G, P), by_group(dt.astype(jnp.float32), H // G),
         A.reshape(G, H // G), by_group(B.astype(dtype), N), by_group(C.astype(dtype), N)))
    y = jnp.moveaxis(y, 0, 3).reshape(b, nc * Q, H, P)[:, :S]               # [G, b, c, Q, h, P] -> [b, S, H, P]
    return y, jnp.moveaxis(last, 0, 1).reshape(b, H, P, N)


# ------------------------------------------------------------- the scan as a kernel
LANES = 128


def _exact(a, b, dims=NN, ones=1):
    """A float32 product one operand of which (``ones``: its index) holds only 0 and 1, at float32's own
    precision: the other goes in as three bfloat16 pieces that add up to it exactly, three passes of the MXU
    where the compiler's ``HIGHEST`` splits both operands and takes six. The sums and transpositions of a chunk's
    per-head vectors are such products."""
    value, pieces = (a, b)[1 - ones].astype(F32), []
    for _ in range(3):
        pieces.append(value.astype(jnp.bfloat16))
        value = value - pieces[-1].astype(F32)
    bits = (a, b)[ones].astype(jnp.bfloat16)
    return sum(mm(bits, piece, dims) if ones == 0 else mm(piece, bits, dims) for piece in pieces)


def _chunk_decays(dt, dta):
    """A chunk's per-head vectors from ``dt`` and ``dt A`` [r, Q] float32 (a head a row, a position a lane), all
    ``r`` heads at once and in both layouts: columns [Q, r] (a position a sublane) come off the rows by a product
    with the identity, the running sum ``a`` by one with the lower triangle, and ``a``'s rows off its columns, so
    that ``a_i - a_i`` is exactly 0."""
    Q = dt.shape[1]
    ii, jj = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), d) for d in (0, 1))
    seen = ii >= jj
    lower, eye = seen.astype(jnp.bfloat16), (ii == jj).astype(jnp.bfloat16)
    a = _exact(lower, dta, NT, 0)                    # [Q, r]: a_i = sum_{l <= i} dt_l A
    total = a[Q - 1:Q]                              # [1, r]
    left = jnp.exp(total - a)                       # exp(a_Q - a_j)
    dt_cols = _exact(eye, dt, NT, 0)
    return dict(seen=seen, lower=lower, eye=eye, dt_rows=dt, a=a, a_rows=_exact(a, eye, TN), left=left,
                to_end=left * dt_cols, decay=jnp.exp(a), through=jnp.exp(total))


def _lane_of(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _wide(cols, t, k, P):
    """``cols`` [rows, r], a head a lane -> [rows, k P]: head ``t k + j``'s column across its own ``P`` lanes of the
    tile that holds ``k`` heads side by side."""
    shape = (cols.shape[0], k * P)
    out = jnp.broadcast_to(cols[:, t * k:t * k + 1], shape)
    for j in range(1, k):
        out = jnp.where(_lane_of(shape) >= j * P, jnp.broadcast_to(cols[:, t * k + j:t * k + j + 1], shape), out)
    return out


def _of_head(tile, j, k, P):
    """A tile of ``k`` heads side by side with every head's lanes but head ``j``'s zeroed: a product with it is
    that head's, and lands in (or reads) its own lanes."""
    lane = _lane_of(tile.shape)
    return jnp.where((lane >= j * P) & (lane < (j + 1) * P), tile, jnp.zeros_like(tile))


def _by_head(t, k, P, r):
    """[k P, r] of 0 and 1: lane ``l`` of tile ``t`` belongs to head ``t k + l // P``. A product with it sums a tile's
    lanes head by head into [.., r]."""
    lane, head = (jax.lax.broadcasted_iota(jnp.int32, (k * P, r), d) for d in (0, 1))
    return ((lane >= (head - t * k) * P) & (lane < (head - t * k + 1) * P)).astype(jnp.bfloat16)


def _weights(ch, cb, h, dtype):
    """Head ``h``'s ``Q x Q`` decays ``exp(a_i - a_j)`` for ``i >= j`` (every exponent a sum of ``dt A <= 0``; the
    mask goes on before the ``exp``) and its weights ``exp(a_i - a_j) dt_j (C_i . B_j)``, formed in float32 and
    rounded once."""
    E = jnp.exp(jnp.where(ch["seen"], ch["a"][:, h:h + 1] - ch["a_rows"][h:h + 1], -jnp.inf))
    return E, (E * ch["dt_rows"][h:h + 1] * cb).astype(dtype)


def _state_after(ch, Bc, x, S, t, k, P, dtype):
    """A tile's states [N, k P] float32 (transposed: the state size on the sublanes, ``k`` heads' ``P`` side by
    side on the lanes, so that one product serves them all) after the chunk."""
    xe = (x.astype(F32) * rounded(_wide(ch["to_end"], t, k, P), dtype)).astype(dtype)
    return _wide(ch["through"], t, k, P) * S + mm(Bc, xe, TN)


def _forward_kernel(x_ref, dt_ref, dta_ref, B_ref, C_ref, y_ref, last_ref, *starts_ref, Q, n, k, P, dtype):
    """One batch row, one group of ``r`` heads (``T = r / k`` tiles of ``k`` heads), ``n`` chunks of the sequence;
    the grid's last axis walks the sequence in order and ``last_ref`` (the same block all along it) carries the
    states. ``starts_ref``, where the backward pass will follow, takes the states this step starts from."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        last_ref[...] = jnp.zeros_like(last_ref)

    if starts_ref:
        starts_ref[0][0, 0, 0] = last_ref[0, 0]
    for c in range(n):
        rows = slice(c * Q, (c + 1) * Q)
        Bc, Cc = B_ref[0, rows], C_ref[0, rows]
        ch, cb = _chunk_decays(dt_ref[0, 0, 0, c], dta_ref[0, 0, 0, c]), mm(Cc, Bc, NT)
        for t in range(last_ref.shape[2]):
            lanes = slice(t * k * P, (t + 1) * k * P)
            x, S = x_ref[0, rows, lanes], last_ref[0, 0, t]
            y = _wide(ch["decay"], t, k, P) * mm(Cc, S.astype(dtype))
            for j in range(k):
                y = y + mm(_weights(ch, cb, t * k + j, dtype)[1], _of_head(x, j, k, P))
            y_ref[0, rows, lanes] = y
            last_ref[0, 0, t] = _state_after(ch, Bc, x, S, t, k, P, dtype)


def _backward_kernel(x_ref, dt_ref, dta_ref, B_ref, C_ref, starts_ref, dy_ref, dlast_ref,
                     dx_ref, ddt_ref, ddta_ref, dB_ref, dC_ref, dS_ref, *, Q, n, k, P, dtype):
    """The same program instance as the forward kernel's, the grid's last axis walking the sequence from its end.
    A step walks its ``n`` chunks' states forward again from the ones the forward kernel left for it, then
    backward through the chunks (each chunk's decays, weights and products computed again, on the chip), and
    ``dS_ref`` carries the states' cotangent towards position 0. ``B`` and ``C`` serve all the group's heads: their
    cotangents are summed over the heads here. Of ``a``'s cotangent, what comes through ``exp(a_i - a_j)`` is the
    row sums less the column sums of ONE matrix (``dW_ij W_ij``): taken from two that round apart (``<dy_i, y_i>``
    would be the row sums) they no longer cancel, and ``A``'s gradient, a sum of their running sums, is what is
    left of them."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_ref[...] = dlast_ref[0, 0]

    T, r = dS_ref.shape[0], dt_ref.shape[4]
    pieces = [slice(c * Q, (c + 1) * Q) for c in range(n)]
    chunks = [_chunk_decays(dt_ref[0, 0, 0, c], dta_ref[0, 0, 0, c]) for c in range(n)]
    states = [[starts_ref[0, 0, 0, t] for t in range(T)]]
    for c in range(n - 1):
        states.append([_state_after(chunks[c], B_ref[0, pieces[c]], x_ref[0, pieces[c], t * k * P:(t + 1) * k * P],
                                    states[c][t], t, k, P, dtype) for t in range(T)])
    head_row = jax.lax.broadcasted_iota(jnp.int32, (r, Q), 0)
    last_row, head_lane = (jax.lax.broadcasted_iota(jnp.int32, (Q, r), 0) == Q - 1), _lane_of((Q, r))
    by_heads = [_by_head(t, k, P, r) for t in range(T)]
    for c in reversed(range(n)):
        rows, ch = pieces[c], chunks[c]
        Bc, Cc = B_ref[0, rows], C_ref[0, rows]
        cb = mm(Cc, Bc, NT)
        d_cb, dB, dC = jnp.zeros((Q, Q), F32), 0.0, 0.0
        seen_by_i, seen_by_j, through_s = jnp.zeros((Q, r), F32), jnp.zeros((Q, r), F32), jnp.zeros((1, r), F32)
        weight_sums = jnp.zeros((r, Q), F32)                  # sum_i dW_ij exp(a_i - a_j) (C_i . B_j), a head a row
        for t in range(T):
            lanes = slice(t * k * P, (t + 1) * k * P)
            x, S, dS, dy = x_ref[0, rows, lanes], states[c][t], dS_ref[t], dy_ref[0, rows, lanes]
            Sb, dSb, dyb = S.astype(dtype), dS.astype(dtype), dy.astype(dtype)
            decay, to_end = _wide(ch["decay"], t, k, P), rounded(_wide(ch["to_end"], t, k, P), dtype)
            xe = (x.astype(F32) * to_end).astype(dtype)
            # y = decay (C Sb) + sum_heads W x;   after = through S + B^T xe
            held = mm(Cc, Sb)
            d_held = (dy * decay).astype(dtype)               # the cotangent of C Sb
            dC = dC + mm(d_held, Sb, NT)
            d_xe = mm(Bc, dSb)
            dB = dB + mm(xe, dSb, NT)
            dx = d_xe * to_end
            for j in range(k):
                h = t * k + j
                E, W = _weights(ch, cb, h, dtype)
                dy_h, dt_row = _of_head(dyb, j, k, P), ch["dt_rows"][h:h + 1]
                dx = dx + mm(W, dy_h, TN)
                dE = mm(dy_h, x, NT) * E                      # dW_ij exp(a_i - a_j)
                d_cb = d_cb + dE * dt_row
                d_span = dE * cb                              # dW_ij W_ij / dt_j
                weight_sums = jnp.where(head_row == h, jnp.sum(d_span, axis=0, keepdims=True), weight_sums)
                seen_by_i = seen_by_i + jnp.where(head_lane == h, jnp.sum(d_span * dt_row, axis=1, keepdims=True), 0.0)
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
            seen_by_i = seen_by_i + _exact(dy * decay * held, by_heads[t])        # a_i under exp(a_i) alone
            seen_by_j = seen_by_j + _exact(d_xe * x.astype(F32), by_heads[t])     # <d_xe_j, x_j>
            through_s = through_s + _exact(jnp.sum(dS * S, axis=0, keepdims=True), by_heads[t])
            dS_ref[t] = _wide(ch["through"], t, k, P) * dS + mm(Cc, d_held, TN)
        d_cb = d_cb.astype(dtype)
        dB_ref[0, rows] = (dB + mm(d_cb, Cc, TN)).astype(dB_ref.dtype)
        dC_ref[0, rows] = (dC + mm(d_cb, Bc)).astype(dC_ref.dtype)
        # a_j enters exp(a_Q - a_j) dt_j (to_end) and, under the weights, -a_j; a_Q enters to_end and exp(a_Q)
        ended = seen_by_j * ch["to_end"]
        at_end = jnp.sum(ended, axis=0, keepdims=True) + through_s * ch["through"]
        da = seen_by_i - ended + jnp.where(last_row, at_end, 0.0)                 # [Q, r]
        da_rows = -ch["dt_rows"] * weight_sums                                    # [r, Q]
        # a_j = sum_{l <= j} (dt A)_l: position l collects every j >= l
        ddta_ref[0, 0, 0, c] = _exact(da, ch["lower"], TN) + _exact(da_rows, ch["lower"])
        ddt_ref[0, 0, 0, c] = weight_sums + _exact(seen_by_j * ch["left"], ch["eye"], TN)


def _kernel_call(kernel, operands, results, b, S, G, r, P, N, Q, reverse, scratch, interpret, name):
    """``chunked_kernel.walk`` over (batch row, group of heads, step of ``CHUNKS_A_STEP`` chunks). Operands and
    results are named by kind: ``heads`` [b, S, H P], ``shared`` [b, S, G N], ``gate`` [b, G, steps, n, r, Q],
    ``state`` [b, G, T, N, k P], ``states`` [b, G, steps, T, N, k P]."""
    n, k = CHUNKS_A_STEP, LANES // P
    T = r // k
    specs = lambda at: {"heads": pl.BlockSpec((1, n * Q, r * P), lambda i, g, j: (i, at(j), g)),
                        "shared": pl.BlockSpec((1, n * Q, N), lambda i, g, j: (i, at(j), g)),
                        "gate": pl.BlockSpec((1, 1, 1, n, r, Q), lambda i, g, j: (i, g, at(j), 0, 0, 0)),
                        "state": pl.BlockSpec((1, 1, T, N, k * P), lambda i, g, j: (i, g, 0, 0, 0)),
                        "states": pl.BlockSpec((1, 1, 1, T, N, k * P), lambda i, g, j: (i, g, at(j), 0, 0, 0))}
    return walk(functools.partial(kernel, Q=Q, n=n, k=k, P=P, dtype=operands[0][1].dtype), specs, operands, results,
                (b, G, S // (n * Q)), reverse, scratch, interpret, name)


def _kernel_forward(x, dt, dta, B, C, chunk: int, interpret: bool, keep_starts: bool):
    """The operands where they lie (heads side by side on the last axis, which is a reshape) but for ``dt`` and
    ``dt A``, which go a head a row (4 bytes a position and head each); the sequence padded to whole steps with
    ``dt = 0``, which leaves the state as it is; the forward kernel, and its results in the caller's layouts."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    Q, n, r, k = chunk, CHUNKS_A_STEP, H // G, LANES // P
    Sp = S + -S % (Q * n)
    steps, T = Sp // (Q * n), r // k
    flat = lambda t: padded(t, Q * n).reshape(b, Sp, -1)
    gate = lambda t: padded(t, Q * n).reshape(b, steps, n, Q, G, r).transpose(0, 4, 1, 2, 5, 3)
    operands = [("heads", flat(x)), ("gate", gate(dt)), ("gate", gate(dta)), ("shared", flat(B)), ("shared", flat(C))]
    shape = jax.ShapeDtypeStruct
    results = [("heads", shape((b, Sp, H * P), F32)), ("state", shape((b, G, T, N, k * P), F32))]
    if keep_starts:
        results.append(("states", shape((b, G, steps, T, N, k * P), F32)))
    out = _kernel_call(_forward_kernel, operands, results, b, Sp, G, r, P, N, Q, False, [], interpret,
                       "mamba2_scan_fwd")
    last = out[1].reshape(b, G, T, N, k, P).transpose(0, 1, 2, 4, 5, 3).reshape(b, H, P, N)
    return (out[0][:, :S].reshape(b, S, H, P), last), ([x for _, x in operands], out[2] if keep_starts else None)


def _scan_kernel_bwd(chunk, interpret, kept, cotangents):
    (x, dt, dta, B, C), starts = kept
    dy, dlast = cotangents
    b, S, H, P = dy.shape
    Sp, (G, _, T, N, tile) = x.shape[1], starts.shape[1:]
    r, k = H // G, tile // P
    operands = [("heads", x), ("gate", dt), ("gate", dta), ("shared", B), ("shared", C), ("states", starts),
                ("heads", jnp.pad(dy.reshape(b, S, H * P), ((0, 0), (0, Sp - S), (0, 0)))),
                ("state", dlast.reshape(b, G, T, k, P, N).transpose(0, 1, 2, 5, 3, 4).reshape(b, G, T, N, tile))]
    shape = jax.ShapeDtypeStruct
    results = [("heads", shape(x.shape, x.dtype)), ("gate", shape(dt.shape, F32)), ("gate", shape(dt.shape, F32)),
               ("shared", shape(B.shape, B.dtype)), ("shared", shape(B.shape, B.dtype))]
    dx, ddt, ddta, dB, dC = _kernel_call(_backward_kernel, operands, results, b, Sp, G, r, P, N, chunk, True,
                                         [pltpu.VMEM((T, N, tile), F32)], interpret, "mamba2_scan_bwd")
    ungate = lambda t: t.transpose(0, 2, 3, 5, 1, 4).reshape(b, Sp, H)[:, :S]      # [b, G, steps, n, r, Q] -> [b, S, H]
    return (dx[:, :S].reshape(b, S, H, P), ungate(ddt), ungate(ddta), dB[:, :S].reshape(b, S, G, N),
            dC[:, :S].reshape(b, S, G, N))


# ``chunked_scan`` as two Pallas kernels, ``(x, dt, dta, B, C, chunk, interpret)``: ``x``/``B``/``C`` in the products'
# dtype, ``dt`` and ``dta`` (``dt A``) float32 (``A``'s gradient is the chain rule's, outside). The backward pass keeps
# the five operands and the states every grid step starts from
_scan_kernel = kept_starts(_kernel_forward, _scan_kernel_bwd)


def kernel_takes(P: int, N: int, G: int, H: int, chunk: int) -> bool:
    """The kernels' tiles: heads of half a lane tile, two side by side in one (heads of a whole tile do not get
    through the TPU compiler as the kernels stand), a group's heads whole tiles, the state whole lane tiles, a
    chunk whole sublane tiles of the products' dtype."""
    return 2 * P == LANES and H % G == 0 and (H // G) % 2 == 0 and N % LANES == 0 and chunk % 16 == 0


def chunked_scan(x, dt, A, B, C, chunk: int, dtype: Dtype = jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``x`` [b, S, H, P], ``dt`` [b, S, H] float32, ``A`` [H] float32,
    ``B``/``C`` [b, S, G, N] -> (``y`` [b, S, H, P] float32 without the
    ``D x`` skip, the state after the last position [b, H, P, N] float32).
    Lowered for a TPU, at shapes its tiles take (``kernel_takes``), the Pallas
    kernels above; anywhere else ``_scan_xla``."""
    xla = lambda *a: _scan_xla(*a, chunk, dtype)
    if not kernel_takes(x.shape[3], B.shape[3], B.shape[2], x.shape[2], chunk):
        return xla(x, dt, A, B, C)

    def kernel(x, dt, A, B, C):
        dt = dt.astype(F32)
        return _scan_kernel(x.astype(dtype), dt, dt * A, B.astype(dtype), C.astype(dtype), chunk, False)

    return jax.lax.platform_dependent(x, dt, A, B, C, tpu=kernel, default=xla)


# ------------------------------------------------------------- Mamba-1: the selective scan
# channels a program instance of the selective scan's kernels owns at the most (lanes: eight tiles of 128; the columns
# of ``B_t`` and ``C_t`` are spread over the lanes once a position however many tiles read them, and 2,048 would not
# fit the backward kernel's states beside its blocks), and positions a chunk. The chip, 2 x 8,192 x 5,120, forward /
# forward + backward, ms (PR 42): 32 x 512 3.5 / 22.3, 16 x 512 4.0 / 24.0, 64 x 512 3.3 / 24.1, 32 x 256 4.1 / 36.7,
# 32 x 1,024 3.5 / 17.0
S6_LANES = 1024
S6_CHUNK = 32


def _s6_lanes(c: int) -> int:
    """The widest block of whole lane tiles, ``S6_LANES`` at the most, that divides ``c`` channels."""
    return max(lanes for lanes in range(LANES, min(c, S6_LANES) + 1, LANES) if c % lanes == 0)


def _s6_xla(x, dt, A, B, C, chunk: int):
    """``selective_scan`` in plain XLA: a ``lax.scan`` over chunks of ``chunk`` positions that carries the state,
    each chunk the literal recurrence (a ``lax.scan`` step a position) under ``jax.checkpoint``, so that forward
    and backward hold one chunk's ``[chunk, b, c, N]`` states and no more."""
    b, S, c = x.shape
    Q = min(chunk, S)
    x, dt, B, C = (padded(t.astype(F32), Q) for t in (x, dt, B, C))       # dt = 0 behind the end: the state stays
    by_chunk = lambda t: t.reshape(b, -1, Q, t.shape[-1]).transpose(1, 2, 0, 3)          # [chunks, Q, b, .]

    @jax.checkpoint
    def one_chunk(h, of_chunk):
        def step(h, at):
            x_t, dt_t, B_t, C_t = at
            h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
            return h, jnp.sum(h * C_t[:, None, :], axis=-1)

        return jax.lax.scan(step, h, of_chunk)

    last, y = jax.lax.scan(one_chunk, jnp.zeros((b, c, A.shape[1]), F32), tuple(by_chunk(t) for t in (x, dt, B, C)))
    return y.transpose(2, 0, 1, 3).reshape(b, -1, c)[:, :S], last        # [chunks, Q, b, c] -> [b, S, c]


def _s6_forward_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, y_ref, last_ref, *starts_ref, Q, n):
    """One batch row, one block of channels (a channel a lane, the state ``[N, lanes]`` float32 with ``N`` on the
    sublanes), ``n`` chunks of ``Q`` positions; the grid's last axis walks the sequence in order and ``last_ref``
    (the same block all along it) carries the state. A position is one step of elementwise work on the state's
    tiles: ``dt_t`` and ``dt_t x_t`` a row across the sublanes, ``B_t`` and ``C_t`` a column across the lanes (they
    come transposed, ``[N, Q]`` a chunk), the sum over ``N`` a sum over sublanes."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        last_ref[...] = jnp.zeros_like(last_ref)

    if starts_ref:
        starts_ref[0][0, 0] = last_ref[0]
    A = A_ref[...]

    def chunk(c, h):
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        x, dt = x_ref[0, rows].astype(F32), dt_ref[0, rows]
        Bt, Ct = B_ref[0, c].astype(F32), C_ref[0, c].astype(F32)
        dtx = dt * x
        for t in range(Q):
            h = jnp.exp(dt[t:t + 1] * A) * h + dtx[t:t + 1] * Bt[:, t:t + 1]
            y_ref[0, pl.ds(c * Q + t, 1)] = jnp.sum(h * Ct[:, t:t + 1], axis=0, keepdims=True)
        return h

    last_ref[0] = jax.lax.fori_loop(0, n, chunk, last_ref[0])


def _s6_backward_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, starts_ref, dy_ref, dlast_ref,
                        dx_ref, ddt_ref, dA_ref, dB_ref, dC_ref, carry_ref, states_ref, *, Q, n):
    """The same program instance as the forward kernel's, the grid's last axis walking the sequence from its end.
    A step walks its ``n Q`` positions forward again from the state the forward kernel left for it, every
    position's state into ``states_ref`` (``[n Q + 1, N, lanes]``, on the chip), then backward: with ``g_t`` the
    state's cotangent (``carry_ref`` carries ``a_{t+1} g_{t+1}`` towards position 0) and ``a_t = exp(dt_t A)``,

      g_t = carry + dy_t C_t^T,   dC_t = h_t^T dy_t,   d(dt x)_t = g_t B_t,   dB_t = g_t^T (dt x)_t,
      d(dt A)_t = g_t * h_{t-1} * a_t,   carry = a_t * g_t.

    ``B`` and ``C`` serve every channel: this block's part of their cotangents goes out transposed, a chunk a tile,
    and the blocks are summed outside; ``A``'s part is summed over the sequence here (``dA_ref``: the same block
    all along it) and over the batch rows outside."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = dlast_ref[0]
        dA_ref[...] = jnp.zeros_like(dA_ref)

    A = A_ref[...]
    states_ref[0] = starts_ref[0, 0]

    def again(c, h):
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        x, dt, Bt = x_ref[0, rows].astype(F32), dt_ref[0, rows], B_ref[0, c].astype(F32)
        dtx = dt * x
        for t in range(Q):
            h = jnp.exp(dt[t:t + 1] * A) * h + dtx[t:t + 1] * Bt[:, t:t + 1]
            states_ref[c * Q + t + 1] = h
        return h

    jax.lax.fori_loop(0, n, again, starts_ref[0, 0])
    lane = jax.lax.broadcasted_iota(jnp.int32, (A.shape[0], Q), 1)

    def back(k, carried):
        g_next, dA = carried
        c = n - 1 - k
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        x, dt, dy = x_ref[0, rows].astype(F32), dt_ref[0, rows], dy_ref[0, rows].astype(F32)
        Bt, Ct = B_ref[0, c].astype(F32), C_ref[0, c].astype(F32)
        dtx = dt * x
        dBt, dCt = jnp.zeros(lane.shape, F32), jnp.zeros(lane.shape, F32)
        for t in reversed(range(Q)):
            h, before = states_ref[c * Q + t + 1], states_ref[c * Q + t]
            g = g_next + dy[t:t + 1] * Ct[:, t:t + 1]
            a = jnp.exp(dt[t:t + 1] * A)
            d_dtx = jnp.sum(g * Bt[:, t:t + 1], axis=0, keepdims=True)              # [1, lanes]
            d_dta = g * before * a
            dCt = jnp.where(lane == t, jnp.sum(h * dy[t:t + 1], axis=1, keepdims=True), dCt)
            dBt = jnp.where(lane == t, jnp.sum(g * dtx[t:t + 1], axis=1, keepdims=True), dBt)
            dx_ref[0, pl.ds(c * Q + t, 1)] = d_dtx * dt[t:t + 1]
            ddt_ref[0, pl.ds(c * Q + t, 1)] = d_dtx * x[t:t + 1] + jnp.sum(d_dta * A, axis=0, keepdims=True)
            dA = dA + d_dta * dt[t:t + 1]
            g_next = a * g
        dB_ref[0, 0, c], dC_ref[0, 0, c] = dBt, dCt
        return g_next, dA

    carry_ref[...], dA = jax.lax.fori_loop(0, n, back, (carry_ref[...], jnp.zeros_like(A)))
    dA_ref[0] += dA


def _s6_call(kernel, operands, results, b, S, c, N, Q, reverse, scratch, interpret, name):
    """``chunked_kernel.walk`` over (batch row, block of channels, step of ``CHUNKS_A_STEP`` chunks). Kinds:
    ``channels`` [b, S, c], ``decay`` [N, c] (``A`` transposed), ``shared`` [b, S / Q, N, Q] (``B``, ``C`` transposed
    a chunk), ``state`` [b, N, c], ``states`` [b, steps, N, c], ``shares`` [b, blocks, S / Q, N, Q]."""
    n, lanes = CHUNKS_A_STEP, _s6_lanes(c)
    specs = lambda at: {"channels": pl.BlockSpec((1, n * Q, lanes), lambda i, g, j: (i, at(j), g)),
                        "decay": pl.BlockSpec((N, lanes), lambda i, g, j: (0, g)),
                        "shared": pl.BlockSpec((1, n, N, Q), lambda i, g, j: (i, at(j), 0, 0)),
                        "state": pl.BlockSpec((1, N, lanes), lambda i, g, j: (i, 0, g)),
                        "states": pl.BlockSpec((1, 1, N, lanes), lambda i, g, j: (i, at(j), 0, g)),
                        "shares": pl.BlockSpec((1, 1, n, N, Q), lambda i, g, j: (i, g, at(j), 0, 0))}
    return walk(functools.partial(kernel, Q=Q, n=n), specs, operands, results, (b, c // lanes, S // (n * Q)),
                reverse, scratch, interpret, name)


def _s6_forward(x, dt, A, B, C, chunk: int, interpret: bool, keep_starts: bool):
    """The sequence padded to whole steps with ``dt = 0``, which leaves the state as it is; ``A``, and ``B`` and
    ``C`` a chunk, transposed (the state lies ``[N, channels]``); the forward kernel."""
    b, S, c = x.shape
    N, Q, n = A.shape[1], chunk, CHUNKS_A_STEP
    x, dt, B, C = (padded(t, Q * n) for t in (x, dt, B, C))
    Sp = x.shape[1]
    by_chunk = lambda t: t.reshape(b, Sp // Q, Q, N).swapaxes(2, 3)
    operands = [("channels", x), ("channels", dt), ("decay", A.T), ("shared", by_chunk(B)), ("shared", by_chunk(C))]
    shape = jax.ShapeDtypeStruct
    results = [("channels", shape((b, Sp, c), F32)), ("state", shape((b, N, c), F32))]
    if keep_starts:
        results.append(("states", shape((b, Sp // (Q * n), N, c), F32)))
    out = _s6_call(_s6_forward_kernel, operands, results, b, Sp, c, N, Q, False, [], interpret, "mamba1_scan_fwd")
    return (out[0][:, :S], out[1].swapaxes(1, 2)), ([t for _, t in operands], out[2] if keep_starts else None)


def _s6_backward(chunk, interpret, kept, cotangents):
    (x, dt, At, Bt, Ct), starts = kept
    dy, dlast = cotangents
    b, Sp, c = x.shape
    S, N, Q, n = dy.shape[1], At.shape[0], chunk, CHUNKS_A_STEP
    lanes = _s6_lanes(c)
    operands = [("channels", x), ("channels", dt), ("decay", At), ("shared", Bt), ("shared", Ct), ("states", starts),
                ("channels", jnp.pad(dy, ((0, 0), (0, Sp - S), (0, 0)))), ("state", dlast.swapaxes(1, 2))]
    shape = jax.ShapeDtypeStruct
    share = ("shares", shape((b, c // lanes, Sp // Q, N, Q), F32))
    results = [("channels", shape(x.shape, F32)), ("channels", shape(x.shape, F32)), ("state", shape((b, N, c), F32)),
               share, share]
    dx, ddt, dA, dB, dC = _s6_call(
        _s6_backward_kernel, operands, results, b, Sp, c, N, Q, True,
        [pltpu.VMEM((N, lanes), F32), pltpu.VMEM((n * Q + 1, N, lanes), F32)], interpret, "mamba1_scan_bwd")
    whole = lambda t, like: t.sum(axis=1).swapaxes(2, 3).reshape(b, Sp, N)[:, :S].astype(like.dtype)
    return dx[:, :S].astype(x.dtype), ddt[:, :S], dA.sum(axis=0).T, whole(dB, Bt), whole(dC, Ct)


# ``selective_scan`` as two Pallas kernels, ``(x, dt, A, B, C, chunk, interpret)``; the backward pass keeps the five
# operands as the kernel is handed them and the state every grid step starts from
_s6_kernel = kept_starts(_s6_forward, _s6_backward)


def s6_kernel_takes(c: int, N: int) -> bool:
    """The kernels' tiles: whole lane tiles of channels, the state size whole sublane tiles."""
    return c % LANES == 0 and N % 8 == 0


def selective_scan(x, dt, A, B, C) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba-1's scan: ``x`` [b, S, c], ``dt`` [b, S, c] float32, ``A`` [c, N] float32 (negative), ``B``/``C``
    [b, S, N] -> (``y`` [b, S, c] float32 without the ``D x`` skip, the state after the last position [b, c, N]
    float32):

      h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t^T  (elementwise over [c, N], h_{-1} = 0),   y_t = h_t C_t

    A decay for every (channel, state) pair: no product form, all of it elementwise work in float32. Lowered for a
    TPU, at shapes its tiles take (``s6_kernel_takes``), two Pallas kernels (``mamba1_scan_fwd`` and
    ``mamba1_scan_bwd`` in a trace) through ``chunked_kernel.walk``, the state on the chip from the first position
    to the last and the backward pass computing a grid step's states again from the one it started from; anywhere
    else ``_s6_xla``, a ``lax.scan`` over chunks. Neither holds more than a step's ``[positions, c, N]``."""
    xla = lambda *a: _s6_xla(*a, S6_CHUNK)
    if not s6_kernel_takes(x.shape[2], A.shape[1]):
        return xla(x, dt, A, B, C)
    kernel = lambda x, dt, A, B, C: _s6_kernel(x, dt.astype(F32), A, B, C, S6_CHUNK, False)
    return jax.lax.platform_dependent(x, dt, A, B, C, tpu=kernel, default=xla)


def state_rms(per_head):
    """One number for a layer's carried state, from the mean square of each
    head's ``[P, N]`` state: the geometric mean over the heads of their RMS.
    The plain RMS is the few heads with the largest ``dt`` (they decay within
    a few positions, so it moves by half a percent with bfloat16's rounding of
    a few hundred numbers and hardly at all when the carry across chunks is
    lost); here every head counts alike, the slow ones that live off the carry
    among them."""
    return jnp.exp(0.5 * jnp.mean(jnp.log(per_head + 1e-30)))


def _dt_bias_init(lo: float, hi: float, floor: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in [lo, hi], not below ``floor``."""
    def init(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus

    return init


class GroupedRMSNormGated(nn.Module):
    """``RMSNorm(y * silu(z))`` over groups of ``group`` channels, times a learned scale; float32."""

    group: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],), jnp.float32)
        t = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        # a group at a time, as slices of the channel axis: a reshape to [.., groups, group] splits the lane axis,
        # which on a TPU is a copy of the tensor each way once a kernel has pinned its layout (PERF.md section 6, PR 40)
        groups = jnp.split(t, t.shape[-1] // self.group, axis=-1)
        g = jnp.concatenate([g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + self.eps) for g in groups], -1)
        return (g * scale).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    """``u`` [B, S, d] -> (the mixer's output [B, S, d], ``state_rms`` of the
    state after the last position). Parameters: ``in_proj`` [d, 2 H P + 2 G N + H],
    ``conv_kernel`` [L, H P + 2 G N] and ``conv_bias``, ``dt_bias``, ``A_log``
    (``A = -exp(A_log)``, drawn as log(1..H)) and ``D`` (ones), one a head,
    ``gated_norm`` [H P], ``out_proj`` [H P, d]."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    conv_bias: bool = True
    dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)   # time_step_min, _max, _floor
    eps: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        Bt, S, d = u.shape
        H, P, G, N = self.heads, self.head_dim, self.groups, self.state
        inner, bc = H * P, G * N
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = jnp.split(dense(2 * inner + 2 * bc + H, self.dtype, "in_proj")(u),
                                   [inner, 2 * inner + 2 * bc], axis=-1)
            kernel = self.param("conv_kernel", conv_kernel_init(self.conv_kernel),
                                (self.conv_kernel, inner + 2 * bc))
            bias = self.param("conv_bias", conv_kernel_init(self.conv_kernel),
                              (inner + 2 * bc,)) if self.conv_bias else None
            x, B, C = jnp.split(nn.silu(causal_conv(xbc, kernel, bias)), [inner, inner + bc], axis=-1)
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_range), (H,))
            A = -jnp.exp(self.param("A_log", lambda key, shape: jnp.log(jnp.arange(1.0, H + 1.0)), (H,)))
            D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
            dt = nn.softplus(dt.astype(jnp.float32) + dt_bias)
        with jax.named_scope("ssm_scan"):
            y, last = chunked_scan(x.reshape(Bt, S, H, P), dt, A, B.reshape(Bt, S, G, N), C.reshape(Bt, S, G, N),
                                   self.chunk, self.dtype)
        with jax.named_scope("ssm_proj"):
            # the skip where the projections' operands lie ([.., H P], a head's D over its P lanes), no head an axis
            y = (y.reshape(Bt, S, inner) + jnp.repeat(D, P) * x.astype(jnp.float32)).astype(self.dtype)
            y = GroupedRMSNormGated(inner // G, self.eps, name="gated_norm")(y, z)
            out = dense(d, self.dtype, "out_proj")(y)
        return out, state_rms(jnp.mean(jnp.square(last), axis=(0, 2, 3)))


class Mamba1Mixer(nn.Module):
    """The Mamba-1 layer as ``phi4flash`` has it: ``[x, z] = W_in u``; ``x <- silu(causal_conv(x) + b)``;
    ``[dt_r, B, C] = W_x x``; ``dt = softplus(W_dt dt_r + dt_bias)``; ``A = -exp(A_log)``; ``y = selective_scan(x,
    dt, A, B, C) + D x``; ``W_out (y silu(z))``. ``u`` [B, S, d] -> (the mixer's output [B, S, d], ``y`` [B, S,
    inner] BEFORE the gate ``silu(z)``: what a later layer's gated memory unit reads, ``state_rms`` of the state
    after the last position, a channel a head). Parameters: ``in_proj`` [d, 2 inner], ``conv_kernel`` [L, inner]
    and ``conv_bias``, ``x_proj`` [inner, dt_rank + 2 N], ``dt_proj`` [dt_rank, inner], ``dt_bias`` [inner],
    ``A_log`` [inner, N] (drawn log(1..N) a channel), ``D`` [inner] (ones), ``out_proj`` [inner, d]. The scan alone
    runs under the scope ``mamba1_scan``, everything else under ``mamba1_proj``."""

    inner: int
    state: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4
    conv_bias: bool = True
    dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)   # time_step_min, _max, _floor
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        inner, N, R = self.inner, self.state, self.dt_rank
        with jax.named_scope("mamba1_proj"):
            x, z = jnp.split(dense(2 * inner, self.dtype, "in_proj")(u), 2, axis=-1)
            kernel = self.param("conv_kernel", conv_kernel_init(self.conv_kernel), (self.conv_kernel, inner))
            bias = self.param("conv_bias", conv_kernel_init(self.conv_kernel), (inner,)) if self.conv_bias else None
            x = nn.silu(causal_conv(x, kernel, bias))
            dt, B, C = jnp.split(dense(R + 2 * N, self.dtype, "x_proj")(x), [R, R + N], axis=-1)
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_range), (inner,))
            dt = nn.softplus(dense(inner, self.dtype, "dt_proj")(dt).astype(jnp.float32) + dt_bias)
            A = -jnp.exp(self.param(
                "A_log", lambda key, shape: jnp.log(jnp.broadcast_to(jnp.arange(1.0, N + 1.0), shape)), (inner, N)))
            D = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        with jax.named_scope("mamba1_scan"):
            y, last = selective_scan(x, dt, A, B, C)
        with jax.named_scope("mamba1_proj"):
            y = (y + D * x.astype(jnp.float32)).astype(self.dtype)
            out = dense(u.shape[-1], self.dtype, "out_proj")(y * nn.silu(z))
        return out, y, state_rms(jnp.mean(jnp.square(last), axis=(0, 2)))


class GatedMemoryUnit(nn.Module):
    """``W_out (M * silu(W_in u))`` (``phi4flash``'s cross-decoder): ``memory`` [B, S, inner] is an earlier
    Mamba-1 layer's pre-gate ``y``, which gates this layer's own projection of ``u`` [B, S, d]. No scan, no
    convolution and no parameters of the state-space kind: ``in_proj`` [d, inner] and ``out_proj`` [inner, d]."""

    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u, memory):
        gate = nn.silu(dense(memory.shape[-1], self.dtype, "in_proj")(u))
        return dense(u.shape[-1], self.dtype, "out_proj")(memory * gate)

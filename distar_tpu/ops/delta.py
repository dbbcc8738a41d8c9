"""Gated DeltaNet: the gated delta rule in chunks, and the layer around it.

One value head carries a state ``S`` [K, V] (``K`` = key head size, ``V`` =
value head size) along the sequence, zero before position 0:

  S'_t = alpha_t S_{t-1}
  S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T          o_t = S_t^T q_t

with ``alpha_t = exp(g_t)`` in (0, 1] the decay and ``beta_t`` in (0, 1) the
strength of the write, a number a head and position each. The transition
``alpha_t (I - beta_t k_t k_t^T)`` is a matrix that depends on the data, so
unlike Mamba-2's diagonal decay (``ops/ssm.py``) the state a chunk adds is not
one product: inside a chunk of ``C`` positions the rows are coupled through a
unit lower-triangular system. With ``gamma_i`` the sum of ``g`` inside the
chunk up to ``i`` and ``Gamma_ij = exp(gamma_i - gamma_j)`` for ``i >= j``
(every exponent <= 0, so nothing overflows):

  L_ij = beta_i (k_i . k_j) Gamma_ij  for i > j,      T = (I + L)^-1
  U = T (beta * V),        W = T (beta * exp(gamma) * K)
  per chunk in order, ``S`` the state it starts from:
    V' = U - W S
    O  = (exp(gamma) * Q) S + tril(Q K^T * Gamma) V'
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) * K)^T V'

``chunked_delta_rule`` computes that, by one of two forms that the platform
the program is lowered for and the shapes choose (``jax.lax.platform_dependent``,
``kernel_takes``; no option). **On a TPU, two Pallas kernels** (``_rule_kernel``,
a ``jax.custom_vjp``): a program instance owns a batch row, a key head and its
value heads and walks the sequence ``CHUNKS_A_STEP`` chunks a grid step, the
heads' states in VMEM from the first chunk to the last; a chunk's ``C x C``
matrices, ``T`` (an exact float32 inverse: substitution on 16 x 16 diagonal
blocks, merged by float32 products: ``_kernel_inverse``), ``U``, ``W`` and
``V'`` never leave the chip. The backward kernel keeps the five inputs and the
state each grid step starts from, walks the sequence from its end, recomputes a
step's chunks on the chip and carries the state's cotangent; its recomputation
is its own (booked as backward time). **Anywhere else ``_rule_xla``**: ``T`` for
every chunk at once, in float32, by XLA's batched triangular solve against the
identity (the other way to it, ``L`` being nilpotent, is ``(I - L)(I + L^2)(I +
L^4)...``, ``log2 C`` squarings and as many products: on the chip it lost in XLA,
72.7 ms a layer forward and backward against 53.5, and again inside the kernel;
PERF.md section 6, PR 36 and PR 37), ``U`` and ``W`` as products in ``dtype``,
then ``S / C`` dependent steps of two products each that carry ``S`` in float32
and emit each chunk's ``V'`` and starting state, and ``O`` from those for all
chunks at once; the backward pass JAX's own derivative of these products and of
the solve, a group of heads at a time (``lax.map``), each group's intermediates
computed again in its backward pass, as ``chunked_scan`` bounds its memory. Both
forms round where the other does (products take ``dtype`` operands and
accumulate in float32, ``T`` is cast after the inverse, the state is float32).
A sequence whose length ``C`` does not divide is padded with positions of ``g =
0``, ``beta = 0``, which leave the state as it is and are cut off.

``GatedDeltaNet`` is the layer, as ``qwen3_next`` publishes it: ``[q k v z] =
W_qkvz u``, ``[b a] = W_ba u``; ``[q k v] <- silu(causal_conv([q k v]))``;
``q <- q / |q| / sqrt(K)``, ``k <- k / |k|`` per head, each q/k head serving
``value_heads / key_heads`` value heads in a row (repeated, not tiled);
``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the rule;
``y = RMSNorm_V(o) * w_o * silu(z)`` per head (the norm first, then the gate:
not ``ops.ssm.GroupedRMSNormGated``, which gates first); ``W_out y``. Its
operations sit under two scopes: ``gdn_scan`` (``chunked_delta_rule`` alone,
the triangular system included) and ``gdn_proj`` (everything else).
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

from .chunked_kernel import CHUNKS_A_STEP, F32, HIGHEST, NT, TN, kept_starts, mm, padded, rounded, walk
from .sequence import causal_conv, conv_kernel_init, dense
from .ssm import _dt_bias_init, state_rms

Dtype = Any


def _inverse(L):
    """``(I + L)^-1`` of strictly lower-triangular ``L`` [..., C, C], in its own dtype: XLA's batched triangular
    solve against the identity."""
    eye = jnp.broadcast_to(jnp.eye(L.shape[-1], dtype=L.dtype), L.shape)
    return jax.lax.linalg.triangular_solve(eye + L, eye, left_side=True, lower=True, unit_diagonal=True)



def _group_rule(q, k, v, g, beta, dtype: Dtype):
    """One group's heads: ``q``/``k`` [b, c, hk, C, K], ``v`` [b, c, h, C, V],
    ``g``/``beta`` [b, c, h, C] float32 -> (``o`` [b, c, h, C, V] float32, the
    last state [b, h, K, V] float32). Value head ``j`` reads key head
    ``j // (h / hk)``."""
    C, r = q.shape[3], v.shape[2] // k.shape[2]
    per_value_head = lambda t: jnp.repeat(t, r, axis=2)
    gamma = jnp.cumsum(g, axis=-1)
    seen = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    Gamma = jnp.exp(jnp.where(seen, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))   # [b, c, h, i, j]
    kk = per_value_head(jnp.einsum("bchid,bchjd->bchij", k, k, preferred_element_type=F32))
    qk = per_value_head(jnp.einsum("bchid,bchjd->bchij", q, k, preferred_element_type=F32))
    T = _inverse(jnp.tril(beta[..., None] * kk * Gamma, -1)).astype(dtype)
    q, k = per_value_head(q), per_value_head(k)
    decay = jnp.exp(gamma)
    U = jnp.einsum("bchij,bchjd->bchid", T, v * beta[..., None].astype(dtype), preferred_element_type=F32)
    W = jnp.einsum("bchij,bchjd->bchid", T, k * (beta * decay)[..., None].astype(dtype),
                   preferred_element_type=F32).astype(dtype)
    k_to_end = k * jnp.exp(gamma[..., -1:] - gamma)[..., None].astype(dtype)   # exp(gamma_C - gamma_j) k_j
    through = jnp.exp(gamma[..., -1])                                           # [b, c, h]

    def carry(S, chunk_of):
        U_c, W_c, k_c, through_c = chunk_of
        new = U_c - jnp.einsum("bhik,bhkv->bhiv", W_c, S.astype(dtype), preferred_element_type=F32)
        added = jnp.einsum("bhik,bhiv->bhkv", k_c, new.astype(dtype), preferred_element_type=F32)
        return through_c[..., None, None] * S + added, (S, new)

    by_chunk = lambda t: t.swapaxes(0, 1)
    start = jnp.zeros((q.shape[0], v.shape[2], q.shape[-1], v.shape[-1]), F32)
    last, (S_in, new) = jax.lax.scan(carry, start, tuple(map(by_chunk, (U, W, k_to_end, through))))
    o = jnp.einsum("bchik,cbhkv->bchiv", q * decay[..., None].astype(dtype), S_in.astype(dtype),
                   preferred_element_type=F32)
    o = o + jnp.einsum("bchij,cbhjv->bchiv", (qk * Gamma).astype(dtype), new.astype(dtype),
                       preferred_element_type=F32)
    return o, last


def _rule_xla(q, k, v, g, beta, chunk: int, dtype: Dtype, groups: int):
    """``chunked_delta_rule`` in plain XLA: ``groups`` groups of ``H / groups`` value heads (and their ``Hk / groups``
    key heads) one after another, each group's intermediates computed again in
    its backward pass: the ``C x C`` matrices, ``U``, ``W`` and every chunk's
    starting state of all 32 heads at once are 1.5 GB a layer at 16,384
    positions, and on the chip eight groups of four were also the fastest
    (PERF.md section 6, PR 36)."""
    b, S, H, V = v.shape
    Hk, K = q.shape[2:]
    groups = math.gcd(groups, Hk)
    C = min(chunk, S)
    q, k, v, g, beta = (padded(t, C) for t in (q, k, v, g, beta))
    nc = q.shape[1] // C

    def by_group(t, heads, *rest):   # [b, S, heads, ...] -> [groups, b, nc, heads / groups, C, ...]
        t = t.reshape(b, nc, C, groups, heads // groups, *rest)
        return jnp.moveaxis(jnp.moveaxis(t, 3, 0), 3, 4)

    o, last = jax.lax.map(
        lambda group: jax.checkpoint(functools.partial(_group_rule, dtype=dtype))(*group),
        (by_group(q.astype(dtype), Hk, K), by_group(k.astype(dtype), Hk, K), by_group(v.astype(dtype), H, V),
         by_group(g.astype(F32), H), by_group(beta.astype(F32), H)))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 4), 0, 3).reshape(b, nc * C, H, V)[:, :S]   # [g, b, c, h, C, V] -> [b, S, H, V]
    return o, jnp.moveaxis(last, 0, 1).reshape(b, H, K, V)


# ------------------------------------------------------------- the rule as a kernel
BLOCK = 16            # the diagonal blocks the kernel's inverse solves by substitution


def _times(x, scale, dtype):
    """``x * scale.astype(dtype)`` in ``dtype``, as the XLA form multiplies: the product of two ``dtype`` values,
    rounded once (computed in float32, where the product of two bfloat16 values is exact)."""
    return (x.astype(F32) * rounded(scale, dtype)).astype(dtype)


def _kernel_inverse(L):
    """``L`` [C, 2 C] float32, two strictly lower-triangular matrices side by side (a chunk of 64 fills the 128
    lanes) -> their ``(I + L)^-1`` side by side, on the chip's own memory. The ``BLOCK x BLOCK`` diagonal blocks by
    forward substitution, all of them at once and compactly ([BLOCK, 2 C]: row ``i`` of every block a sublane; step
    ``s`` takes column ``s`` of a block times row ``s`` of its inverse off the rows below), then pairs of blocks
    merged by ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``: two float32 products a level, the second
    matrix of each laid out block-diagonally ([2 C, 2 C]) so that one product serves both systems."""
    C = L.shape[0]
    ii, ll = (jax.lax.broadcasted_iota(jnp.int32, L.shape, d) for d in (0, 1))
    jj = jnp.where(ll >= C, ll - C, ll)                                   # the column inside its own system
    shift = BLOCK.bit_length() - 1
    same = lambda s: (ii >> s) == (jj >> s)
    diagonal = jnp.where(same(shift), L, 0.0)
    D = sum(diagonal[m:m + BLOCK] for m in range(0, C, BLOCK))            # [BLOCK, 2 C]: the blocks side by side
    i, l = (jax.lax.broadcasted_iota(jnp.int32, D.shape, d) for d in (0, 1))
    first = l >> shift << shift                                           # a block's first lane
    X = (l - first == i).astype(F32)
    for s in range(BLOCK - 1):
        X = X - jnp.take_along_axis(D, first + s, axis=1) * X[s:s + 1]
    X = jnp.where(same(shift), jnp.concatenate([X] * (C // BLOCK), axis=0), 0.0)
    by_system = lambda A: jnp.concatenate([jnp.where(ll < C, A, 0.0), jnp.where(ll >= C, A, 0.0)], axis=0)
    while (1 << shift) < C:
        w = 1 << shift
        below = jnp.where(same(shift + 1) & ~same(shift), L, 0.0)
        # only the second block of every pair of blocks changes: its rows alone are multiplied
        blocks = [X[m:m + w] for m in range(0, C, w)]
        rows = jnp.concatenate(blocks[1::2], axis=0)
        rows = rows - mm(mm(rows, by_system(below), precision=HIGHEST), by_system(X), precision=HIGHEST)
        blocks[1::2] = jnp.split(rows, list(range(w, rows.shape[0], w)))
        X = jnp.concatenate(blocks, axis=0)
        shift += 1
    return X


def _chunk_system(q, k, g, beta):
    """What a chunk computes before it meets the state, from ``q``/``k`` [C, K] and ``g``/``beta`` [1, C] float32
    (rows: a position a lane), but for the inverse. Columns ([C, 1], a position a sublane) come off the rows by a
    masked sum, so nothing is transposed."""
    C = q.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye, seen = ii == jj, ii >= jj
    column = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)
    gamma = jnp.sum(jnp.where(seen, g, 0.0), axis=1, keepdims=True)                      # [C, 1]: cumsum(g)
    gamma_row = jnp.sum(jnp.where(eye, gamma, 0.0), axis=0, keepdims=True)               # [1, C]
    total = gamma[C - 1:C]                                                                # [1, 1]
    Gamma = jnp.where(seen, jnp.exp(jnp.where(seen, gamma - gamma_row, 0.0)), 0.0)
    kk, qk = mm(k, k, NT), mm(q, k, NT)
    beta_c = column(beta)
    return dict(ii=ii, jj=jj, eye=eye, seen=seen, Gamma=Gamma, kk=kk, qk=qk, beta=beta_c,
                L=jnp.where(ii > jj, beta_c * kk * Gamma, 0.0),
                decay=jnp.exp(gamma), to_end=jnp.exp(total - gamma), through=jnp.exp(total))


def _step_systems(qs, ks, g_ref, beta_ref, r, dtype):
    """The systems of a grid step's chunks and value heads (chunk ``c``, head ``h`` at ``c r + h``), inverted two
    at a time."""
    C = qs[0].shape[0]
    systems = [_chunk_system(q, k, g_ref[0, h, 0, c:c + 1], beta_ref[0, h, 0, c:c + 1])
               for c, (q, k) in enumerate(zip(qs, ks)) for h in range(r)]
    for pair in zip(*[iter(systems + systems[-1:] * (len(systems) % 2))] * 2):
        T = _kernel_inverse(jnp.concatenate([sy["L"] for sy in pair], axis=1))
        for sy, T_one in zip(pair, (T[:, :C], T[:, C:])):
            sy.update(T=T_one, Tb=T_one.astype(dtype))
    return systems


def _chunk_with_state(sy, q, k, v, S, dtype):
    """The rest of a chunk's forward pass: ``S`` [K, V] float32 the state it starts from."""
    vb, kb = _times(v, sy["beta"], dtype), _times(k, sy["beta"] * sy["decay"], dtype)
    U, W = mm(sy["Tb"], vb), mm(sy["Tb"], kb).astype(dtype)
    Sb = S.astype(dtype)
    new = (U - mm(W, Sb)).astype(dtype)
    k_to_end, q_decayed = _times(k, sy["to_end"], dtype), _times(q, sy["decay"], dtype)
    within = (sy["qk"] * sy["Gamma"]).astype(dtype)
    o = mm(q_decayed, Sb) + mm(within, new)
    after = sy["through"] * S + mm(k_to_end, new, TN)
    return o, after, dict(vb=vb, kb=kb, W=W, Sb=Sb, new=new, k_to_end=k_to_end, q_decayed=q_decayed, within=within)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, last_ref, *starts_ref, C, n, r, V, dtype):
    """One batch row, one key head and its ``r`` value heads, ``n`` chunks of the sequence; the grid's last axis
    walks the sequence in order and ``last_ref`` (the same block all along it) carries the states. ``starts_ref``,
    where the backward pass will follow, takes the states this step starts from."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        last_ref[...] = jnp.zeros_like(last_ref)

    if starts_ref:
        starts_ref[0][0, :, 0] = last_ref[0]
    pieces = [slice(c * C, (c + 1) * C) for c in range(n)]
    qs, ks = [q_ref[0, rows] for rows in pieces], [k_ref[0, rows] for rows in pieces]
    systems = _step_systems(qs, ks, g_ref, beta_ref, r, dtype)
    for c, rows in enumerate(pieces):
        for h in range(r):
            heads = slice(h * V, (h + 1) * V)
            o_ref[0, rows, heads], last_ref[0, h], _ = _chunk_with_state(
                systems[c * r + h], qs[c], ks[c], v_ref[0, rows, heads], last_ref[0, h], dtype)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref, dlast_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dS_ref, *, C, n, r, V, dtype):
    """The same program instance as the forward kernel's, the grid's last axis walking the sequence from its end.
    A step walks its ``n`` chunks forward again from the state the forward kernel left for it (each chunk's system
    and products stay on the chip), then backward through them, and ``dS_ref`` carries the state's cotangent
    towards position 0."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_ref[...] = dlast_ref[0]

    row_sum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    pieces = [slice(c * C, (c + 1) * C) for c in range(n)]
    qs, ks = [q_ref[0, rows] for rows in pieces], [k_ref[0, rows] for rows in pieces]
    dq, dk = [0.0] * n, [0.0] * n
    systems = _step_systems(qs, ks, g_ref, beta_ref, r, dtype)
    for h in range(r):
        heads = slice(h * V, (h + 1) * V)
        walked, S = [], starts_ref[0, h, 0]
        for c, rows in enumerate(pieces):
            sy = systems[c * r + h]
            _, after, fw = _chunk_with_state(sy, qs[c], ks[c], v_ref[0, rows, heads], S, dtype)
            walked.append((sy, fw, S))
            S = after
        for c in reversed(range(n)):
            (sy, fw, S), rows, q, k, dS = walked[c], pieces[c], qs[c], ks[c], dS_ref[h]
            qf, kf = q.astype(F32), k.astype(F32)
            T, Tb, Gamma, kk, qk, beta, decay, to_end = (
                sy[x] for x in ("T", "Tb", "Gamma", "kk", "qk", "beta", "decay", "to_end"))
            do, dSb = do_ref[0, rows, heads].astype(dtype), dS.astype(dtype)
            # o = q_decayed Sb + within new;  after = through S + k_to_end^T new;  new = U - W Sb
            d_q_decayed, d_within = mm(do, fw["Sb"], NT), mm(do, fw["new"], NT)
            d_new = (mm(fw["within"], do, TN) + mm(fw["k_to_end"], dSb)).astype(dtype)
            d_k_to_end = mm(fw["new"], dSb, NT)
            d_W = (-mm(d_new, fw["Sb"], NT)).astype(dtype)
            dS_ref[h] = sy["through"] * dS + mm(fw["q_decayed"], do, TN) - mm(fw["W"], d_new, TN)
            d_through = jnp.sum(row_sum(dS * S), axis=0, keepdims=True)                   # [1, 1]
            # U = Tb vb;  W = Tb kb;  T = (I + L)^-1;  L = beta kk Gamma below the diagonal
            d_T = mm(d_new, fw["vb"], NT) + mm(d_W, fw["kb"], NT)
            d_vb, d_kb = mm(Tb, d_new, TN), mm(Tb, d_W, TN)
            d_L = jnp.where(sy["ii"] > sy["jj"],
                            -mm(mm(T, d_T, TN, precision=HIGHEST), T, NT, precision=HIGHEST), 0.0)
            d_L_Gamma = d_L * Gamma
            d_kk, d_qk = (d_L_Gamma * beta).astype(dtype), (d_within * Gamma).astype(dtype)
            d_exponent = d_L_Gamma * beta * kk + d_within * Gamma * qk      # the cotangent of gamma_i - gamma_j
            on_k, on_q = row_sum(d_kb * kf), row_sum(d_q_decayed * qf)
            ended = row_sum(d_k_to_end * kf) * to_end
            dk[c] = dk[c] + (mm(d_kk, k) + mm(d_kk, k, TN) + mm(d_qk, q, TN)
                             + d_kb * rounded(beta * decay, dtype)
                             + d_k_to_end * rounded(to_end, dtype))
            dq[c] = dq[c] + mm(d_qk, k) + d_q_decayed * rounded(decay, dtype)
            dv_ref[0, rows, heads] = (d_vb * rounded(beta, dtype)).astype(dv_ref.dtype)
            d_beta = row_sum(d_L_Gamma * kk) + row_sum(d_vb * v_ref[0, rows, heads].astype(F32)) + decay * on_k
            to_the_left = jnp.sum(jnp.where(sy["eye"], jnp.sum(d_exponent, axis=0, keepdims=True), 0.0), axis=1,
                                  keepdims=True)                                         # the column sums, as a column
            d_gamma = row_sum(d_exponent) - to_the_left + (beta * on_k + on_q) * decay - ended
            at_end = jnp.sum(ended, axis=0, keepdims=True) + d_through * sy["through"]
            d_gamma = d_gamma + jnp.where(sy["ii"][:, :1] == C - 1, at_end, 0.0)
            dg_ref[0, h, 0, c:c + 1] = jnp.sum(jnp.where(sy["seen"], d_gamma, 0.0), axis=0, keepdims=True)
            dbeta_ref[0, h, 0, c:c + 1] = jnp.sum(jnp.where(sy["eye"], d_beta, 0.0), axis=0, keepdims=True)
    for c, rows in enumerate(pieces):
        dq_ref[0, rows], dk_ref[0, rows] = dq[c].astype(dq_ref.dtype), dk[c].astype(dk_ref.dtype)


def _kernel_call(kernel, operands, results, b, S, Hk, r, K, V, C, reverse, scratch, interpret, name):
    """``chunked_kernel.walk`` over (batch row, key head, step of ``CHUNKS_A_STEP`` chunks). Operands and results are
    named by kind: ``key`` [b, S, Hk K], ``value`` [b, S, H V], ``gate`` [b, H, steps, n, C], ``state`` [b, H, K, V],
    ``states`` [b, H, steps, K, V]."""
    n = CHUNKS_A_STEP
    specs = lambda at: {"key": pl.BlockSpec((1, n * C, K), lambda i, h, j: (i, at(j), h)),
                        "value": pl.BlockSpec((1, n * C, r * V), lambda i, h, j: (i, at(j), h)),
                        "gate": pl.BlockSpec((1, r, 1, n, C), lambda i, h, j: (i, h, at(j), 0, 0)),
                        "state": pl.BlockSpec((1, r, K, V), lambda i, h, j: (i, h, 0, 0)),
                        "states": pl.BlockSpec((1, r, 1, K, V), lambda i, h, j: (i, h, at(j), 0, 0))}
    return walk(functools.partial(kernel, C=C, n=n, r=r, V=V, dtype=operands[0][1].dtype), specs, operands, results,
                (b, Hk, S // (n * C)), reverse, scratch, interpret, name)


def _kernel_forward(q, k, v, g, beta, chunk: int, interpret: bool, keep_starts: bool):
    """The operands in the kernel's layouts (the sequence padded to whole steps with ``g = 0``, ``beta = 0``, which
    leave the state as it is; heads side by side on the last axis, which is a reshape; ``g``/``beta`` a head a row,
    which moves 2 x 4 bytes a position and head), the forward kernel, and its results in the caller's."""
    b, S, H, V = v.shape
    Hk, K = q.shape[2:]
    C, r, step = chunk, H // Hk, chunk * CHUNKS_A_STEP
    Sp = S + -S % step
    flat = lambda t: padded(t, step).reshape(b, Sp, -1)
    gate = lambda t: padded(t.astype(F32), step).swapaxes(1, 2).reshape(b, H, Sp // step, CHUNKS_A_STEP, C)
    operands = [("key", flat(q)), ("key", flat(k)), ("value", flat(v)), ("gate", gate(g)), ("gate", gate(beta))]
    shape = jax.ShapeDtypeStruct
    results = [("value", shape((b, Sp, H * V), F32)), ("state", shape((b, H, K, V), F32))]
    if keep_starts:
        results.append(("states", shape((b, H, Sp // step, K, V), F32)))
    out = _kernel_call(_forward_kernel, operands, results, b, Sp, Hk, r, K, V, C, False, [], interpret,
                       "gated_delta_rule_fwd")
    return (out[0][:, :S].reshape(b, S, H, V), out[1]), ([x for _, x in operands], out[2] if keep_starts else None)


def _rule_kernel_bwd(chunk, interpret, kept, cotangents):
    (q, k, v, g, beta), starts = kept
    do, dlast = cotangents
    b, Sp = q.shape[:2]
    H, K, V = starts.shape[1], starts.shape[3], starts.shape[4]
    Hk, S = q.shape[2] // K, do.shape[1]
    do = do.reshape(b, S, H * V)
    operands = [("key", q), ("key", k), ("value", v), ("gate", g), ("gate", beta), ("states", starts),
                ("value", jnp.pad(do, ((0, 0), (0, Sp - S), (0, 0)))), ("state", dlast)]
    shape = jax.ShapeDtypeStruct
    results = [("key", shape(q.shape, q.dtype)), ("key", shape(q.shape, q.dtype)), ("value", shape(v.shape, v.dtype)),
               ("gate", shape(g.shape, F32)), ("gate", shape(g.shape, F32))]
    dq, dk, dv, dg, dbeta = _kernel_call(_backward_kernel, operands, results, b, Sp, Hk, H // Hk, K, V, chunk, True,
                                         [pltpu.VMEM((H // Hk, K, V), F32)], interpret, "gated_delta_rule_bwd")
    heads = lambda t, n: t[:, :S].reshape(b, S, n, -1)
    ungate = lambda t: t.reshape(b, H, Sp).swapaxes(1, 2)[:, :S]
    return heads(dq, Hk), heads(dk, Hk), heads(dv, H), ungate(dg), ungate(dbeta)


# ``chunked_delta_rule`` as two Pallas kernels, ``(q, k, v, g, beta, chunk, interpret)``: ``q``/``k``/``v`` in the
# products' dtype, ``g``/``beta`` float32. The backward pass keeps the five operands and the state every grid step
# starts from
_rule_kernel = kept_starts(_kernel_forward, _rule_kernel_bwd)


def kernel_takes(K: int, V: int, chunk: int) -> bool:
    """The kernels' tiles: a head a lane tile or several, a chunk whole ``BLOCK``s (and whole bfloat16 sublane
    tiles), two chunks' systems side by side within the 128 lanes."""
    return K % 128 == 0 and V % 128 == 0 and chunk % BLOCK == 0 and 2 * chunk <= 128


def chunked_delta_rule(q, k, v, g, beta, chunk: int = 64, dtype: Dtype = jnp.float32,
                       groups: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``q``/``k`` [b, S, Hk, K] (normalised and scaled by the caller), ``v``
    [b, S, H, V], ``g``/``beta`` [b, S, H] float32 -> (``o`` [b, S, H, V]
    float32, the state after the last position [b, H, K, V] float32). Lowered
    for a TPU, at shapes its tiles take (``kernel_takes``), the Pallas kernels
    above; anywhere else ``_rule_xla``, which alone reads ``groups``."""
    xla = lambda *a: _rule_xla(*a, chunk, dtype, groups)
    if not kernel_takes(q.shape[-1], v.shape[-1], chunk):
        return xla(q, k, v, g, beta)
    kernel = lambda q, k, v, g, beta: _rule_kernel(q.astype(dtype), k.astype(dtype), v.astype(dtype), g.astype(F32),
                                                   beta.astype(F32), chunk, False)
    return jax.lax.platform_dependent(q, k, v, g, beta, tpu=kernel, default=xla)


def _l2_normalised(x, eps: float = 1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def output_gate(o, z, scale, eps: float):
    """``RMSNorm(o) * scale * silu(z)`` over the last axis (a head), float32: the norm first, then the gate."""
    o, z = o.astype(F32), z.astype(F32)
    return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * scale * nn.silu(z)


class GatedDeltaNet(nn.Module):
    """``u`` [B, S, d] -> (the mixer's output [B, S, d], stats: ``state_rms``
    (``ops.ssm.state_rms`` over the value heads' last states) and
    ``decay_mean`` (the mean ``alpha`` over heads and positions: a run that
    drops the decay reads 1)). Parameters: ``in_proj_qkvz`` [d, 2 Hk K + 2 H V],
    ``in_proj_ba`` [d, 2 H], ``conv_kernel`` [L, 2 Hk K + H V] (no bias),
    ``dt_bias`` and ``A_log`` one a value head, ``out_norm`` [V] (ones),
    ``out_proj`` [H V, d]. ``A = exp(A_log)`` is drawn uniform in (0, 16) and
    ``softplus(dt_bias)`` log-uniform in ``dt_range``, as the linear-attention
    library the published code follows draws them: the decays then spread from
    a few positions to thousands (docs/token_models.md)."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: Dtype = jnp.float32
    dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)   # softplus(dt_bias): least, largest, floor
    groups: int = 8

    @nn.compact
    def __call__(self, u):
        Bt, S, d = u.shape
        Hk, H, K, V = self.key_heads, self.value_heads, self.key_dim, self.value_dim
        qk_width, v_width = Hk * K, H * V
        with jax.named_scope("gdn_proj"):
            qkv, z = jnp.split(dense(2 * qk_width + 2 * v_width, self.dtype, "in_proj_qkvz")(u),
                               [2 * qk_width + v_width], axis=-1)
            b, a = jnp.split(dense(2 * H, self.dtype, "in_proj_ba")(u).astype(F32), 2, axis=-1)
            kernel = self.param("conv_kernel", conv_kernel_init(self.conv_kernel),
                                (self.conv_kernel, 2 * qk_width + v_width))
            q, k, v = jnp.split(nn.silu(causal_conv(qkv, kernel)), [qk_width, 2 * qk_width], axis=-1)
            q = _l2_normalised(q.reshape(Bt, S, Hk, K)) * K ** -0.5
            k = _l2_normalised(k.reshape(Bt, S, Hk, K))
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_range), (H,))
            A_log = self.param("A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, F32, 1e-4, 16.0)),
                               (H,))
            g = -jnp.exp(A_log) * nn.softplus(a + dt_bias)
            beta = jax.nn.sigmoid(b)
        with jax.named_scope("gdn_scan"):
            o, last = chunked_delta_rule(q, k, v.reshape(Bt, S, H, V), g, beta, self.chunk, self.dtype, self.groups)
        with jax.named_scope("gdn_proj"):
            scale = self.param("out_norm", nn.initializers.ones, (V,), F32)
            y = output_gate(o, z.reshape(Bt, S, H, V), scale, self.eps).astype(self.dtype)
            out = dense(d, self.dtype, "out_proj")(y.reshape(Bt, S, v_width))
        return out, {"state_rms": state_rms(jnp.mean(jnp.square(last), axis=(0, 2, 3))),
                     "decay_mean": jnp.mean(jnp.exp(g))}

"""Operations and bytes the attention kernel of a latent-attention step
requires, from shapes: what its roofline share divides its time into
(``readers/kernel_roofline_mla.py``). Recomputation is not required work and
is not counted, and neither is what a kernel pads a head size to.
"""
from __future__ import annotations

from typing import Dict


def causal_core(positions: float, seq_len: int, heads: int, qk_dim: int, v_dim: int, layers: int,
                bytes_per_value: int = 2) -> Dict[str, float]:
    """Causal attention of ``layers`` layers over ``positions`` positions in
    sequences of ``seq_len``, ``heads`` heads whose scores are over ``qk_dim``
    dimensions and whose values are ``v_dim`` wide, forward and backward.

    FLOPs, a position and layer, forward: ``2 heads qk_dim (S / 2)`` for the
    scores and ``2 heads v_dim (S / 2)`` for the values, over the ``S / 2``
    keys a causal query sees; twice that backward (dV and dP from the output's
    gradient, dQ and dK from the scores'; the backward kernels' own
    recomputation of the scores is not required work). Bytes, at the least:
    forward reads q and k (``heads qk_dim`` each) and v and writes o (``heads
    v_dim`` each); backward reads the four and the output's gradient and
    writes the gradients of q, k and v. Scores and probabilities are counted
    as staying on the chip."""
    flops = 3.0 * 2.0 * heads * (qk_dim + v_dim) * seq_len / 2.0
    forward = heads * (2 * qk_dim + 2 * v_dim)                        # q, k | v, o
    backward = heads * ((2 * qk_dim + 3 * v_dim) + (2 * qk_dim + v_dim))  # q, k, v, o, do in | dq, dk, dv out
    values = forward + backward
    return {"flops": positions * layers * flops, "bytes": positions * layers * bytes_per_value * values}

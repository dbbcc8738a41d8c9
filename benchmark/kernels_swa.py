"""Operations and bytes the banded (sliding-window) attention kernel of a
step requires, from shapes: what its roofline share divides its time into
(``readers/kernel_roofline_swa.py``). Recomputation is not required work and
is not counted, and neither is what a kernel computes of a key block that its
band only touches.
"""
from __future__ import annotations

from typing import Dict

from benchmark.flops_laguna import band_keys


def banded_core(positions: float, seq_len: int, window: int, heads: int, kv_heads: int, head_dim: int, layers: int,
                bytes_per_value: int = 2) -> Dict[str, float]:
    """Causal attention under a band of ``window`` keys, ``layers`` layers over
    ``positions`` positions in sequences of ``seq_len``, ``heads`` query heads
    of ``head_dim`` over ``kv_heads`` key/value heads, forward and backward.

    FLOPs, a position and layer, forward: ``2 heads head_dim band`` for the
    scores and as much for the values, over the ``band_keys`` a query sees on
    average (``window``, less what the first queries of a sequence lack); twice
    that backward (dV and dP from the output's gradient, dQ and dK from the
    scores'; the backward kernels' own recomputation of the scores is not
    required work). Bytes, at the least: forward reads q, k and v and writes
    o; backward reads the four and the output's gradient and writes the
    gradients of q, k and v: six tensors of ``heads head_dim`` a position and
    six of ``kv_heads head_dim`` (a key/value head is read once, not once a
    query head of its group). Scores and probabilities are counted as staying
    on the chip."""
    flops = 3.0 * 4.0 * heads * head_dim * band_keys(seq_len, window)
    values = 6.0 * head_dim * (heads + kv_heads)       # q, o | q, o, do, dq ; k, v | k, v, dk, dv
    return {"flops": positions * layers * flops, "bytes": positions * layers * bytes_per_value * values}

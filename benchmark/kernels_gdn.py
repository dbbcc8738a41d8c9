"""Operations and bytes the gated delta rule of a ``qwen3_next`` step
requires, from shapes: what its roofline share divides its time into
(``readers/kernel_roofline_gdn.py``). Recomputation is not required work and
is not counted, and neither is what a chunked form spends beyond the
recurrence (the products inside a chunk, the triangular system).
"""
from __future__ import annotations

from typing import Dict


def delta_rule(positions: float, key_heads: int, value_heads: int, key_dim: int, value_dim: int,
               layers: int, bytes_per_value: int = 2) -> Dict[str, float]:
    """The gated delta rule of ``layers`` Gated DeltaNet layers over
    ``positions`` positions each, forward and backward.

    FLOPs, a position, value head and layer, forward: ``2 K V`` each for the
    state read (``S'^T k``), the rank-one write (``k (v - .)^T``) and the
    read-out (``S^T q``); twice that backward.
    Bytes, at the least: forward reads ``q`` and ``k`` (``Hk K`` each), ``v``
    (``H V``), ``g`` and ``beta`` (``H`` each, float32) and writes ``o``
    (``H V``); backward reads ``o``'s gradient and the five inputs again and
    writes their gradients. The state (``H K V`` float32) is counted as
    staying on the chip."""
    Hk, H, K, V = key_heads, value_heads, key_dim, value_dim
    flops = 3.0 * 6.0 * K * V * H
    values = (2 * Hk * K + 2 * H * V) + (4 * Hk * K + 3 * H * V)         # forward; backward
    return {"flops": positions * layers * flops,
            "bytes": positions * layers * (bytes_per_value * values + 4.0 * 6 * H)}

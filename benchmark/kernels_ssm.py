"""Operations and bytes the kernels of a ``nemotron_h`` step require, from
shapes: what a kernel's roofline share divides its time into
(``readers/kernel_roofline_ssm.py``). Recomputation is not required work and
is not counted.
"""
from __future__ import annotations

from typing import Dict


def chunked_scan(positions: float, heads: int, head_dim: int, groups: int, state: int, chunk: int,
                 layers: int, bytes_per_value: int = 2) -> Dict[str, float]:
    """The state-space scan of ``layers`` Mamba-2 layers over ``positions``
    positions each, in chunks of ``chunk``, forward and backward.

    FLOPs, a position and layer, forward: ``2 Q N G`` (``C_i . B_j`` inside
    the chunk) + ``2 Q P H`` (the chunk's own positions into ``y``) +
    ``4 P N H`` (into the carried state and out of it); twice that backward.
    Bytes, at the least: forward reads ``x`` (``H P``), ``B`` and ``C``
    (``2 G N``) and ``dt`` (``H``, float32) and writes ``y`` (``H P``);
    backward reads ``y``'s gradient and the four inputs again and writes
    their gradients. The ``Q x Q`` decay matrices and the carried state
    (``H P N`` float32 a chunk) are counted as staying on the chip."""
    H, P, G, N, Q = heads, head_dim, groups, state, chunk
    flops = 3.0 * (2.0 * Q * N * G + 2.0 * Q * P * H + 4.0 * P * N * H)
    values = (2 * H * P + 2 * G * N) + (3 * H * P + 2 * 2 * G * N)      # forward; backward
    return {"flops": positions * layers * flops,
            "bytes": positions * layers * (bytes_per_value * values + 4.0 * 3 * H)}


def grouped_relu2(rows: float, d: int, width: int, experts: int, layers: int,
                  bytes_per_value: int = 2) -> Dict[str, float]:
    """The grouped squared-ReLU experts of ``layers`` expert layers over
    ``rows`` rows in all (every layer's rows together), forward and backward.

    FLOPs: two products a row forward (``d x width``, ``width x d``), and
    twice that backward (the rows' gradient and the weights'): 12 rows d width.
    Bytes, at the least, as ``kernels_lm.grouped_swiglu`` counts them with two
    matrices an expert: forward reads the rows and writes the result
    (``2 rows d``) and reads each held expert's matrices once; backward reads
    the result's gradient and the rows and writes the rows' gradient
    (``3 rows d``), reads the matrices again and writes their gradients."""
    weights = 2.0 * experts * d * width * layers
    return {"flops": 12.0 * rows * d * width,
            "bytes": bytes_per_value * (5.0 * rows * d + 3.0 * weights)}

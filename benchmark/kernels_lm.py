"""Operations and bytes the kernels of the token-sequence step require, from
shapes: what a kernel's roofline share divides its time into. Recomputation
is not required work and is not counted.
"""
from __future__ import annotations

from typing import Dict


def grouped_swiglu(rows: float, d: int, width: int, experts: int, layers: int,
                   bytes_per_value: int = 2) -> Dict[str, float]:
    """The grouped SwiGLU of ``layers`` expert layers over ``rows`` rows in
    all (every layer's rows together), forward and backward.

    FLOPs: three products a row forward (``d x width`` twice, ``width x d``),
    and twice that backward (the rows' gradient and the weights'): 18 rows d width.
    Bytes, at the least: forward reads the rows and writes the result
    (``2 rows d``), and reads each held expert's three matrices once; backward
    reads the result's gradient and the rows and writes the rows' gradient
    (``3 rows d``), reads the matrices again and writes their gradients. The
    hidden activations are counted as staying on the chip."""
    weights = 3.0 * experts * d * width * layers
    return {"flops": 18.0 * rows * d * width,
            "bytes": bytes_per_value * (5.0 * rows * d + 3.0 * weights)}

"""Operations and bytes the kernels of a ``phi4flash`` step require, from
shapes: what a kernel's roofline share divides its time into
(``readers/kernel_roofline_phi4flash.py``). Recomputation is not required work
and is not counted, and neither is what a kernel computes of a key block that
its band only touches.
"""
from __future__ import annotations

from typing import Dict

from benchmark.flops_laguna import band_keys


def selective_scan(positions: float, channels: int, state: int, layers: int,
                   bytes_per_value: int = 2) -> Dict[str, float]:
    """Mamba-1's selective scan of ``layers`` layers over ``positions`` positions each, forward and backward.

    FLOPs: 0. The scan is elementwise work over ``[channels, state]`` (an exponential, three multiplies and two
    adds a (channel, state) pair and position forward), none of it a matrix product, and the chip's published
    peaks hold no vector-unit figure to divide it by. So the bound is the bytes', and the share reads the scan's
    distance from the speed of the chip's memory, whatever implements it. Bytes, at the least: forward reads
    ``x`` and ``dt`` and writes ``y`` (``channels`` each), and reads ``B`` and ``C`` (``state`` each); backward
    reads ``y``'s gradient and the four inputs again and writes the four gradients: ``8 channels + 6 state``
    values a position and layer. The state's history (``[positions, channels, state]`` float32) and the
    backward pass's states computed again are counted as staying on the chip."""
    values = (3 * channels + 2 * state) + (3 * channels + 2 * state) + (2 * channels + 2 * state)
    return {"flops": 0.0, "bytes": positions * layers * bytes_per_value * values}


def _core(positions: float, keys: float, heads: int, kv_heads: int, head_dim: int, layers: int,
          bytes_per_value: int) -> Dict[str, float]:
    """``heads`` maps (two a pair) with scores over ``head_dim`` and a value ``2 head_dim`` wide over ``keys`` keys
    a query (every layer reads a key/value tensor and returns its gradient, whoever projected it). FLOPs, a position and layer, forward: ``2 heads head_dim
    keys`` for the scores and ``2 heads 2 head_dim keys`` for the values; twice that backward (the backward
    kernels' own recomputation of the scores is not required work). Bytes, at the least: forward reads q
    (``heads head_dim``), k and v (``kv_heads head_dim`` each: a key head once and not once a query head, a
    pair's value once and not once a map) and writes the maps' outputs (``heads 2 head_dim``); backward reads the
    four and the outputs' gradient and writes the gradients of q, k and v."""
    flops = 3.0 * heads * 6.0 * head_dim * keys
    q, kv, o = heads * head_dim, 2 * kv_heads * head_dim, heads * 2 * head_dim
    values = (q + kv + o) + (q + kv + 2 * o) + (q + kv)
    return {"flops": positions * layers * flops, "bytes": positions * layers * bytes_per_value * values}


def diff_core(positions: float, seq_len: int, heads: int, kv_heads: int, head_dim: int, layers: int,
              bytes_per_value: int = 2) -> Dict[str, float]:
    """The differential attention cores over every key up to the query's own (``seq_len / 2`` keys a causal query
    on average), forward and backward: the full layer's and the cross layers' together."""
    return _core(positions, seq_len / 2.0, heads, kv_heads, head_dim, layers, bytes_per_value)


def diff_banded_core(positions: float, seq_len: int, window: int, heads: int, kv_heads: int, head_dim: int,
                     layers: int, bytes_per_value: int = 2) -> Dict[str, float]:
    """The differential attention cores under a band of ``window`` keys (the FLOPs of the band ALONE:
    ``flops_laguna.band_keys`` keys a query on average), forward and backward."""
    return _core(positions, band_keys(seq_len, window), heads, kv_heads, head_dim, layers, bytes_per_value)

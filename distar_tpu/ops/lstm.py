"""LSTM cells and a stacked scan-based runner.

Fills the role of the reference's TorchScript LN-LSTM core
(reference: distar/agent/default/model/lstm.py: LSTMCell :69-93,
LayerNormLSTMCell :120+, StackedLSTM). TPU-first design: execution is
LAYER-MAJOR — per layer, the input projection for ALL timesteps is one
big [T*B, D] x [D, 4H] matmul on the MXU (the cuDNN-style split), and
only the small recurrent [B, H] x [H, 4H] matmul + gate pointwise stays
inside the `lax.scan` over time. Identical parameters and numerics to the
step-per-layer formulation (`tests/test_ops.py` holds it to a time-major
loop over `_step`). State layout is a tuple of (h, c) pairs, one per
layer, each [B, hidden].
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any
LSTMState = Tuple[jnp.ndarray, jnp.ndarray]  # (h, c) each [B, H]


class PlainLSTMCell(nn.Module):
    """Standard LSTM cell: gates = x W_ih + h W_hh + b."""

    hidden_size: int
    dtype: Dtype = jnp.float32

    def setup(self):
        self.ih = nn.Dense(4 * self.hidden_size, dtype=self.dtype)
        self.hh = nn.Dense(4 * self.hidden_size, dtype=self.dtype)

    def input_proj(self, x):
        """The x-dependent half of the gates; batched over any leading dims
        (one MXU matmul for a whole [T, B, D] sequence)."""
        return self.ih(x)

    def step_from_proj(self, ih, state: LSTMState) -> Tuple[jnp.ndarray, LSTMState]:
        h, c = state
        gates = ih + self.hh(h)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        # recurrent state stays in the carry's dtype (f32 under mixed
        # precision) so scan carries type-check and accumulation is stable
        h_new = h_new.astype(h.dtype)
        c_new = c_new.astype(c.dtype)
        return h_new, (h_new, c_new)

    def __call__(self, x, state: LSTMState) -> Tuple[jnp.ndarray, LSTMState]:
        return self.step_from_proj(self.input_proj(x), state)


class LayerNormLSTMCell(nn.Module):
    """LSTM cell with layer-normalised input/recurrent projections and cell
    state, matching the reference's LayerNormLSTMCell gate structure:
    gates = LN(x W_ih) + LN(h W_hh); c' = LN(f*c + i*g); h' = o * tanh(c')."""

    hidden_size: int
    dtype: Dtype = jnp.float32

    def setup(self):
        self.ih = nn.Dense(4 * self.hidden_size, use_bias=False, dtype=self.dtype)
        self.ln_ih = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype)
        self.hh = nn.Dense(4 * self.hidden_size, use_bias=False, dtype=self.dtype)
        self.ln_hh = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype)
        self.ln_c = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype)

    def input_proj(self, x):
        """LN(x W_ih); LayerNorm is per-row, so batching the whole [T, B, D]
        sequence through one matmul is numerically identical to per-step."""
        return self.ln_ih(self.ih(x))

    def step_from_proj(self, ih, state: LSTMState) -> Tuple[jnp.ndarray, LSTMState]:
        h, c = state
        gates = ih + self.ln_hh(self.hh(h))
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c_new = self.ln_c(
            jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        )
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        h_new = h_new.astype(h.dtype)
        c_new = c_new.astype(c.dtype)
        return h_new, (h_new, c_new)

    def __call__(self, x, state: LSTMState) -> Tuple[jnp.ndarray, LSTMState]:
        return self.step_from_proj(self.input_proj(x), state)


class StackedLSTM(nn.Module):
    """N stacked cells over time.

    Input [T, B, D] -> output [T, B, H] plus final per-layer states.
    Layer-major: each layer hoists its input projection out of the time
    scan (see module docstring).
    """

    hidden_size: int
    num_layers: int
    norm: str = "LN"  # 'LN' -> LayerNormLSTMCell, 'none' -> PlainLSTMCell
    dtype: Dtype = jnp.float32

    def setup(self):
        cell_cls = LayerNormLSTMCell if self.norm == "LN" else PlainLSTMCell
        self.cells = [
            cell_cls(self.hidden_size, self.dtype, name=f"layer{i}")
            for i in range(self.num_layers)
        ]

    def init_state(self, batch_size: int) -> Tuple[LSTMState, ...]:
        # carry in f32 regardless of compute dtype (accumulation stability)
        z = jnp.zeros((batch_size, self.hidden_size), dtype=jnp.float32)
        return tuple((z, z) for _ in range(self.num_layers))

    def _step(self, states, x):
        new_states = []
        for cell, st in zip(self.cells, states):
            x, st = cell(x, st)
            new_states.append(st)
        return tuple(new_states), x

    def __call__(
        self, xs: jnp.ndarray, states: Optional[Tuple[LSTMState, ...]] = None
    ) -> Tuple[jnp.ndarray, Tuple[LSTMState, ...]]:
        if states is None:
            states = self.init_state(xs.shape[1])
        if self.is_initializing():
            # trace one step eagerly so params exist before scan
            final, y = self._step(states, xs[0])
            ys = jnp.broadcast_to(y[None], (xs.shape[0],) + y.shape)
            return ys, final
        # hoist each layer's input projection out of the scan
        h_seq = xs
        new_states = []
        for cell, st in zip(self.cells, states):
            proj = cell.input_proj(h_seq)  # [T, B, 4H]: ONE MXU matmul
            st, h_seq = nn.transforms.scan(
                lambda mdl, carry, p: tuple(reversed(mdl.step_from_proj(p, carry))),
                variable_broadcast="params",
                split_rngs={"params": False},
            )(cell, st, proj)
            new_states.append(st)
        return h_seq, tuple(new_states)

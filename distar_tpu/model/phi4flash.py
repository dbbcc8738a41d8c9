"""``phi4flash``: a decoder-hybrid-decoder token model (SambaY). A self-decoder of
Mamba-1 state-space layers and differential attention (over a window, and
once over every key), then a cross-decoder whose layers compute no scan and
no keys or values of their own: its gated memory units read ONE state-space
layer's output and its cross-attention layers ONE attention layer's keys and
values, made layers earlier.

The equations are those of the published ``phi4flash`` architecture
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning, ``config.json``
and the ``modeling_phi4flash.py`` beside it; arXiv:2507.06607); the keys of
the model config are that file's keys, and what it has no key for
(``mamba_*``, ``time_step_*``, ``use_conv_bias``: the Mamba-1 defaults the
modeling file passes) is named in docs/token_models.md. Every norm is a
LayerNorm with scale and bias; there is no position encoding: the
state-space layers carry the order. With ``n = num_hidden_layers`` (32
published), layer ``i``:

  layer           h = x + Mixer_i(LN(x)),  y = h + W_2 (silu(W_1 LN(h)) * W_3 LN(h))   (``ops.SwiGLU``, no bias)
  i % mb_per_layer == 0, i <= n/2
                  Mamba-1 (``ops.ssm.Mamba1Mixer``): inner width ``mamba_expand hidden_size``, state
                  ``mamba_d_state``, ``mamba_d_conv`` taps, ``mamba_dt_rank``. Layer ``n/2`` also hands on
                  ``M``, its scan's output (with the ``D x`` skip) BEFORE the gate: the memory
  other i < n/2   differential attention (``ops.DifferentialAttention``) over the ``sliding_window`` keys up to
                  the query's own; ``i = n/2 + 1``: the same over every key up to it, and its ``k`` and ``v``
                  are handed on. ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` with ``i`` the PUBLISHED index
  i % mb_per_layer == 0, i >= n/2 + 2
                  gated memory unit: ``W_out (M * silu(W_in u))``, ``W_in`` [d, inner], ``W_out`` [inner, d]
  other i >= n/2 + 2
                  cross-attention: ``q = W_q u + b`` only, the differential form over the ``k`` and ``v``
                  handed on, every key up to the query's own; ``W_o``, ``lambda`` and the pair norm its own
  output          LayerNorm, then logits = h E^T over the ``vocab_size`` rows of the embedding held (tied)

``layers_held`` lists the published indices of the layers that run here, in
order; a layer keeps its published index (its kind and ``lambda_init`` read
it) and is ``layer_<position in the list>`` in the parameter tree.
``default_phi4flash_config()`` is Phi-4-mini-flash-reasoning cut to one chip:
published layers 14-19, the seam of the two decoders (one of each kind of
layer, both hand-overs), 25,008 of the 200,064 vocabulary rows, every width as
published (docs/token_models.md).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.sequence import DifferentialAttention, LayerNorm, SwiGLU
from ..ops.ssm import GatedMemoryUnit, Mamba1Mixer
from ..utils import Config
from .config import cdtype, static_cfg
from .token_decoder import decode, rms


def default_phi4flash_config() -> Config:
    return Config({
        "model_type": "phi4flash",
        "dtype": "float32",          # compute dtype of the matrix products; parameters are float32
        "remat": True,               # recompute each decoder layer in the backward pass
        "hidden_size": 2560,
        "intermediate_size": 10240,
        "num_hidden_layers": 32,     # the published depth: a layer's kind follows from its index in it
        "layers_held": [14, 15, 16, 17, 18, 19],
        "mb_per_layer": 2,
        "num_attention_heads": 40,
        "num_key_value_heads": 20,
        "sliding_window": 512,
        "mamba_d_state": 16,
        "mamba_d_conv": 4,
        "mamba_expand": 2,
        "mamba_dt_rank": 160,
        "use_conv_bias": True,
        "time_step_min": 1e-3,
        "time_step_max": 1e-1,
        "time_step_floor": 1e-4,
        "layer_norm_eps": 1e-5,
        "tie_word_embeddings": True,
        "vocab_size": 25008,
    })


def layer_kind(cfg, index: int) -> str:
    """``mamba``, ``sliding``, ``full``, ``gmu`` or ``cross``: the kind of the PUBLISHED layer ``index``."""
    half = cfg["num_hidden_layers"] // 2
    if index % cfg["mb_per_layer"] == 0:
        return "mamba" if index <= half else "gmu"
    return "sliding" if index < half else "full" if index == half + 1 else "cross"


class DecoderLayer(nn.Module):
    cfg: Dict
    index: int                       # the layer's position in ``layers_held``

    @nn.compact
    def __call__(self, x, handed: Dict) -> Tuple[jnp.ndarray, Dict, Dict[str, jnp.ndarray]]:
        """``handed`` is what earlier layers made for later ones (``memory`` [B, S, inner]; ``kv``: (k, v) [B, S,
        kv heads, head size]); a layer that makes one returns it added."""
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        published, eps, d = cfg.layers_held[self.index], cfg.layer_norm_eps, cfg.hidden_size
        kind, inner = layer_kind(cfg, published), cfg.mamba_expand * cfg.hidden_size
        half, stats = cfg.num_hidden_layers // 2, {}
        if kind == "mamba":
            with jax.named_scope("mamba1_proj"):
                u = LayerNorm(eps, name="operator_norm")(x)
            mixed, memory, stats["ssm_state_rms"] = Mamba1Mixer(
                inner, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv, cfg.use_conv_bias,
                (cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor), dtype, name="mamba")(u)
            if published == half:
                handed, stats["memory_rms"] = dict(handed, memory=memory), rms(memory)
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                u = LayerNorm(eps, name="operator_norm")(x)
                mixed = GatedMemoryUnit(dtype, name="gmu")(u, handed["memory"])
        else:
            with jax.named_scope("attn_proj"):
                u = LayerNorm(eps, name="operator_norm")(x)
            heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
            mixed, kv, stats["diff_lambda"] = DifferentialAttention(
                heads, kv_heads, d // heads, 0.8 - 0.6 * math.exp(-0.3 * published), eps, dtype,
                window=cfg.sliding_window if kind == "sliding" else None, name="attn")(
                    u, handed["kv"] if kind == "cross" else None)
            if published == half + 1:
                handed = dict(handed, kv=kv)
        x = x + mixed
        with jax.named_scope("dense_mlp"):
            ff = SwiGLU(cfg.intermediate_size, dtype, name="dense_mlp")(LayerNorm(eps, name="ffn_norm")(x))
        x = x + ff
        return x, handed, dict(stats, rms=rms(x), mixer_rms=rms(mixed), ff_rms=rms(ff))


class Phi4Flash(nn.Module):
    """``__call__(tokens [B, S] int32) -> (logits [B, S, vocab_size] float32,
    stats)``. ``stats``: ``rms`` [layers] of the residual stream after each
    layer, ``mixer_rms`` and ``ff_rms`` [layers] of each layer's mixer and
    feed-forward outputs, ``ssm_state_rms`` {``layer_<i>``: []} of every
    Mamba-1 layer (``ops.ssm.state_rms``, a channel a head), ``diff_lambda``
    {``layer_<i>``: []}, the ``lambda`` of every attention layer, ``memory_rms``
    [] of the memory handed on, and the expert layers' keys as zeros (the model
    has none)."""

    cfg: Dict

    @staticmethod
    def moe_layers(cfg) -> List[int]:
        return []

    @nn.compact
    def __call__(self, tokens):
        cfg = static_cfg(self.cfg)
        half, seen = cfg.num_hidden_layers // 2, set()
        for published in cfg.layers_held:
            needs = {"gmu": half, "cross": half + 1}.get(layer_kind(cfg, published))
            if needs is not None and needs not in seen:
                raise ValueError(f"layers_held: layer {published} reads what layer {needs} hands on, which is not before it")
            seen.add(published)
        if not cfg.tie_word_embeddings:
            raise ValueError("tie_word_embeddings false: not this model's equations")
        logits, stats = decode(self, tokens, DecoderLayer, len(cfg.layers_held), eps=cfg.layer_norm_eps, tied=True,
                               layer_norm=True, hands_on=True, stacked=("rms", "mixer_rms", "ff_rms"),
                               by_layer=("ssm_state_rms", "diff_lambda", "memory_rms"))
        (memory_rms,) = stats.pop("memory_rms").values() or (jnp.zeros((), jnp.float32),)
        return logits, dict(stats, memory_rms=memory_rms)

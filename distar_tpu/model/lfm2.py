"""LFM2: a hybrid token-sequence model of gated short convolutions and
grouped-query attention, with dense and mixture-of-experts feed-forwards.

The equations are those of the published ``lfm2_moe`` architecture
(https://huggingface.co/LiquidAI/LFM2-24B-A2B, ``config.json``); the keys of
the model config are that file's keys:

  decoder layer   h = x + Op(RMSNorm(x)),  y = h + FF(RMSNorm(h))
  Op              ``conv``: the gated short convolution (``ops.ShortConv``);
                  ``full_attention``: causal GQA (``ops.CausalGQAttention``)
  FF              the first ``num_dense_layers`` layers: SwiGLU of width
                  ``intermediate_size``; the others: ``num_experts`` experts of
                  width ``moe_intermediate_size``, ``num_experts_per_tok`` a
                  position, of which this chip computes those it holds
                  (``experts_held``, ``ops.ExpertsHeldMoE``)
  output          RMSNorm, then logits = h W_emb^T over the ``vocab_size``
                  rows held (embedding and head tied)

``layer_types`` lists the layers that run here, so ``num_hidden_layers`` is
its length. ``default_lfm2_config()`` is LFM2-24B-A2B cut to one chip's share
of an 8-way expert-parallel group: published layers 0 and 2-5, 8 of the 64
experts, 8,192 of the 65,536 vocabulary rows, every width as published
(docs/token_models.md).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.moe import ExpertsHeldMoE
from ..ops.sequence import CausalGQAttention, RMSNorm, ShortConv, SwiGLU
from ..utils import Config
from .config import cdtype, static_cfg
from .token_decoder import decode, rms


def default_lfm2_config() -> Config:
    return Config({
        "dtype": "float32",          # compute dtype of the matrix products; parameters are float32
        "remat": True,               # recompute each decoder layer in the backward pass
        "hidden_size": 2048,
        "intermediate_size": 11776,
        "moe_intermediate_size": 1536,
        "num_attention_heads": 32,
        "num_key_value_heads": 8,
        "head_dim": 64,
        "conv_L_cache": 3,
        "norm_eps": 1e-5,
        "rope_theta": 1e6,
        "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
        "num_dense_layers": 1,
        "num_experts": 64,
        "num_experts_per_tok": 4,
        "routed_scaling_factor": 1.0,
        "use_expert_bias": True,
        "experts_held": {"offset": 0, "count": 8},
        "vocab_size": 8192,
    })


class DecoderLayer(nn.Module):
    cfg: Dict
    index: int

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        kind = cfg.layer_types[self.index]
        if kind == "conv":
            with jax.named_scope("short_conv"):
                u = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
                x = x + ShortConv(cfg.conv_L_cache, dtype, name="short_conv")(u)
        elif kind == "full_attention":
            with jax.named_scope("attention"):
                u = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
                x = x + CausalGQAttention(
                    cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                    cfg.rope_theta, cfg.norm_eps, dtype, name="attention")(u)
        else:
            raise ValueError(f"layer type {kind!r}: 'conv' or 'full_attention'")
        stats = {}
        if self.index < cfg.num_dense_layers:
            with jax.named_scope("dense_mlp"):
                u = RMSNorm(cfg.norm_eps, name="ffn_norm")(x)
                ff = SwiGLU(cfg.intermediate_size, dtype, name="dense_mlp")(u)
        else:
            held = cfg.experts_held
            ff, stats = ExpertsHeldMoE(
                cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
                held.offset, held.count, cfg.routed_scaling_factor, cfg.use_expert_bias,
                cfg.norm_eps, dtype, name="moe")(x)
        x = x + ff
        return x, dict(stats, rms=rms(x), ff_rms=rms(ff))


class LFM2(nn.Module):
    """``__call__(tokens [B, S] int32) -> (logits [B, S, vocab_size] float32,
    stats)``. ``stats``: ``rms`` [layers] of the residual stream after each
    layer, ``ff_rms`` [layers] of each layer's feed-forward output, ``rows`` [expert layers, experts held] routed to each held expert,
    ``overflow`` [] rows the expert buffers did not take (0 by construction),
    ``buffer_rows`` [] rows of the expert buffers that were walked, all layers,
    ``row_indexed`` [] the expert layers whose program moved rows by buffer row."""

    cfg: Dict

    @staticmethod
    def moe_layers(cfg) -> List[int]:
        """The layers that report ``rows``: those after the leading dense ones."""
        return list(range(cfg["num_dense_layers"], len(cfg["layer_types"])))

    @nn.compact
    def __call__(self, tokens):
        cfg = static_cfg(self.cfg)
        return decode(self, tokens, DecoderLayer, len(cfg.layer_types), eps=cfg.norm_eps, tied=True,
                      stacked=("rms", "ff_rms"))

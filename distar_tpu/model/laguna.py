"""``laguna``: a token-sequence model of sliding-window and full causal
attention layers with different head counts, a sigmoid gate a head, a leading
dense feed-forward and mixture-of-experts feed-forwards with a shared expert.

The equations are those of the published ``laguna`` architecture
(https://huggingface.co/poolside/Laguna-S-2.1, ``config.json``); the keys of
the model config are that file's keys:

  decoder layer   h = x + Attn_i(RMSNorm(x)),  y = h + FF_i(RMSNorm(h))
  Attn_i          ``ops.CausalGQAttention``: ``num_attention_heads_per_layer[i]``
                  query heads of ``head_dim`` over ``num_key_value_heads``,
                  RMSNorm over each head of q and k, then the rotation that
                  ``rope_parameters[layer_types[i]]`` gives: ``rope_theta`` over
                  the first ``partial_rotary_factor`` of each head (rotate-half),
                  with ``rope_type: yarn`` by YaRN's frequency table and its
                  ``attention_factor`` on cos and sin. ``full_attention``: every
                  key up to the query's own; ``sliding_attention``: the
                  ``sliding_window`` keys up to it. ``gating: per-head``: one
                  number a head and position, ``sigmoid(W_g u)``, times the
                  head's output before ``o_proj``
  FF_i            ``mlp_layer_types[i]`` ``dense``: SwiGLU of width
                  ``intermediate_size``; ``sparse``: ``num_experts`` SwiGLU
                  experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a
                  position by a softmax over ALL experts, renormalised over the
                  picks (``norm_topk_prob``) and times
                  ``moe_routed_scaling_factor``, of which this chip computes those
                  it holds (``experts_held``), plus one shared expert of
                  ``shared_expert_intermediate_size`` for every position, not
                  gated (``ops.ExpertsHeldMoE``)
  output          RMSNorm, then logits = h W_head over the ``vocab_size`` rows
                  held (embedding and head untied)

``layer_types`` lists the layers that run here, so ``num_hidden_layers`` is its
length; ``mlp_layer_types`` and ``num_attention_heads_per_layer`` are as long.
``kv_heads_held`` (``count``) is this chip's share of attention's
heads where two or more chips divide them: it builds ``count`` of the
``num_key_value_heads`` key/value heads with their query heads (each keeps its
published group, ``num_attention_heads_per_layer[i] / num_key_value_heads``
query heads), their gates and their rows of ``o_proj``, and its attention
output is its heads' part of the sum over all heads. What the absent heads
would add is left out; nothing stands in for them.

``default_laguna_config()`` is Laguna-S-2.1 cut to one chip's share of a
32-chip group (the experts 32 ways, attention's heads 2 ways): published layers
0-4, 8 of the 256 experts, 4 of the 8 key/value heads with 24 | 36 of the 48 |
72 query heads, 12,544 of the 100,352 vocabulary rows, every width as
published (docs/token_models.md).

Matrices are drawn normal 0.02 and the embedding normal 1.0, as
``model/deepseek_v3.py`` argues them.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.moe import ExpertsHeldMoE
from ..ops.sequence import CausalGQAttention, RMSNorm, SwiGLU
from ..utils import Config
from .config import cdtype, static_cfg
from .token_decoder import decode, rms

YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor")


def default_laguna_config() -> Config:
    return Config({
        "model_type": "laguna",
        "dtype": "float32",          # compute dtype of the matrix products; parameters are float32
        "remat": True,               # recompute each decoder layer in the backward pass
        "hidden_size": 3072,
        "intermediate_size": 12288,
        "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention",
                        "full_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
        "num_attention_heads_per_layer": [48, 72, 72, 72, 48],
        "num_key_value_heads": 8,
        "kv_heads_held": {"count": 4},
        "head_dim": 128,
        "sliding_window": 512,
        "gating": "per-head",
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 128.0,
                               "original_max_position_embeddings": 8192, "beta_fast": 32.0, "beta_slow": 1.0,
                               "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0, "partial_rotary_factor": 1.0},
        },
        "num_experts": 256,
        "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024,
        "norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5,
        "experts_held": {"offset": 0, "count": 8},
        "rms_norm_eps": 1e-6,
        "vocab_size": 12544,
    })


def heads_here(cfg, index: int) -> Tuple[int, int]:
    """The query and key/value heads of layer ``index`` that this chip builds."""
    held, all_kv = cfg["kv_heads_held"]["count"], cfg["num_key_value_heads"]
    return cfg["num_attention_heads_per_layer"][index] // all_kv * held, held


class DecoderLayer(nn.Module):
    cfg: Dict
    index: int

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        eps, kind = cfg.rms_norm_eps, cfg.layer_types[self.index]
        turn = cfg.rope_parameters[kind]
        heads, kv_heads = heads_here(cfg, self.index)
        with jax.named_scope("attn_proj"):
            u = RMSNorm(eps, name="operator_norm")(x)
        mixed, opened = CausalGQAttention(
            heads, kv_heads, cfg.head_dim, turn["rope_theta"], eps, dtype,
            rotary_dim=int(cfg.head_dim * turn["partial_rotary_factor"]), gate="head",
            window=cfg.sliding_window if kind == "sliding_attention" else None,
            yarn={k: turn[k] for k in YARN_KEYS} if turn["rope_type"] == "yarn" else None, name="attn")(u)
        x = x + mixed
        stats = {"attn_gate_mean": opened}
        if cfg.mlp_layer_types[self.index] == "dense":
            with jax.named_scope("dense_mlp"):
                u = RMSNorm(eps, name="ffn_norm")(x)
                ff = SwiGLU(cfg.intermediate_size, dtype, name="dense_mlp")(u)
        else:
            held = cfg.experts_held
            ff, moe = ExpertsHeldMoE(
                cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, held.offset, held.count,
                cfg.moe_routed_scaling_factor, use_bias=False, eps=eps, dtype=dtype, body="swiglu",
                shared_width=cfg.shared_expert_intermediate_size, scoring="softmax", name="moe")(x)
            stats.update(moe)
        x = x + ff
        return x, dict(stats, rms=rms(x), mixer_rms=rms(mixed), ff_rms=rms(ff))


class Laguna(nn.Module):
    """``__call__(tokens [B, S] int32) -> (logits [B, S, vocab_size] float32,
    stats)``. ``stats``: ``rms`` [layers] of the residual stream after each
    layer, ``mixer_rms`` and ``ff_rms`` [layers] of each layer's attention and
    feed-forward outputs, ``attn_gate_mean`` {``layer_<i>``: []} of every
    layer, ``rows`` [expert layers, experts held], ``overflow`` [],
    ``buffer_rows`` [] and ``row_indexed`` [] as ``LFM2`` reports them."""

    cfg: Dict

    @staticmethod
    def moe_layers(cfg) -> List[int]:
        """The layers that report ``rows``: the ``sparse`` ones."""
        return [i for i, kind in enumerate(cfg["mlp_layer_types"]) if kind == "sparse"]

    @nn.compact
    def __call__(self, tokens):
        cfg = static_cfg(self.cfg)
        layers = len(cfg.layer_types)
        if not (len(cfg.mlp_layer_types) == len(cfg.num_attention_heads_per_layer) == layers):
            raise ValueError("layer_types, mlp_layer_types and num_attention_heads_per_layer list the same layers")
        if not cfg.norm_topk_prob or cfg.gating != "per-head":
            raise ValueError("norm_topk_prob false or gating other than 'per-head': not this model's equations")
        return decode(self, tokens, DecoderLayer, layers, eps=cfg.rms_norm_eps, embedding_scale=1.0,
                      stacked=("rms", "mixer_rms", "ff_rms"), by_layer=("attn_gate_mean",))

"""``deepseek_v3``: a token-sequence model of multi-head latent attention
and mixture-of-experts feed-forwards with shared experts.

The equations are those of the published ``deepseek_v3`` family as
Kimi-VL-A3B-Instruct's language model configures it
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct, ``config.json``);
the keys of the model config are that file's keys:

  decoder layer   h = x + MLA(RMSNorm(x)),  y = h + FF(RMSNorm(h))
  MLA             ``ops.LatentAttention``: ``num_attention_heads`` heads whose
                  keys and values are expanded from a normalised latent of
                  ``kv_lora_rank``; a head's score is over ``qk_nope_head_dim``
                  dimensions without positions and ``qk_rope_head_dim`` rotary
                  ones (interleaved pairs, ``rope_theta``) whose key is one head
                  shared by all; its value is ``v_head_dim`` wide. ``q_lora_rank``
                  is null in the published file: the query has no latent
  FF              the first ``first_k_dense_replace`` layers: SwiGLU of width
                  ``intermediate_size``; the others: ``n_routed_experts`` SwiGLU
                  experts of width ``moe_intermediate_size``,
                  ``num_experts_per_tok`` a position by sigmoid score plus a
                  selection bias, normalised, times ``routed_scaling_factor``,
                  of which this chip computes those it holds (``experts_held``),
                  plus ``n_shared_experts`` shared experts as one SwiGLU of
                  width ``n_shared_experts * moe_intermediate_size``
                  (``ops.ExpertsHeldMoE``)
  output          RMSNorm, then logits = h W_head over the ``vocab_size`` rows
                  held (embedding and head untied)

``default_deepseek_v3_config()`` is Kimi-VL-A3B-Instruct's language model cut
to one chip's share of an 8-way expert-parallel group: published layers 0-5,
8 of the 64 experts, 20,480 of the 163,840 vocabulary rows, every width as
published (docs/token_models.md). The vision tower has no key in that part of
the config and is not built.

Matrices are drawn normal 0.02 and the embedding normal 1.0 (torch's
``nn.Embedding`` default). At 0.02 the first layer's attention output (an rms
of 0.06, nearly one vector at every position: an untrained softmax over the
prefix is near uniform) is three times the embedding, every later router reads
nearly the same input at every position, and six of the 64 experts take
nearly all of them (PERF.md section 6, PR 31).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.moe import ExpertsHeldMoE
from ..ops.sequence import LatentAttention, RMSNorm, SwiGLU
from ..utils import Config
from .config import cdtype, static_cfg
from .token_decoder import decode, rms


def default_deepseek_v3_config() -> Config:
    return Config({
        "model_type": "deepseek_v3",
        "dtype": "float32",          # compute dtype of the matrix products; parameters are float32
        "remat": True,               # recompute each decoder layer in the backward pass
        "hidden_size": 2048,
        "num_hidden_layers": 6,
        "intermediate_size": 11264,
        "moe_intermediate_size": 1408,
        "num_attention_heads": 16,
        "kv_lora_rank": 512,
        "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64,
        "v_head_dim": 128,
        "rope_theta": 800000.0,
        "first_k_dense_replace": 1,
        "n_routed_experts": 64,
        "num_experts_per_tok": 6,
        "n_shared_experts": 2,
        "routed_scaling_factor": 2.446,
        "use_expert_bias": True,
        "experts_held": {"offset": 0, "count": 8},
        "rms_norm_eps": 1e-5,
        "vocab_size": 20480,
    })


class DecoderLayer(nn.Module):
    cfg: Dict
    index: int

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        with jax.named_scope("mla_proj"):
            u = RMSNorm(cfg.rms_norm_eps, name="operator_norm")(x)
        attn = LatentAttention(
            cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rope_theta, cfg.rms_norm_eps, dtype, name="mla")(u)
        x = x + attn
        stats = {}
        if self.index < cfg.first_k_dense_replace:
            with jax.named_scope("dense_mlp"):
                u = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(x)
                ff = SwiGLU(cfg.intermediate_size, dtype, name="dense_mlp")(u)
        else:
            held = cfg.experts_held
            ff, stats = ExpertsHeldMoE(
                cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
                held.offset, held.count, cfg.routed_scaling_factor, cfg.use_expert_bias,
                cfg.rms_norm_eps, dtype, body="swiglu",
                shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size, name="moe")(x)
        x = x + ff
        return x, dict(stats, rms=rms(x), attn_rms=rms(attn), ff_rms=rms(ff))


class DeepseekV3(nn.Module):
    """``__call__(tokens [B, S] int32) -> (logits [B, S, vocab_size] float32,
    stats)``. ``stats``: ``rms`` [layers] of the residual stream after each
    layer, ``attn_rms`` and ``ff_rms`` [layers] of each layer's attention and
    feed-forward outputs, ``rows`` [expert layers, experts held], ``overflow``
    [], ``buffer_rows`` [] and ``row_indexed`` [] as ``LFM2`` reports them."""

    cfg: Dict

    @staticmethod
    def moe_layers(cfg) -> List[int]:
        """The layers that report ``rows``: those after the leading dense ones."""
        return list(range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]))

    @nn.compact
    def __call__(self, tokens):
        cfg = static_cfg(self.cfg)
        return decode(self, tokens, DecoderLayer, cfg.num_hidden_layers, eps=cfg.rms_norm_eps, embedding_scale=1.0,
                      stacked=("rms", "attn_rms", "ff_rms"))

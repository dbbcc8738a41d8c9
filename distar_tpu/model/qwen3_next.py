"""``qwen3_next``: a token-sequence model of Gated DeltaNet linear-attention
layers, gated softmax-attention layers and mixture-of-experts feed-forwards
with a gated shared expert.

The equations are those of the published ``qwen3_next`` architecture
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``config.json``);
the keys of the model config are that file's keys. ``Norm(x) = x /
sqrt(mean(x^2) + eps) * (1 + w)`` with ``w`` zero at init (the family's
zero-centred RMSNorm), and a layer is a mixer and an expert block, each behind
a pre-norm residual:

  decoder layer   h = x + Mixer_i(Norm(x)),  y = h + FF(Norm(h))
  Mixer_i         full attention where ``(i + 1) % full_attention_interval ==
                  0`` (layers 3, 7, ...), Gated DeltaNet elsewhere
  Gated DeltaNet  ``ops.delta.GatedDeltaNet``: ``linear_num_key_heads`` q/k
                  heads of ``linear_key_head_dim`` serving
                  ``linear_num_value_heads`` value heads of
                  ``linear_value_head_dim``, a depthwise causal convolution of
                  ``linear_conv_kernel_dim`` taps over q, k and v, L2-normalised
                  q and k, the gated delta rule in chunks of ``gdn_chunk_size``
                  (the program's own key: the published file has none), a
                  per-head norm and a ``silu`` output gate
  attention       ``ops.CausalGQAttention`` with its gate: ``num_attention_heads``
                  heads of ``head_dim`` over ``num_key_value_heads``, zero-centred
                  q/k norms, rotary positions (rotate-half, ``rope_theta``) over
                  the first ``partial_rotary_factor`` of each head, the heads'
                  outputs times ``sigmoid(gate)``, the gate the other half of
                  ``q_proj``
  FF              ``num_experts`` SwiGLU experts of ``moe_intermediate_size``,
                  ``num_experts_per_tok`` a position by a softmax over ALL
                  experts, renormalised over the picks (``norm_topk_prob``), no
                  bias and no scale, of which this chip computes those it holds
                  (``experts_held``), plus one shared expert of
                  ``shared_expert_intermediate_size`` times ``sigmoid(u . w_g)``
                  (``ops.ExpertsHeldMoE``)
  output          Norm, then logits = h W_head over the ``vocab_size`` rows held
                  (embedding and head untied)

``default_qwen3_next_config()`` is Qwen3-Next-80B-A3B-Instruct cut to one
chip's share of a 16-way expert-parallel group: published layers 0-3 (one
period: three Gated DeltaNet layers and one attention layer), 32 of the 512
experts, 18,992 of the 151,936 vocabulary rows, every width as published
(docs/token_models.md). The multi-token-prediction head the model card speaks
of has no key in the public config and is not built.

Matrices are drawn normal 0.02 and the embedding normal 1.0, as
``model/deepseek_v3.py`` argues them.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.delta import GatedDeltaNet
from ..ops.moe import ExpertsHeldMoE
from ..ops.sequence import CausalGQAttention, RMSNorm
from ..utils import Config
from .config import cdtype, static_cfg
from .token_decoder import decode, rms


def default_qwen3_next_config() -> Config:
    return Config({
        "model_type": "qwen3_next",
        "dtype": "float32",          # compute dtype of the matrix products; parameters are float32
        "remat": True,               # recompute each decoder layer in the backward pass
        "hidden_size": 2048,
        "num_hidden_layers": 4,
        "full_attention_interval": 4,
        "linear_num_key_heads": 16,
        "linear_key_head_dim": 128,
        "linear_num_value_heads": 32,
        "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4,
        "gdn_chunk_size": 64,
        "num_attention_heads": 16,
        "num_key_value_heads": 2,
        "head_dim": 256,
        "partial_rotary_factor": 0.25,
        "rope_theta": 1e7,
        "num_experts": 512,
        "num_experts_per_tok": 10,
        "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "norm_topk_prob": True,
        "experts_held": {"offset": 0, "count": 32},
        "rms_norm_eps": 1e-6,
        "vocab_size": 18992,
    })


def is_attention(cfg, index: int) -> bool:
    return (index + 1) % cfg["full_attention_interval"] == 0


class DecoderLayer(nn.Module):
    cfg: Dict
    index: int

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        eps = cfg.rms_norm_eps
        stats = {}
        if is_attention(cfg, self.index):
            with jax.named_scope("attention"):
                u = RMSNorm(eps, zero_centred=True, name="operator_norm")(x)
                mixed, stats["attn_gate_mean"] = CausalGQAttention(
                    cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rope_theta, eps, dtype,
                    rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor), zero_centred=True, gate=True,
                    name="attention")(u)
        else:
            with jax.named_scope("gdn_proj"):
                u = RMSNorm(eps, zero_centred=True, name="operator_norm")(x)
            mixed, gdn = GatedDeltaNet(
                cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim, cfg.gdn_chunk_size, eps, dtype,
                name="gdn")(u)
            stats.update(gdn_state_rms=gdn["state_rms"], gdn_decay_mean=gdn["decay_mean"])
        x = x + mixed
        held = cfg.experts_held
        ff, moe = ExpertsHeldMoE(
            cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, held.offset, held.count,
            use_bias=False, eps=eps, dtype=dtype, body="swiglu", shared_width=cfg.shared_expert_intermediate_size,
            scoring="softmax", gated_shared=True, zero_centred=True, name="moe")(x)
        x = x + ff
        return x, dict(stats, **moe, rms=rms(x), mixer_rms=rms(mixed), ff_rms=rms(ff))


class Qwen3Next(nn.Module):
    """``__call__(tokens [B, S] int32) -> (logits [B, S, vocab_size] float32,
    stats)``. ``stats``: ``rms`` [layers] of the residual stream after each
    layer, ``mixer_rms`` and ``ff_rms`` [layers] of each layer's mixer and
    feed-forward outputs, ``gdn_state_rms`` and ``gdn_decay_mean``
    {``layer_<i>``: []} of each Gated DeltaNet layer (``ops.ssm.state_rms`` of
    its state after the last position; its mean decay), ``attn_gate_mean``
    {``layer_<i>``: []} of each attention layer, ``rows`` [layers, experts
    held], ``overflow`` [], ``buffer_rows`` [] and ``row_indexed`` [] as
    ``LFM2`` reports them."""

    cfg: Dict

    @staticmethod
    def moe_layers(cfg) -> List[int]:
        """The layers that report ``rows``: every one."""
        return list(range(cfg["num_hidden_layers"]))

    @nn.compact
    def __call__(self, tokens):
        cfg = static_cfg(self.cfg)
        if not cfg.norm_topk_prob:
            raise ValueError("norm_topk_prob false: the router renormalises over its picks (ops.moe.route)")
        return decode(self, tokens, DecoderLayer, cfg.num_hidden_layers, eps=cfg.rms_norm_eps, zero_centred=True,
                      embedding_scale=1.0, stacked=("rms", "mixer_rms", "ff_rms"),
                      by_layer=("gdn_state_rms", "gdn_decay_mean", "attn_gate_mean"))

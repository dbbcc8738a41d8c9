"""``nemotron_h``: a hybrid token-sequence model of Mamba-2 state-space
layers, grouped-query attention without positions and mixture-of-experts
feed-forwards with a shared expert.

The equations are those of the published ``nemotron_h`` architecture
(https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
``config.json``); the keys of the model config are that file's keys. A layer
is ONE mixer behind one pre-norm residual, its kind the letter of
``hybrid_override_pattern`` at its index:

  layer     x <- x + Mixer_i(RMSNorm(x))
  ``M``     the Mamba-2 mixer (``ops.ssm.Mamba2Mixer``): ``mamba_num_heads``
            heads of ``mamba_head_dim``, ``n_groups`` groups, state
            ``ssm_state_size``, a causal convolution of ``conv_kernel`` taps
            with a bias, a scan in chunks of ``chunk_size``
  ``*``     causal GQA (``ops.CausalGQAttention`` without positions: no rotary
            embedding, no q/k norm; ``rope_theta`` is in the published config
            and read by nothing)
  ``E``     ``n_routed_experts`` experts of width ``moe_intermediate_size``,
            ``W_2 relu(W_1 u)^2``, ``num_experts_per_tok`` a position by
            sigmoid score plus a selection bias, normalised, times
            ``routed_scaling_factor``, of which this chip computes those it
            holds (``experts_held``), plus one shared expert of width
            ``moe_shared_expert_intermediate_size`` (``ops.ExpertsHeldMoE``)
  output    RMSNorm, then logits = h W_head over the ``vocab_size`` rows held
            (embedding and head untied)

``hybrid_override_pattern`` lists the layers that run here, so
``num_hidden_layers`` is its length. ``default_nemotron_h_config()`` is
Nemotron-Labs-TwoTower-30B-A3B's ``config.json`` tower cut to one chip's share
of a 16-way expert-parallel group: published layers 0-8, 8 of the 128 experts,
16,384 of the 131,072 vocabulary rows, every width as published
(docs/token_models.md). The second, denoiser tower that the model's name
speaks of has no key in the public config and is not built.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.moe import ExpertsHeldMoE
from ..ops.sequence import CausalGQAttention, RMSNorm
from ..ops.ssm import Mamba2Mixer
from ..utils import Config
from .config import cdtype, static_cfg
from .token_decoder import decode, rms


def default_nemotron_h_config() -> Config:
    return Config({
        "model_type": "nemotron_h",
        "dtype": "float32",          # compute dtype of the matrix products; parameters are float32
        "remat": True,               # recompute each layer in the backward pass
        "hidden_size": 2688,
        "hybrid_override_pattern": "MEMEM*EME",
        "mamba_num_heads": 64,
        "mamba_head_dim": 64,
        "n_groups": 8,
        "ssm_state_size": 128,
        "conv_kernel": 4,
        "chunk_size": 128,
        "use_conv_bias": True,
        "time_step_min": 1e-3,
        "time_step_max": 1e-1,
        "time_step_floor": 1e-4,
        "num_attention_heads": 32,
        "num_key_value_heads": 2,
        "head_dim": 128,
        "n_routed_experts": 128,
        "num_experts_per_tok": 6,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "routed_scaling_factor": 2.5,
        "use_expert_bias": True,
        "experts_held": {"offset": 0, "count": 8},
        "norm_eps": 1e-5,
        "vocab_size": 16384,
    })


class MixerLayer(nn.Module):
    cfg: Dict
    index: int

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        kind = cfg.hybrid_override_pattern[self.index]
        stats = {}
        if kind == "M":
            with jax.named_scope("ssm_proj"):
                u = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
            out, stats["ssm_state_rms"] = Mamba2Mixer(
                cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size,
                cfg.conv_kernel, cfg.chunk_size, cfg.use_conv_bias,
                (cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor), cfg.norm_eps, dtype,
                name="mamba")(u)
        elif kind == "*":
            with jax.named_scope("attention"):
                u = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
                out = CausalGQAttention(
                    cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                    eps=cfg.norm_eps, dtype=dtype, positions=False, name="attention")(u)
        elif kind == "E":
            held = cfg.experts_held
            out, stats = ExpertsHeldMoE(
                cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
                held.offset, held.count, cfg.routed_scaling_factor, cfg.use_expert_bias,
                cfg.norm_eps, dtype, body="relu2",
                shared_width=cfg.moe_shared_expert_intermediate_size, name="moe")(x)
        else:
            raise ValueError(f"layer {self.index} of the pattern is {kind!r}: 'M', '*' or 'E'")
        x = x + out
        return x, dict(stats, rms=rms(x), mixer_rms=rms(out))


class NemotronH(nn.Module):
    """``__call__(tokens [B, S] int32) -> (logits [B, S, vocab_size] float32,
    stats)``. ``stats``: ``rms`` [layers] of the residual stream after each
    layer, ``mixer_rms`` [layers] of each layer's mixer output,
    ``ssm_state_rms`` {``layer_<i>``: []}, ``ops.ssm.state_rms`` of each ``M`` layer's state after the last position,
    ``rows`` [``E`` layers, experts held], ``overflow`` [], ``buffer_rows``
    [] and ``row_indexed`` [] as ``LFM2`` reports them."""

    cfg: Dict

    @staticmethod
    def moe_layers(cfg) -> List[int]:
        """The layers that report ``rows``: the pattern's ``E``."""
        return [i for i, k in enumerate(cfg["hybrid_override_pattern"]) if k == "E"]

    @nn.compact
    def __call__(self, tokens):
        cfg = static_cfg(self.cfg)
        return decode(self, tokens, MixerLayer, len(cfg.hybrid_override_pattern), eps=cfg.norm_eps,
                      stacked=("rms", "mixer_rms"), by_layer=("ssm_state_rms",))

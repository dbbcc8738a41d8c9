"""The frame every token-sequence model shares: tokens to logits around the
model's own layers.

  x_0     = W_emb[tokens]                          (scope ``embed``)
  x_{i+1}, stats_i = Layer_i(x_i)                  (module ``layer_<i>``, recomputed in the backward pass
                                                    where the config's ``remat`` says so, but for what
                                                    ``ops.sequence.CORE_KEPT`` names)
  x_{i+1}, handed_{i+1}, stats_i = Layer_i(x_i, handed_i)   where a model's layers hand tensors on to later
                                                    layers (``hands_on``; ``handed_0`` = {})
  logits  = Norm(x_L) W_head, float32              (scope ``lm_head``, module ``final_norm``)

``decode`` is called from a model's ``__call__`` with the model itself, so
every parameter lies where it lay when each model wrote this out: ``embedding``
[``vocab_size``, ``hidden_size``], ``layer_<i>/...``, ``final_norm/...`` and,
where the head is not the embedding, ``lm_head`` [``hidden_size``,
``vocab_size``] straight under the model, no module between. A model file
(``lfm2.py``, ``nemotron_h.py``, ``deepseek_v3.py``, ``qwen3_next.py``,
``laguna.py``, ``phi4flash.py``) keeps its layer, its defaults and the facts it
hands over here: how many layers, the norm's kind (RMSNorm, zero-centred or
not, or LayerNorm with a bias) and epsilon, the embedding's scale, whether the
head is tied, which of its layers' statistics are stacked and which are kept
by layer, and whether its layers hand tensors on. ``phi4flash``'s do: a dict
of what earlier layers made for later ones (the memory of one state-space
layer's scan, one attention layer's keys and values) goes through the layer
loop beside the residual stream as a second argument and a second result of
every layer, under the same ``nn.remat``, so a reader's gradient reaches the
maker through every layer between and nothing is a side channel.

What the remat keeps: a layer's input (and what was handed to it), as any
``jax.checkpoint`` does, and the forward results of a full-causal attention
core, ``out`` [B, heads, S, Dv] and ``logsumexp`` [B, heads, S] float32, which
splash attention names ``CORE_KEPT`` inside its forward rule. Its backward
kernels need those two and only its forward kernel can make them, at a cost
quadratic in ``S``: 95 and 110 ms of replay for every GB kept at 2 x 8,192 and
16,384 positions (PERF.md section 5, PR 44), so the replay runs the layer
up to the kernel's operands (the projections: cheap) and stops there.
Everything else is replayed: matrix products and elementwise chains, whose
activations are what memory bounds, and the kernels that are linear in ``S``
(the banded core, the scans, the delta rule: 4-25 ms a GB). The policy is one
rule for every model and needs no key: with ``remat`` off everything is kept
and the name does nothing.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple, Type

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.sequence import CORE_KEPT, LayerNorm, RMSNorm
from .config import cdtype, static_cfg


def rms(t):
    """The root mean square of ``t``, in float32: what the layers report of the residual stream and of their parts."""
    return jnp.sqrt(jnp.mean(jnp.square(t.astype(jnp.float32))))


def decode(model: nn.Module, tokens, layer: Type[nn.Module], layers: int, *, eps: float, stacked: Sequence[str],
           by_layer: Sequence[str] = (), zero_centred: bool = False, embedding_scale: float = 0.02,
           tied: bool = False, layer_norm: bool = False, hands_on: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """``tokens`` [B, S] int32 -> (logits [B, S, vocab_size] float32, stats),
    inside ``model.__call__``. ``layer(model.cfg, i, name=f"layer_{i}")(x) ->
    (x, stats_i)`` is layer ``i`` (with ``hands_on``, ``(x, handed) -> (x, handed,
    stats_i)``, ``handed`` a dict of arrays, empty before layer 0); ``model.cfg`` names ``vocab_size``,
    ``hidden_size``, ``dtype`` and ``remat``. ``stats`` is the layers' as one
    tree: ``stacked`` keys (every layer has them) [layers]; ``by_layer`` keys
    {``layer_<i>``: []} of the layers that have them; of the expert layers
    (those that report ``rows``, ``ops.ExpertsHeldMoE``) ``rows`` [expert
    layers, experts held] and ``overflow``, ``buffer_rows``, ``row_indexed`` []
    summed, zeros where a model has none."""
    cfg, dtype = static_cfg(model.cfg), cdtype(model.cfg)
    embedding = model.param("embedding", nn.initializers.normal(embedding_scale),
                            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
    with jax.named_scope("embed"):
        x = embedding.astype(dtype)[tokens]
    # a layer's replay keeps what only a full-causal attention kernel can make, and nothing else
    layer_cls = nn.remat(layer, policy=jax.checkpoint_policies.save_only_these_names(CORE_KEPT)) if cfg.remat else layer
    per_layer, handed = [], {}
    for i in range(layers):
        if hands_on:
            x, handed, stats = layer_cls(model.cfg, i, name=f"layer_{i}")(x, handed)
        else:
            x, stats = layer_cls(model.cfg, i, name=f"layer_{i}")(x)
        per_layer.append(stats)
    with jax.named_scope("lm_head"):
        norm = LayerNorm(eps, name="final_norm") if layer_norm else RMSNorm(eps, zero_centred, name="final_norm")
        h = norm(x)
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", h, embedding.astype(dtype), preferred_element_type=jnp.float32)
        else:
            head = model.param("lm_head", nn.initializers.normal(0.02), (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dtype), preferred_element_type=jnp.float32)
    moe = [s for s in per_layer if "rows" in s]
    return logits, {
        **{k: jnp.stack([s[k] for s in per_layer]) for k in stacked},
        **{k: {f"layer_{i}": s[k] for i, s in enumerate(per_layer) if k in s} for k in by_layer},
        # no expert layer: no rows, of one held expert (the learner's diagnostics take a maximum over the experts)
        "rows": jnp.stack([s["rows"] for s in moe]) if moe else jnp.zeros((0, 1), jnp.int32),
        **{k: sum(s[k] for s in moe) if moe else jnp.zeros((), jnp.int32)
           for k in ("overflow", "buffer_rows", "row_indexed")},
    }

"""The frame every token-sequence model shares: tokens to logits around the
model's own layers.

  x_0     = W_emb[tokens]                          (scope ``embed``)
  x_{i+1}, stats_i = Layer_i(x_i)                  (module ``layer_<i>``, recomputed in the backward pass
                                                    where the config's ``remat`` says so)
  logits  = Norm(x_L) W_head, float32              (scope ``lm_head``, module ``final_norm``)

``decode`` is called from a model's ``__call__`` with the model itself, so
every parameter lies where it lay when each model wrote this out: ``embedding``
[``vocab_size``, ``hidden_size``], ``layer_<i>/...``, ``final_norm/...`` and,
where the head is not the embedding, ``lm_head`` [``hidden_size``,
``vocab_size``] straight under the model, no module between. A model file
(``lfm2.py``, ``nemotron_h.py``, ``deepseek_v3.py``, ``qwen3_next.py``,
``laguna.py``) keeps its layer, its defaults and the facts it hands over here:
how many layers, the norm's epsilon and whether it is zero-centred, the
embedding's scale, whether the head is tied, and which of its layers'
statistics are stacked and which are kept by layer.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple, Type

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.sequence import RMSNorm
from .config import cdtype, static_cfg


def rms(t):
    """The root mean square of ``t``, in float32: what the layers report of the residual stream and of their parts."""
    return jnp.sqrt(jnp.mean(jnp.square(t.astype(jnp.float32))))


def decode(model: nn.Module, tokens, layer: Type[nn.Module], layers: int, *, eps: float, stacked: Sequence[str],
           by_layer: Sequence[str] = (), zero_centred: bool = False, embedding_scale: float = 0.02,
           tied: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """``tokens`` [B, S] int32 -> (logits [B, S, vocab_size] float32, stats),
    inside ``model.__call__``. ``layer(model.cfg, i, name=f"layer_{i}")(x) ->
    (x, stats_i)`` is layer ``i``; ``model.cfg`` names ``vocab_size``,
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
    layer_cls = nn.remat(layer) if cfg.remat else layer
    per_layer = []
    for i in range(layers):
        x, stats = layer_cls(model.cfg, i, name=f"layer_{i}")(x)
        per_layer.append(stats)
    with jax.named_scope("lm_head"):
        h = RMSNorm(eps, zero_centred, name="final_norm")(x)
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", h, embedding.astype(dtype), preferred_element_type=jnp.float32)
        else:
            head = model.param("lm_head", nn.initializers.normal(0.02), (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dtype), preferred_element_type=jnp.float32)
    moe = [s for s in per_layer if "rows" in s]
    return logits, {
        **{k: jnp.stack([s[k] for s in per_layer]) for k in stacked},
        **{k: {f"layer_{i}": s[k] for i, s in enumerate(per_layer) if k in s} for k in by_layer},
        "rows": jnp.stack([s["rows"] for s in moe]) if moe else jnp.zeros((0, 0), jnp.int32),
        **{k: sum(s[k] for s in moe) if moe else jnp.zeros((), jnp.int32)
           for k in ("overflow", "buffer_rows", "row_indexed")},
    }

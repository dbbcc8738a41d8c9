"""Next-token cross-entropy of a token-sequence model: the mean over every
position of ``-log softmax(logits)[label]``, in float32, over the rows of
the vocabulary that are held (a sliced vocabulary is a smaller vocabulary:
ids, logits and the loss are over the slice). No masking across documents,
no auxiliary loss."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def compute_lm_loss(logits, labels) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``logits`` [B, S, V] float32, ``labels`` [B, S] int -> (loss, info)."""
    logits = logits.astype(jnp.float32)
    log_z = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(log_z - picked)
    accuracy = jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
    return loss, {"total_loss": loss, "token_acc": accuracy}

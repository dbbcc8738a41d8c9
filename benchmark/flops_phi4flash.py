"""Required FLOPs per position of a ``phi4flash`` train step, counted from the
model config: multiply-adds of the matrix products only, as ``flops.py``,
``flops_lm.py`` and ``flops_laguna.py`` count, analytic because the walker sees
no kernel. With ``d`` the hidden size, ``e`` the Mamba-1 inner width
(``mamba_expand d``), ``H`` query heads and ``Hkv`` key/value heads of ``D = d /
H``, by the kind of each published layer in ``layers_held``:

  Mamba-1          2 d 2e (in) + 2 e (R + 2 N) (x_proj) + 2 R e (dt_proj) + 2 e d (out).
                   The selective scan is elementwise work over [e, N], not a
                   matrix product: it counts 0 here, and its time is the
                   vector units' (``kernels_phi4flash.selective_scan`` bounds
                   it by its bytes)
  memory unit      2 d e (in) + 2 e d (out)
  attention        2 d H D (q) + 2 * 2 d Hkv D (k, v; a cross layer has none) + 2 H D d (o)
  full, cross core H / 2 pairs x 2 maps x (2 D for the scores + 2 * 2 D for the
                   pair's 2 D-wide value) over the S / 2 keys a causal query sees
  banded core      the same over the keys of the band alone (``flops_laguna.band_keys``)
  feed-forward     3 * 2 d f (SwiGLU), every layer
  head             2 d V_rows (the rows of the tied embedding held; the embedding itself is a gather)

A step requires three times its forward pass; recomputation is not required
work, and neither is what a kernel computes of the blocks its band only
touches. The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file:

  python -m benchmark.flops_phi4flash --config <configuration>
"""
from __future__ import annotations

from typing import Dict

from benchmark.flops_laguna import band_keys
from benchmark.references.phi4flash_plain import layer_kind  # the kind of a published layer, from its index


def core_per_position(heads: int, head_dim: int, keys: float) -> float:
    """Forward FLOPs of a differential attention core, per position and layer: ``heads`` maps (two a pair), each
    with scores over ``head_dim`` and a value ``2 head_dim`` wide, over ``keys`` keys a query."""
    return heads * (2.0 * head_dim + 2.0 * 2 * head_dim) * keys


def forward_parts(m, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position by part, summed over the layers held."""
    d, H, Hkv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    D, e, N, R = d // H, m["mamba_expand"] * m["hidden_size"], m["mamba_d_state"], m["mamba_dt_rank"]
    parts = dict.fromkeys(("mamba1_proj", "gmu", "attn_proj", "attn_core", "swa_core", "cross_core"), 0.0)
    for published in m["layers_held"]:
        kind = layer_kind(m, published)
        if kind == "mamba":
            parts["mamba1_proj"] += 2.0 * d * 2 * e + 2.0 * e * (R + 2 * N) + 2.0 * R * e + 2.0 * e * d
        elif kind == "gmu":
            parts["gmu"] += 4.0 * d * e
        else:
            parts["attn_proj"] += 4.0 * d * H * D + (0.0 if kind == "cross" else 4.0 * d * Hkv * D)
            core = {"sliding": "swa_core", "full": "attn_core", "cross": "cross_core"}[kind]
            keys = band_keys(seq_len, m["sliding_window"]) if kind == "sliding" else seq_len / 2.0
            parts[core] += core_per_position(H, D, keys)
    return {**parts, "dense_mlp": len(m["layers_held"]) * 6.0 * d * m["intermediate_size"],
            "lm_head": 2.0 * d * m["vocab_size"]}


def required_per_frame(model_cfg, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position and the step's required FLOPs (3x)."""
    fwd = sum(forward_parts(model_cfg, seq_len).values())
    return {"forward": fwd, "step": 3.0 * fwd}


if __name__ == "__main__":
    import argparse
    import json

    from benchmark import cells

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    a = p.parse_args()
    cfg = cells.load("configs", a.config)
    model = cells.program_config(cfg)["model"]
    seq = cfg["as_run"]["learner"]["unroll_len"]
    print(json.dumps({**required_per_frame(model, seq), "parts": forward_parts(model, seq)}))

"""Required FLOPs per position of a ``laguna`` train step, counted from the
model config: multiply-adds of the matrix products only, as ``flops.py``,
``flops_lm.py`` and ``flops_deepseek_v3.py`` count, analytic because the walker
sees neither a grouped product nor a kernel and the rows an expert layer
computes depend on the routing. The heads are those this chip builds
(``kv_heads_held`` key/value heads with their query heads).

  attention, projections  2 d H D (q) + 2 * 2 d Hkv D (k, v) + 2 d H (the gate, one
                          number a head) + 2 H D d (o)
  full core               2 * 2 H D (S / 2): scores and values over the S/2 keys a
                          causal query sees
  banded core             2 * 2 H D band(S, W): over the keys of the band alone,
                          band = W - W (W - 1) / (2 S) a query on average (the
                          first W - 1 queries of a sequence see fewer than W)
  dense                   3 * 2 d f (SwiGLU: three products)
  experts                 2 d E (router over all E experts) + 3 * 2 d f_s (the
                          shared expert) + k * held / E expected rows a position
                          * 3 * 2 d f_e
  head                    2 d V_rows (the rows of the vocabulary held; the embedding is a gather)

A step requires three times its forward pass; recomputation is not required
work, and neither is what a kernel computes of the blocks its band only
touches. The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file:

  python -m benchmark.flops_laguna --config <configuration>
"""
from __future__ import annotations

from typing import Dict


def band_keys(seq_len: int, window: int) -> float:
    """Keys a query sees under a band of ``window``, averaged over a sequence."""
    w = min(window, seq_len)
    return w - w * (w - 1) / (2.0 * seq_len)


def core_per_position(heads: int, head_dim: int, keys: float) -> float:
    """Forward FLOPs of an attention core, per position and layer, over ``keys`` keys a query."""
    return 4.0 * heads * head_dim * keys


def forward_parts(m, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position by part, summed over the layers."""
    d, D, Hkv = m["hidden_size"], m["head_dim"], m["kv_heads_held"]["count"]
    rows = m["num_experts_per_tok"] * m["experts_held"]["count"] / m["num_experts"]
    n_dense = sum(kind == "dense" for kind in m["mlp_layer_types"])
    n_e = len(m["mlp_layer_types"]) - n_dense
    parts = dict.fromkeys(("attn_proj", "attn_core", "swa_core"), 0.0)
    for kind, all_heads in zip(m["layer_types"], m["num_attention_heads_per_layer"]):
        H = all_heads // m["num_key_value_heads"] * Hkv
        parts["attn_proj"] += 2.0 * d * H * D + 4.0 * d * Hkv * D + 2.0 * d * H + 2.0 * H * D * d
        if kind == "sliding_attention":
            parts["swa_core"] += core_per_position(H, D, band_keys(seq_len, m["sliding_window"]))
        else:
            parts["attn_core"] += core_per_position(H, D, seq_len / 2.0)
    return {
        **parts,
        "dense_mlp": n_dense * 6.0 * d * m["intermediate_size"],
        "moe_router": n_e * 2.0 * d * m["num_experts"],
        "moe_shared": n_e * 6.0 * d * m["shared_expert_intermediate_size"],
        "moe_experts": n_e * rows * 6.0 * d * m["moe_intermediate_size"],
        "lm_head": 2.0 * d * m["vocab_size"],
    }


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

"""Required FLOPs per position of a token-sequence train step, counted from
the model config.

``flops.py`` walks the forward pass as traced; that walker sees neither a
grouped product over uneven groups nor a kernel, and the rows an expert
layer computes depend on the routing. So this count is analytic, from the
config's sizes, multiply-adds of the matrix products only (as ``flops.py``):

  short convolution   2 d (3d) + 2 d d                        (in, out; the 3 taps are elementwise)
  attention           2 d (H D) (q) + 2 * 2 d (Hkv D) (k, v) + 2 (H D) d (o)
                      + 2 * 2 H D (S / 2): scores and values over the S/2 keys a
                      causal query sees on average
  dense MLP           3 * 2 d f
  expert layer        2 d E (router over all E experts)
                      + k * held / E expected rows a position * 3 * 2 d f_e
  head                2 d V (the rows of the vocabulary held; the embedding is a gather)

A step requires three times its forward pass; recomputation is not required
work. The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file:

  python -m benchmark.flops_lm --config <configuration>
"""
from __future__ import annotations

from typing import Dict


def forward_parts(m, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position by part, summed over the layers."""
    d, H, Hkv, D = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    types = list(m["layer_types"])
    n_dense = m["num_dense_layers"]
    n_moe = len(types) - n_dense
    held = m["experts_held"]["count"]
    rows = m["num_experts_per_tok"] * held / m["num_experts"]
    return {
        "short_conv": types.count("conv") * (2.0 * d * 3 * d + 2.0 * d * d),
        "attention": types.count("full_attention") * (
            2.0 * d * H * D + 4.0 * d * Hkv * D + 2.0 * H * D * d + 4.0 * H * D * seq_len / 2.0),
        "dense_mlp": n_dense * 6.0 * d * m["intermediate_size"],
        "moe_router": n_moe * 2.0 * d * m["num_experts"],
        "moe_experts": n_moe * rows * 6.0 * d * m["moe_intermediate_size"],
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

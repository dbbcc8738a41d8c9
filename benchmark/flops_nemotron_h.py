"""Required FLOPs per position of a ``nemotron_h`` train step, counted from the
model config: multiply-adds of the matrix products only, as ``flops.py`` and
``flops_lm.py`` count, analytic because the walker sees neither a grouped
product nor a kernel and the rows an expert layer computes depend on the routing.

  M   2 d (2 H P + 2 G N + H) (in) + 2 H P d (out)
      + the scan in chunks of Q: 2 Q N G (C_i . B_j) + 2 Q P H (the chunk's own
      positions) + 4 P N H (into the carried state and out of it)
  *   2 d (Hq D) (q) + 2 * 2 d (Hkv D) (k, v) + 2 (Hq D) d (o)
      + 2 * 2 Hq D (S / 2): scores and values over the S/2 keys a causal query sees
  E   2 d E (router over all E experts) + 2 * 2 d f_s (the shared expert)
      + k * held / E expected rows a position * 2 * 2 d f_e
  head  2 d V (the rows of the vocabulary held; the embedding is a gather)

A step requires three times its forward pass; recomputation is not required
work. The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file:

  python -m benchmark.flops_nemotron_h --config <configuration>
"""
from __future__ import annotations

from typing import Dict


def scan_per_position(m) -> float:
    """Forward FLOPs of the chunked scan, per position and ``M`` layer."""
    Q, H, P = m["chunk_size"], m["mamba_num_heads"], m["mamba_head_dim"]
    G, N = m["n_groups"], m["ssm_state_size"]
    return 2.0 * Q * N * G + 2.0 * Q * P * H + 4.0 * P * N * H


def forward_parts(m, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position by part, summed over the layers."""
    d, Hq, Hkv, D = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    H, P, G, N = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]
    pattern = m["hybrid_override_pattern"]
    n_m, n_a, n_e = pattern.count("M"), pattern.count("*"), pattern.count("E")
    rows = m["num_experts_per_tok"] * m["experts_held"]["count"] / m["n_routed_experts"]
    return {
        "ssm_proj": n_m * (2.0 * d * (2 * H * P + 2 * G * N + H) + 2.0 * H * P * d),
        "ssm_scan": n_m * scan_per_position(m),
        "attention": n_a * (2.0 * d * Hq * D + 4.0 * d * Hkv * D + 2.0 * Hq * D * d + 4.0 * Hq * D * seq_len / 2.0),
        "moe_router": n_e * 2.0 * d * m["n_routed_experts"],
        "moe_shared": n_e * 4.0 * d * m["moe_shared_expert_intermediate_size"],
        "moe_experts": n_e * rows * 4.0 * d * m["moe_intermediate_size"],
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

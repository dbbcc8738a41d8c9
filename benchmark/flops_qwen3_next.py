"""Required FLOPs per position of a ``qwen3_next`` train step, counted from the
model config: multiply-adds of the matrix products only, as ``flops.py``,
``flops_lm.py``, ``flops_nemotron_h.py`` and ``flops_deepseek_v3.py`` count,
analytic because the walker sees neither a grouped product nor a kernel and the
rows an expert layer computes depend on the routing.

  Gated DeltaNet, projections  2 d (2 Hk K + 2 H V) (q, k, v, z) + 2 d 2 H (b, a)
                    + 2 H V d (out)
  Gated DeltaNet, rule  3 * 2 K V H: what the RECURRENCE itself requires of a
                    position and value head, the state read (S'^T k), the
                    rank-one write (k (v - .)^T) and the read-out (S^T q), and
                    not what a chunked form spends (its C x C products and its
                    triangular system are the implementation's)
  attention, projections  2 d (2 Hq D) (q and its gate) + 2 * 2 d (Hkv D) (k, v)
                    + 2 (Hq D) d (o)
  attention, core   2 * 2 Hq D (S / 2): scores and values over the S/2 keys a
                    causal query sees
  experts           2 d E (router over all E experts) + 3 * 2 d f_s + 2 d (the
                    shared expert and its gate) + k * held / E expected rows a
                    position * 3 * 2 d f_e
  head              2 d V_rows (the rows of the vocabulary held; the embedding is a gather)

A step requires three times its forward pass; recomputation is not required
work. The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file:

  python -m benchmark.flops_qwen3_next --config <configuration>
"""
from __future__ import annotations

from typing import Dict


def rule_per_position(m) -> float:
    """Forward FLOPs the gated delta rule requires, per position and Gated DeltaNet layer."""
    return 6.0 * m["linear_key_head_dim"] * m["linear_value_head_dim"] * m["linear_num_value_heads"]


def core_per_position(m, seq_len: int) -> float:
    """Forward FLOPs of the attention core, per position and attention layer."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] * seq_len / 2.0


def forward_parts(m, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position by part, summed over the layers."""
    d, layers = m["hidden_size"], m["num_hidden_layers"]
    n_a = layers // m["full_attention_interval"]
    n_g = layers - n_a
    Hk, K = m["linear_num_key_heads"], m["linear_key_head_dim"]
    H, V = m["linear_num_value_heads"], m["linear_value_head_dim"]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    rows = m["num_experts_per_tok"] * m["experts_held"]["count"] / m["num_experts"]
    return {
        "gdn_proj": n_g * (2.0 * d * (2 * Hk * K + 2 * H * V) + 2.0 * d * 2 * H + 2.0 * H * V * d),
        "gdn_scan": n_g * rule_per_position(m),
        "attention": n_a * (2.0 * d * 2 * Hq * D + 4.0 * d * Hkv * D + 2.0 * Hq * D * d + core_per_position(m, seq_len)),
        "moe_router": layers * 2.0 * d * m["num_experts"],
        "moe_shared": layers * (6.0 * d * m["shared_expert_intermediate_size"] + 2.0 * d),
        "moe_experts": layers * rows * 6.0 * d * m["moe_intermediate_size"],
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

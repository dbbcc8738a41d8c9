"""Required FLOPs per position of a ``deepseek_v3`` train step, counted from
the model config: multiply-adds of the matrix products only, as ``flops.py``,
``flops_lm.py`` and ``flops_nemotron_h.py`` count, analytic because the walker
sees neither a grouped product nor a kernel and the rows an expert layer
computes depend on the routing.

  MLA, projections  2 d H (N + R) (q) + 2 d (c + R) (kv_a) + 2 c H (N + V) (kv_b)
                    + 2 H V d (o): keys and values expanded from the latent
  MLA, core         2 H (N + R) (S / 2) (scores) + 2 H V (S / 2) (values): the
                    S/2 keys a causal query sees, at the PUBLISHED head sizes
                    whatever the kernel pads to
  dense             3 * 2 d f (SwiGLU: three products)
  experts           2 d E (router over all E experts) + 3 * 2 d (n_shared f_e)
                    (the shared experts) + k * held / E expected rows a position
                    * 3 * 2 d f_e
  head              2 d V_rows (the rows of the vocabulary held; the embedding is a gather)

A step requires three times its forward pass; recomputation is not required
work. The number a cell's ``mfu_pct`` uses is the one RECORDED in its
configuration's file:

  python -m benchmark.flops_deepseek_v3 --config <configuration>
"""
from __future__ import annotations

from typing import Dict


def core_per_position(m, seq_len: int) -> float:
    """Forward FLOPs of the attention core, per position and layer."""
    H = m["num_attention_heads"]
    return 2.0 * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]) * seq_len / 2.0


def forward_parts(m, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs per position by part, summed over the layers."""
    d, H, c = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    N, R, V = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    layers, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    n_e, f_e = layers - dense, m["moe_intermediate_size"]
    rows = m["num_experts_per_tok"] * m["experts_held"]["count"] / m["n_routed_experts"]
    return {
        "mla_proj": layers * (2.0 * d * H * (N + R) + 2.0 * d * (c + R) + 2.0 * c * H * (N + V) + 2.0 * H * V * d),
        "mla_core": layers * core_per_position(m, seq_len),
        "dense_mlp": dense * 6.0 * d * m["intermediate_size"],
        "moe_router": n_e * 2.0 * d * m["n_routed_experts"],
        "moe_shared": n_e * 6.0 * d * m["n_shared_experts"] * f_e,
        "moe_experts": n_e * rows * 6.0 * d * f_e,
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

"""The seam between the token models and the frame they share (``model/token_decoder.py``): each model's
variable tree (paths, shapes, dtypes) and ``stats`` tree at a small config are the literal tables recorded from the
tree BEFORE the frame was written once (PR 41's parent). Checkpoints, ``benchmark/references/`` and
``benchmark/tools/gradients_on_chip.py`` find weights by path, and ``lm_learner._flat_log`` turns ``stats`` into the
names the benchmark's ``check`` compares: a frame that renames, reorders or restacks anything fails here.
``jax.eval_shape`` only: nothing is compiled."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distar_tpu.model import TOKEN_MODELS  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

B, S = 2, 24
# every kind of layer a model has, once or twice, at widths of a few dozen
SMALL = {
    "lfm2_moe": {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "num_attention_heads": 4,
                 "num_key_value_heads": 2, "head_dim": 16, "layer_types": ["conv", "full_attention", "conv"],
                 "num_experts": 8, "num_experts_per_tok": 2, "experts_held": {"offset": 2, "count": 4},
                 "vocab_size": 128},
    "nemotron_h": {"hidden_size": 64, "hybrid_override_pattern": "M*EM", "mamba_num_heads": 8, "mamba_head_dim": 8,
                   "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
                   "moe_intermediate_size": 24,
                   "moe_shared_expert_intermediate_size": 48, "experts_held": {"offset": 2, "count": 4},
                   "vocab_size": 128},
    "deepseek_v3": {"hidden_size": 64, "num_hidden_layers": 2, "intermediate_size": 96, "moe_intermediate_size": 24,
                    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                    "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
                    "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128},
    "qwen3_next": {"hidden_size": 64, "num_hidden_layers": 2, "full_attention_interval": 2, "linear_num_key_heads": 2,
                   "linear_key_head_dim": 8, "linear_num_value_heads": 4, "linear_value_head_dim": 8,
                   "gdn_chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
                   "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 24,
                   "shared_expert_intermediate_size": 24, "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128},
    "laguna": {"hidden_size": 64, "intermediate_size": 96, "layer_types": ["full_attention", "sliding_attention"],
               "mlp_layer_types": ["dense", "sparse"], "num_attention_heads_per_layer": [4, 8],
               "num_key_value_heads": 4, "kv_heads_held": {"count": 2}, "head_dim": 16, "sliding_window": 5,
               "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 24,
               "shared_expert_intermediate_size": 24, "experts_held": {"offset": 2, "count": 4}, "vocab_size": 128},
}

# path -> (shape, dtype) of ``model.init``'s variables, as the parent of PR 41 built them
VARIABLES = {
    "lfm2_moe": {
        "buffers/layer_1/moe/expert_bias": ((8,), "float32"),
        "buffers/layer_2/moe/expert_bias": ((8,), "float32"),
        "params/embedding": ((128, 64), "float32"),
        "params/final_norm/scale": ((64,), "float32"),
        "params/layer_0/dense_mlp/w1/kernel": ((64, 96), "float32"),
        "params/layer_0/dense_mlp/w2/kernel": ((96, 64), "float32"),
        "params/layer_0/dense_mlp/w3/kernel": ((64, 96), "float32"),
        "params/layer_0/ffn_norm/scale": ((64,), "float32"),
        "params/layer_0/operator_norm/scale": ((64,), "float32"),
        "params/layer_0/short_conv/conv_kernel": ((3, 64), "float32"),
        "params/layer_0/short_conv/in_proj/kernel": ((64, 192), "float32"),
        "params/layer_0/short_conv/out_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attention/k_norm/scale": ((16,), "float32"),
        "params/layer_1/attention/k_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/attention/o_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attention/q_norm/scale": ((16,), "float32"),
        "params/layer_1/attention/q_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attention/v_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/moe/norm/scale": ((64,), "float32"),
        "params/layer_1/moe/router": ((64, 8), "float32"),
        "params/layer_1/moe/w1": ((4, 64, 32), "float32"),
        "params/layer_1/moe/w2": ((4, 32, 64), "float32"),
        "params/layer_1/moe/w3": ((4, 64, 32), "float32"),
        "params/layer_1/operator_norm/scale": ((64,), "float32"),
        "params/layer_2/moe/norm/scale": ((64,), "float32"),
        "params/layer_2/moe/router": ((64, 8), "float32"),
        "params/layer_2/moe/w1": ((4, 64, 32), "float32"),
        "params/layer_2/moe/w2": ((4, 32, 64), "float32"),
        "params/layer_2/moe/w3": ((4, 64, 32), "float32"),
        "params/layer_2/operator_norm/scale": ((64,), "float32"),
        "params/layer_2/short_conv/conv_kernel": ((3, 64), "float32"),
        "params/layer_2/short_conv/in_proj/kernel": ((64, 192), "float32"),
        "params/layer_2/short_conv/out_proj/kernel": ((64, 64), "float32"),
    },
    "nemotron_h": {
        "buffers/layer_2/moe/expert_bias": ((8,), "float32"),
        "params/embedding": ((128, 64), "float32"),
        "params/final_norm/scale": ((64,), "float32"),
        "params/layer_0/mamba/A_log": ((8,), "float32"),
        "params/layer_0/mamba/D": ((8,), "float32"),
        "params/layer_0/mamba/conv_bias": ((128,), "float32"),
        "params/layer_0/mamba/conv_kernel": ((4, 128), "float32"),
        "params/layer_0/mamba/dt_bias": ((8,), "float32"),
        "params/layer_0/mamba/gated_norm/scale": ((64,), "float32"),
        "params/layer_0/mamba/in_proj/kernel": ((64, 200), "float32"),
        "params/layer_0/mamba/out_proj/kernel": ((64, 64), "float32"),
        "params/layer_0/operator_norm/scale": ((64,), "float32"),
        "params/layer_1/attention/k_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/attention/o_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attention/q_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attention/v_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/operator_norm/scale": ((64,), "float32"),
        "params/layer_2/moe/norm/scale": ((64,), "float32"),
        "params/layer_2/moe/router": ((64, 8), "float32"),
        "params/layer_2/moe/shared_w1": ((64, 48), "float32"),
        "params/layer_2/moe/shared_w2": ((48, 64), "float32"),
        "params/layer_2/moe/w1": ((4, 64, 24), "float32"),
        "params/layer_2/moe/w2": ((4, 24, 64), "float32"),
        "params/layer_3/mamba/A_log": ((8,), "float32"),
        "params/layer_3/mamba/D": ((8,), "float32"),
        "params/layer_3/mamba/conv_bias": ((128,), "float32"),
        "params/layer_3/mamba/conv_kernel": ((4, 128), "float32"),
        "params/layer_3/mamba/dt_bias": ((8,), "float32"),
        "params/layer_3/mamba/gated_norm/scale": ((64,), "float32"),
        "params/layer_3/mamba/in_proj/kernel": ((64, 200), "float32"),
        "params/layer_3/mamba/out_proj/kernel": ((64, 64), "float32"),
        "params/layer_3/operator_norm/scale": ((64,), "float32"),
        "params/lm_head": ((64, 128), "float32"),
    },
    "deepseek_v3": {
        "buffers/layer_1/moe/expert_bias": ((8,), "float32"),
        "params/embedding": ((128, 64), "float32"),
        "params/final_norm/scale": ((64,), "float32"),
        "params/layer_0/dense_mlp/w1/kernel": ((64, 96), "float32"),
        "params/layer_0/dense_mlp/w2/kernel": ((96, 64), "float32"),
        "params/layer_0/dense_mlp/w3/kernel": ((64, 96), "float32"),
        "params/layer_0/ffn_norm/scale": ((64,), "float32"),
        "params/layer_0/mla/kv_a_proj/kernel": ((64, 40), "float32"),
        "params/layer_0/mla/kv_b_proj/kernel": ((32, 128), "float32"),
        "params/layer_0/mla/kv_norm/scale": ((32,), "float32"),
        "params/layer_0/mla/o_proj/kernel": ((64, 64), "float32"),
        "params/layer_0/mla/q_proj/kernel": ((64, 96), "float32"),
        "params/layer_0/operator_norm/scale": ((64,), "float32"),
        "params/layer_1/mla/kv_a_proj/kernel": ((64, 40), "float32"),
        "params/layer_1/mla/kv_b_proj/kernel": ((32, 128), "float32"),
        "params/layer_1/mla/kv_norm/scale": ((32,), "float32"),
        "params/layer_1/mla/o_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/mla/q_proj/kernel": ((64, 96), "float32"),
        "params/layer_1/moe/norm/scale": ((64,), "float32"),
        "params/layer_1/moe/router": ((64, 8), "float32"),
        "params/layer_1/moe/shared_w1": ((64, 48), "float32"),
        "params/layer_1/moe/shared_w2": ((48, 64), "float32"),
        "params/layer_1/moe/shared_w3": ((64, 48), "float32"),
        "params/layer_1/moe/w1": ((4, 64, 24), "float32"),
        "params/layer_1/moe/w2": ((4, 24, 64), "float32"),
        "params/layer_1/moe/w3": ((4, 64, 24), "float32"),
        "params/layer_1/operator_norm/scale": ((64,), "float32"),
        "params/lm_head": ((64, 128), "float32"),
    },
    "qwen3_next": {
        "buffers/layer_0/moe/expert_bias": ((8,), "float32"),
        "buffers/layer_1/moe/expert_bias": ((8,), "float32"),
        "params/embedding": ((128, 64), "float32"),
        "params/final_norm/w": ((64,), "float32"),
        "params/layer_0/gdn/A_log": ((4,), "float32"),
        "params/layer_0/gdn/conv_kernel": ((4, 64), "float32"),
        "params/layer_0/gdn/dt_bias": ((4,), "float32"),
        "params/layer_0/gdn/in_proj_ba/kernel": ((64, 8), "float32"),
        "params/layer_0/gdn/in_proj_qkvz/kernel": ((64, 96), "float32"),
        "params/layer_0/gdn/out_norm": ((8,), "float32"),
        "params/layer_0/gdn/out_proj/kernel": ((32, 64), "float32"),
        "params/layer_0/moe/norm/w": ((64,), "float32"),
        "params/layer_0/moe/router": ((64, 8), "float32"),
        "params/layer_0/moe/shared_gate": ((64,), "float32"),
        "params/layer_0/moe/shared_w1": ((64, 24), "float32"),
        "params/layer_0/moe/shared_w2": ((24, 64), "float32"),
        "params/layer_0/moe/shared_w3": ((64, 24), "float32"),
        "params/layer_0/moe/w1": ((4, 64, 24), "float32"),
        "params/layer_0/moe/w2": ((4, 24, 64), "float32"),
        "params/layer_0/moe/w3": ((4, 64, 24), "float32"),
        "params/layer_0/operator_norm/w": ((64,), "float32"),
        "params/layer_1/attention/k_norm/w": ((16,), "float32"),
        "params/layer_1/attention/k_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/attention/o_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attention/q_norm/w": ((16,), "float32"),
        "params/layer_1/attention/q_proj/kernel": ((64, 128), "float32"),
        "params/layer_1/attention/v_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/moe/norm/w": ((64,), "float32"),
        "params/layer_1/moe/router": ((64, 8), "float32"),
        "params/layer_1/moe/shared_gate": ((64,), "float32"),
        "params/layer_1/moe/shared_w1": ((64, 24), "float32"),
        "params/layer_1/moe/shared_w2": ((24, 64), "float32"),
        "params/layer_1/moe/shared_w3": ((64, 24), "float32"),
        "params/layer_1/moe/w1": ((4, 64, 24), "float32"),
        "params/layer_1/moe/w2": ((4, 24, 64), "float32"),
        "params/layer_1/moe/w3": ((4, 64, 24), "float32"),
        "params/layer_1/operator_norm/w": ((64,), "float32"),
        "params/lm_head": ((64, 128), "float32"),
    },
    "laguna": {
        "buffers/layer_1/moe/expert_bias": ((8,), "float32"),
        "params/embedding": ((128, 64), "float32"),
        "params/final_norm/scale": ((64,), "float32"),
        "params/layer_0/attn/g_proj/kernel": ((64, 2), "float32"),
        "params/layer_0/attn/k_norm/scale": ((16,), "float32"),
        "params/layer_0/attn/k_proj/kernel": ((64, 32), "float32"),
        "params/layer_0/attn/o_proj/kernel": ((32, 64), "float32"),
        "params/layer_0/attn/q_norm/scale": ((16,), "float32"),
        "params/layer_0/attn/q_proj/kernel": ((64, 32), "float32"),
        "params/layer_0/attn/v_proj/kernel": ((64, 32), "float32"),
        "params/layer_0/dense_mlp/w1/kernel": ((64, 96), "float32"),
        "params/layer_0/dense_mlp/w2/kernel": ((96, 64), "float32"),
        "params/layer_0/dense_mlp/w3/kernel": ((64, 96), "float32"),
        "params/layer_0/ffn_norm/scale": ((64,), "float32"),
        "params/layer_0/operator_norm/scale": ((64,), "float32"),
        "params/layer_1/attn/g_proj/kernel": ((64, 4), "float32"),
        "params/layer_1/attn/k_norm/scale": ((16,), "float32"),
        "params/layer_1/attn/k_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/attn/o_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attn/q_norm/scale": ((16,), "float32"),
        "params/layer_1/attn/q_proj/kernel": ((64, 64), "float32"),
        "params/layer_1/attn/v_proj/kernel": ((64, 32), "float32"),
        "params/layer_1/moe/norm/scale": ((64,), "float32"),
        "params/layer_1/moe/router": ((64, 8), "float32"),
        "params/layer_1/moe/shared_w1": ((64, 24), "float32"),
        "params/layer_1/moe/shared_w2": ((24, 64), "float32"),
        "params/layer_1/moe/shared_w3": ((64, 24), "float32"),
        "params/layer_1/moe/w1": ((4, 64, 24), "float32"),
        "params/layer_1/moe/w2": ((4, 24, 64), "float32"),
        "params/layer_1/moe/w3": ((4, 64, 24), "float32"),
        "params/layer_1/operator_norm/scale": ((64,), "float32"),
        "params/lm_head": ((64, 128), "float32"),
    },
}

# path -> (shape, dtype) of the ``stats`` a forward pass returns beside the logits, likewise
STATS = {
    "lfm2_moe": {
        "buffer_rows": ((), "int32"),
        "ff_rms": ((3,), "float32"),
        "overflow": ((), "int32"),
        "rms": ((3,), "float32"),
        "row_indexed": ((), "int32"),
        "rows": ((2, 4), "int32"),
    },
    "nemotron_h": {
        "buffer_rows": ((), "int32"),
        "mixer_rms": ((4,), "float32"),
        "overflow": ((), "int32"),
        "rms": ((4,), "float32"),
        "row_indexed": ((), "int32"),
        "rows": ((1, 4), "int32"),
        "ssm_state_rms/layer_0": ((), "float32"),
        "ssm_state_rms/layer_3": ((), "float32"),
    },
    "deepseek_v3": {
        "attn_rms": ((2,), "float32"),
        "buffer_rows": ((), "int32"),
        "ff_rms": ((2,), "float32"),
        "overflow": ((), "int32"),
        "rms": ((2,), "float32"),
        "row_indexed": ((), "int32"),
        "rows": ((1, 4), "int32"),
    },
    "qwen3_next": {
        "attn_gate_mean/layer_1": ((), "float32"),
        "buffer_rows": ((), "int32"),
        "ff_rms": ((2,), "float32"),
        "gdn_decay_mean/layer_0": ((), "float32"),
        "gdn_state_rms/layer_0": ((), "float32"),
        "mixer_rms": ((2,), "float32"),
        "overflow": ((), "int32"),
        "rms": ((2,), "float32"),
        "row_indexed": ((), "int32"),
        "rows": ((2, 4), "int32"),
    },
    "laguna": {
        "attn_gate_mean/layer_0": ((), "float32"),
        "attn_gate_mean/layer_1": ((), "float32"),
        "buffer_rows": ((), "int32"),
        "ff_rms": ((2,), "float32"),
        "mixer_rms": ((2,), "float32"),
        "overflow": ((), "int32"),
        "rms": ((2,), "float32"),
        "row_indexed": ((), "int32"),
        "rows": ((1, 4), "int32"),
    },
}


def flat(tree):
    return {"/".join(str(k.key) for k in path): (x.shape, str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def shapes_of(name, **over):
    """The variables, logits and stats of ``name``'s small model, as shapes."""
    cls, default = TOKEN_MODELS[name]
    model = cls(deep_merge_dicts(default(), dict(SMALL[name], **over)))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    variables = jax.eval_shape(lambda t: model.init(jax.random.PRNGKey(0), t), tokens)
    return variables, jax.eval_shape(model.apply, variables, tokens)


@pytest.mark.parametrize("name", list(TOKEN_MODELS))
def test_every_variable_has_the_parents_path_shape_and_dtype(name):
    assert flat(shapes_of(name)[0]) == VARIABLES[name]


@pytest.mark.parametrize("name", list(TOKEN_MODELS))
def test_the_stats_tree_has_the_parents_keys_shapes_and_dtypes(name):
    for remat in (True, False):
        _, (logits, stats) = shapes_of(name, remat=remat)
        assert (logits.shape, logits.dtype) == ((B, S, SMALL[name]["vocab_size"]), jnp.float32)
        assert flat(stats) == STATS[name]

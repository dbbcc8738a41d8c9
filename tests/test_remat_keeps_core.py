"""What a decoder layer's remat keeps (``model/token_decoder.py::decode``): the forward results of a full-causal
attention core (``ops.sequence.CORE_KEPT``: splash attention's ``out`` and ``logsumexp``, which only the
forward kernel can make), so the replay in the backward pass runs that kernel no second time; a banded
core, linear in the sequence, is replayed whole. Read from the gradient's jaxpr, where ``lax.platform_dependent``
holds the TPU branch beside the CPU's: nothing here needs a chip or compiles a kernel."""
import collections
import os
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distar_tpu.losses import compute_lm_loss  # noqa: E402
from distar_tpu.model import TOKEN_MODELS  # noqa: E402
from distar_tpu.model.config import cdtype, static_cfg  # noqa: E402
from distar_tpu.model.token_decoder import decode, rms  # noqa: E402
from distar_tpu.ops import sequence  # noqa: E402
from distar_tpu.utils import deep_merge_dicts  # noqa: E402

B, S, WINDOW = 2, 128, 32        # the kernel's smallest tile divides S, so the TPU branch is traced
CORES = ("CausalGQAttention", "LatentAttention", "DifferentialAttention")


class OneCoreLayer(nn.Module):
    """A norm and one attention module of the kind ``cfg.core`` names, around the residual stream."""

    cfg: Dict
    index: int

    @nn.compact
    def __call__(self, x):
        cfg, dtype = static_cfg(self.cfg), cdtype(self.cfg)
        u = sequence.RMSNorm(1e-5, name="norm")(x)
        if cfg.core == "CausalGQAttention":
            y = sequence.CausalGQAttention(4, 2, 16, dtype=dtype, window=cfg.window, name="attention")(u)
        elif cfg.core == "LatentAttention":
            y = sequence.LatentAttention(4, 32, 16, 8, 16, dtype=dtype, name="attention")(u)
        else:
            y, _, _ = sequence.DifferentialAttention(8, 4, 8, 0.5, dtype=dtype, window=cfg.window, name="attention")(u)
        return x + y, {"rms": rms(x)}


class OneCoreModel(nn.Module):
    cfg: Dict

    @nn.compact
    def __call__(self, tokens):
        return decode(self, tokens, OneCoreLayer, 1, eps=1e-5, stacked=("rms",))


def one_core(core, window=None, remat=True):
    cfg = {"core": core, "window": window, "remat": remat, "vocab_size": 64, "hidden_size": 32, "dtype": "float32"}
    return OneCoreModel(cfg)


def batch():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (B, S + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(ids[:, :-1]), "labels": jnp.asarray(ids[:, 1:])}


def loss_of(model, variables, data):
    def loss(params):
        logits, _ = model.apply({**variables, "params": params}, data["tokens"])
        return compute_lm_loss(logits, data["labels"])[0]

    return loss


def kernels_in(jaxpr, found=None):
    """The ``pallas_call``s of a jaxpr and of every jaxpr inside it (branches, remats, custom rules), by name."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    kernels_in(inner, found)
    return found


def forward_kernels(model):
    data = batch()
    variables = model.init(jax.random.PRNGKey(0), data["tokens"])
    found = kernels_in(jax.make_jaxpr(jax.grad(loss_of(model, variables, data)))(variables["params"]).jaxpr)
    return sum(n for name, n in found.items() if name.startswith("splash_mha_fwd"))


@pytest.mark.parametrize("core, window, runs", [
    ("CausalGQAttention", None, 1), ("LatentAttention", None, 1), ("DifferentialAttention", None, 1),
    # a window that covers the sequence is the whole triangle: ``causal_attention`` hands the core no window
    ("CausalGQAttention", S, 1),
    # under a band the forward kernel is linear in the sequence: replayed, as it was
    ("CausalGQAttention", WINDOW, 2), ("DifferentialAttention", WINDOW, 2),
])
def test_the_gradient_runs_a_full_causal_cores_forward_kernel_once_and_a_banded_cores_twice(core, window, runs):
    assert forward_kernels(one_core(core, window)) == runs


@pytest.mark.parametrize("core", CORES)
def test_without_the_policy_the_replay_runs_the_forward_kernel_again(core, monkeypatch):
    """What the parent's program holds, and what the name alone changes: nothing."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: None)
    assert forward_kernels(one_core(core)) == 2


@pytest.mark.parametrize("core", CORES)
def test_with_no_remat_there_is_one_forward_kernel_and_the_name_is_inert(core):
    assert forward_kernels(one_core(core, remat=False)) == 1


def loss_and_gradients(core, window, variables, data, remat=True):
    return jax.jit(jax.value_and_grad(loss_of(one_core(core, window, remat), variables, data)))(variables["params"])


@pytest.mark.parametrize("against", ["no_remat", "the_remat_with_no_policy"])
@pytest.mark.parametrize("core, window", [(core, None) for core in CORES] + [("CausalGQAttention", WINDOW)])
def test_loss_and_gradients_with_the_policy_are_those_without(core, window, against, monkeypatch):
    """Against the parent's remat (every layer replayed whole) bit for bit: what is kept is what the replay made.
    Against ``remat: false`` to float32's last places: XLA:CPU orders the sums of a program with a replay in it
    otherwise (grouped-query attention's leaves differ by 5e-9 of 1e-2 with either remat; the others not at all)."""
    data = batch()
    variables = one_core(core, window).init(jax.random.PRNGKey(1), data["tokens"])
    kept_loss, kept_grads = loss_and_gradients(core, window, variables, data)
    if against == "no_remat":
        loss, grads = loss_and_gradients(core, window, variables, data, remat=False)
        same = lambda a, b, path: np.testing.assert_allclose(b, a, rtol=0, atol=1e-5 * np.abs(a).max(), err_msg=path)
    else:
        monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: None)
        loss, grads = loss_and_gradients(core, window, variables, data)
        same = lambda a, b, path: np.testing.assert_array_equal(b, a, err_msg=path)
    assert np.isfinite(loss)
    same(np.asarray(loss), np.asarray(kept_loss), "loss")
    flat, other = jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(kept_grads)
    assert len(flat) == len(other) > 4
    for (path, a), b in zip(flat, other):
        assert np.any(np.asarray(a) != 0), jax.tree_util.keystr(path)
        same(np.asarray(a), np.asarray(b), jax.tree_util.keystr(path))


LAGUNA_SHAPED = {
    "hidden_size": 64, "intermediate_size": 96,
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 8, 8, 4, 8], "num_key_value_heads": 4, "kv_heads_held": {"count": 2},
    "head_dim": 16, "sliding_window": WINDOW, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24, "experts_held": {"offset": 2, "count": 4},
    "vocab_size": 128, "dtype": "bfloat16",
}
# a full layer of the stack: 2 of its 4 query heads are held here, each 16 values in bfloat16 and a float32 logsumexp a position
LAGUNA_CORE_BYTES = B * 2 * S * (16 * 2 + 4)


def test_the_counter_reads_the_named_cores_of_a_laguna_shaped_stack_and_their_bytes():
    model_cls, defaults = TOKEN_MODELS["laguna"]
    model = model_cls(deep_merge_dicts(defaults(), LAGUNA_SHAPED))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    with sequence.cores_kept() as kept:
        jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    assert kept == [LAGUNA_CORE_BYTES] * 2                      # 2 full, 3 banded -> 2
    # outside the ``with`` nothing is counted, and a second count starts empty
    jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    assert kept == [LAGUNA_CORE_BYTES] * 2
    with sequence.cores_kept() as again:
        pass
    assert again == []


def test_the_learner_puts_the_count_and_the_bytes_in_its_registry_and_its_log(tmp_path):
    from distar_tpu.learner.lm_learner import LMLearner

    lrn = LMLearner({
        "common": {"experiment_name": "kept", "save_path": str(tmp_path / "kept")},
        "learner": {"batch_size": B, "unroll_len": S, "save_freq": 100000},
        "model": dict(LAGUNA_SHAPED, model_type="laguna"),
    })
    read = {fam["name"]: [inst.value for _, inst in fam["series"]] for fam in lrn.metrics.collect()
            if fam["name"].startswith("distar_lm_cores_kept")}
    assert read == {"distar_lm_cores_kept": [2], "distar_lm_cores_kept_bytes": [2 * LAGUNA_CORE_BYTES]}
    with open(os.path.join(str(tmp_path / "kept"), "logs", f"{lrn.name}_rank0.log")) as f:
        assert f"2 full-causal attention cores name their forward results (out and logsumexp, {2 * LAGUNA_CORE_BYTES} bytes)" in f.read()

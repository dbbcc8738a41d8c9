"""A pool of seeded RL trajectory batches, generated without per-(t, b) loops.

A vectorised copy of ``distar_tpu.learner.data.fake_rl_batch`` (time-major
layout, near-deterministic fake teacher on the label positions, the rest as
the original draws it). See ``sl_pool`` for why the original is not used.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from distar_tpu.learner.data import RL_REWARD_FIELDS
from distar_tpu.lib import actions as A
from distar_tpu.lib import features as F

from .sl_pool import cycle, selected_units, zero_obs  # noqa: F401  (cycle: the drivers' iterator)


def _near_onehot(labels: np.ndarray, classes: int) -> np.ndarray:
    """40 on the label, -20 elsewhere: a teacher whose mass sits where the
    target keeps finite logits (random logits on masked slots blow the KL up)."""
    out = np.full(labels.shape + (classes,), -20.0, np.float32)
    np.put_along_axis(out, labels[..., None], 20.0, axis=-1)
    return out


def rl_batch(rng: np.random.Generator, batch_size: int, unroll_len: int, p: dict,
             hidden_size: int, hidden_layers: int) -> Dict:
    T, B, S, N = unroll_len, batch_size, F.MAX_SELECTED_UNITS_NUM, F.MAX_ENTITY_NUM
    lo, hi = p["entity_num"]
    entity_num = np.maximum(rng.integers(lo, hi + 1, (T + 1, B)), p["entity_num_floor"])
    lo, hi = p["selected_units_num"]
    sun = rng.integers(lo, hi + 1, (T, B))
    actions = {
        "action_type": rng.integers(0, A.NUM_ACTIONS, (T, B)),
        "delay": rng.integers(0, F.MAX_DELAY + 1, (T, B)),
        "queued": rng.integers(0, 2, (T, B)),
        "selected_units": selected_units(rng, sun, entity_num[:T]),
        "target_unit": rng.integers(0, 8, (T, B)),
        "target_location": rng.integers(0, F.SPATIAL_SIZE[0] * F.SPATIAL_SIZE[1], (T, B)),
    }
    teacher = {
        k: rng.standard_normal((T, B) + shape, np.float32)
        for k, shape in F.LOGIT_SHAPES.items() if k not in ("selected_units", "target_unit")
    }
    teacher["selected_units"] = _near_onehot(actions["selected_units"], N + 1)
    teacher["target_unit"] = _near_onehot(actions["target_unit"], N)
    ones = lambda: np.ones((T, B), np.float32)
    return {
        **zero_obs((T + 1, B)),
        "entity_num": entity_num,
        "hidden_state": tuple(
            (np.zeros((B, hidden_size), np.float32), np.zeros((B, hidden_size), np.float32))
            for _ in range(hidden_layers)),
        "action_info": actions,
        "selected_units_num": sun,
        "behaviour_logp": {
            k: -np.abs(rng.standard_normal(
                (T, B) + ((S,) if k == "selected_units" else ()), np.float32))
            for k in F.ACTION_HEADS},
        "teacher_logit": teacher,
        "reward": {f: rng.integers(-1, 2, (T, B)).astype(np.float32) for f in RL_REWARD_FIELDS},
        "step": rng.integers(0, 10000, (T, B)).astype(np.float32),
        "done": np.zeros((T, B), np.float32),
        "mask": {
            "actions_mask": {k: ones() for k in F.ACTION_HEADS},
            "selected_units_mask": np.arange(S)[None, None] < sun[..., None],
            "build_order_mask": ones(), "built_unit_mask": ones(),
            "effect_mask": ones(), "cum_action_mask": ones(), "step_mask": ones(),
        },
        "model_last_iter": np.zeros((B,), np.float32),
    }


def build(seed: int, params: dict, model_cfg=None, **_) -> List[Dict]:
    core = model_cfg["encoder"]["core_lstm"]
    rng = np.random.default_rng(seed)
    return [rl_batch(rng, params["batch_size"], params["unroll_len"], params,
                     core["hidden_size"], core["num_layers"])
            for _ in range(params["pool"])]

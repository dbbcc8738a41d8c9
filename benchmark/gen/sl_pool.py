"""A pool of seeded SL batches, generated without a per-frame Python loop.

A vectorised copy of ``distar_tpu.learner.data.fake_sl_batch`` (same
schema, same distributions: zeroed features, ``entity_num`` uniform with a
floor, 2-6 selected units drawn as distinct indices followed by the end
token, uniform labels). The original draws frame by frame, which a live run
would time instead of the learner; it is listed for deletion in PERF.md.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

from distar_tpu.lib import actions as A
from distar_tpu.lib import features as F


def zero_obs(lead: tuple) -> Dict:
    """Schema-complete zeroed observation fields with leading dims ``lead``."""
    spatial = {
        k: np.zeros(lead + ((F.EFFECT_LENGTH,) if k.startswith("effect_") else F.SPATIAL_SIZE), dt)
        for k, dt in F.SPATIAL_INFO.items()
    }
    scalar = {k: np.zeros(lead + shape, dt) for k, (dt, shape) in F.SCALAR_INFO.items()}
    entity = {k: np.zeros(lead + (F.MAX_ENTITY_NUM,), dt) for k, dt in F.ENTITY_INFO.items()}
    return {"spatial_info": spatial, "scalar_info": scalar, "entity_info": entity}


def selected_units(rng: np.random.Generator, sun: np.ndarray, entity_num: np.ndarray,
                   candidates: int = 8) -> np.ndarray:
    """``sun-1`` distinct unit indices below ``candidates``, then the end
    token (== entity_num), zero after it: the pointer mask forbids picking a
    unit twice, so repeated labels would sit on -1e9 logits."""
    S = F.MAX_SELECTED_UNITS_NUM
    perm = np.argsort(rng.random(sun.shape + (candidates,)), axis=-1)
    su = np.zeros(sun.shape + (S,), np.int64)
    su[..., :candidates] = perm
    pos = np.arange(S)
    su = np.where(pos < (sun[..., None] - 1), su, 0)
    return np.where(pos == (sun[..., None] - 1), entity_num[..., None], su)


def sl_batch(rng: np.random.Generator, batch_size: int, unroll_len: int, p: dict) -> Dict:
    n = batch_size * unroll_len
    lo, hi = p["entity_num"]
    entity_num = np.maximum(rng.integers(lo, hi + 1, (n,)), p["entity_num_floor"])
    lo, hi = p["selected_units_num"]
    sun = rng.integers(lo, hi + 1, (n,))
    return {
        **zero_obs((n,)),
        "entity_num": entity_num,
        "action_info": {
            "action_type": rng.integers(0, A.NUM_ACTIONS, (n,)),
            "delay": rng.integers(0, F.MAX_DELAY + 1, (n,)),
            "queued": rng.integers(0, 2, (n,)),
            "selected_units": selected_units(rng, sun, entity_num),
            "target_unit": rng.integers(0, 8, (n,)),
            "target_location": rng.integers(0, F.SPATIAL_SIZE[0] * F.SPATIAL_SIZE[1], (n,)),
        },
        "action_mask": {k: np.ones((n,), np.float32) for k in F.ACTION_HEADS},
        "selected_units_num": sun,
        "new_episodes": np.zeros((batch_size,), bool),
        "traj_lens": np.full((batch_size,), unroll_len, np.int64),
    }


def build(seed: int, params: dict, **_) -> List[Dict]:
    """``params['pool']`` batches from ``seed``; the same seed gives the same bytes."""
    rng = np.random.default_rng(seed)
    return [sl_batch(rng, params["batch_size"], params["unroll_len"], params)
            for _ in range(params["pool"])]


def cycle(pool: List[Dict]) -> Iterator[Dict]:
    """Serve the pool round-robin, for ever. Each batch goes out as a fresh
    top-level dict: the learner pops host fields off what it is given."""
    i = 0
    while True:
        yield dict(pool[i % len(pool)])
        i += 1

"""Learner-facing trajectory batch schema + fake dataloaders.

The RL trajectory batch layout (time-major, mirroring the reference's
(T+1, B)-flattened learner batches, rl_dataloader.py:45-76):

  obs fields                [T+1, B, ...]   (T+1: the last step bootstraps)
  hidden_state              tuple of (h, c), each [B, H]
  action_info[head]         [T, B(, S)]
  selected_units_num        [T, B]
  behaviour_logp[head]      [T, B(, S)]
  teacher_logit[head]       [T, B, ...]
  reward[field]             [T, B]
  step                      [T, B]
  done                      [T, B]  (1 from the terminal step onward)
  mask                      dict (see losses.rl_loss)
  model_last_iter           [B]

Fake dataloaders (role of the reference FakeDataloader, rl_learner.py:196)
produce schema-complete random batches for learner job_type 'train_test'.
"""
from __future__ import annotations

from typing import Dict, Iterator, Optional

import numpy as np

from ..lib import actions as A
from ..lib import features as F

RL_REWARD_FIELDS = ("winloss", "build_order", "built_unit", "effect", "upgrade", "battle")


def fake_rl_batch(
    batch_size: int,
    unroll_len: int,
    rng: Optional[np.random.Generator] = None,
    hidden_size: int = 384,
    hidden_layers: int = 3,
    use_value_feature: bool = False,
) -> Dict:
    """Schema-complete random RL trajectory batch (numpy, host-side)."""
    rng = rng or np.random.default_rng(0)
    T, B, S, N = unroll_len, batch_size, F.MAX_SELECTED_UNITS_NUM, F.MAX_ENTITY_NUM

    obs = F.batch_tree(
        [
            F.batch_tree([F.fake_step_data(train=False, rng=rng) for _ in range(B)])
            for _ in range(T + 1)
        ],
        stack=np.stack,
    )
    entity_num = np.maximum(obs["entity_num"], 8)

    sun = rng.integers(2, 7, (T, B))

    def head_actions():
        # selected-units rows must be DISTINCT units followed by the end
        # token (== entity_num) — the pointer mask forbids re-selecting a
        # unit, so repeated fake labels would sit on -1e9 logits
        su = np.zeros((T, B, S), np.int64)
        for t in range(T):
            for b in range(B):
                n = sun[t, b]
                su[t, b, : n - 1] = rng.permutation(8)[: n - 1]
                su[t, b, n - 1] = entity_num[t, b]  # end flag
        return {
            "action_type": rng.integers(0, A.NUM_ACTIONS, (T, B)),
            "delay": rng.integers(0, F.MAX_DELAY + 1, (T, B)),
            "queued": rng.integers(0, 2, (T, B)),
            "selected_units": su,
            "target_unit": rng.integers(0, 8, (T, B)),
            "target_location": rng.integers(0, F.SPATIAL_SIZE[0] * F.SPATIAL_SIZE[1], (T, B)),
        }

    logit_shapes = dict(F.LOGIT_SHAPES)
    teacher_logit = {
        k: rng.standard_normal((T, B) + shape).astype(np.float32)
        for k, shape in logit_shapes.items()
    }
    actions = head_actions()
    # a real teacher runs the same teacher-forced masking as the learner, so
    # its mass sits on positions the target keeps finite. Random fake logits
    # on target-masked slots make the KL explode (p_teacher * 1e9), so make
    # the fake teacher near-deterministic on the label positions.
    su_onehot = np.eye(N + 1, dtype=np.float32)[actions["selected_units"]]
    teacher_logit["selected_units"] = (40.0 * su_onehot - 20.0).astype(np.float32)
    tu_onehot = np.eye(N, dtype=np.float32)[actions["target_unit"]]
    teacher_logit["target_unit"] = (40.0 * tu_onehot - 20.0).astype(np.float32)
    behaviour_logp = {
        k: -np.abs(rng.standard_normal((T, B) + ((S,) if k == "selected_units" else ()))).astype(
            np.float32
        )
        for k in F.ACTION_HEADS
    }
    masks = {
        "actions_mask": {k: np.ones((T, B), np.float32) for k in F.ACTION_HEADS},
        "selected_units_mask": (np.arange(S)[None, None] < sun[..., None]),
        "build_order_mask": np.ones((T, B), np.float32),
        "built_unit_mask": np.ones((T, B), np.float32),
        "effect_mask": np.ones((T, B), np.float32),
        "cum_action_mask": np.ones((T, B), np.float32),
        "step_mask": np.ones((T, B), np.float32),
    }
    rewards = {
        f: rng.integers(-1, 2, (T, B)).astype(np.float32) for f in RL_REWARD_FIELDS
    }
    extra = {}
    if use_value_feature:
        extra["value_feature"] = F.batch_tree(
            [
                F.batch_tree([F.fake_value_feature(rng) for _ in range(B)])
                for _ in range(T + 1)
            ]
        )
    return {
        **extra,
        "spatial_info": obs["spatial_info"],
        "entity_info": obs["entity_info"],
        "scalar_info": obs["scalar_info"],
        "entity_num": entity_num,
        "hidden_state": tuple(
            (
                np.zeros((B, hidden_size), np.float32),
                np.zeros((B, hidden_size), np.float32),
            )
            for _ in range(hidden_layers)
        ),
        "action_info": actions,
        "selected_units_num": sun,
        "behaviour_logp": behaviour_logp,
        "teacher_logit": teacher_logit,
        "reward": rewards,
        "step": rng.integers(0, 10000, (T, B)).astype(np.float32),
        "done": np.zeros((T, B), np.float32),
        "mask": masks,
        "model_last_iter": np.zeros((B,), np.float32),
    }


def fake_sl_batch(
    batch_size: int,
    unroll_len: int,
    rng: Optional[np.random.Generator] = None,
) -> Dict:
    """SL batch: [B*T] flat obs + labels, batch-major trajectories."""
    rng = rng or np.random.default_rng(0)
    B, T, S = batch_size, unroll_len, F.MAX_SELECTED_UNITS_NUM
    n = B * T
    obs = F.batch_tree([F.fake_step_data(train=False, rng=rng) for _ in range(n)])
    entity_num = np.maximum(obs["entity_num"], 8)
    sun = rng.integers(2, 7, (n,))
    su = np.zeros((n, S), np.int64)
    for i in range(n):
        # distinct units then the end token (see fake_rl_batch)
        su[i, : sun[i] - 1] = rng.permutation(8)[: sun[i] - 1]
        su[i, sun[i] - 1] = entity_num[i]
    return {
        "spatial_info": obs["spatial_info"],
        "entity_info": obs["entity_info"],
        "scalar_info": obs["scalar_info"],
        "entity_num": entity_num,
        "action_info": {
            "action_type": rng.integers(0, A.NUM_ACTIONS, (n,)),
            "delay": rng.integers(0, F.MAX_DELAY + 1, (n,)),
            "queued": rng.integers(0, 2, (n,)),
            "selected_units": su,
            "target_unit": rng.integers(0, 8, (n,)),
            "target_location": rng.integers(0, F.SPATIAL_SIZE[0] * F.SPATIAL_SIZE[1], (n,)),
        },
        "action_mask": {k: np.ones((n,), np.float32) for k in F.ACTION_HEADS},
        "selected_units_num": sun,
        "new_episodes": np.zeros((B,), bool),
        "traj_lens": np.full((B,), T, np.int64),
    }


class FakeRLDataloader:
    """Infinite iterator of fake RL batches (learner job_type 'train_test')."""

    def __init__(self, batch_size: int, unroll_len: int, hidden_size: int = 384,
                 hidden_layers: int = 3, seed: int = 0, use_value_feature: bool = False):
        self._rng = np.random.default_rng(seed)
        self._kwargs = dict(
            batch_size=batch_size, unroll_len=unroll_len,
            hidden_size=hidden_size, hidden_layers=hidden_layers,
            use_value_feature=use_value_feature,
        )

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        return fake_rl_batch(rng=self._rng, **self._kwargs)


class FakeSLDataloader:
    def __init__(self, batch_size: int, unroll_len: int, seed: int = 0):
        self._rng = np.random.default_rng(seed)
        self._batch_size = batch_size
        self._unroll_len = unroll_len

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        return fake_sl_batch(self._batch_size, self._unroll_len, rng=self._rng)


def cap_entities(batch: Dict, n: int) -> Dict:
    """Slice a (host) SL batch's entity axis to the first ``n`` slots.

    The pad-to-bucket throughput lever (SURVEY §7 hard part 5): the entity
    transformer and pointer decode are O(N^2)/O(N) in the PADDED entity
    count, and real decoded frames rarely exceed ~300 entities, so training
    at the reference's MAX_ENTITY_NUM=512 pad wastes most of the set-
    attention FLOPs. Every model shape derives from the input, and padded
    rows are masked out of every reduction, so for samples with
    entity_num <= n the sliced batch is numerically EXACT (tested).

    Samples above the cap follow the reference's own cap semantics
    (truncate at the ceiling), with affected heads masked out of the loss
    rather than mislabeled: entity_num clamps to n, end-token labels remap
    to the new end slot, and any selected_units/target_unit label that
    referenced a dropped entity zeroes that head's action_mask for the
    step (no loss contribution).
    """
    entity_info = {k: v[:, :n] for k, v in batch["entity_info"].items()}
    old_num = np.asarray(batch["entity_num"])
    new_num = np.minimum(old_num, n)

    ai = dict(batch["action_info"])
    am = dict(batch["action_mask"])
    su = np.asarray(ai["selected_units"])
    was_end = su == old_num[..., None]
    dropped = (su >= new_num[..., None]) & ~was_end
    ai["selected_units"] = np.where(was_end | dropped, new_num[..., None], su)
    su_mask = np.asarray(am["selected_units"])
    am["selected_units"] = np.where(dropped.any(-1), 0.0, su_mask).astype(su_mask.dtype)

    tu = np.asarray(ai["target_unit"])
    tu_bad = tu >= new_num
    ai["target_unit"] = np.where(tu_bad, 0, tu)
    tu_mask = np.asarray(am["target_unit"])
    am["target_unit"] = np.where(tu_bad, 0.0, tu_mask).astype(tu_mask.dtype)

    return dict(
        batch,
        entity_info=entity_info,
        entity_num=new_num,
        action_info=ai,
        action_mask=am,
    )


def cap_entities_rl(batch: Dict, n: int) -> Dict:
    """RL-layout counterpart of :func:`cap_entities` (time-major batches:
    obs [T+1, B, N, ...], actions/teacher logits [T, B, ...]).

    Same contract: numerically exact for samples with entity_num <= n —
    model shapes derive from inputs, masked rows vanish from every
    reduction, and within the cap a teacher's sliced logit tail carries
    ~zero mass. ABOVE the cap the teacher's sliced distribution would
    renormalize over a truncated candidate set (a biased KL), so overflow
    steps zero their selected_units/target_unit action masks entirely —
    no loss contribution rather than a distorted one.
    """
    entity_info = {k: v[:, :, :n] for k, v in batch["entity_info"].items()}
    old_num = np.asarray(batch["entity_num"])          # [T+1, B]
    new_num = np.minimum(old_num, n)
    act_num_old = old_num[:-1]                         # the acted steps
    act_num_new = new_num[:-1]
    overflow = act_num_old > n                         # [T, B]

    ai = dict(batch["action_info"])
    su = np.asarray(ai["selected_units"])              # [T, B, S]
    was_end = su == act_num_old[..., None]
    # clamp EVERY out-of-range lane (post-end sampled junk included: left
    # >= n it would gather out of bounds in the sliced pointer decode)
    oob = (su >= act_num_new[..., None]) & ~was_end
    ai["selected_units"] = np.where(was_end | oob, act_num_new[..., None], su)
    tu = np.asarray(ai["target_unit"])                 # [T, B]
    tu_bad = tu >= act_num_new
    ai["target_unit"] = np.where(tu_bad, 0, tu)

    mask = {k: (dict(v) if isinstance(v, dict) else v) for k, v in batch["mask"].items()}
    am = mask["actions_mask"]
    su_mask = np.asarray(am["selected_units"])
    am["selected_units"] = np.where(overflow, 0.0, su_mask).astype(su_mask.dtype)
    tu_mask = np.asarray(am["target_unit"])
    am["target_unit"] = np.where(overflow | tu_bad, 0.0, tu_mask).astype(tu_mask.dtype)

    teacher = dict(batch["teacher_logit"])
    teacher["selected_units"] = np.asarray(teacher["selected_units"])[..., : n + 1]
    teacher["target_unit"] = np.asarray(teacher["target_unit"])[..., :n]

    out = dict(
        batch,
        entity_info=entity_info,
        entity_num=new_num,
        action_info=ai,
        mask=mask,
        teacher_logit=teacher,
    )
    if "successive_logit" in batch:  # DAPO carries the same logit layout
        succ = dict(batch["successive_logit"])
        succ["selected_units"] = np.asarray(succ["selected_units"])[..., : n + 1]
        succ["target_unit"] = np.asarray(succ["target_unit"])[..., :n]
        out["successive_logit"] = succ
    return out

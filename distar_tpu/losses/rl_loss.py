"""League-RL loss: per-head V-trace PG + UPGO + TD(lambda) critics + entropy
+ teacher-KL (+ optional DAPO successive-policy KL).

Pure-jnp equivalent of the reference ReinforcementLoss
(reference: distar/agent/default/rl_training/rl_loss.py:33-185 and
as_rl_utils.py:1-127), jit-safe end to end: every branch in the reference's
Python control flow is either a static config switch or a masked arithmetic
path here. Default weights mirror default_reinforcement_loss.yaml.

Input layout (time-major):
  target_logit[head]      [T, B, ...]      learner policy logits
  value[field]            [T+1, B]         baseline values
  action_log_prob[head]   [T, B] / [T,B,S] behaviour log-probs (actor-side)
  teacher_logit[head]     [T, B, ...]
  action[head]            [T, B] / [T,B,S]
  reward[field]           [T, B]
  step                    [T, B]           game steps
  mask:
    actions_mask[head]    [T, B]   per-step head applicability
    selected_units_mask   [T, B, S]
    step_mask             [T, B]   1 real step / 0 pad step (optional)
    build_order_mask, built_unit_mask, effect_mask, cum_action_mask  [T, B]
  done                    [T, B]   1 from the terminal step onward (optional)
  entity_num              [T, B]   for entropy normalisation
  selected_units_num      [T, B]
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import upgo_returns, vtrace_advantages, generalized_lambda_returns

HEADS = ("action_type", "delay", "queued", "selected_units", "target_unit", "target_location")
# heads whose losses are always active (the rest gate on actions_mask)
ALWAYS_ON = ("action_type", "delay")
# the reward/value fields of the info grid (pg/{field}/{head}, td/{field},
# reward/{field}, value/{field}) — the obs layer's bounded label vocabulary
# for the distar_train_loss_* gauges lives HERE, next to the keys it names
REWARD_FIELDS = ("winloss", "build_order", "built_unit", "effect", "upgrade",
                 "battle")
# loss-term prefixes the info dict produces ("{term}/total" and, for the
# per-head terms, "{term}/{head}")
LOSS_TERMS = ("pg", "upgo", "td", "entropy", "kl", "dapo")
FIELD_MASKS = {"build_order": "build_order_mask", "built_unit": "built_unit_mask", "effect": "effect_mask"}


def _default_head_weights(selected_units: float = 0.01) -> Dict[str, float]:
    return {h: (selected_units if h == "selected_units" else 1.0) for h in HEADS}


@dataclasses.dataclass(frozen=True)
class ReinforcementLossConfig:
    """Mirrors default_reinforcement_loss.yaml."""

    baseline_weights: Tuple[Tuple[str, float], ...] = (
        ("winloss", 10.0), ("build_order", 0.0), ("built_unit", 0.0),
        ("effect", 0.0), ("upgrade", 0.0), ("battle", 0.0),
    )
    pg_weights: Tuple[Tuple[str, float], ...] = (
        ("winloss", 1.0), ("build_order", 0.0), ("built_unit", 0.0),
        ("effect", 0.0), ("upgrade", 0.0), ("battle", 0.0),
    )
    upgo_weight: float = 1.0
    kl_weight: float = 0.02
    action_type_kl_weight: float = 0.1
    entropy_weight: float = 1e-4
    dapo_weight: float = 0.0
    gammas: Tuple[Tuple[str, float], ...] = (
        ("winloss", 1.0), ("build_order", 1.0), ("built_unit", 1.0),
        ("effect", 1.0), ("upgrade", 1.0), ("battle", 0.997),
    )
    td_lambda: float = 0.8
    vtrace_lambda: float = 1.0
    pg_gamma: float = 1.0  # reference passes gamma=1.0 into the PG vtrace
    action_type_kl_steps: int = 2400
    dapo_steps: int = 2400
    use_dapo: bool = False
    only_update_value: bool = False
    selected_units_head_weight: float = 0.01

    def head_weights(self) -> Dict[str, float]:
        return _default_head_weights(self.selected_units_head_weight)


def _log_softmax(logits):
    return jax.nn.log_softmax(logits, axis=-1)


def _gather(logp, action):
    return jnp.take_along_axis(logp, action[..., None].astype(jnp.int32), axis=-1)[..., 0]


def compute_rl_loss(
    inputs: Dict,
    cfg: ReinforcementLossConfig = ReinforcementLossConfig(),
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    target_logit = inputs["target_logit"]
    values = dict(inputs["value"])
    behaviour_logp = inputs["action_log_prob"]
    teacher_logit = inputs["teacher_logit"]
    masks = inputs["mask"]
    actions = inputs["action"]
    rewards = inputs["reward"]
    steps = inputs["step"]
    entity_num = inputs["entity_num"]
    su_mask = masks["selected_units_mask"]

    info: Dict[str, jnp.ndarray] = {}

    vdtype = values[next(iter(values))].dtype
    # step_mask: 1 on real steps, 0 on the pad steps that fill a trajectory
    # window after a mid-window episode end. Padded steps must contribute to
    # NO loss term (incl. the always-on action_type/delay heads) and their
    # post-terminal values are 0 by definition.
    step_mask = masks.get("step_mask")
    if step_mask is None:
        step_mask = jnp.ones_like(rewards["winloss"], dtype=vdtype)
    else:
        step_mask = step_mask.astype(vdtype)
    # explicit done flag [T, B] (1 from the terminal step onward): zero the
    # bootstrap value when the episode ended anywhere in this window — the
    # reference zeroes it on done (rl_loss.py:47-49); inferring done from
    # reward[-1]==0 breaks when the terminal +-1 sits mid-window before pads.
    done = inputs.get("done")
    if done is None:
        not_done = (rewards["winloss"][-1] == 0).astype(vdtype)
    else:
        not_done = 1.0 - done[-1].astype(vdtype)
    for field in values:
        v = values[field]
        v = v.at[:-1].multiply(step_mask)  # post-terminal states have value 0
        v = v.at[-1].multiply(not_done)
        values[field] = v

    # per-head distribution prep
    target_logp_full: Dict[str, jnp.ndarray] = {}
    target_prob_full: Dict[str, jnp.ndarray] = {}
    target_action_logp: Dict[str, jnp.ndarray] = {}
    clipped_rhos: Dict[str, jnp.ndarray] = {}
    for head in HEADS:
        logp_full = _log_softmax(target_logit[head])
        target_logp_full[head] = logp_full
        target_prob_full[head] = jnp.exp(logp_full)
        alogp = _gather(logp_full, actions[head])
        blogp = behaviour_logp[head]
        if head == "selected_units":
            alogp = jnp.where(su_mask, alogp, 0.0).sum(-1)
            log_rho = jax.lax.stop_gradient(
                (jnp.where(su_mask, _gather(logp_full, actions[head]) - blogp, 0.0)).sum(-1)
            )
        else:
            log_rho = jax.lax.stop_gradient(alogp - blogp)
        target_action_logp[head] = alogp
        clipped_rhos[head] = jnp.minimum(jnp.exp(log_rho), 1.0)

    head_w = cfg.head_weights()
    gammas = dict(cfg.gammas)

    # ------------------------------------------------ policy gradient (vtrace)
    with jax.named_scope("vtrace"):
        total_pg = 0.0
        for field, field_w in cfg.pg_weights:
            if field not in values or field not in rewards:
                continue
            reward = rewards[field].astype(jnp.float32)
            baseline = values[field]
            field_pg = 0.0
            for head in HEADS:
                adv = jax.lax.stop_gradient(
                    vtrace_advantages(
                        clipped_rhos[head], clipped_rhos[head], reward, baseline,
                        gammas=cfg.pg_gamma, lambda_=cfg.vtrace_lambda,
                    )
                )
                pg = -adv * target_action_logp[head] * step_mask
                if head not in ALWAYS_ON:
                    pg = pg * masks["actions_mask"][head]
                if field in FIELD_MASKS:
                    pg = pg * masks[FIELD_MASKS[field]]
                pg = pg.mean()
                field_pg += pg * head_w[head]
                info[f"pg/{field}/{head}"] = pg
            total_pg += field_w * field_pg
        info["pg/total"] = total_pg

    # ------------------------------------------------------------------ UPGO
    with jax.named_scope("upgo"):
        total_upgo = 0.0
        upgo_adv_base = jax.lax.stop_gradient(
            upgo_returns(rewards["winloss"].astype(jnp.float32), values["winloss"])
            - values["winloss"][:-1]
        )
        for head in HEADS:
            adv = clipped_rhos[head] * upgo_adv_base
            ug = -adv * target_action_logp[head] * step_mask
            if head not in ALWAYS_ON:
                ug = ug * masks["actions_mask"][head]
            ug = ug.mean()
            total_upgo += ug * head_w[head]
            info[f"upgo/{head}"] = ug
        total_upgo = total_upgo * cfg.upgo_weight
        info["upgo/total"] = total_upgo

    # ---------------------------------------------------------------- critic
    with jax.named_scope("td"):
        total_critic = 0.0
        for field, field_w in cfg.baseline_weights:
            if field not in values or field not in rewards:
                continue
            reward = rewards[field].astype(jnp.float32)
            baseline = values[field]
            returns = jax.lax.stop_gradient(
                generalized_lambda_returns(reward, gammas[field], baseline, cfg.td_lambda)
            )
            td = 0.5 * jnp.square(returns - baseline[:-1]) * step_mask
            if field in FIELD_MASKS:
                td = td * masks[FIELD_MASKS[field]]
            td = td.mean()
            total_critic += field_w * td
            info[f"td/{field}"] = td
            info[f"reward/{field}"] = reward.mean()
            info[f"value/{field}"] = baseline.mean()
        info["td/total"] = total_critic

    # --------------------------------------------------------------- entropy
    with jax.named_scope("entropy"):
        total_entropy_loss = 0.0
        for head in HEADS:
            ent = -target_prob_full[head] * target_logp_full[head]
            if head == "selected_units":
                # normalise by log(valid candidates + 1) and average over real steps
                norm = jnp.log(entity_num.astype(jnp.float32) + 1.0 + 1e-9)[..., None]
                ent = ent.sum(-1) / norm
                ent = (ent * su_mask).sum(-1) / (su_mask.sum(-1) + 1e-9)
            elif head == "target_unit":
                # log(num_valid_targets + 1) (reference as_rl_utils.py:59-61);
                # the +1 inside the log also guards entity_num == 1
                ent = ent.sum(-1) / (jnp.log(entity_num.astype(jnp.float32) + 1.0) + 1e-9)
            else:
                ent = ent.sum(-1) / jnp.log(float(ent.shape[-1]))
            ent = ent * step_mask
            if head not in ALWAYS_ON:
                ent = ent * masks["actions_mask"][head]
            ent_mean = ent.mean()
            info[f"entropy/{head}"] = ent_mean
            total_entropy_loss += -ent_mean * head_w[head]
        total_entropy_loss = total_entropy_loss * cfg.entropy_weight
        info["entropy/total"] = total_entropy_loss

    # -------------------------------------------------------------------- KL
    with jax.named_scope("kl"):
        def _kl_terms(ref_logit):
            out = {}
            for head in HEADS:
                ref_logp = _log_softmax(ref_logit[head])
                kl = (jnp.exp(ref_logp) * (ref_logp - target_logp_full[head])).sum(-1)
                if head == "selected_units":
                    kl = (kl * su_mask).sum(-1)
                kl = kl * step_mask
                if head not in ALWAYS_ON:
                    kl = kl * masks["actions_mask"][head]
                out[head] = kl
            return out

        kls = _kl_terms(teacher_logit)
        total_kl = 0.0
        for head, kl in kls.items():
            kl_mean = kl.mean()
            total_kl += kl_mean * head_w[head]
            info[f"kl/{head}"] = kl_mean
        at_kl = (
            kls["action_type"]
            * (steps < cfg.action_type_kl_steps)
            * masks["cum_action_mask"]
        ).mean()
        total_kl = total_kl * cfg.kl_weight
        at_kl = at_kl * cfg.action_type_kl_weight
        info["kl/total"] = total_kl
        info["kl/extra_at"] = at_kl

    # ------------------------------------------------------------------ DAPO
    total_dapo = 0.0
    if cfg.use_dapo:
        dapo_kls = _kl_terms(inputs["successive_logit"])
        flag = steps < cfg.dapo_steps
        for head, kl in dapo_kls.items():
            kl_mean = (kl * flag).mean()
            total_dapo += kl_mean * head_w[head]
            info[f"dapo/{head}"] = kl_mean
        total_dapo = total_dapo * cfg.dapo_weight
        info["dapo/total"] = total_dapo

    if cfg.only_update_value:
        total = total_critic
    else:
        total = (
            total_pg + total_upgo + total_critic + total_entropy_loss
            + total_kl + at_kl + total_dapo
        )
    info["total_loss"] = total
    return total, info

"""The full policy/value network and its four forward modes.

Role parity with the reference Model (reference: distar/agent/default/model/
model.py:22-189, encoder.py:15-45, policy.py): Encoder (scalar+spatial+entity
with entity->map scatter connection) -> 3x384 LN-LSTM core -> autoregressive
policy heads -> per-baseline value towers.

TPU-first structure: the network is a pure Flax module; time handling for the
learner modes reshapes [(T+1)*B, ...] flat batches around a `lax.scan` LSTM
exactly once (reference model.py:117-129 does the same reshape around its
TorchScript LSTM). Sampling modes take explicit PRNG keys. All shapes static.

Forward modes (mirroring model.py):
  * sample_action        — actor inference: sample every head, return
                           actions + per-head log-probs + new hidden state
                           (reference compute_logp_action :56).
  * teacher_logits       — teacher-forced logits for a given action
                           (reference compute_teacher_logit :76).
  * rl_forward           — (T+1, B) learner forward: policy logits on the
                           first T steps, six baselines on all T+1
                           (reference rl_learner_forward :95).
  * sl_forward           — supervised teacher-forced forward over [T, B]
                           windows with carried hidden state
                           (reference sl_train :170).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..lib.features import MAX_SELECTED_UNITS_NUM
from ..ops import FCBlock, StackedLSTM, scatter_connection
from .config import cdtype, static_cfg
from .encoders import EntityEncoder, ScalarEncoder, SpatialEncoder, ValueEncoder
from .heads import (
    ActionTypeHead,
    DelayHead,
    LocationHead,
    QueuedHead,
    SelectedUnitsHead,
    TargetUnitHead,
)
from .value import ValueBaseline

NEG_INF = -1e9


class Encoder(nn.Module):
    """Fuse the three observation encoders; scatter entity embeddings onto
    the map before the spatial conv stack (reference encoder.py:28-45)."""

    cfg: dict

    @nn.compact
    def __call__(self, spatial_info, entity_info, scalar_info, entity_num):
        embedded_scalar, scalar_context, baseline_feature = ScalarEncoder(
            static_cfg(self.cfg), name="scalar_encoder"
        )(scalar_info)
        entity_embeddings, embedded_entity, entity_mask = EntityEncoder(
            static_cfg(self.cfg), name="entity_encoder"
        )(entity_info, entity_num)
        # the projection belongs to the scatter connection's scope (the
        # function names its own operations the same: obs.STEP_SCOPES)
        with jax.named_scope("scatter_connection"):
            proj = FCBlock(static_cfg(self.cfg).encoder.scatter.output_dim, "relu", dtype=cdtype(self.cfg))(
                entity_embeddings
            )
            proj = proj * entity_mask[..., None]
            locations = jnp.stack(
                [entity_info["x"].astype(jnp.int32), entity_info["y"].astype(jnp.int32)], axis=-1
            )
        scatter_map = scatter_connection(
            proj,
            locations,
            (static_cfg(self.cfg).spatial_y, static_cfg(self.cfg).spatial_x),
            static_cfg(self.cfg).encoder.scatter.type,
            impl=static_cfg(self.cfg).encoder.scatter.get("impl", "product"),
        )
        spatial_cls = (
            nn.remat(SpatialEncoder)
            if static_cfg(self.cfg).get("remat", False)
            else SpatialEncoder
        )
        embedded_spatial, map_skip = spatial_cls(static_cfg(self.cfg), name="spatial_encoder")(
            spatial_info, scatter_map
        )
        lstm_input = jnp.concatenate(
            [embedded_scalar, embedded_entity, embedded_spatial], axis=-1
        )
        return lstm_input, scalar_context, baseline_feature, entity_embeddings, map_skip


class Policy(nn.Module):
    """The six-head autoregressive chain (reference policy.py)."""

    cfg: dict

    def setup(self):
        self.action_type_head = ActionTypeHead(static_cfg(self.cfg))
        self.delay_head = DelayHead(static_cfg(self.cfg))
        self.queued_head = QueuedHead(static_cfg(self.cfg))
        self.selected_units_head = SelectedUnitsHead(static_cfg(self.cfg))
        self.target_unit_head = TargetUnitHead(static_cfg(self.cfg))
        self.location_head = LocationHead(static_cfg(self.cfg))

    def sample(self, lstm_output, entity_embeddings, map_skip, scalar_context, entity_num,
               rng, legal_mask=None):
        r = jax.random.split(rng, 6)
        logit: Dict[str, jnp.ndarray] = {}
        action: Dict[str, jnp.ndarray] = {}
        logit["action_type"], action["action_type"], emb = self.action_type_head(
            lstm_output, scalar_context, None, r[0], legal_mask
        )
        logit["delay"], action["delay"], emb = self.delay_head(emb, None, r[1])
        logit["queued"], action["queued"], emb = self.queued_head(emb, None, r[2])
        # whether this action type selects units at all (contract table)
        from ..lib.actions import SELECTED_UNITS_MASK

        su_mask = jnp.asarray(SELECTED_UNITS_MASK)[action["action_type"]]
        (
            logit["selected_units"],
            action["selected_units"],
            emb,
            selected_units_num,
            extra_units,
        ) = self.selected_units_head(
            emb, entity_embeddings, entity_num, None, None, su_mask, r[3]
        )
        logit["target_unit"], action["target_unit"] = self.target_unit_head(
            emb, entity_embeddings, entity_num, None, r[4]
        )
        logit["target_location"], action["target_location"] = self.location_head(
            emb, map_skip, None, r[5]
        )
        return action, selected_units_num, logit, extra_units

    def train_forward(self, lstm_output, entity_embeddings, map_skip, scalar_context,
                      entity_num, action_info, selected_units_num):
        logit: Dict[str, jnp.ndarray] = {}
        logit["action_type"], _, emb = self.action_type_head(
            lstm_output, scalar_context, action_info["action_type"]
        )
        logit["delay"], _, emb = self.delay_head(emb, action_info["delay"])
        logit["queued"], _, emb = self.queued_head(emb, action_info["queued"])
        logit["selected_units"], _, emb, _, _ = self.selected_units_head(
            emb,
            entity_embeddings,
            entity_num,
            action_info["selected_units"],
            selected_units_num,
        )
        logit["target_unit"], _ = self.target_unit_head(
            emb, entity_embeddings, entity_num, action_info["target_unit"]
        )
        logit["target_location"], _ = self.location_head(
            emb, map_skip, action_info["target_location"]
        )
        return logit


class Model(nn.Module):
    """Encoder + LSTM core + Policy + value baselines."""

    cfg: dict

    def setup(self):
        self.encoder = Encoder(static_cfg(self.cfg))
        self.policy = Policy(static_cfg(self.cfg))
        core = static_cfg(self.cfg).encoder.core_lstm
        self.core_lstm = StackedLSTM(
            hidden_size=core.hidden_size, num_layers=core.num_layers, norm="LN",
            dtype=cdtype(self.cfg),
        )
        if static_cfg(self.cfg).use_value_network:
            self.value_networks = {
                name: ValueBaseline(
                    res_dim=static_cfg(self.cfg).value.res_dim,
                    res_num=static_cfg(self.cfg).value.res_num,
                    norm_type=static_cfg(self.cfg).value.norm_type,
                    atan=static_cfg(self.cfg).value.baselines[name].atan,
                    dtype=cdtype(self.cfg),
                    name=f"value_{name}",
                )
                for name in static_cfg(self.cfg).enable_baselines
            }
            if static_cfg(self.cfg).use_value_feature:
                self.value_encoder = ValueEncoder(static_cfg(self.cfg))

    # ---------------------------------------------------------------- actor
    def sample_action(self, spatial_info, entity_info, scalar_info, entity_num,
                      hidden_state, rng, legal_mask=None):
        """Single-step batched inference (reference compute_logp_action)."""
        lstm_input, scalar_context, baseline_feature, entity_embeddings, map_skip = self.encoder(
            spatial_info, entity_info, scalar_info, entity_num
        )
        lstm_output, out_state = self.core_lstm(lstm_input[None], hidden_state)
        lstm_output = lstm_output[0]
        action, selected_units_num, logit, extra_units = self.policy.sample(
            lstm_output, entity_embeddings, map_skip, scalar_context, entity_num,
            rng, legal_mask,
        )
        logp = {k: _log_prob(logit[k], action[k]) for k in action}
        return {
            "action_info": action,
            "action_logp": logp,
            "selected_units_num": selected_units_num,
            "entity_num": entity_num,
            "hidden_state": out_state,
            "logit": logit,
            "extra_units": extra_units,
        }

    # -------------------------------------------------------------- teacher
    def teacher_logits(self, spatial_info, entity_info, scalar_info, entity_num,
                       hidden_state, action_info, selected_units_num):
        lstm_input, scalar_context, _, entity_embeddings, map_skip = self.encoder(
            spatial_info, entity_info, scalar_info, entity_num
        )
        lstm_output, out_state = self.core_lstm(lstm_input[None], hidden_state)
        logit = self.policy.train_forward(
            lstm_output[0], entity_embeddings, map_skip, scalar_context, entity_num,
            action_info, selected_units_num,
        )
        return {
            "logit": logit,
            "hidden_state": out_state,
            "entity_num": entity_num,
            "selected_units_num": selected_units_num,
        }

    # ------------------------------------------------------------- learner
    def _learner_logits(self, spatial_info, entity_info, scalar_info,
                        entity_num, hidden_state, action_info,
                        selected_units_num, batch_size, unroll_len):
        """Shared logits half of the learner forwards: encoder -> LSTM over
        the [T+1, B] window -> teacher-forced policy logits on the first T
        steps. Returns (logits [T, B, ...] dict with the selected-units S
        axis padded static, flat LSTM outputs, baseline_feature) — the
        value-tower consumers take the last two."""
        flat_action = {k: v.reshape((-1,) + v.shape[2:]) for k, v in action_info.items()}
        flat_sun = selected_units_num.reshape(-1)

        lstm_input, scalar_context, baseline_feature, entity_embeddings, map_skip = self.encoder(
            spatial_info, entity_info, scalar_info, entity_num
        )
        seq = lstm_input.reshape(-1, batch_size, lstm_input.shape[-1])  # [T+1, B, D]
        lstm_output, _ = self.core_lstm(seq, hidden_state)
        flat_out = lstm_output.reshape(-1, lstm_output.shape[-1])  # [(T+1)*B, H]

        n_policy = unroll_len * batch_size
        logits = self.policy.train_forward(
            flat_out[:n_policy],
            entity_embeddings[:n_policy],
            [m[:n_policy] for m in map_skip],
            scalar_context[:n_policy],
            entity_num[:n_policy],
            flat_action,
            flat_sun,
        )
        logits = {
            k: v.reshape((unroll_len, batch_size) + v.shape[1:]) for k, v in logits.items()
        }
        # pad selected-units logits to the fixed S axis so downstream shapes
        # are static (reference model.py:156-158)
        su = logits["selected_units"]
        if su.shape[2] < MAX_SELECTED_UNITS_NUM:
            su = jnp.pad(
                su,
                ((0, 0), (0, 0), (0, MAX_SELECTED_UNITS_NUM - su.shape[2]), (0, 0)),
                constant_values=NEG_INF,
            )
        logits["selected_units"] = su
        return logits, flat_out, baseline_feature

    def policy_forward(self, spatial_info, entity_info, scalar_info, entity_num,
                       hidden_state, action_info, selected_units_num, batch_size,
                       unroll_len):
        """``rl_forward``'s policy half without the value towers — the
        distillation student's train-time forward (student models carry no
        baselines; their training signal is the teacher's logits, not
        returns). Same flat [(T+1)*B, ...] input layout, returns
        ``{"target_logit": [T, B, ...]}``."""
        logits, _, _ = self._learner_logits(
            spatial_info, entity_info, scalar_info, entity_num, hidden_state,
            action_info, selected_units_num, batch_size, unroll_len,
        )
        return {"target_logit": logits}

    def rl_forward(self, spatial_info, entity_info, scalar_info, entity_num,
                   hidden_state, action_info, selected_units_num, batch_size,
                   unroll_len, value_feature=None):
        """Flat [(T+1)*B, ...] inputs -> policy logits [T, B, ...] and six
        baseline values [T+1, B] (reference rl_learner_forward :95-168).

        ``hidden_state`` is the per-trajectory initial state, tuple of
        (h, c) pairs each [B, H].
        """
        logits, flat_out, baseline_feature = self._learner_logits(
            spatial_info, entity_info, scalar_info, entity_num, hidden_state,
            action_info, selected_units_num, batch_size, unroll_len,
        )

        if not static_cfg(self.cfg).use_value_network:
            raise ValueError(
                "rl_forward requires cfg.use_value_network=True (the RL learner "
                "constructs its model with value towers; the default config ships "
                "False for actor-side models, mirroring the reference's "
                "use_value_network ctor flag, model.py:23)"
            )
        with jax.named_scope("value"):
            critic_input = flat_out
            if static_cfg(self.cfg).only_update_baseline:
                critic_input = jax.lax.stop_gradient(critic_input)
                baseline_feature = jax.lax.stop_gradient(baseline_feature)
            if static_cfg(self.cfg).use_value_feature:
                if value_feature is None:
                    raise ValueError(
                        "cfg.use_value_feature=True but the batch carries no "
                        "value_feature — the data source (actor collect_data / "
                        "fake_rl_batch) must include the centralized-critic "
                        "features (lib.features.VALUE_FEATURE_INFO)"
                    )
                vf = self.value_encoder(value_feature)
                critic_input = jnp.concatenate([critic_input, vf, baseline_feature], axis=1)
            values = {
                k: v(critic_input).reshape(unroll_len + 1, batch_size)
                for k, v in self.value_networks.items()
            }
        return {"target_logit": logits, "value": values}

    # ------------------------------------------------------------------ SL
    def sl_forward(self, spatial_info, entity_info, scalar_info, entity_num,
                   action_info, selected_units_num, hidden_state, batch_size):
        """Teacher-forced forward over flat [B*T, ...] batches; carries and
        returns the LSTM state (reference sl_train :170-189; note the
        reference lays SL batches out batch-major [B, T])."""
        lstm_input, scalar_context, _, entity_embeddings, map_skip = self.encoder(
            spatial_info, entity_info, scalar_info, entity_num
        )
        seq = lstm_input.reshape(batch_size, -1, lstm_input.shape[-1]).transpose(1, 0, 2)
        lstm_output, out_state = self.core_lstm(seq, hidden_state)
        flat_out = lstm_output.transpose(1, 0, 2).reshape(-1, lstm_output.shape[-1])
        logits = self.policy.train_forward(
            flat_out, entity_embeddings, map_skip, scalar_context, entity_num,
            action_info, selected_units_num,
        )
        return logits, out_state

    def __call__(self, spatial_info, entity_info, scalar_info, entity_num, hidden_state, rng):
        """Default apply target == actor sampling (used for init)."""
        return self.sample_action(
            spatial_info, entity_info, scalar_info, entity_num, hidden_state, rng
        )


def _log_prob(logits: jnp.ndarray, action: jnp.ndarray) -> jnp.ndarray:
    """Categorical log-prob of ``action`` under ``logits`` (last axis)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, action[..., None].astype(jnp.int32), axis=-1)[..., 0]

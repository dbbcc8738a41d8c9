"""Autoregressive policy heads.

Role parity with the reference heads (reference: distar/agent/default/model/
head/action_type_head.py, action_arg_head.py). The autoregressive chain is
action_type -> delay -> queued -> selected_units -> target_unit -> location,
each head consuming and extending a 1024-d autoregressive embedding.

TPU-first reformulations:
* Every sampling path takes an explicit PRNG key and uses
  jax.random.categorical — no in-place logit mutation; temperature is a
  static config scalar folded into the logits once.
* SelectedUnitsHead runs a fixed MAX_SELECTED_UNITS_NUM-step `lax.scan` for
  BOTH teacher-forced training and sampling inference (the reference's
  dynamic-length Python loops, action_arg_head.py:168-313, cannot compile to
  a single XLA program). Ended lanes are masked no-ops, preserving the
  reference's semantics with static shapes.
* LocationHead upsamples with jax.image.resize (bilinear) over NHWC maps.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .config import cdtype, static_cfg
from ..lib.features import MAX_ENTITY_NUM, MAX_SELECTED_UNITS_NUM
from ..ops import GLU, Conv2DBlock, FCBlock, GatedResBlock, ResBlock, ResFCBlock, sequence_mask
from ..ops.blocks import build_activation
from ..ops.lstm import LayerNormLSTMCell

NEG_INF = -1e9


class ActionTypeHead(nn.Module):
    """ResFC tower + GLU logits over 327 action types; emits the initial
    autoregressive embedding (role of reference action_type_head.py:18-67)."""

    cfg: dict

    @nn.compact
    def __call__(
        self,
        lstm_output: jnp.ndarray,
        scalar_context: jnp.ndarray,
        action_type: Optional[jnp.ndarray] = None,
        rng: Optional[jax.Array] = None,
        legal_mask: Optional[jnp.ndarray] = None,
    ):
        hc = static_cfg(self.cfg).policy.action_type_head
        x = FCBlock(hc.res_dim, "relu", dtype=cdtype(self.cfg))(lstm_output)
        for _ in range(hc.res_num):
            x = ResFCBlock(hc.res_dim, "relu", hc.norm_type, dtype=cdtype(self.cfg))(x)
        logits = GLU(hc.action_num, dtype=cdtype(self.cfg), name="action_glu")(x, scalar_context)
        # logits leave every head in f32: log-prob differences (CE, vtrace
        # rhos) are too quantized in bf16
        logits = logits.astype(jnp.float32) / static_cfg(self.cfg).temperature
        if legal_mask is not None:
            logits = jnp.where(legal_mask.astype(bool), logits, NEG_INF)
        if action_type is None:
            action_type = jax.random.categorical(rng, logits, axis=-1)
        one_hot_action = jax.nn.one_hot(action_type, hc.action_num, dtype=jnp.float32)
        e1 = FCBlock(hc.action_map_dim, "relu", dtype=cdtype(self.cfg))(one_hot_action)
        e1 = FCBlock(hc.action_map_dim, None, dtype=cdtype(self.cfg))(e1)
        e1 = GLU(hc.gate_dim, dtype=cdtype(self.cfg), name="glu1")(e1, scalar_context)
        e2 = GLU(hc.gate_dim, dtype=cdtype(self.cfg), name="glu2")(lstm_output, scalar_context)
        return logits, action_type, e1 + e2


class DelayHead(nn.Module):
    """128-way delay logits; no temperature (reference action_arg_head.py:27-53)."""

    cfg: dict

    @nn.compact
    def __call__(self, embedding, delay=None, rng=None):
        hc = static_cfg(self.cfg).policy.delay_head
        x = FCBlock(hc.decode_dim, "relu", dtype=cdtype(self.cfg))(embedding)
        x = FCBlock(hc.decode_dim, "relu", dtype=cdtype(self.cfg))(x)
        logits = FCBlock(hc.delay_dim, None, dtype=cdtype(self.cfg))(x).astype(jnp.float32)
        if delay is None:
            delay = jax.random.categorical(rng, logits, axis=-1)
        dh = jax.nn.one_hot(delay, hc.delay_dim, dtype=jnp.float32)
        e = FCBlock(hc.delay_map_dim, "relu", dtype=cdtype(self.cfg))(dh)
        e = FCBlock(embedding.shape[-1], None, dtype=cdtype(self.cfg))(e)
        return logits, delay, embedding + e


class QueuedHead(nn.Module):
    """Binary queued flag (reference action_arg_head.py:56-86)."""

    cfg: dict

    @nn.compact
    def __call__(self, embedding, queued=None, rng=None):
        hc = static_cfg(self.cfg).policy.queued_head
        x = FCBlock(hc.decode_dim, "relu", dtype=cdtype(self.cfg))(embedding)
        x = FCBlock(hc.decode_dim, "relu", dtype=cdtype(self.cfg))(x)
        logits = FCBlock(hc.queued_dim, None, dtype=cdtype(self.cfg))(x).astype(
            jnp.float32
        ) / static_cfg(self.cfg).temperature
        if queued is None:
            queued = jax.random.categorical(rng, logits, axis=-1)
        qh = jax.nn.one_hot(queued, hc.queued_dim, dtype=jnp.float32)
        e = FCBlock(hc.queued_map_dim, "relu", dtype=cdtype(self.cfg))(qh)
        e = FCBlock(embedding.shape[-1], None, dtype=cdtype(self.cfg))(e)
        return logits, queued, embedding + e


class SelectedUnitsHead(nn.Module):
    """LSTM pointer network selecting <=64 units with an end-flag token.

    Fixed-length scan over MAX_SELECTED_UNITS_NUM steps; per-step the query
    LSTM attends over entity keys (+1 end slot at index entity_num). Masking
    schedule matches the reference (action_arg_head.py:151-314): step 0
    disables the end slot, steps >=1 enable it and disable already-selected
    units; after a lane selects the end token all its updates become no-ops.
    """

    cfg: dict

    def setup(self):
        hc = static_cfg(self.cfg).policy.selected_units_head
        # the query LSTM's output dots against the keys, so widths must match
        assert hc.hidden_dim == hc.key_dim, "selected_units_head: hidden_dim must equal key_dim"
        self.key_fc = FCBlock(hc.key_dim, None, dtype=cdtype(self.cfg), name="key_fc")
        self.query_fc1 = FCBlock(hc.func_dim, "relu", dtype=cdtype(self.cfg), name="query_fc1")
        self.query_fc2 = FCBlock(hc.key_dim, None, dtype=cdtype(self.cfg), name="query_fc2")
        self.embed_fc1 = FCBlock(hc.func_dim, "relu", dtype=cdtype(self.cfg), name="embed_fc1")
        self.embed_fc2 = FCBlock(
            static_cfg(self.cfg).policy.action_type_head.gate_dim, None, dtype=cdtype(self.cfg), name="embed_fc2"
        )
        # the reference hardcodes script_lnlstm for the pointer decoder
        # (action_arg_head.py:108), overriding its own lstm_type config
        self.lstm_cells = [
            LayerNormLSTMCell(hc.hidden_dim, dtype=cdtype(self.cfg), name=f"lstm{i}")
            for i in range(hc.get("num_layers", 1))
        ]

        self.end_embedding = self.param(
            "end_embedding", nn.initializers.uniform(scale=2.0 / (32 ** 0.5)), (hc.key_dim,)
        )

    def _keys(self, entity_embedding, entity_num):
        """Per-entity keys with the end token written at index entity_num.
        Returns key [B, N+1, K] and validity mask [B, N+1]."""
        B, N, _ = entity_embedding.shape
        key = self.key_fc(entity_embedding)  # B, N, K
        key = jnp.concatenate([key, jnp.zeros_like(key[:, :1])], axis=1)  # B, N+1, K
        is_end = jnp.arange(N + 1)[None, :] == entity_num[:, None]  # B, N+1
        key = jnp.where(is_end[..., None], self.end_embedding[None, None, :], key)
        mask = sequence_mask(entity_num + 1, N + 1)
        return key, mask

    def _lstm(self, x, states):
        """Stacked LN-LSTM step; ``states`` is a tuple of (h, c) per layer."""
        new_states = []
        for cell, st in zip(self.lstm_cells, states):
            x, st = cell(x, st)
            new_states.append(st)
        return x, tuple(new_states)

    def _ae_update(self, base_ae, key, sel_onehot, count):
        """ae = base + embed(mean of selected keys). The MLP applies even to
        a zero selection (the reference feeds the raw zero sum through
        embed_fc1/2, whose biases contribute — action_arg_head.py:193-200);
        only step 0 uses the raw base ae (handled by callers)."""
        s = (key * sel_onehot[..., None]).sum(axis=1)
        denom = jnp.maximum(count, 1.0)[:, None]
        return base_ae + self.embed_fc2(self.embed_fc1(s / denom))

    def _su_step(self, carry, result_fn, temperature: float = 1.0):
        """One pointer-decode step; ``result_fn(logits)`` picks the unit."""
        key, valid, entity_num = carry["key"], carry["valid"], carry["entity_num"]
        N1 = key.shape[1]
        q = self.query_fc2(self.query_fc1(carry["ae"]))
        out, lstm_state = self._lstm(q, carry["lstm_state"])
        logits = (out[:, None, :] * key).sum(-1).astype(jnp.float32)  # B, N+1
        logits = jnp.where(carry["logit_mask"], logits, NEG_INF) / temperature
        result = result_fn(logits)
        picked_end = result == entity_num
        newly_end = picked_end & ~carry["end_flag"]
        num = jnp.where(newly_end, carry["i"] + 1, carry["num"])
        end_flag = carry["end_flag"] | picked_end
        slot = jnp.arange(N1)[None, :] == result[:, None]
        add = (~end_flag)[:, None] & slot
        sel_onehot = jnp.maximum(carry["sel_onehot"], add.astype(jnp.float32))
        count = sel_onehot.sum(axis=1)
        ae = self._ae_update(carry["base_ae"], key, sel_onehot, count)
        is_end_slot = jnp.arange(N1)[None, :] == entity_num[:, None]
        logit_mask = carry["logit_mask"] | (is_end_slot & valid)  # end selectable from step 1
        logit_mask = logit_mask & ~(slot & ~picked_end[:, None])  # chosen unit now off
        new_carry = dict(
            carry,
            lstm_state=lstm_state,
            ae=ae,
            logit_mask=logit_mask,
            sel_onehot=sel_onehot,
            end_flag=end_flag,
            num=num,
            i=carry["i"] + 1,
        )
        return new_carry, (logits, result)

    def _train_forward_parallel(
        self, base_ae, key, valid, entity_num, labels, selected_units_num, states0
    ):
        """Teacher-forced decode with everything except the tiny query LSTM
        batched over the 64 steps.

        Under teacher forcing the per-step state (selected one-hots, masks,
        autoregressive embeddings) is a pure function of the *labels*, so the
        reference's step-by-step recomputation (and the scan path's 64
        sequential 1024-wide matmuls) collapses into cumulative ops + three
        big MXU matmuls; only the 32-dim pointer LSTM stays sequential.
        Produces logits identical to the scan path (equivalence-tested)."""
        B, N1, K = key.shape
        S = MAX_SELECTED_UNITS_NUM
        slot = jax.nn.one_hot(labels, N1, dtype=jnp.float32)  # [B, S, N+1]
        picked_end = labels == entity_num[:, None]  # [B, S]
        end_before = jnp.concatenate(
            [jnp.zeros((B, 1), bool), jnp.cumsum(picked_end, axis=1)[:, :-1] > 0], axis=1
        )
        # selection accumulated AFTER each step i (ended lanes stop adding)
        add = slot * (~(end_before | picked_end))[..., None]
        sel_after = jnp.minimum(jnp.cumsum(add, axis=1), 1.0)  # [B, S, N+1]
        # ae at step i uses selections from steps < i
        sel_before = jnp.concatenate(
            [jnp.zeros((B, 1, N1), jnp.float32), sel_after[:, :-1]], axis=1
        )
        count_before = sel_before.sum(-1)  # [B, S]
        pooled = jnp.einsum("bsn,bnk->bsk", sel_before, key) / jnp.maximum(
            count_before, 1.0
        )[..., None]
        emb = self.embed_fc2(self.embed_fc1(pooled))  # [B, S, 1024] one batched matmul
        # step 0 queries the raw base ae; every later step adds the selection
        # MLP (incl. its bias for empty selections — see _ae_update)
        ae_all = base_ae[:, None, :] + jnp.where(
            (jnp.arange(S) > 0)[None, :, None], emb, 0.0
        )
        # per-step logits mask: end slot off at step 0, on after; previously
        # selected units off (the end pick itself stays maskable)
        picked_slots_before = jnp.concatenate(
            [
                jnp.zeros((B, 1, N1), jnp.float32),
                jnp.cumsum(slot * (~picked_end)[..., None], axis=1)[:, :-1],
            ],
            axis=1,
        )
        is_end_slot = (jnp.arange(N1)[None, :] == entity_num[:, None])[:, None, :]
        step_idx = jnp.arange(S)[None, :, None]
        mask_all = (
            valid[:, None, :]
            & ((step_idx > 0) | ~is_end_slot)  # init_mask semantics at step 0
            & (picked_slots_before == 0)
        )
        # tiny pointer LSTM over the precomputed query inputs
        q_in = self.query_fc2(self.query_fc1(ae_all))  # [B, S, K]
        _, lstm_out = nn.transforms.scan(
            lambda mdl, carry, x: tuple(reversed(mdl._lstm(x, carry))),
            variable_broadcast="params",
            split_rngs={"params": False},
        )(self, states0, q_in.transpose(1, 0, 2))
        lstm_out = lstm_out.transpose(1, 0, 2)  # [B, S, K]
        logits = jnp.einsum("bsk,bnk->bsn", lstm_out, key).astype(jnp.float32)
        logits = jnp.where(mask_all, logits, NEG_INF)
        # final ae (feeds target_unit/location heads) = ae after step S-1
        count_after = sel_after[:, -1].sum(-1)
        pooled_final = jnp.einsum(
            "bn,bnk->bk", sel_after[:, -1], key
        ) / jnp.maximum(count_after, 1.0)[:, None]
        emb_final = self.embed_fc2(self.embed_fc1(pooled_final))
        ae_final = base_ae + emb_final
        end_flag = end_before[:, -1] | picked_end[:, -1]
        last_logits = logits[:, -1, :]
        end_logit = jnp.take_along_axis(last_logits, entity_num[:, None], axis=1)
        extra_units = ((last_logits > end_logit) & ~end_flag[:, None]).astype(jnp.float32)
        return logits, labels, ae_final, selected_units_num, extra_units

    def _su_step_train(self, carry, label):
        return self._su_step(carry, lambda logits: label)

    def _su_step_sample(self, carry, step_rng):
        # temperature folds into the *returned* logits so action_logp is
        # computed under the same distribution that sampled (the reference's
        # in-place logit.div_ in _get_pred_with_logit has the same effect,
        # action_arg_head.py:145-149)
        return self._su_step(
            carry,
            lambda logits: jax.random.categorical(step_rng, logits, axis=-1),
            temperature=static_cfg(self.cfg).temperature,
        )

    def __call__(
        self,
        embedding: jnp.ndarray,  # [B, 1024] autoregressive embedding
        entity_embedding: jnp.ndarray,  # [B, N, 256]
        entity_num: jnp.ndarray,  # [B]
        selected_units: Optional[jnp.ndarray] = None,  # [B, S] teacher labels
        selected_units_num: Optional[jnp.ndarray] = None,  # [B]
        su_mask: Optional[jnp.ndarray] = None,  # [B] does this action select units
        rng: Optional[jax.Array] = None,
    ):
        hc = static_cfg(self.cfg).policy.selected_units_head
        B, N, _ = entity_embedding.shape
        S = MAX_SELECTED_UNITS_NUM
        key, valid = self._keys(entity_embedding, entity_num)
        base_ae = embedding
        h0 = jnp.zeros((B, hc.hidden_dim), jnp.float32)  # carry stays f32
        states0 = tuple((h0, h0) for _ in self.lstm_cells)
        init_mask = valid & (jnp.arange(N + 1)[None, :] != entity_num[:, None])  # end off at step 0

        train = selected_units is not None
        if train:
            labels = selected_units[:, :S].astype(jnp.int32)
            if labels.shape[1] < S:
                labels = jnp.pad(labels, ((0, 0), (0, S - labels.shape[1])))
            if (
                hc.get("train_impl", "parallel") != "scan"
                and not self.is_initializing()
            ):
                return self._train_forward_parallel(
                    base_ae, key, valid, entity_num, labels, selected_units_num, states0
                )
            xs = labels.T  # [S, B]
        else:
            xs = jax.random.split(rng, S)

        end0 = jnp.zeros((B,), bool)
        num0 = jnp.full((B,), S, jnp.int32)
        if su_mask is not None:
            end0 = ~su_mask.astype(bool)
            num0 = jnp.where(su_mask.astype(bool), num0, 0)
        carry0 = dict(
            lstm_state=states0,
            # step 0 queries the RAW base ae (the selection MLP only joins
            # from step 1, reference :188-200)
            ae=base_ae,
            logit_mask=init_mask,
            sel_onehot=jnp.zeros((B, N + 1), jnp.float32),
            end_flag=end0,
            num=num0,
            i=jnp.zeros((), jnp.int32),
            # loop-invariant context, threaded through the carry so the
            # lifted-scan step sees it without closures
            key=key,
            valid=valid,
            base_ae=base_ae,
            entity_num=entity_num,
        )

        step_method = self._su_step_train if train else self._su_step_sample
        if self.is_initializing():
            carry, (logits0, result0) = step_method(carry0, jax.tree.map(lambda a: a[0], xs))
            logits_seq = jnp.broadcast_to(logits0[None], (S, B, N + 1))
            results_seq = jnp.broadcast_to(result0[None], (S, B))
            final = carry
        else:
            final, (logits_seq, results_seq) = nn.transforms.scan(
                type(self)._su_step_train if train else type(self)._su_step_sample,
                variable_broadcast="params",
                split_rngs={"params": False},
            )(self, carry0, xs)

        ae = final["ae"]
        end_flag = final["end_flag"]
        num = final["num"]
        logits_seq = logits_seq.transpose(1, 0, 2)  # B, S, N+1
        results_seq = results_seq.transpose(1, 0)  # B, S
        if train:
            out_num = selected_units_num
        else:
            out_num = num
        # extra-units proposal: entities scoring above the end token at the
        # final step, for lanes that never ended (reference :307-309)
        last_logits = logits_seq[:, -1, :]
        end_logit = jnp.take_along_axis(last_logits, entity_num[:, None], axis=1)
        extra_units = ((last_logits > end_logit) & ~end_flag[:, None]).astype(jnp.float32)
        return logits_seq, results_seq, ae, out_num, extra_units


class TargetUnitHead(nn.Module):
    """Key-query attention over entities (reference action_arg_head.py:331-363)."""

    cfg: dict

    @nn.compact
    def __call__(self, embedding, entity_embedding, entity_num, target_unit=None, rng=None):
        hc = static_cfg(self.cfg).policy.target_unit_head
        key = FCBlock(hc.key_dim, None, dtype=cdtype(self.cfg))(entity_embedding)
        q = FCBlock(hc.key_dim, "relu", dtype=cdtype(self.cfg))(embedding)
        q = FCBlock(hc.key_dim, None, dtype=cdtype(self.cfg))(q)
        logits = (q[:, None, :] * key).sum(-1).astype(jnp.float32)
        mask = sequence_mask(entity_num, entity_embedding.shape[1])
        logits = jnp.where(mask, logits, NEG_INF) / static_cfg(self.cfg).temperature
        if target_unit is None:
            target_unit = jax.random.categorical(rng, logits, axis=-1)
        return logits, target_unit


class LocationHead(nn.Module):
    """Gated res stack over map_skip + 3x bilinear upsample to 152x160 logits
    (reference action_arg_head.py:366-450; gate=True, film/unet off)."""

    cfg: dict

    @nn.compact
    def __call__(self, embedding, map_skip: List[jnp.ndarray], location=None, rng=None):
        hc = static_cfg(self.cfg).policy.location_head
        H8, W8 = static_cfg(self.cfg).spatial_y // 8, static_cfg(self.cfg).spatial_x // 8
        proj = FCBlock(H8 * W8 * hc.reshape_channel, "relu", dtype=cdtype(self.cfg))(embedding)
        proj = proj.reshape(-1, H8, W8, hc.reshape_channel)
        x = jnp.concatenate([proj, map_skip[-1]], axis=-1)
        x = jax.nn.relu(x)
        x = Conv2DBlock(hc.res_dim, 1, 1, "SAME", "relu", dtype=cdtype(self.cfg))(x)
        for i in range(hc.res_num):
            x = x + map_skip[len(map_skip) - i - 1]
            if hc.gate:
                x = GatedResBlock(hc.res_dim, "relu", dtype=cdtype(self.cfg))(x, x)
            else:
                x = ResBlock(hc.res_dim, "relu", dtype=cdtype(self.cfg))(x)
        for i, ch in enumerate(hc.upsample_dims):
            B, h, w, c = x.shape
            x = jax.image.resize(x, (B, h * 2, w * 2, c), "bilinear")
            act = "relu" if i < len(hc.upsample_dims) - 1 else None
            x = Conv2DBlock(ch, 3, 1, "SAME", act, dtype=cdtype(self.cfg))(x)
        logits = x.reshape(x.shape[0], -1).astype(jnp.float32) / static_cfg(self.cfg).temperature
        if location is None:
            location = jax.random.categorical(rng, logits, axis=-1)
        return logits, location

"""Batched jitted inference for the actor fleet.

Role of the reference's gpu_batch_inference (reference: distar/agent/default/
agent.py:715-739 and actor.py:268-299 — shared-memory slots + spin-wait
signals feeding one GPU forward): here every env slot's prepared observation
is stacked into ONE fixed-shape device batch and a single jitted
``sample_action`` serves all slots; teacher logits batch the same way. No
shared memory, no signal tensors — the batch IS the protocol, and fixed
shapes mean one compilation.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import features as F
from ..model import Model


def decollate(tree, idx: int):
    """Slice one slot out of a batched output pytree.

    The whole pytree is fetched to host in ONE ``jax.device_get`` (a single
    transfer covering every leaf) and the slot is handed out as views of
    that host copy — never one ``np.asarray`` device sync per leaf per
    slot, which cost num_slots x num_leaves transfers per step."""
    host = jax.device_get(tree)
    return jax.tree.map(lambda x: x[idx], host)


class BatchedInference:
    """Owns params + hidden states for all slots of one player_id.

    Also owns the (optional) frozen-teacher side of the rollout contract:
    ``teacher_params`` plus one teacher LSTM carry per slot, advanced by
    ``teacher_step`` and zeroed alongside the policy carry in
    ``reset_slot`` — so an engine built on this object holds the COMPLETE
    per-slot recurrent state server-side (the serve plane's session-per-slot
    contract, docs/serving.md)."""

    def __init__(self, model: Model, params, num_slots: int, seed: int = 0,
                 teacher_params=None):
        self.model = model
        self.params = params
        self.num_slots = num_slots
        cfg = model.cfg
        self._hidden_size = cfg["encoder"]["core_lstm"]["hidden_size"]
        self._num_layers = cfg["encoder"]["core_lstm"]["num_layers"]
        self.hidden = self._zero_hidden()
        self.teacher_params = teacher_params
        self.teacher_hidden = self._zero_hidden()
        self._rng = jax.random.PRNGKey(seed)

        self._sample = jax.jit(
            lambda p, d, h, r: model.apply(
                p, d["spatial_info"], d["entity_info"], d["scalar_info"], d["entity_num"],
                h, r, method=model.sample_action,
            )
        )
        def teacher(p, d, h, a, n):
            # the frozen pass under one scope of its own, apart from the
            # sampling pass that shares its modules' names (obs.STEP_SCOPES)
            with jax.named_scope("teacher"):
                return model.apply(
                    p, d["spatial_info"], d["entity_info"], d["scalar_info"], d["entity_num"],
                    h, a, n, method=model.teacher_logits,
                )

        self._teacher = jax.jit(teacher)

    def _zero_hidden(self):
        z = jnp.zeros((self.num_slots, self._hidden_size))
        return tuple((z, z) for _ in range(self._num_layers))

    def set_params(self, params) -> None:
        """Install new weights (serve-plane hot swap). The pytree structure
        and leaf shapes must match the old params, so the jitted forward is
        reused — a swap never recompiles. A forward already executing keeps
        the params reference it was called with; the swap takes effect from
        the next ``sample``."""
        self.params = params

    def set_teacher_params(self, params) -> None:
        """Install (or replace) the frozen teacher weights. Same shape-
        stability contract as ``set_params``: the jitted teacher forward is
        reused, never recompiled."""
        self.teacher_params = params

    def warmup(self, template_obs: dict, params=None) -> None:
        """One throwaway batched forward on scratch hidden state.

        Compiles (first call) or exercises the jitted ``sample_action``
        without touching ``self.params``, ``self.hidden`` or the RNG — safe
        to run concurrently with serving traffic, which is the point: the
        registry warms a freshly loaded checkpoint off the serving path
        before atomically swapping it in."""
        batch = jax.tree.map(jnp.asarray, F.batch_tree([template_obs] * self.num_slots))
        self._sample(
            params if params is not None else self.params,
            batch, self._zero_hidden(), jax.random.PRNGKey(0),
        )

    def reset_slot(self, idx: int) -> None:
        """Zero one slot's policy AND teacher hidden state (episode
        boundary — the slot's whole recurrent state restarts together)."""
        self.hidden = tuple(
            (h.at[idx].set(0.0), c.at[idx].set(0.0)) for h, c in self.hidden
        )
        self.teacher_hidden = tuple(
            (h.at[idx].set(0.0), c.at[idx].set(0.0)) for h, c in self.teacher_hidden
        )

    def hidden_for_slot(self, idx: int):
        # slice on device, then ONE host fetch for the whole carry tuple
        return jax.device_get(tuple((h[idx], c[idx]) for h, c in self.hidden))

    def sample(self, prepared: List[dict], active: Optional[List[bool]] = None) -> List[dict]:
        """One batched forward over all slots; returns per-slot outputs.

        ``active`` marks slots that are actually acting this cycle (variable
        per-agent delays mean some slots carry stale observations as batch
        filler): inactive slots keep their previous hidden state and their
        outputs must be ignored by the caller. The batch shape stays static —
        inactive-lane compute is the price of one compiled program.
        """
        assert len(prepared) == self.num_slots
        batch = jax.tree.map(jnp.asarray, F.batch_tree(prepared))
        self._rng, key = jax.random.split(self._rng)
        old_hidden = self.hidden
        out = self._sample(self.params, batch, self.hidden, key)
        self.hidden = self._merge_hidden(out["hidden_state"], old_hidden, active)
        # ONE device->host transfer for the whole batched output pytree;
        # per-slot dicts are views of that host copy (satellite of the
        # rollout plane: the old per-leaf np.asarray cost one sync each)
        host = jax.device_get({k: v for k, v in out.items() if k != "hidden_state"})
        return [jax.tree.map(lambda x: x[i], host) for i in range(self.num_slots)]

    def _merge_hidden(self, new, old, active: Optional[List[bool]]):
        if active is None or all(active):
            return new
        mask = jnp.asarray(np.asarray(active, bool))[:, None]
        return jax.tree.map(lambda n, o: jnp.where(mask, n, o), new, old)

    def teacher_logits(
        self, teacher_params, prepared: List[dict], teacher_hidden, outputs: List[dict],
        active: Optional[List[bool]] = None,
    ):
        """Teacher-forced logits for the freshly sampled actions; returns
        (per-slot logit dicts, new teacher hidden — inactive slots keep the
        old carry)."""
        batch = jax.tree.map(jnp.asarray, F.batch_tree(prepared))
        action_info = jax.tree.map(
            jnp.asarray, F.batch_tree([o["action_info"] for o in outputs])
        )
        sun = jnp.asarray(np.stack([np.asarray(o["selected_units_num"]) for o in outputs]))
        out = self._teacher(teacher_params, batch, teacher_hidden, action_info, sun)
        merged = self._merge_hidden(out["hidden_state"], teacher_hidden, active)
        host_logit = jax.device_get(out["logit"])  # one transfer, slots view it
        per_slot = [jax.tree.map(lambda x: x[i], host_logit) for i in range(self.num_slots)]
        return per_slot, merged

    def teacher_step(
        self, prepared: List[dict], outputs: List[dict],
        active: Optional[List[bool]] = None,
    ) -> List[dict]:
        """Stateful teacher forward over the instance's own frozen teacher
        weights and per-slot teacher carries (advanced here; inactive slots
        keep theirs). Requires ``teacher_params`` to be installed."""
        if self.teacher_params is None:
            raise RuntimeError(
                "teacher_step: no teacher params installed "
                "(set_teacher_params / teacher_params ctor arg)"
            )
        per_slot, self.teacher_hidden = self.teacher_logits(
            self.teacher_params, prepared, self.teacher_hidden, outputs, active
        )
        return per_slot

"""Actor-learner distillation: the student tier's learner role.

Trains the small student policy (``model.student_model_config``) on the RL
learner's OWN trajectory batches: the teacher logits already ride every
rollout flush (PR 8 ``want_teacher``), so distillation adds zero teacher
forwards to the hot path — the student consumes ``batch["teacher_logit"]``
exactly as the RL loss's KL term does, through the masked per-head KL in
:mod:`losses.distill_loss`.

Two contracts distinguish this learner from the RL one:

  * **Hidden state**: the batch's ``hidden_state`` carries the TEACHER's
    LSTM dims (the actor's carry). The student has its own, smaller carry,
    so every window trains from a zero initial state (the standard
    actor-learner-distillation treatment; the [T+1] window is its own
    burn-in).
  * **Checkpoint role**: student checkpoints publish through
    ``CheckpointManager`` under the ``student`` role key (their own
    ``latest_student.json`` pointer + role-stamped generations), so a
    teacher's crash-resume can never pick a student generation and vice
    versa — even inside one shared experiment directory.

Live drift surfaces through ``distar_distill_*`` gauges (divergence total
and per head, student vs teacher generation, FLOPs-derived step-cost
ratio); the ``distill_divergence_runaway`` rule in the default rulebook
watches the KL gauge's trend.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..losses import DistillLossConfig, compute_distill_loss
from ..model import Model, student_model_config
from ..utils import deep_merge_dicts
from .base_learner import DEFAULT_LEARNER_CONFIG, BaseLearner
from .data import FakeRLDataloader, cap_entities_rl

DISTILL_LEARNER_DEFAULTS = deep_merge_dicts(
    DEFAULT_LEARNER_CONFIG,
    {
        "learner": {
            "player_id": "MP0",
            "batch_size": 4,
            "unroll_len": 16,
            # distillation is supervised: a larger LR than the RL
            # learner's 1e-5 converges the student orders faster
            "learning_rate": 1e-3,
            "betas": [0.9, 0.99],
            "eps": 1e-5,
            "grad_clip": {"type": "norm", "threshold": 10.0},
            "max_entities": None,
            # cascades into DistillLossConfig (temperature, head weights)
            "distill": {},
            # when set (e.g. from the DISTILL_r* bench artifact), the
            # learner publishes its FLOPs-derived step-cost ratio gauge
            "teacher_flops_per_step": 0,
        },
        "model": {},
    },
)


def make_distill_loss_config(learner_cfg) -> DistillLossConfig:
    overrides = dict(learner_cfg.get("distill", {}) or {})
    return DistillLossConfig(**overrides)


def _flatten_time(tree):
    return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), tree)


def make_distill_train_step(model: Model, loss_cfg: DistillLossConfig,
                            optimizer, batch_size: int, unroll_len: int,
                            hidden_size: int, hidden_layers: int,
                            dynamics=None):
    """(params, opt_state, batch) -> (params, opt_state, info). The student's
    zero initial carry is built inside the jitted step (its dims are the
    STUDENT's, not the batch's — see the module docstring)."""

    def loss_fn(params, batch):
        hidden = tuple(
            (jnp.zeros((batch_size, hidden_size), jnp.float32),
             jnp.zeros((batch_size, hidden_size), jnp.float32))
            for _ in range(hidden_layers)
        )
        out = model.apply(
            params,
            _flatten_time(batch["spatial_info"]),
            _flatten_time(batch["entity_info"]),
            _flatten_time(batch["scalar_info"]),
            batch["entity_num"].reshape(-1),
            hidden, batch["action_info"], batch["selected_units_num"],
            batch_size, unroll_len,
            method=model.policy_forward,
        )
        inputs = {
            "student_logit": out["target_logit"],
            "teacher_logit": batch["teacher_logit"],
            "mask": batch["mask"],
        }
        with jax.named_scope("loss"):
            return compute_distill_loss(inputs, loss_cfg)

    def distill_train_step(params, opt_state, batch):
        (_, info), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        with jax.named_scope("diagnostics/grad_norm"):
            info["grad_norm"] = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        if dynamics is not None:
            from ..obs import dynamics_tree

            with jax.named_scope("diagnostics/dynamics_tree"):
                info.update(dynamics_tree(
                    params, grads, updates=updates, batch=batch, spec=dynamics
                ))
        with jax.named_scope("optimizer"):
            params = optax.apply_updates(params, updates)
        return params, opt_state, info

    return distill_train_step


class DistillLearner(BaseLearner):
    """Student-tier learner: masked-KL distillation on RL batches."""

    _CAP_FN = staticmethod(cap_entities_rl)
    CKPT_ROLE = "student"

    def __init__(self, cfg: Optional[dict] = None, mesh=None):
        # ``mesh`` accepted for launcher symmetry with RLLearner; the
        # student is small enough that the step runs un-sharded
        cfg = deep_merge_dicts(DISTILL_LEARNER_DEFAULTS, cfg or {})
        self.model_cfg = student_model_config(cfg.get("model", {}))
        self.model_cfg.use_value_network = False
        self.model = Model(self.model_cfg)
        self.loss_cfg = make_distill_loss_config(cfg.learner)
        super().__init__(cfg)

    # ------------------------------------------------------------ state init
    def _setup_dataloader(self) -> None:
        lc = self.cfg.learner if hasattr(self, "cfg") else DISTILL_LEARNER_DEFAULTS.learner
        self._dataloader = iter(
            FakeRLDataloader(
                batch_size=lc.batch_size,
                unroll_len=lc.unroll_len,
                hidden_size=self.model_cfg.encoder.core_lstm.hidden_size,
                hidden_layers=self.model_cfg.encoder.core_lstm.num_layers,
            )
        )

    def set_dataloader(self, it) -> None:
        self._dataloader = iter(it)

    def _student_zero_hidden(self, batch_size: int):
        core = self.model_cfg.encoder.core_lstm
        return tuple(
            (np.zeros((batch_size, core.hidden_size), np.float32),
             np.zeros((batch_size, core.hidden_size), np.float32))
            for _ in range(core.num_layers)
        )

    def _setup_state(self) -> None:
        lc = self.cfg.learner
        B, T = lc.batch_size, lc.unroll_len
        self.optimizer = self._build_optimizer()

        def init_fn(rng, spatial, entity, scalar, entity_num, hidden, action, sun):
            return self.model.init(
                rng, spatial, entity, scalar, entity_num, hidden, action, sun,
                B, T, method=self.model.policy_forward,
            )

        setup = self._setup_spans
        with setup.span("fake_batch"):
            data = dict(next(self._dataloader))
            data.pop("model_last_iter", None)  # host-side; _train pops it too
            batch = jax.tree.map(jnp.asarray, self._strip_batch(self._cap(data)))
            init_args = (
                *(_flatten_time(batch[k]) for k in ("spatial_info", "entity_info", "scalar_info")),
                batch["entity_num"].reshape(-1),
                jax.tree.map(jnp.asarray, self._student_zero_hidden(B)),
                batch["action_info"],
                batch["selected_units_num"],
            )
        with setup.span("model_init"):
            params = jax.jit(init_fn)(jax.random.PRNGKey(self.init_prng_seed), *init_args)
        with setup.span("opt_init"):
            self._state = {
                "params": params,
                "opt_state": jax.jit(self.optimizer.init)(params),
            }
        core = self.model_cfg.encoder.core_lstm
        step_fn = make_distill_train_step(
            self.model, self.loss_cfg, self.optimizer, B, T,
            hidden_size=core.hidden_size, hidden_layers=core.num_layers,
            dynamics=self._dynamics_spec(),
        )
        self._train_step = jax.jit(step_fn, donate_argnums=(0, 1))
        reg = self.metrics
        self._g_kl = reg.gauge(
            "distar_distill_kl",
            "student-vs-teacher masked KL (unweighted sum over heads) at the "
            "last distill step — the distill_divergence_runaway input",
        )
        self._g_head_kl = {}
        self._g_student_gen = reg.gauge(
            "distar_distill_student_generation",
            "learner iteration of the newest published student checkpoint",
        )
        self._g_teacher_gen = reg.gauge(
            "distar_distill_teacher_generation",
            "newest teacher iteration observed in the training batches",
        )
        teacher_flops = float(lc.get("teacher_flops_per_step") or 0)
        if teacher_flops > 0:
            from ..obs.perf import flops_of_lowered

            lowered = self._train_step.lower(
                self._state["params"], self._state["opt_state"], batch)
            student_flops = flops_of_lowered(lowered)
            if student_flops:
                reg.gauge(
                    "distar_distill_step_cost_ratio",
                    "student/teacher per-step cost ratio (FLOPs-derived; "
                    "teacher side from learner.teacher_flops_per_step)",
                ).set(student_flops / teacher_flops)

    # ---------------------------------------------------------------- saving
    def checkpoint_path(self) -> str:
        import os

        return os.path.join(self.save_dir, "checkpoints",
                            f"student_iteration_{self.last_iter.val}.ckpt")

    def save(self, path: str, sync: bool = False) -> None:
        super().save(path, sync=sync)
        self._g_student_gen.set(float(self.last_iter.val))

    # -------------------------------------------------------------- training
    def _strip_batch(self, data: Dict) -> Dict:
        """Drop the RL-batch fields distillation does not consume: the
        TEACHER-shaped carry, rewards/values inputs, and host-side
        bookkeeping the caller pops separately."""
        data = dict(data)
        for k in ("hidden_state", "reward", "step", "done", "behaviour_logp",
                  "value_feature", "successive_logit"):
            data.pop(k, None)
        return data

    def _train(self, data) -> Dict[str, Any]:
        spans = self.spans
        with spans.span("prepare"):
            data = dict(data)
            data.pop("_on_device", None)
            model_last_iter = np.asarray(data.pop("model_last_iter", 0.0))
            data.pop("trace_span_ids", None)
            data.pop("trace_age_s", None)
            data = self._strip_batch(self._cap(data))
            batch = jax.tree.map(jnp.asarray, data)
        with spans.span("dispatch"):
            params, opt_state, info = self._train_step(
                self._state["params"], self._state["opt_state"], batch)
            # after the call: the background flop count re-uses this trace
            self._perf_note_step_args(self._train_step, params, opt_state, batch)
            self._state = {"params": params, "opt_state": opt_state}
        with spans.span("fetch"):
            log = {k: float(v) for k, v in jax.device_get(info).items()}
            self._g_kl.set(log["divergence"])
            for head in ("action_type", "delay", "queued", "selected_units",
                         "target_unit", "target_location"):
                g = self._g_head_kl.get(head)
                if g is None:
                    g = self._g_head_kl[head] = self.metrics.gauge(
                        "distar_distill_head_kl",
                        "per-action-head masked KL vs the teacher", head=head)
                g.set(log[f"kl/{head}"])
            self._g_teacher_gen.set(float(np.max(model_last_iter)))
            if getattr(self, "_pending_save", False):
                self._pending_save = False
                self.save(self.checkpoint_path(), sync=True)
                self.logger.info(f"admin checkpoint saved: {self.checkpoint_path()}")
        return log

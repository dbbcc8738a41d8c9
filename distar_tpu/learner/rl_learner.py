"""RL learner: pjit data-parallel V-trace/UPGO training on a device mesh.

Role of the reference RLLearner (reference: distar/agent/default/
rl_learner.py:23-160): model with value towers, Adam(0, 0.99) + grad clip,
staleness tracking, value-pretrain gate, weight publication hooks.

TPU-first train step: ONE jitted function carries forward + loss + backward
+ optimizer update; inputs arrive sharded [*, B/dp, ...] over the mesh's dp
axis, params/opt-state replicated, and XLA inserts the gradient psum over
ICI (replacing DistModule.sync_gradients' per-param NCCL loop,
dist_helper.py:421-431). Params and opt state are donated, so the update is
in-place in HBM.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..losses import ReinforcementLossConfig, compute_rl_loss
from ..model import Model, default_model_config
from ..parallel import MeshSpec, assemble_global, make_mesh
from ..parallel.grad_clip import leaf_norms
from ..utils import Config, deep_merge_dicts
from .base_learner import DEFAULT_LEARNER_CONFIG, BaseLearner
from .data import FakeRLDataloader, cap_entities_rl

RL_LEARNER_DEFAULTS = deep_merge_dicts(
    DEFAULT_LEARNER_CONFIG,
    {
        "learner": {
            "player_id": "MP0",
            "batch_size": 4,
            "unroll_len": 16,
            "learning_rate": 1e-5,
            "betas": [0.0, 0.99],
            "eps": 1e-5,
            "grad_clip": {"type": "norm", "threshold": 10.0},
            "value_pretrain_iters": -1,
            "use_dapo": False,
            # per-parameter grad/param-norm logging (reference save_grad)
            "save_grad": False,
            # pad-to-bucket entity cap (throughput; see data.cap_entities_rl)
            "max_entities": None,
        },
        "model": {},
    },
)


def make_loss_config(learner_cfg) -> ReinforcementLossConfig:
    """Loss weights are yaml-surface config like the reference's
    default_reinforcement_loss.yaml: any ReinforcementLossConfig field can
    be overridden via ``learner.loss`` (e.g. kl_weight, entropy_weight,
    pg_weights). List-valued fields arriving from yaml are normalised to
    the dataclass's tuple-of-tuples form."""
    overrides = {
        k: (tuple(tuple(x) for x in v) if isinstance(v, (list, tuple)) else v)
        for k, v in dict(learner_cfg.get("loss", {}) or {}).items()
    }
    # an explicit loss.use_dapo wins over the top-level learner.use_dapo
    overrides.setdefault("use_dapo", learner_cfg.use_dapo)
    return ReinforcementLossConfig(**overrides)


def _flatten_time(tree):
    return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), tree)


def make_rl_train_step(model: Model, loss_cfg: ReinforcementLossConfig, optimizer,
                       batch_size: int, unroll_len: int, save_grad: bool = False,
                       dynamics=None):
    """Build the pure train-step fn (params, opt_state, batch) -> updated.

    With ``save_grad`` the info dict additionally carries per-parameter
    grad/param L2 norms (reference save_grad TB dumps,
    rl_learner.py:35-47,118-130) — static at trace time, so the toggle
    never mixes compiled variants. ``dynamics`` (an obs.DynamicsSpec, or
    None) statically folds the training-dynamics diagnostics tree into the
    info dict — computed against pre-step params and post-clip updates, so
    the update-to-weight ratios and non-finite censuses describe exactly
    this step."""

    def loss_fn(params, batch, only_update_value):
        obs = {
            "spatial_info": _flatten_time(batch["spatial_info"]),
            "entity_info": _flatten_time(batch["entity_info"]),
            "scalar_info": _flatten_time(batch["scalar_info"]),
            "entity_num": batch["entity_num"].reshape(-1),
        }
        value_feature = batch.get("value_feature")
        if value_feature is not None:
            value_feature = _flatten_time(value_feature)
        out = model.apply(
            params,
            obs["spatial_info"], obs["entity_info"], obs["scalar_info"], obs["entity_num"],
            batch["hidden_state"], batch["action_info"], batch["selected_units_num"],
            batch_size, unroll_len,
            value_feature=value_feature,
            method=model.rl_forward,
        )
        inputs = {
            "target_logit": out["target_logit"],
            "value": out["value"],
            "action_log_prob": batch["behaviour_logp"],
            "teacher_logit": batch["teacher_logit"],
            "action": batch["action_info"],
            "reward": batch["reward"],
            "step": batch["step"],
            "done": batch.get("done"),
            "mask": batch["mask"],
            "entity_num": batch["entity_num"].reshape(-1, batch_size)[:unroll_len],
            "selected_units_num": batch["selected_units_num"],
        }
        if loss_cfg.use_dapo:
            inputs["successive_logit"] = batch["successive_logit"]
        import dataclasses

        cfg = dataclasses.replace(loss_cfg, only_update_value=False)
        with jax.named_scope("loss"):
            total, info = compute_rl_loss(inputs, cfg)
            total_value_only = info["td/total"]
            total = jnp.where(only_update_value, total_value_only, total)
        return total, info

    def rl_train_step(params, opt_state, batch, only_update_value):
        (_, info), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, only_update_value
        )
        # as in the SL step: what the model's modules do not name is named
        # here, under obs.STEP_SCOPES
        with jax.named_scope("diagnostics/grad_norm"):
            info["grad_norm"] = optax.global_norm(grads)
        if save_grad:
            with jax.named_scope("diagnostics/leaf_norms"):
                info.update(leaf_norms(grads, "grad_norm"))
                info.update(leaf_norms(params, "param_norm"))
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

    return rl_train_step


class RLLearner(BaseLearner):
    """Data-parallel league-RL learner."""

    _CAP_FN = staticmethod(cap_entities_rl)

    def __init__(self, cfg: Optional[dict] = None, mesh=None):
        cfg = deep_merge_dicts(RL_LEARNER_DEFAULTS, cfg or {})
        self.mesh = mesh if mesh is not None else make_mesh(MeshSpec())
        self.model_cfg = deep_merge_dicts(default_model_config(), cfg.get("model", {}))
        self.model_cfg.use_value_network = True
        self.model = Model(self.model_cfg)
        self.loss_cfg = make_loss_config(cfg.learner)
        self._remaining_value_pretrain = cfg.learner.get("value_pretrain_iters", -1)
        super().__init__(cfg)

    # ------------------------------------------------------------ state init
    def _setup_dataloader(self) -> None:
        lc = self.cfg.learner if hasattr(self, "cfg") else RL_LEARNER_DEFAULTS.learner
        self._dataloader = iter(
            FakeRLDataloader(
                batch_size=lc.batch_size,
                unroll_len=lc.unroll_len,
                hidden_size=self.model_cfg.encoder.core_lstm.hidden_size,
                hidden_layers=self.model_cfg.encoder.core_lstm.num_layers,
                use_value_feature=self.model_cfg.use_value_feature,
            )
        )

    def set_dataloader(self, it) -> None:
        self._dataloader = iter(it)

    def _setup_state(self) -> None:
        lc = self.cfg.learner
        B, T = lc.batch_size, lc.unroll_len
        from ..parallel.mesh import shrink_dp

        new_mesh = shrink_dp(self.mesh, B)
        if new_mesh is not self.mesh:
            self.logger.info(
                f"batch {B} not divisible by mesh dp={self.mesh.shape['dp']}; "
                f"shrunk to dp={new_mesh.shape['dp']} (other axes preserved)"
            )
            self.mesh = new_mesh
        from ..parallel.mesh import set_context_mesh

        set_context_mesh(self.mesh)  # ring attention resolves sp at trace time
        self.optimizer = self._build_optimizer()
        # jit the init: eager init dispatches thousands of tiny ops
        def init_fn(rng, spatial, entity, scalar, entity_num, hidden, action, sun, vf):
            return self.model.init(
                rng, spatial, entity, scalar, entity_num, hidden, action, sun, B, T,
                value_feature=vf,
                method=self.model.rl_forward,
            )

        setup = self._setup_spans
        with setup.span("fake_batch"):
            batch = self._cap(next(self._dataloader))
            batch = jax.tree.map(jnp.asarray, batch)
            vf = batch.get("value_feature")
            init_args = (
                *(_flatten_time(batch[k]) for k in ("spatial_info", "entity_info", "scalar_info")),
                batch["entity_num"].reshape(-1),
                batch["hidden_state"],
                batch["action_info"],
                batch["selected_units_num"],
                _flatten_time(vf) if vf is not None else None,
            )
        jitted_init = jax.jit(init_fn)
        # for admin-triggered value resets: keep only shape/dtype specs (not
        # the batch itself — that would pin it in HBM for the whole run)
        init_specs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), init_args
        )

        def _reinit(rng):
            dummy = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), init_specs)
            return jitted_init(rng, *dummy)

        self._init_params = _reinit
        with setup.span("model_init"):
            params = jitted_init(jax.random.PRNGKey(self.init_prng_seed), *init_args)
        del init_args
        from ..parallel.mesh import batch_sharding, fsdp_param_sharding, time_batch_sharding

        with setup.span("state_place"):
            repl = NamedSharding(self.mesh, P())
            # params sharded over the fsdp axis (replicated when fsdp == 1);
            # Adam moments follow the same shardings, so optimizer state is
            # 1/fsdp-sized per device
            param_sh = fsdp_param_sharding(self.mesh, params)
            params = jax.device_put(params, param_sh)
        with setup.span("opt_init"):
            opt_sh = fsdp_param_sharding(self.mesh, jax.eval_shape(self.optimizer.init, params))
            self._state = {
                "params": params,
                "opt_state": jax.jit(self.optimizer.init, out_shardings=opt_sh)(params),
            }
        step_fn = make_rl_train_step(
            self.model, self.loss_cfg, self.optimizer, B, T,
            save_grad=self.cfg.learner.get("save_grad", False),
            dynamics=self._dynamics_spec(),
        )
        from ..parallel.mesh import dp_axes

        self._shardings = dict(
            repl=repl,
            param=param_sh,
            opt=opt_sh,  # restore() re-places host state onto param/opt
            batch=time_batch_sharding(self.mesh),  # [T(,+1), B, ...]
            batch_nosp=NamedSharding(self.mesh, P(None, dp_axes(self.mesh))),
            # batch_size validates here: typed MeshConfigError at compile
            # time, not an opaque XLA sharding error on the first step
            flat=batch_sharding(self.mesh, batch_size=B),  # [B]-leading leaves
        )
        self._train_step = jax.jit(
            step_fn,
            donate_argnums=(0, 1),
            # pin params/opt outputs to their fsdp shardings; the loss-info
            # scalars replicate
            out_shardings=(param_sh, opt_sh, repl),
        )
        # the step's only_update_value argument, replicated on the mesh like
        # the rest of its arguments. A bare jnp.asarray is the one argument
        # whose sharding the call leaves open; a lowering from the call's
        # argument types (jit.lower of their ShapeDtypeStructs: the perf
        # monitor's cost analysis, the benchmark's memory analysis) then pins
        # it to its device, a program that differs from the call's in that one
        # annotation, which the compile cache keys apart: the step compiled
        # twice in full (190 s each at the flagship size on a v5e)
        self._value_only_flags = {
            v: jax.device_put(np.asarray(v), repl) for v in (False, True)}
        # analytic per-step collective estimate from the live mesh + params
        # (obs/perf.py) — the sanity bar a trace's collective bucket is read
        # against
        self._perf.set_collectives(self.mesh, self._state["params"])
        self._perf.set_state_bytes(self._state)

    def shard_batch(self, batch):
        """Place a host batch onto the mesh: B sharded over dp everywhere
        (axis 1 for time-major leaves, axis 0 for hidden_state). On an sp>1
        mesh the time axis additionally shards over sp — per leaf, because
        the batch mixes T+1 (obs/values) and T (reward/mask) leading dims
        and only sp-divisible ones can shard.

        Placement goes through ``parallel.feeder.assemble_global``, once
        for the batch's host tree under the tree of these shardings: on one
        host that is one async ``device_put``, each shard from host memory
        to its own chip; on a pod every host contributes its own batch
        shard and jax assembles the global array
        (``make_array_from_process_local_data``)."""
        sp = self.mesh.shape["sp"]
        dp_prod = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]

        def sharding_of(x):
            shape = np.shape(x)
            if len(shape) >= 2:
                if sp > 1 and shape[0] % sp:
                    return self._shardings["batch_nosp"]
                return self._shardings["batch"]
            if len(shape) == 1 and shape[0] % dp_prod == 0:
                return self._shardings["flat"]
            return self._shardings["repl"]

        shardings = jax.tree.map(sharding_of, {k: v for k, v in batch.items() if k != "hidden_state"})
        shardings["hidden_state"] = jax.tree.map(lambda _: self._shardings["flat"], batch["hidden_state"])
        return assemble_global(batch, shardings, token=self.name)

    def _place_batch(self, batch):
        """Prefetch placement: everything device-put ahead of time except the
        host-side staleness/trace fields."""
        with self._feed_spans.span("cap"):
            batch = self._cap(dict(batch))
            model_last_iter = np.asarray(batch.pop("model_last_iter"))
            span_ids = batch.pop("trace_span_ids", None)
            trace_age = batch.pop("trace_age_s", None)
        with self._feed_spans.span("put"):
            out = self.shard_batch(batch)
        out["model_last_iter"] = model_last_iter
        if span_ids is not None:
            out["trace_span_ids"] = span_ids
            out["trace_age_s"] = trace_age
        out["_on_device"] = True
        return out

    # ----------------------------------------------------------------- comm
    def attach_comm(self, adapter, player_id: str, league=None, send_model_freq: int = 4,
                    send_train_info_freq: int = 4, model_accept_count: int = 8) -> None:
        """Wire weight publication + league train-info (roles of the
        reference LearnerComm: _send_model_loop learner_comm.py:83-99 and
        send_train_info :101-137 incl. the remote-triggered checkpoint
        reset)."""
        from .hooks import LambdaHook

        lc = self.cfg.learner
        frames_per_iter = lc.batch_size * lc.unroll_len

        self._pending_reset_flag = False

        def send_model(learner):
            params_host = jax.tree.map(np.asarray, learner.state["params"])
            adapter.push(
                f"{player_id}model",
                {
                    "params": params_host,
                    "iter": learner.last_iter.val,
                    # actors restart episodes when a league reset swapped the
                    # checkpoint (reference actor_comm.py:191-196)
                    "reset_flag": learner._pending_reset_flag,
                },
                accept_count=model_accept_count,
                timeout_ms=120_000,
            )
            learner._pending_reset_flag = False

        def send_train_info(learner):
            if league is None:
                return
            reply = league.learner_send_train_info(
                player_id, train_steps=frames_per_iter * send_train_info_freq
            )
            reset_path = (reply or {}).get("reset_checkpoint_path")
            if reset_path:
                import os

                if os.path.exists(reset_path):
                    learner.restore(reset_path)
                    # only a real checkpoint swap makes actors restart
                    learner._pending_reset_flag = True
                    learner.logger.info(f"league reset: restored {reset_path}")
                else:
                    learner.logger.info(
                        f"league reset requested ({reset_path}); checkpoint absent, keeping weights"
                    )

        self.hooks.add(LambdaHook("send_model", "after_iter", send_model, freq=send_model_freq))
        self.hooks.add(LambdaHook("send_model_init", "before_run", send_model))
        self.hooks.add(
            LambdaHook("send_train_info", "after_iter", send_train_info, freq=send_train_info_freq)
        )

    # ----------------------------------------------------------------- admin
    # (start_admin / request_save / request_profile live on BaseLearner; the
    # RL learner adds the config-patch and value-reset surfaces)
    def request_update_config(self, cfg_patch: dict) -> None:
        self._pending_config_patch = cfg_patch

    def request_value_reset(self) -> None:
        self._pending_value_reset = True

    def _apply_admin_requests(self) -> None:
        patch = getattr(self, "_pending_config_patch", None)
        if patch:
            self._pending_config_patch = None
            self.cfg = deep_merge_dicts(self.cfg, patch)
            lc = self.cfg.learner
            # hyperparameter changes rebuild the optax chain; opt state resets
            # (the reference rebuilds the optimizer on update_config too)
            self.optimizer = self._build_optimizer()
            from ..parallel.mesh import fsdp_param_sharding

            opt_sh = fsdp_param_sharding(
                self.mesh, jax.eval_shape(self.optimizer.init, self._state["params"])
            )
            self._shardings["opt"] = opt_sh
            self._state["opt_state"] = jax.jit(self.optimizer.init, out_shardings=opt_sh)(
                self._state["params"]
            )
            self._train_step = jax.jit(
                make_rl_train_step(
                    self.model, self.loss_cfg, self.optimizer,
                    lc.batch_size, lc.unroll_len,
                    save_grad=lc.get("save_grad", False),
                    dynamics=self._dynamics_spec(),
                ),
                donate_argnums=(0, 1),
                out_shardings=(self._shardings["param"], opt_sh, self._shardings["repl"]),
            )
            self.logger.info(f"applied config patch: {patch}")
        if getattr(self, "_pending_save", False):
            self._pending_save = False
            path = self.checkpoint_path()
            # an operator asked for this one: durable before we log "saved"
            self.save(path, sync=True)
            self.logger.info(f"admin checkpoint saved: {path}")
        if getattr(self, "_pending_value_reset", False):
            self._pending_value_reset = False
            # re-init ONLY the value towers (reference reset_value,
            # rl_learner.py:233-247)
            fresh = self._init_params(jax.random.PRNGKey(self.last_iter.val + 1))
            params = self._state["params"]
            new_params = {"params": dict(params["params"])}
            for k in params["params"]:
                if k.startswith("value_") or k == "value_encoder":
                    new_params["params"][k] = fresh["params"][k]
            self._state["params"] = jax.device_put(
                new_params, self._shardings["param"]
            )
            self.logger.info("value networks reset")

    # ------------------------------------------------------------- training
    def step_value_pretrain(self) -> bool:
        """Value-pretrain gate (reference rl_learner.py:160-180): during the
        first value_pretrain_iters only the critics train."""
        if self._remaining_value_pretrain > 0:
            self._remaining_value_pretrain -= 1
            return True
        return False

    def _dynamics_aux(self) -> Dict[str, Any]:
        """Pre-step extras for a black-box bundle: the value-pretrain gate
        the step is ABOUT to use (read before _train decrements it) — host
        scalars only, so before_step stays free on the healthy path."""
        return {"only_update_value": self._remaining_value_pretrain > 0}

    def _train(self, data) -> Dict[str, Any]:
        spans = self.spans
        with spans.span("prepare"):
            only_value = self._value_only_flags[self.step_value_pretrain()]
            data = dict(data)  # callers may reuse the batch dict
            on_device = data.pop("_on_device", False)
            model_last_iter = np.asarray(data.pop("model_last_iter"))
            staleness = self.last_iter.val - model_last_iter
            # pipeline-span fields minted in the actor (host-side: never sharded)
            span_ids = data.pop("trace_span_ids", None)
            trace_age = data.pop("trace_age_s", None)
            if not on_device:
                data = self.shard_batch(self._cap(data))
        with spans.span("dispatch"):
            params, opt_state, info = self._train_step(
                self._state["params"], self._state["opt_state"], data, only_value)
            # after the call (the new state has the donated one's types): the
            # background flop count then re-uses this trace instead of racing it
            self._perf_note_step_args(
                self._train_step, params, opt_state, data, only_value)
            self._state = {"params": params, "opt_state": opt_state}
        with spans.span("fetch"):
            # one batched D2H transfer — per-scalar float() would round-trip
            # once per metric across the ~60-entry loss grid every iteration
            log = {k: float(v) for k, v in jax.device_get(info).items()}
            log["staleness/mean"] = float(staleness.mean())
            log["staleness/max"] = float(staleness.max())
            log["staleness/std"] = float(staleness.std())
            if trace_age is not None and len(trace_age):
                # wall-clock counterpart of iteration staleness: seconds from the
                # trajectory's birth in the actor to this train step (span ids in
                # trace_span_ids attribute outliers to specific trajectories)
                log["trace/age_s_mean"] = float(np.mean(trace_age))
                log["trace/age_s_max"] = float(np.max(trace_age))
                self._last_span_ids = list(span_ids or [])
            self._apply_admin_requests()
        return log

"""Supervised learner: behaviour cloning from decoded replays.

Role of the reference SLLearner (reference: distar/agent/default/
sl_learner.py:23-86): teacher-forced CE training with LSTM hidden state
carried across iterations and reset on new episodes. The carry lives in the
learner (host-managed [B, H] arrays fed back into the jitted step), matching
the reference's stateful-BPTT-across-windows design.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..losses import SupervisedLossConfig, compute_sl_loss
from ..model import Model, default_model_config
from ..parallel import MeshSpec, assemble_global, make_mesh
from ..parallel.grad_clip import leaf_norms
from ..utils import deep_merge_dicts
from .base_learner import DEFAULT_LEARNER_CONFIG, BaseLearner
from .data import FakeSLDataloader, cap_entities

SL_LEARNER_DEFAULTS = deep_merge_dicts(
    DEFAULT_LEARNER_CONFIG,
    {
        "learner": {
            "batch_size": 2,
            "unroll_len": 32,
            "learning_rate": 1e-3,
            "betas": [0.9, 0.999],
            "eps": 1e-8,
            "weight_decay": 1e-5,
            "grad_clip": {"type": "norm", "threshold": 1.0},
            "label_smooth": 0.0,
            # per-parameter grad/param-norm logging (reference save_grad)
            "save_grad": False,
            # pad-to-bucket entity cap (throughput; see data.cap_entities)
            "max_entities": None,
            # loss-spike debug snapshots (reference sl_learner debug mode)
            "debug_loss_spike": False,
            "debug_spike_factor": 10.0,
            "debug_spike_warmup": 200,
        },
        "model": {},
    },
)


def make_sl_train_step(model: Model, loss_cfg: SupervisedLossConfig, optimizer,
                       batch_size: int, save_grad: bool = False, dynamics=None):
    def loss_fn(params, batch, hidden_state):
        logits, out_state = model.apply(
            params,
            batch["spatial_info"], batch["entity_info"], batch["scalar_info"],
            batch["entity_num"], batch["action_info"], batch["selected_units_num"],
            hidden_state, batch_size,
            method=model.sl_forward,
        )
        with jax.named_scope("loss"):
            total, info = compute_sl_loss(
                logits,
                batch["action_info"],
                batch["action_mask"],
                batch["selected_units_num"],
                batch["entity_num"],
                loss_cfg,
            )
        return total, (info, out_state)

    # the function's name is the compiled program's (``jit_sl_train_step`` in
    # a trace) and the head of its compile-cache key: an executable cached
    # before the scopes below existed carries none of them, and is not served
    def sl_train_step(params, opt_state, batch, hidden_state):
        (_, (info, out_state)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, hidden_state
        )
        # every operation of the step sits under one of obs.STEP_SCOPES: the
        # model's modules name theirs, the rest is named here
        with jax.named_scope("diagnostics/grad_norm"):
            info["grad_norm"] = optax.global_norm(grads)
        if save_grad:
            # per-parameter norms (reference save_grad TB dumps)
            with jax.named_scope("diagnostics/leaf_norms"):
                info.update(leaf_norms(grads, "grad_norm"))
                info.update(leaf_norms(params, "param_norm"))
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        if dynamics is not None:
            # pre-step params + post-clip updates: ratios/censuses describe
            # exactly this step (obs/dynamics.py)
            from ..obs import dynamics_tree

            with jax.named_scope("diagnostics/dynamics_tree"):
                info.update(dynamics_tree(
                    params, grads, updates=updates, batch=batch, spec=dynamics
                ))
        with jax.named_scope("optimizer"):
            params = optax.apply_updates(params, updates)
        return params, opt_state, out_state, info

    return sl_train_step


class SLLearner(BaseLearner):
    _CAP_FN = staticmethod(cap_entities)

    def __init__(self, cfg: Optional[dict] = None, mesh=None):
        cfg = deep_merge_dicts(SL_LEARNER_DEFAULTS, cfg or {})
        self.mesh = mesh if mesh is not None else make_mesh(MeshSpec())
        self.model_cfg = deep_merge_dicts(default_model_config(), cfg.get("model", {}))
        self.model = Model(self.model_cfg)
        self.loss_cfg = SupervisedLossConfig(label_smooth=cfg.learner.label_smooth)
        super().__init__(cfg)

    def _setup_dataloader(self) -> None:
        lc = self.cfg.learner
        self._dataloader = iter(FakeSLDataloader(lc.batch_size, lc.unroll_len))

    def set_dataloader(self, it) -> None:
        self._dataloader = iter(it)

    def _setup_state(self) -> None:
        lc = self.cfg.learner
        B = lc.batch_size
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
        setup = self._setup_spans
        with setup.span("fake_batch"):
            core = self.model_cfg.encoder.core_lstm
            self._hidden = tuple(
                (jnp.zeros((B, core.hidden_size)), jnp.zeros((B, core.hidden_size)))
                for _ in range(core.num_layers)
            )
            batch = next(self._dataloader)
            batch.pop("new_episodes", None)
            batch.pop("traj_lens", None)
            batch = self._cap(batch)  # init at the capped shape: one compile, not two
            batch = jax.tree.map(jnp.asarray, batch)

        def init_fn(rng, spatial, entity, scalar, entity_num, action, sun, hidden):
            return self.model.init(
                rng, spatial, entity, scalar, entity_num, action, sun, hidden, B,
                method=self.model.sl_forward,
            )

        with setup.span("model_init"):
            params = jax.jit(init_fn)(
                jax.random.PRNGKey(self.init_prng_seed),
                batch["spatial_info"], batch["entity_info"], batch["scalar_info"],
                batch["entity_num"], batch["action_info"], batch["selected_units_num"],
                self._hidden,
            )
        from ..parallel.mesh import batch_sharding, fsdp_param_sharding

        with setup.span("state_place"):
            repl = NamedSharding(self.mesh, P())
            param_sh = fsdp_param_sharding(self.mesh, params)
            params = jax.device_put(params, param_sh)
        with setup.span("opt_init"):
            opt_sh = fsdp_param_sharding(self.mesh, jax.eval_shape(self.optimizer.init, params))
            self._state = {
                "params": params,
                "opt_state": jax.jit(self.optimizer.init, out_shardings=opt_sh)(params),
            }
        with setup.span("state_place"):
            # batch_size validates here: typed MeshConfigError at compile time,
            # not an opaque XLA sharding error on the first step
            flat_sh = batch_sharding(self.mesh, batch_size=B)
            self._shardings = dict(repl=repl, param=param_sh, opt=opt_sh, flat=flat_sh)
            # the carry the step is first called with must be typed like the one
            # it hands back (out_shardings below), or the second iteration
            # re-traces and re-compiles the whole step
            self._hidden = jax.device_put(self._hidden, flat_sh)
        self._train_step = jax.jit(
            make_sl_train_step(
                self.model, self.loss_cfg, self.optimizer, B,
                save_grad=self.cfg.learner.get("save_grad", False),
                dynamics=self._dynamics_spec(),
            ),
            donate_argnums=(0, 1),
            # params/opt keep their fsdp shardings; the carried hidden state
            # shards over batch; the info scalars replicate (prefix leaves
            # broadcast over their subtrees)
            out_shardings=(param_sh, opt_sh, flat_sh, repl),
        )
        # analytic per-step collective estimate (obs/perf.py)
        self._perf.set_collectives(self.mesh, self._state["params"])
        self._perf.set_state_bytes(self._state)

    def evaluate(self, dataloader, max_batches: int = 0) -> Dict[str, float]:
        """Held-out metric pass: run the SL forward + loss/metric grid over
        a dataloader WITHOUT gradients or state mutation, averaging the
        scalar metrics across batches (the eval axis of SURVEY §7 milestone
        4 — train acc alone can't show generalization). Hidden state starts
        cold per batch; windows within a batch still carry it forward
        through the unroll. Stops at ``max_batches`` (0 = drain)."""
        if not hasattr(self, "_eval_step"):
            B = self.cfg.learner.batch_size

            def eval_step(params, batch, hidden_state):
                logits, out_state = self.model.apply(
                    params,
                    batch["spatial_info"], batch["entity_info"],
                    batch["scalar_info"], batch["entity_num"],
                    batch["action_info"], batch["selected_units_num"],
                    hidden_state, B,
                    method=self.model.sl_forward,
                )
                total, info = compute_sl_loss(
                    logits, batch["action_info"], batch["action_mask"],
                    batch["selected_units_num"], batch["entity_num"],
                    self.loss_cfg,
                )
                info["total_loss"] = total
                return info

            self._eval_step = jax.jit(eval_step)
        sums: Dict[str, float] = {}
        n = 0
        core = self.model_cfg.encoder.core_lstm
        B = self.cfg.learner.batch_size
        hidden = tuple(
            (jnp.zeros((B, core.hidden_size)), jnp.zeros((B, core.hidden_size)))
            for _ in range(core.num_layers)
        )
        for batch in dataloader:
            batch = dict(batch)
            batch.pop("new_episodes", None)
            batch.pop("traj_lens", None)
            batch = self._cap(batch)
            batch = jax.tree.map(jnp.asarray, batch)
            info = self._eval_step(self.state["params"], batch, hidden)
            for k, v in info.items():
                v = np.asarray(v)
                if v.ndim == 0 and np.issubdtype(v.dtype, np.floating):
                    sums[k] = sums.get(k, 0.0) + float(v)
            n += 1
            if max_batches and n >= max_batches:
                break
        return {k: v / max(n, 1) for k, v in sums.items()}

    def _place_batch(self, data):
        """Prefetch placement: placed (mesh-sharded) ahead of time, host
        fields kept. Routes through ``assemble_global`` so per-host shards
        assemble into global arrays on a pod."""
        with self._feed_spans.span("cap"):
            data = self._cap(dict(data))
            host = {k: np.asarray(data.pop(k)) for k in ("new_episodes", "traj_lens") if k in data}
        with self._feed_spans.span("put"):
            out = assemble_global(data, self._shardings["flat"], token=self.name)
        out.update(host)
        out["_on_device"] = True
        return out

    def _dynamics_aux(self) -> Dict[str, Any]:
        """Pre-step extras for a black-box bundle: the carried LSTM hidden
        BEFORE this step's episode-reset (replay restores it and lets
        _train re-apply the reset from the batch's own new_episodes).
        Device-array REFS only — hidden is not donated, so they stay valid;
        the D2H fetch happens only if a bundle is written."""
        return {"hidden_state": self._hidden}

    def _train(self, data) -> Dict[str, Any]:
        spans = self.spans
        with spans.span("prepare"):
            data = dict(data)  # callers may reuse the batch dict
            on_device = data.pop("_on_device", False)
            if not on_device:
                data = self._cap(data)
            new_episodes = np.asarray(data.pop("new_episodes"))
            traj_lens = data.pop("traj_lens", None)
            if new_episodes.any():
                # reset hidden state for restarted trajectories (reference
                # sl_learner.py:31-35)
                keep = jnp.asarray(~new_episodes, jnp.float32)[:, None]
                self._hidden = tuple((h * keep, c * keep) for h, c in self._hidden)
            if not on_device:
                data = assemble_global(data, self._shardings["flat"], token=self.name)
            debug_on = self.cfg.learner.get("debug_loss_spike", False)
            if debug_on:
                # the step's exact inputs: batch + post-reset hidden (params are
                # donated, so a spike's checkpoint is one Adam step past — noted
                # in the snapshot)
                pre_step = {
                    "batch": data,
                    "hidden_state": self._hidden,
                    "new_episodes": new_episodes,
                    "traj_lens": traj_lens,
                }
        with spans.span("dispatch"):
            params, opt_state, out_state, info = self._train_step(
                self._state["params"], self._state["opt_state"], data, self._hidden
            )
            # after the call (the new state has the donated one's types): the
            # background flop count then re-uses this trace instead of racing it
            self._perf_note_step_args(
                self._train_step, params, opt_state, data, self._hidden)
            self._state = {"params": params, "opt_state": opt_state}
            self._hidden = jax.tree.map(jax.lax.stop_gradient, out_state)
        with spans.span("fetch"):
            # one batched D2H transfer instead of a round-trip per metric
            log = {k: float(v) for k, v in jax.device_get(info).items()}
            if debug_on:
                self._loss_spike_guard(log, pre_step)
        return log

    # snapshots per run: a misbehaving trigger must not flood the disk
    _DEBUG_DUMP_CAP = 20
    # EMAs this small are "no signal yet" (masked heads are exactly 0.0 for
    # batches without those actions) — never treat growth from them as a spike
    _DEBUG_EMA_FLOOR = 0.01

    def _loss_spike_guard(self, log: Dict[str, float], pre_step: dict) -> None:
        """Debug mode: EMA-track every loss term; when one spikes past
        ``debug_spike_factor``× its EMA after ``debug_spike_warmup`` iters —
        or goes non-finite — save a checkpoint and dump the step's exact
        inputs (batch, post-reset hidden state, episode boundaries) + log
        for offline repro (role of the reference SL debug mode,
        sl_learner.py:55-60: 0.95/0.05 EMA, 10x trigger, iter>200)."""
        if not hasattr(self, "_debug_ema"):
            self._debug_ema = {}
            self._debug_dumps = 0
            self._debug_nonfinite = set()  # keys already reported as blown up
        factor = float(self.cfg.learner.get("debug_spike_factor", 10.0))
        warmup = int(self.cfg.learner.get("debug_spike_warmup", 200))
        dumped = False
        for k, v in log.items():
            if "loss" not in k:
                continue
            prev = self._debug_ema.get(k)
            blown_up = not np.isfinite(v)  # divergence is the headline event
            if not blown_up:
                self._debug_nonfinite.discard(k)  # recovered: re-arm
            elif k in self._debug_nonfinite:
                continue  # one snapshot per divergence event, not per iter
            # blown_up alone qualifies — a run that is non-finite from the
            # FIRST iteration (prev never seeded) is exactly the scenario
            # this mode exists to capture; ratio spikes need a finite EMA
            spiked = blown_up or (
                prev is not None
                and np.isfinite(prev)
                and prev > self._DEBUG_EMA_FLOOR
                and v > prev * factor
            )
            if (
                spiked
                # warmup only mutes ratio spikes (noisy early losses); a
                # non-finite loss must dump even at iteration 1
                and (blown_up or self.last_iter.val > warmup)
                and not dumped  # one snapshot per iteration is plenty
                and self._debug_dumps < self._DEBUG_DUMP_CAP
            ):
                dumped = True
                self._debug_dumps += 1
                if blown_up:
                    self._debug_nonfinite.add(k)
                self._dump_spike(k, v, prev, log, pre_step)
            if not blown_up:  # never poison the EMA with inf/nan
                self._debug_ema[k] = v if prev is None else prev * 0.95 + v * 0.05
        return

    def _dump_spike(self, key, value, ema, log, pre_step) -> None:
        from ..comm.serializer import dumps

        os.makedirs(os.path.join(self.save_dir, "debug"), exist_ok=True)
        path = os.path.join(
            self.save_dir, "debug",
            f"{key.replace('/', '_')}_iter_{self.last_iter.val}"
            f"_rank{self.rank}_{self._debug_dumps}.spike",
        )
        with open(path, "wb") as f:
            f.write(dumps({
                "key": key, "value": value, "ema": ema, "log": log,
                **{k: jax.device_get(v) for k, v in pre_step.items()},
                "note": "params in the companion checkpoint are one "
                        "optimizer step PAST the spike (donated buffers); "
                        "batch/hidden_state are the step's exact inputs",
            }, compress=True))
        self.save(self.checkpoint_path(), sync=True)  # debug artifacts are durable
        ema_txt = f"{ema:.4f}" if ema is not None else "unseeded"
        self.logger.info(
            f"loss spike: {key}={value:.4f} (ema {ema_txt}); snapshot {path}"
        )

"""Token-sequence learner: next-token training of a token model
(``model.TOKEN_MODELS``: ``LFM2``, ``NemotronH``, ``DeepseekV3``, ``Qwen3Next``, ``Laguna``, ``Phi4Flash``; the config's
``model.model_type`` says which, ``lfm2_moe`` when it says nothing) on
``BaseLearner``'s run loop, feeder, optimizer, dynamics tree and checkpoints.

A batch is two int32 leaves, ``tokens`` and ``labels`` ``[B, S]``, with
``S = learner.unroll_len``: a "frame" of the frames/s gauges is one position.
Nothing is carried between steps. The update is one jitted program,
``lm_train_step``. The train state is ``{"params": variables, "opt_state"}``
where ``variables`` holds the model's ``params`` and its ``buffers`` (the
routers' ``expert_bias``, which is not trained): gradients, the optimizer and
its state see ``variables["params"]`` only.

Started through the ordinary launcher, which resolves a learner by pipeline
(``plugins.load_component``); this module is such a pipeline:

  python -m distar_tpu.bin.sl_train --pipeline distar_tpu.learner.lm_learner \\
      --config configs/lfm2_24b_a2b_v5e.yaml --iters N
      (or configs/nemotron_twotower_30b_a3b_v5e.yaml, configs/kimi_vl_a3b_v5e.yaml,
      configs/qwen3_next_80b_a3b_v5e.yaml, configs/laguna_s_v5e.yaml, configs/phi4_mini_flash_v5e.yaml)

With no ``set_dataloader`` it trains on ``FakeTokenDataloader`` (Zipf ids).
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..losses import compute_lm_loss
from ..model import TOKEN_MODELS
from ..ops.sequence import cores_kept, heads_fused
from ..parallel import MeshSpec, assemble_global, make_mesh
from ..utils import deep_merge_dicts
from .base_learner import DEFAULT_LEARNER_CONFIG, BaseLearner

LM_LEARNER_DEFAULTS = deep_merge_dicts(
    DEFAULT_LEARNER_CONFIG,
    {
        "learner": {
            "batch_size": 2,
            "unroll_len": 64,          # the sequence length
            # AdamW; what the published-width files under configs/ train with (docs/token_models.md)
            "learning_rate": 1e-5,
            "betas": [0.9, 0.95],
            "eps": 1e-8,
            "weight_decay": 0.1,
            "grad_clip": {"type": "norm", "threshold": 1.0},
        },
        "model": {},
    },
)


def fake_token_batch(batch_size: int, seq_len: int, vocab_size: int,
                     rng: Optional[np.random.Generator] = None) -> Dict[str, np.ndarray]:
    """Zipf(1) ids over the vocabulary; labels are the next id."""
    rng = rng or np.random.default_rng(0)
    p = 1.0 / np.arange(1, vocab_size + 1)
    ids = rng.choice(vocab_size, size=(batch_size, seq_len + 1), p=p / p.sum()).astype(np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


class FakeTokenDataloader:
    def __init__(self, batch_size: int, seq_len: int, vocab_size: int, seed: int = 0):
        self._rng = np.random.default_rng(seed)
        self._shape = (batch_size, seq_len, vocab_size)

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        return fake_token_batch(*self._shape, rng=self._rng)


def forward_loss(model, variables, params, batch):
    """The loss of ``params`` on a batch and the step's log: what the train
    step differentiates and ``evaluate`` reports."""
    logits, stats = model.apply({**variables, "params": params}, batch["tokens"])
    with jax.named_scope("loss"):
        total, info = compute_lm_loss(logits, batch["labels"])
    with jax.named_scope("diagnostics/moe"):
        rows = stats["rows"].astype(jnp.float32)              # [expert layers, experts held]
        info.update(stats, moe_rows_here=rows.sum(), moe_load_max_over_mean=jnp.max(
            rows.max(axis=-1) / jnp.maximum(rows.mean(axis=-1), 1.0), initial=0.0))
    return total, info


def make_lm_train_step(model, optimizer, dynamics=None):
    # the function's name is the compiled program's name and heads its
    # compile-cache key (see make_sl_train_step)
    def lm_train_step(variables, opt_state, batch):
        params = variables["params"]
        (_, info), grads = jax.value_and_grad(
            lambda p: forward_loss(model, variables, p, batch), has_aux=True)(params)
        with jax.named_scope("diagnostics/grad_norm"):
            info["grad_norm"] = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        if dynamics is not None:
            from ..obs import dynamics_tree

            with jax.named_scope("diagnostics/dynamics_tree"):
                info.update(dynamics_tree(params, grads, updates=updates, batch=batch, spec=dynamics))
        with jax.named_scope("optimizer"):
            params = optax.apply_updates(params, updates)
        return {**variables, "params": params}, opt_state, info

    return lm_train_step


def _flat_log(info: Dict[str, Any], moe_layers) -> Dict[str, float]:
    """The step's fetched outputs as named scalars: per-layer vectors become
    ``residual_rms/layer_<i>``, ``ff_rms/layer_<i>``, ``attn_rms/layer_<i>`` or ``mixer_rms/layer_<i>``
    and ``moe_rows/layer_<i>/expert_<e>`` (``e`` counts the experts held) with
    ``moe_rows_sum/layer_<i>`` and ``moe_rows_max/layer_<i>`` over them; a
    statistic that only some layers have comes as a dict by layer
    (``ssm_state_rms/layer_<i>``; ``gdn_state_rms/``, ``gdn_decay_mean/`` and
    ``attn_gate_mean/layer_<i>`` of ``qwen3_next``; ``attn_gate_mean/`` of every ``laguna`` layer;
    ``ssm_state_rms/`` and ``diff_lambda/layer_<i>`` of ``phi4flash``, whose ``memory_rms`` is one number)."""
    log = {}
    for k, v in info.items():
        if isinstance(v, dict):
            log.update({f"{k}/{name}": float(x) for name, x in v.items()})
            continue
        v = np.asarray(v)
        if k in ("rms", "ff_rms", "mixer_rms", "attn_rms"):
            name = "residual_rms" if k == "rms" else k
            log.update({f"{name}/layer_{i}": float(x) for i, x in enumerate(v)})
        elif k == "rows":
            log.update({f"moe_rows/layer_{moe_layers[j]}/expert_{e}": float(x)
                        for j, row in enumerate(v) for e, x in enumerate(row)})
            # a layer's rows here and its busiest held expert's: thousands of rows, so a pick in fifty
            # that falls the other way in bfloat16 moves them by half a percent, a held expert of 26 rows by 8
            log.update({f"moe_rows_sum/layer_{moe_layers[j]}": float(row.sum()) for j, row in enumerate(v)})
            log.update({f"moe_rows_max/layer_{moe_layers[j]}": float(row.max()) for j, row in enumerate(v)})
        elif k == "overflow":
            log["moe_overflow_rows"] = float(v)
        elif k == "buffer_rows":
            log["moe_buffer_rows"] = float(v)
        elif k == "row_indexed":
            log["moe_row_indexed_layers"] = float(v)
        else:
            log[k] = float(v)
    return log


class LMLearner(BaseLearner):
    def __init__(self, cfg: Optional[dict] = None, mesh=None):
        cfg = deep_merge_dicts(LM_LEARNER_DEFAULTS, cfg or {})
        self.mesh = mesh if mesh is not None else make_mesh(MeshSpec())
        model_type = cfg.get("model", {}).get("model_type", "lfm2_moe")
        if model_type not in TOKEN_MODELS:
            raise ValueError(f"model.model_type {model_type!r}: one of {sorted(TOKEN_MODELS)}")
        model_cls, defaults = TOKEN_MODELS[model_type]
        self.model_cfg = deep_merge_dicts(defaults(), cfg.get("model", {}))
        self.model = model_cls(self.model_cfg)
        self._moe_layers = model_cls.moe_layers(self.model_cfg)
        super().__init__(cfg)

    def _setup_dataloader(self) -> None:
        lc = self.cfg.learner
        self._dataloader = iter(FakeTokenDataloader(
            lc.batch_size, lc.unroll_len, self.model_cfg.vocab_size))

    def set_dataloader(self, it) -> None:
        self._dataloader = iter(it)

    def _setup_state(self) -> None:
        from ..parallel.mesh import batch_sharding, fsdp_param_sharding, shrink_dp

        lc = self.cfg.learner
        B, S = lc.batch_size, lc.unroll_len
        self.mesh = shrink_dp(self.mesh, B)
        self.optimizer = self._build_optimizer()
        setup = self._setup_spans
        # a whole host trace of the model, for its shardings, for what its layers' remat keeps and for which form
        # its attention layers' q/k preparations take
        with setup.span("init_shapes"), cores_kept() as kept, heads_fused() as fused:
            tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
            rng = jax.random.PRNGKey(self.init_prng_seed)
            param_sh = fsdp_param_sharding(self.mesh, jax.eval_shape(self.model.init, rng, tokens))
        with setup.span("model_init"):
            # made where they will live: the state is 16 bytes a parameter and
            # no second copy of it fits beside it
            variables = jax.jit(
                lambda r: self.model.init(r, jnp.zeros(tokens.shape, tokens.dtype)),
                out_shardings=param_sh)(rng)
        with setup.span("opt_init"):
            opt_sh = fsdp_param_sharding(
                self.mesh, jax.eval_shape(self.optimizer.init, variables["params"]))
            self._state = {
                "params": variables,
                "opt_state": jax.jit(self.optimizer.init, out_shardings=opt_sh)(variables["params"]),
            }
        repl = NamedSharding(self.mesh, P())
        self._shardings = dict(repl=repl, param=param_sh, opt=opt_sh,
                               flat=batch_sharding(self.mesh, batch_size=B))
        self._train_step = jax.jit(
            make_lm_train_step(self.model, self.optimizer, dynamics=self._dynamics_spec()),
            donate_argnums=(0, 1), out_shardings=(param_sh, opt_sh, repl))
        self._perf.set_collectives(self.mesh, variables)
        self._perf.set_state_bytes(self._state)
        self.metrics.gauge(
            "distar_lm_cores_kept",
            "full-causal attention cores whose forward results go by a name a layer's remat saves").set(len(kept))
        self.metrics.gauge(
            "distar_lm_cores_kept_bytes", "bytes of those results (out and logsumexp), all cores").set(sum(kept))
        for form, count in (("fused", sum(fused)), ("xla", len(fused) - sum(fused))):
            self.metrics.gauge(
                "distar_lm_heads_fused",
                "q/k preparations (head norm, rotation, scale, the kernel's layout) by the form a TPU's program gives them",
                form=form).set(count)
        self.logger.info(
            f"{type(self.model).__name__}: {len(kept)} full-causal attention cores name their forward results (out "
            f"and logsumexp, {sum(kept)} bytes) for the layers' remat to keep (remat={bool(self.model_cfg.remat)})")
        self._moe_rows = self.metrics.histogram(
            "distar_moe_rows_here", "rows routed to the experts held here, all layers, per step")
        self._moe_load = self.metrics.histogram(
            "distar_moe_load_max_over_mean",
            "largest over the expert layers of (most loaded held expert / mean held expert), per step")
        self._moe_buffer_rows = self.metrics.histogram(
            "distar_moe_buffer_rows",
            "rows of the expert buffers walked (chunks of one row a position that held rows), all layers, per step")
        self._moe_row_indexed = self.metrics.histogram(
            "distar_moe_row_indexed_layers",
            "expert layers whose program moved its rows by buffer row, not by pick, per step")
        self._moe_overflow = self.metrics.counter(
            "distar_moe_overflow_rows_total", "rows routed here that an expert buffer could not take")

    def _put(self, data) -> Dict[str, jax.Array]:
        return assemble_global({k: np.asarray(data[k], np.int32) for k in ("tokens", "labels")},
                               self._shardings["flat"], token=self.name)

    def _place_batch(self, data):
        with self._feed_spans.span("put"):
            return dict(self._put(data), _on_device=True)

    def evaluate(self, dataloader, max_batches: int = 0) -> Dict[str, float]:
        """Forward pass and loss over a dataloader, no gradient and no
        update; the named scalars of ``_flat_log``, averaged over batches."""
        if not hasattr(self, "_eval_step"):
            self._eval_step = jax.jit(lambda variables, batch: forward_loss(
                self.model, variables, variables["params"], batch)[1])
        sums: Dict[str, float] = {}
        n = 0
        for batch in dataloader:
            # analysis: allow(jax-device-get-in-loop) — one fetch of the whole info tree a batch, as a train step makes
            log = _flat_log(jax.device_get(self._eval_step(self._state["params"], self._put(batch))),
                            self._moe_layers)
            sums = {k: sums.get(k, 0.0) + v for k, v in log.items()}
            n += 1
            if max_batches and n >= max_batches:
                break
        return {k: v / max(n, 1) for k, v in sums.items()}

    def _train(self, data) -> Dict[str, Any]:
        spans = self.spans
        with spans.span("prepare"):
            data = dict(data)
            if not data.pop("_on_device", False):
                data = self._put(data)
        with spans.span("dispatch"):
            variables, opt_state, info = self._train_step(
                self._state["params"], self._state["opt_state"], data)
            self._perf_note_step_args(self._train_step, variables, opt_state, data)
            self._state = {"params": variables, "opt_state": opt_state}
        with spans.span("fetch"):
            log = _flat_log(jax.device_get(info), self._moe_layers)
            self._moe_rows.observe(log["moe_rows_here"])
            self._moe_load.observe(log["moe_load_max_over_mean"])
            self._moe_buffer_rows.observe(log["moe_buffer_rows"])
            self._moe_row_indexed.observe(log["moe_row_indexed_layers"])
            self._moe_overflow.inc(log["moe_overflow_rows"])
            # the layer's buffer is its provable bound: a row that found no place is a fault of ops/moe
            if log["moe_overflow_rows"]:
                raise RuntimeError(f"{log['moe_overflow_rows']:.0f} rows routed to the experts held "
                                   f"here were not computed at iteration {self.last_iter.val}")
        return log


# the pipeline contract (plugins.py): this module is a pipeline whose
# supervised learner is the token-sequence learner
SLLearner = LMLearner

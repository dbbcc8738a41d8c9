"""Abstract hook-driven train engine.

Role of the reference BaseLearner (reference: distar/ctools/worker/learner/
base_learner.py:24-272): owns the model/optimizer state, a dataloader
iterator, the hook registry, timing, logging, and the crash-safe run loop.
Subclasses implement `_setup_state()` (build params/opt) and `_train(data)`
(one jitted step). Distributed-ness is ambient: the train step is pjit'd
over a mesh, rank == jax.process_index().
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

import jax

from ..obs import (
    DYNAMICS_DEFAULTS,
    DynamicsMonitor,
    PerfMonitor,
    feed_spans,
    get_registry,
    loop_spans,
    mark_process_age,
    setup_spans,
    tree_spec,
)
from ..utils import Config, build_logger, deep_merge_dicts
from ..utils.checkpoint import (
    AsyncCheckpointer,
    CheckpointCorruptError,
    CheckpointManager,
    CountVar,
    auto_checkpoint,
    load_checkpoint,
    save_checkpoint,
)
from .hooks import HookRegistry, default_hooks


def experiments_root() -> str:
    """Default experiment-dir root. ``DISTAR_EXPERIMENTS_ROOT`` overrides the
    cwd-relative ``experiments/`` — the test harness points it at a tmp dir
    so a stale ``experiments/`` from a previous run can never poison a later
    run's auto-resume (the PR 5 tier-1 failure mode)."""
    return os.environ.get("DISTAR_EXPERIMENTS_ROOT") or os.path.join(
        os.getcwd(), "experiments"
    )


DEFAULT_LEARNER_CONFIG = Config(
    {
        "common": {"experiment_name": "default_experiment", "save_path": ""},
        "learner": {
            "job_type": "train",
            "learning_rate": 1e-5,
            "save_freq": 1000,
            "log_freq": 100,
            "load_path": "",
            "max_iterations": 10 ** 9,
            "grad_clip": {"type": "none", "threshold": 1.0},
            # sharded checkpoints (parallel/ckpt.py): one CRC'd blob per
            # parameter shard + layout manifest, restorable onto ANY mesh.
            # Default off: monolithic .ckpt files stay the single-chip norm;
            # the --mesh CLI path and the executor turn it on.
            "sharded_ckpt": False,
            # device profiler hook: every profile.freq iters capture
            # profile.duration iters of jax.profiler trace (0 = disabled)
            "profile": {"freq": 0, "duration": 2, "logdir": ""},
            # live perf gauges (obs/perf.py): frames/s + step time always;
            # perf.aot extracts the step's flop count (MFU numerator) on a
            # background thread ("auto" = on unless DISTAR_PERF_AOT=0 — the
            # test harness opts out so dozens of small learners don't each
            # trace in the background); aot_compile additionally compiles
            # for the static memory_analysis footprint (cache-served when
            # the live step already compiled)
            "perf": {"aot": "auto", "aot_compile": False,
                     "mem_sample_every": 1},
            # training-dynamics observatory (obs/dynamics.py): the in-jit
            # diagnostics tree is computed every step; every_n gates gauge
            # EXPORT; anomalies (non-finite loss/grads, grad explosion,
            # entropy collapse) write debounced black-box bundles that
            # tools/stepreplay.py re-executes deterministically
            "dynamics": dict(DYNAMICS_DEFAULTS),
        },
    }
)


class BaseLearner:
    def __init__(self, cfg: Optional[dict] = None):
        self.metrics = get_registry()
        # set-up under named phases (obs/profiler.py::SETUP_PHASES), from here
        # to the end of the first step of ``run``; the process's age at four
        # points holds them to the clock and counts what came before and between
        mark_process_age("learner_init", self.metrics)
        self._setup_spans = setup_spans(self.metrics)
        with self._setup_spans.span("learner_base"):
            self._setup_base(cfg)
        with self._setup_spans.span("dataloader"):
            self._setup_dataloader()
        self._setup_state()  # its own phases, by learner
        with self._setup_spans.span("state_ready"):
            # the init programs' device time is set-up's, not the first step's
            jax.block_until_ready(self._state)
        mark_process_age("learner_ready", self.metrics)

    def _setup_base(self, cfg: Optional[dict]) -> None:
        """Logger, checkpoint manager, spans, hooks and monitors."""
        self.cfg = deep_merge_dicts(DEFAULT_LEARNER_CONFIG, cfg or {})
        self.rank = jax.process_index()
        self.world_size = jax.process_count()
        exp = self.cfg.common.experiment_name
        root = self.cfg.common.save_path or os.path.join(experiments_root(), exp)
        self.save_dir = root
        self.logger, self.scalar_sink, self.variable_record = build_logger(
            os.path.join(root, "logs"), f"{self.name}_rank{self.rank}", to_console=self.rank == 0
        )
        dev = jax.devices()[0]
        self.logger.info(
            f"devices: platform={dev.platform} device_kind={dev.device_kind!r} "
            f"count={jax.device_count()}"
        )
        self.last_iter = CountVar(0)
        self._checkpointer = AsyncCheckpointer()
        self._ckpt_manager = CheckpointManager(
            os.path.join(root, "checkpoints"),
            role=self.cfg.learner.get("ckpt_role", "") or self.CKPT_ROLE,
        )
        self.log_buffer: Dict[str, Any] = {}
        # the phases of the run loop and of the feeder thread: spans on the
        # profiler's clock and the phase histograms (obs/profiler.py)
        self.spans = loop_spans(self.metrics)
        self._feed_spans = feed_spans(self.name, self.metrics)
        self.metrics.gauge(
            "distar_device_count", "jax devices this process sees",
            platform=dev.platform, kind=dev.device_kind,
        ).set(jax.device_count())
        prof = self.cfg.learner.get("profile", {})
        self.hooks: HookRegistry = default_hooks(
            save_freq=self.cfg.learner.save_freq,
            log_freq=self.cfg.learner.log_freq,
            profile_freq=int(prof.get("freq", 0)),
            profile_duration=int(prof.get("duration", 2)),
            profile_logdir=prof.get("logdir", "")
            or os.path.join(root, "profiles"),
        )
        pcfg = self.cfg.learner.get("perf", {})
        aot = pcfg.get("aot", "auto")
        if aot == "auto":
            aot = os.environ.get("DISTAR_PERF_AOT", "1").lower() not in ("0", "false")
        self._perf_aot = bool(aot)
        self._perf = PerfMonitor(
            token=self.name,
            registry=self.metrics,
            aot_compile=bool(pcfg.get("aot_compile", False)),
            mem_sample_every=int(pcfg.get("mem_sample_every", 1)),
        )
        self._dynamics = DynamicsMonitor(
            dict(self.cfg.learner.get("dynamics", {}) or {}),
            name=self.name,
            registry=self.metrics,
            blackbox_dir=os.path.join(root, "blackbox"),
        )
        self._profile_lock = threading.Lock()
        self._profile_req: Optional[Dict[str, Any]] = None
        self._state = None  # TrainState pytree (params, opt_state, step)
        self._dataloader: Optional[Iterator] = None

    # pad-to-bucket entity cap: subclasses set _CAP_FN to the layout-aware
    # slicer (data.cap_entities / cap_entities_rl); one choke point for all
    # of setup/prefetch/train host paths
    _CAP_FN = None

    # params-init PRNG seed; recorded in black-box bundles so stepreplay can
    # rebuild bit-identical init state when a bundle omits the train state
    init_prng_seed = 0

    # checkpoint role key (utils.checkpoint.CheckpointManager): "" is the
    # teacher/default tier; the distillation student sets "student" so the
    # two tiers' generations can never cross on resume even when they share
    # an experiment directory
    CKPT_ROLE = ""

    def _cap(self, batch):
        n = self.cfg.learner.get("max_entities")
        fn = type(self)._CAP_FN
        if n and fn is not None:
            batch = fn(batch, int(n))
        return batch

    # -------------------------------------------------------------- plumbing
    @property
    def name(self) -> str:
        return type(self).__name__.lower()

    @property
    def state(self):
        return self._state

    def checkpoint_path(self) -> str:
        return os.path.join(self.save_dir, "checkpoints", f"iteration_{self.last_iter.val}.ckpt")

    @property
    def checkpoint_manager(self) -> CheckpointManager:
        return self._ckpt_manager

    def save(self, path: str, sync: bool = False) -> None:
        """Checkpoint the train state. By default (learner.async_save) the
        serialize+write overlaps training on a background thread; ``sync``
        forces a durable write before returning (crash/debug paths). Every
        save publishes the ``latest`` pointer only AFTER the bytes are
        durable, so crash-resume never points at a half-written file."""
        meta = {"last_iter": self.last_iter.val}
        step = self.last_iter.val
        snapshot_fn = write_fn = None
        if self.cfg.learner.get("sharded_ckpt", False):
            # distributed mode: per-shard D2H snapshot (sync — donated
            # buffers), per-shard CRC'd blob writes + layout manifest
            # (background); generation pointer discipline is identical
            from ..parallel import ckpt as dist_ckpt

            snapshot_fn = dist_ckpt.snapshot_sharded
            write_fn = dist_ckpt.write_sharded
        if sync or not self.cfg.learner.get("async_save", True):
            self._checkpointer.wait()  # never race an in-flight async write
            if write_fn is not None:
                write_fn(path, snapshot_fn(self._state), meta)
            else:
                save_checkpoint(path, self._state, metadata=meta)
            self._ckpt_manager.record(path, step=step)
        else:
            self._checkpointer.save(
                path, self._state, metadata=meta,
                on_complete=lambda p, s=step: self._ckpt_manager.record(p, step=s),
                snapshot_fn=snapshot_fn, write_fn=write_fn,
            )

    def restore(self, path: str) -> None:
        with self._setup_spans.span("restore"):
            self._checkpointer.wait()  # the path may still be being written
            out = load_checkpoint(path, target=self._state)
            self._validate_restored(path, out["state"])
        layout = out.get("sharding_layout") or {}
        saved_mesh = layout.get("mesh_shape")
        cur_mesh = dict(self.mesh.shape) if getattr(self, "mesh", None) is not None else None
        if saved_mesh and cur_mesh and dict(saved_mesh) != cur_mesh:
            # resharding restore: the checkpoint's host-global arrays are
            # about to be re-pinned onto a DIFFERENT mesh layout
            self.metrics.counter(
                "distar_ckpt_reshards_total",
                "sharded checkpoints restored onto a different mesh shape",
            ).inc()
            self.logger.info(
                f"resharding restore: checkpoint mesh {saved_mesh} -> "
                f"live mesh {cur_mesh}"
            )
        with self._setup_spans.span("state_place"):
            self._state = self._place_state(out["state"])
        self.last_iter.update(out["metadata"].get("last_iter", 0))

    def _validate_restored(self, path: str, state) -> None:
        """Auto-resume guard: a checkpoint whose leaves don't match this
        learner's state shapes (different model config — typically a stale
        experiment dir from an unrelated run) must fail TYPED here, so
        ``resume_latest`` falls back/cold-starts instead of poisoning the
        run (and a direct ``restore`` fails before the train step does,
        with the offending leaves named)."""
        if self._state is None:
            return
        from ..utils.checkpoint import CheckpointMismatchError

        cur = jax.tree_util.tree_flatten_with_path(self._state)[0]
        new = jax.tree_util.tree_flatten_with_path(state)[0]
        cur_shapes = {
            jax.tree_util.keystr(p): tuple(getattr(x, "shape", ()) or ())
            for p, x in cur
        }
        mismatched = []
        for p, x in new:
            key = jax.tree_util.keystr(p)
            shape = tuple(getattr(x, "shape", ()) or ())
            if key in cur_shapes and cur_shapes[key] != shape:
                mismatched.append(f"{key}: ckpt {shape} != state {cur_shapes[key]}")
        if mismatched:
            raise CheckpointMismatchError(
                f"{path} does not fit this learner "
                f"({len(mismatched)} mismatched leaves, e.g. "
                f"{'; '.join(mismatched[:3])}); refusing to resume from it"
            )

    def resume_latest(self) -> Optional[str]:
        """Crash-resume: restore from the newest VALID generation behind the
        durable ``latest`` pointer. A corrupt/truncated newest checkpoint is
        detected (manifest CRC/size) and skipped in favour of the previous
        generation. Returns the restored path, or None when nothing usable
        exists (cold start)."""
        self._checkpointer.wait()
        for gen in self._ckpt_manager.generations():
            try:
                self.restore(gen["path"])
            except (CheckpointCorruptError, FileNotFoundError, OSError, ValueError):
                CheckpointManager._note_fallback(gen["path"])
                continue
            self.metrics.counter(
                "distar_resilience_resumes_total",
                "learner restarts resumed from the latest pointer",
            ).inc()
            from ..obs import get_flight_recorder

            get_flight_recorder().record(
                "learner_resume", path=gen["path"], step=gen.get("step", 0)
            )
            self.logger.info(f"resumed from {gen['path']} (iter {self.last_iter.val})")
            return gen["path"]
        return None

    def _place_state(self, state):
        """Re-place restored host leaves onto this instance's compiled
        shardings. The donated train step's executable pairs each donated
        input buffer with a same-shaped output; uncommitted host arrays let
        the compiler choose input shardings on the next call, and its choice
        can disagree with the donation aliasing (observed: a replicated
        f32[8] output aliased to an input placed as f32[1] dp-shards ->
        XlaRuntimeError INTERNAL). Committing the state per-instance, to the
        exact shardings its train step was compiled for, removes the
        compiler's freedom to disagree."""
        shardings = getattr(self, "_shardings", None)
        if not shardings:
            return state

        def put(tree, sh):
            # materialize through a jitted add-0 rather than device_put: the
            # outputs are freshly XLA-allocated buffers pinned to ``sh``.
            # device_put of host numpy can be ZERO-COPY on the CPU backend,
            # and the train step DONATES these buffers — XLA reusing/freeing
            # memory that numpy's allocator owns is heap corruption
            # (observed: "corrupted double-linked list" aborts on the second
            # post-restore iteration), the runtime sibling of the hazard
            # checkpoint._host_snapshot documents
            place = jax.jit(
                lambda t: jax.tree.map(
                    lambda a: a + 0 if hasattr(a, "shape") else a, t
                ),
                out_shardings=sh,
            )
            return place(tree)

        state = dict(state)
        for key, sh_key in (("params", "param"), ("opt_state", "opt")):
            if key in state and sh_key in shardings:
                state[key] = put(state[key], shardings[sh_key])
        return state

    # ------------------------------------------------------------- optimizer
    def _build_optimizer(self):
        """One optimizer-construction choke point for every learner (and the
        RL admin-rebuild path): learning_rate/betas/eps/weight_decay plus the
        ``grad_clip`` block routed through parallel/grad_clip.py — the norm
        path is exercised end-to-end by tests/test_learner.py."""
        from ..parallel import GradClipConfig, build_optimizer

        lc = self.cfg.learner
        return build_optimizer(
            learning_rate=lc.learning_rate,
            betas=tuple(lc.get("betas", (0.0, 0.99))),
            eps=lc.get("eps", 1e-5),
            weight_decay=float(lc.get("weight_decay", 0.0) or 0.0),
            clip=GradClipConfig(**lc.grad_clip),
        )

    def _dynamics_spec(self):
        """Static spec threaded into make_*_train_step; None compiles the
        step WITHOUT the diagnostics tree (the overhead A/B's off arm)."""
        lc = self.cfg.learner
        return tree_spec(lc.get("dynamics"), lc.get("grad_clip"))

    # -------------------------------------------------------------- abstract
    def _setup_state(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def _setup_dataloader(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def _train(self, data) -> Dict[str, Any]:  # pragma: no cover - abstract
        """One optimisation step; returns the log dict."""
        raise NotImplementedError

    # -------------------------------------------------------------- prefetch
    def _place_batch(self, batch):  # overridden by learners that prefetch
        return batch

    def _maybe_enable_prefetch(self) -> None:
        """Wrap the dataloader in the sharded batch feeder (the reference's
        async copy process, rl_dataloader.py:113-127, generalised to a mesh):
        the next batch is collated on the host and placed — sharded over the
        live mesh — while the current step trains. Disable with
        learner.prefetch_depth=0."""
        from ..parallel.feeder import ShardFeeder

        depth = int(self.cfg.learner.get("prefetch_depth", 2))
        if depth <= 0 or isinstance(self._dataloader, ShardFeeder):
            return
        if type(self)._place_batch is BaseLearner._place_batch:
            return  # learner doesn't define placement
        self._dataloader = ShardFeeder(
            self._dataloader, self._place_batch, depth=depth, token=self.name
        )

    # ----------------------------------------------------------------- perf
    def _perf_note_step_args(self, jitted, *args) -> None:
        """Subclass ``_train`` calls this with the jitted step + its live
        call args on every iteration; the monitor snapshots shape specs once
        and extracts the flop count (MFU numerator) in the background."""
        if self._perf_aot:
            self._perf.note_step_args(jitted, *args)

    # ---------------------------------------------------------------- admin
    def start_admin(self, port: int = 0):
        """Serve the live admin API (status / save_ckpt / profile, plus
        update_config / reset_value on learners that implement them);
        requests apply at iteration boundaries."""
        from .admin import LearnerAdminServer

        self._admin = LearnerAdminServer(self, port=port)
        self._admin.start()
        self.logger.info(f"admin API on {self._admin.host}:{self._admin.port}")
        return self._admin

    def request_save(self) -> None:
        self._pending_save = True

    def request_stop(self) -> None:
        """Cooperative run-loop exit at the next iteration boundary (admin /
        test harness surface): after_run hooks (final checkpoint) still
        run. The next ``run()`` call starts fresh."""
        self._stop_requested = True

    # -------------------------------------------------------------- profile
    def request_profile(self, steps: int = 2, timeout_s: float = 600.0) -> dict:
        """On-demand bounded capture (admin ``POST /profile?steps=N``):
        arm a profiler session that the RUN LOOP starts/stops at iteration
        boundaries (mid-step capture would split device steps), then block
        this (admin-thread) caller until the trace is analyzed. Returns the
        ranked bucket report; raises on timeout / profiler failure."""
        req = {
            "steps": max(1, int(steps)),
            "event": threading.Event(),
            "session": None,
            "stop_at": None,
            "report": None,
            "error": None,
        }
        with self._profile_lock:
            pending = self._profile_req
            if pending is not None and not pending["event"].is_set():
                raise RuntimeError("a profile capture is already in flight")
            self._profile_req = req
        if not req["event"].wait(timeout_s):
            with self._profile_lock:
                if self._profile_req is req:
                    self._profile_req = None  # abandoned: unblock later arms
            raise TimeoutError(
                f"profile did not complete within {timeout_s}s "
                f"(is the learner's run loop advancing?)"
            )
        if req["error"]:
            raise RuntimeError(req["error"])
        return req["report"]

    def _profile_tick(self) -> None:
        """Run-loop leg of on-demand profiling: start the armed session at
        this boundary, stop+analyze once the requested steps elapsed."""
        req = self._profile_req
        if req is None or req["event"].is_set():
            return
        if req["session"] is None:
            from ..obs import ProfilerSession

            logdir = os.path.join(
                self.save_dir, "profiles", f"ondemand_{self.last_iter.val}"
            )
            session = ProfilerSession(logdir, registry=self.metrics)
            if not session.start():
                req["error"] = f"profiler start failed (logdir {logdir!r})"
                self._finish_profile(req)
                return
            req["session"] = session
            req["stop_at"] = self.last_iter.val + req["steps"]
            return
        if self.last_iter.val < req["stop_at"]:
            return
        session = req["session"]
        if not session.stop():
            req["error"] = "profiler stop failed"
            self._finish_profile(req)
            return
        try:
            from ..obs import analyze_trace, render_markdown

            report = analyze_trace(
                session.last_profile_path or session.logdir, steps=req["steps"]
            )
            report["markdown"] = render_markdown(report)
            report["captured_steps"] = req["steps"]
            report["last_iter"] = self.last_iter.val
            report["perf"] = self._perf.snapshot()
            req["report"] = report
        except Exception as e:
            req["error"] = f"trace analysis failed: {e!r}"
        self._finish_profile(req)

    def _finish_profile(self, req) -> None:
        with self._profile_lock:
            if self._profile_req is req:
                self._profile_req = None
        req["event"].set()

    # ------------------------------------------------------------------ run
    def run(self, max_iterations: Optional[int] = None) -> None:
        mark_process_age("run_start", self.metrics)
        max_iterations = max_iterations or self.cfg.learner.max_iterations
        self._maybe_enable_prefetch()

        # crash path writes synchronously: the process may be about to die
        iters_total = self.metrics.counter(
            "distar_learner_iterations_total", "optimisation steps completed"
        )
        step_time = self.metrics.histogram(
            "distar_learner_step_seconds", "device train-step wall time"
        )
        # a gauge (not histogram) on purpose: the NaN/Inf health rule needs
        # the raw last value — a reservoir quantile would mask non-finites
        loss_gauge = self.metrics.gauge(
            "distar_learner_loss", "last total_loss (NaN/Inf watchdog input)"
        )

        frames_per_iter = float(
            (self.cfg.learner.get("batch_size") or 0)
            * (self.cfg.learner.get("unroll_len") or 0)
        )

        self._stop_requested = False

        def iteration():
            # every line of an iteration is under exactly one leaf span:
            # data_wait, pre_step, device_step's prepare / dispatch /
            # fetch (in _train), post_step, host_callback, tick. A function,
            # so that its batch is released when it returns, while the feeder
            # thread still sleeps on its full queue, and not when the next
            # ``next()`` rebinds the name beside the feeder it just woke
            spans = self.spans
            with spans.step("train", self.last_iter.val):
                with spans.span("data_wait") as waited:
                    data = next(self._dataloader)
                with spans.span("pre_step"):
                    self.log_buffer["data_time"] = waited.seconds
                    self._perf.note_batch(data)
                    self.hooks.call("before_iter", self)
                    # stash aux refs (e.g. the SL pre-step hidden carry) so an
                    # anomaly bundle can reconstruct the step's exact inputs
                    self._dynamics.before_step(self)
                with spans.span("device_step") as stepped:
                    log_vars = self._train(data)
                with spans.span("post_step"):
                    t_train = stepped.seconds
                    self.log_buffer["train_time"] = t_train
                    self.log_buffer.update(log_vars)
                    loss = log_vars.get("total_loss")
                    if loss is not None:
                        try:
                            loss_gauge.set(float(loss))
                        except (TypeError, ValueError):
                            pass
                    # detection + gauge export from the already-fetched host
                    # log (no extra device sync); the batch is only touched
                    # if an anomaly writes a black-box bundle
                    self._dynamics.on_step(self, log_vars, data)
                    self.last_iter.add(1)
                    # before the hooks, so the metrics export among them
                    # carries THIS iteration's step time and memory sample
                    iters_total.inc()
                    step_time.observe(t_train)
                    self._perf.on_step(t_train, frames_per_iter)
                # everything after the device step: hook pass (log
                # reduction, checkpoint scheduling, weight publication)
                with spans.span("host_callback"):
                    self.hooks.call("after_iter", self)
                with spans.span("tick"):
                    self._profile_tick()

        def more() -> bool:
            return self.last_iter.val < max_iterations and not self._stop_requested

        @auto_checkpoint(lambda: self.save(self.checkpoint_path(), sync=True))
        def _run():
            self.hooks.call("before_run", self)
            if more():
                # the last set-up phase: this run's first iteration traces,
                # compiles or loads the step and runs it
                with self._setup_spans.span("first_step"):
                    iteration()
                mark_process_age("first_step_done", self.metrics)
            while more():
                iteration()
            self.hooks.call("after_run", self)

        try:
            _run()
        finally:
            # a profile armed while we were the run loop must not strand its
            # admin-thread waiter once no more iterations will happen
            req = self._profile_req
            if req is not None and not req["event"].is_set():
                if req.get("session") is not None:
                    req["session"].stop()
                req["error"] = "learner run ended before the capture completed"
                self._finish_profile(req)
        self._checkpointer.wait()  # drain the async writer before returning

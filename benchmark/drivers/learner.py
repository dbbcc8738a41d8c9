"""Driver for cells that train: one learner, alone on its chips.

Builds the learner the way ``bin/sl_train`` / ``bin/rl_train`` do (the
program's config file, ``--mesh``, the class the configuration names), hands
it the traffic's pool through its own ``set_dataloader`` and runs its own
run loop, so the input path is the program's: pool -> ``ShardFeeder`` ->
placement -> jitted step -> ``device_get`` of the log scalars. The
benchmark stands in the loop as one ``after_iter`` hook: a step counts when
that hook fires, i.e. after the device finished it.

Phases of a run: warm-up steps (the first compiles or loads the cache), the
measured window, with ``--trace 1`` a few more steps under the profiler,
then ``request_stop``.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import time
from typing import Dict, List

from .. import check, device, trace_reduce, window
from ..cells import module, program_config
from ..references import Beside
from ..registry_tap import RegistryTap


def _learner_class(spec: str):
    mod, cls = spec.split(":")
    return getattr(importlib.import_module(mod), cls)


def sized(cell: dict, rehearse: bool) -> Dict:
    """Model and traffic sizes: the program's config file and the traffic
    file, or the configuration's tiny preset in a rehearsal."""
    model = dict(program_config(cell["config"]).get("model", {}))
    traffic = dict(cell["traffic"]["params"])
    if rehearse:
        model = cell["config"]["tiny"]["model"]
        traffic.update(cell["traffic"]["tiny"])
    return {"model": model, "traffic": traffic}


def build_learner(cell: dict, model: dict, traffic: dict, seed: int, mesh, save_path: str):
    """The configuration's learner as ``bin/sl_train`` / ``bin/rl_train``
    construct it, at the traffic's batch, its weights from ``seed``."""
    learner_cls = _learner_class(cell["config"]["learner"])
    seed_was = learner_cls.init_prng_seed
    learner_cls.init_prng_seed = seed  # the weights come from --seed
    try:
        return learner_cls({
            "common": {"experiment_name": cell["name"], "save_path": save_path},
            "learner": {"batch_size": traffic["batch_size"], "unroll_len": traffic["unroll_len"],
                        "save_freq": 10 ** 9, "sharded_ckpt": cell["chips"] > 1,
                        **cell["learner"]},
            "model": model,
        }, mesh=mesh)
    finally:
        learner_cls.init_prng_seed = seed_was


class _SpecTap:
    """Stands where the learner keeps its jitted step and remembers the
    types of the arguments of its first call, so that the traced run can ask
    the compiler for that very program's ``memory_analysis()``."""

    def __init__(self, jitted):
        self._jitted = jitted
        self.specs = None

    def __call__(self, *args):
        if self.specs is None:
            import jax

            self.specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None))
                if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)
        return self._jitted(*args)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def program_bytes(self) -> Dict[str, int]:
        """Arguments + temporaries + outputs - aliased (donated) bytes, per device."""
        mem = self._jitted.lower(*self.specs).compile().memory_analysis()
        parts = {"argument": mem.argument_size_in_bytes, "temp": mem.temp_size_in_bytes,
                 "output": mem.output_size_in_bytes, "alias": mem.alias_size_in_bytes}
        parts["total"] = parts["argument"] + parts["temp"] + parts["output"] - parts["alias"]
        return parts


class _Loop:
    """The benchmark's ``after_iter`` hook and the run's timeline."""

    def __init__(self, learner, cell, seconds, trace, tap, trace_dir, min_steps=0, before_window=None):
        self.learner, self.cell, self.seconds, self.trace = learner, cell, seconds, trace
        self.min_steps = min_steps       # a rehearsal on a loaded CPU still sees enough steps
        self.before_window = before_window  # called once, before the last warm-up step
        self.tap, self.trace_dir = tap, trace_dir
        self.warmup = cell["warmup_steps"]
        self.step_times: List[float] = []
        self.scalars: List[Dict[str, float]] = []   # every step's log scalars, warm-up included
        self.t_open = None
        self.closed_at = None            # step count at which the window closed
        self.trace_until = None
        self.retraces = 0
        self._counting = False
        self.traced = False
        self.params_at_open = None

    def on_trace_event(self, event, duration_secs, **_):
        if self._counting and event == "/jax/core/compile/jaxpr_trace_duration":
            self.retraces += 1

    def __call__(self, learner) -> None:
        import jax

        now = time.perf_counter()
        k = learner.last_iter.val
        self.scalars.append({name: v for name, v in learner.log_buffer.items()
                             if isinstance(v, float)})
        if k == self.warmup - 1 and self.before_window is not None:
            self.before_window()
        elif k == self.warmup:
            self.params_at_open = _param_checksums(learner)
            self.tap.mark("open")
            self._counting = True
            self.t_open = time.perf_counter()
        elif self.t_open is not None and self.closed_at is None:
            self.step_times.append(now)
            if now - self.t_open >= self.seconds and len(self.step_times) >= self.min_steps:
                self._counting = False
                self.tap.mark("close")
                self.closed_at = k
                if self.trace:
                    device.start_trace(self.trace_dir)
                    self.traced = True
                    self.trace_until = k + self.cell["trace_steps"]
                else:
                    learner.request_stop()
        elif self.trace_until is not None and k >= self.trace_until:
            jax.profiler.stop_trace()
            self.trace_until = None
            learner.request_stop()


class _Spans:
    """Host spans on the profiler's clock, from the benchmark's own hooks:
    ``data_next`` (run loop waiting for the feed), ``train`` (``before_iter``
    to ``after_iter``: the step and its ``device_get``) and ``host_callback``
    (the program's ``after_iter`` hooks). One is always open."""

    def __init__(self):
        self._open = None

    def switch(self, name: str) -> None:
        import jax

        if self._open is not None:
            self._open.__exit__(None, None, None)
        self._open = jax.profiler.TraceAnnotation("bench:" + name)
        self._open.__enter__()

    def end(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def _param_checksums(learner) -> Dict[str, float]:
    """One number per device over every parameter shard it holds."""
    import jax
    import numpy as np

    sums: Dict[str, float] = {}
    for leaf in jax.tree.leaves(learner.state["params"]):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            sums[key] = sums.get(key, 0.0) + float(np.asarray(shard.data, np.float64).sum())
    return sums


def _batch_bytes(learner) -> Dict[str, int]:
    """Bytes of the last placed batch per device, from the program's gauge."""
    out = {}
    for fam in learner.metrics.collect():
        if fam["name"] == "distar_perf_batch_bytes":
            for key, inst in fam["series"]:
                out[dict(key).get("device", "?")] = int(inst.value)
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, rehearse: bool,
        out_dir: str, t0: float) -> Dict:
    want = dict(cell["correct"], **(cell["rehearsal"]["correct"] if rehearse else {}))
    # first of all, so that the reference runs while the chip sets up
    reference = Beside(cell["name"], seed, rehearse, out_dir) if want.get("reference") else None
    try:
        return _run(cell, want, reference, seed, seconds, trace, rehearse, out_dir, t0)
    finally:
        if reference is not None:
            reference.stop()


def _run(cell, want, reference, seed, seconds, trace, rehearse, out_dir, t0) -> Dict:
    device.require(cell["chips"], rehearse)
    import jax

    from distar_tpu.learner.hooks import LambdaHook
    from distar_tpu.parallel import MeshSpec, make_mesh

    size = sized(cell, rehearse)
    traffic = size["traffic"]
    phases = {"import_s": time.perf_counter() - t0}

    spec = MeshSpec.parse(cell["mesh"])
    mesh = make_mesh(spec, jax.devices()[: spec.dp * spec.fsdp * spec.tp * spec.sp])
    save_path = os.path.join(out_dir, "run")
    shutil.rmtree(save_path, ignore_errors=True)
    t = time.perf_counter()
    learner = build_learner(cell, size["model"], traffic, seed, mesh, save_path)
    phases["learner_init_s"] = time.perf_counter() - t

    t = time.perf_counter()
    gen = module("gen", cell["traffic"]["generator"])
    pool = gen.build(seed, traffic, model_cfg=learner.model_cfg)
    learner.set_dataloader(gen.cycle(pool))
    phases["pool_s"] = time.perf_counter() - t

    tap = RegistryTap(learner.metrics)
    tap.mark("start")
    loop = _Loop(learner, cell, seconds, trace, tap, os.path.join(out_dir, "trace"),
                 min_steps=cell["rehearsal"]["min_steps"] if rehearse else 0,
                 before_window=reference.wait if reference else None)
    shutil.rmtree(loop.trace_dir, ignore_errors=True)
    jax.monitoring.register_event_duration_secs_listener(loop.on_trace_event)
    # before the program's log_reduce hook (priority 10) empties the log buffer
    learner.hooks.add(LambdaHook("benchmark", "after_iter", loop, priority=5))
    spec_tap = None
    if trace:
        spec_tap = learner._train_step = _SpecTap(learner._train_step)
        spans = _Spans()
        learner.hooks.add(LambdaHook("bench_train", "before_iter", lambda _: spans.switch("train")))
        learner.hooks.add(LambdaHook("bench_hooks", "after_iter",
                                     lambda _: spans.switch("host_callback"), priority=6))
        learner.hooks.add(LambdaHook("bench_feed", "after_iter",
                                     lambda _: spans.switch("data_next"), priority=99))

    failed_run = None
    t_run = time.perf_counter()
    try:
        learner.run(max_iterations=10 ** 9)
    except Exception as e:  # a step that raised: the run failed, the line still goes out
        failed_run = repr(e)
    finally:
        if trace:
            spans.end()
        if loop.trace_until is not None:
            jax.profiler.stop_trace()
        if hasattr(learner._dataloader, "close"):
            learner._dataloader.close()  # the feeder's thread places batches on the device
    shutil.rmtree(os.path.join(save_path, "checkpoints"), ignore_errors=True)

    B, T = traffic["batch_size"], traffic["unroll_len"]
    win = window.step_window(loop.step_times, loop.t_open or 0.0, seconds)
    frames_per_s = win["per_s"] * B * T if win["per_s"] else None
    closed = "close" in tap.marks
    phase_s = {ph: tap.observed_between("distar_learner_step_phase_seconds", "open", "close",
                                        {"phase": ph}) if closed else []
               for ph in ("data_wait", "device_step", "host_callback")}
    ref = (reference.result() or {}) if reference is not None else {}
    with open(os.path.join(out_dir, "steps.json"), "w") as f:  # to compare two runs by hand
        json.dump({"cell": cell["name"], "seed": seed, "loss": cell["correct"]["loss"],
                   "reference_first_step": ref.get("first_step"),
                   "step_done_s": [t - (loop.t_open or 0.0) for t in loop.step_times],
                   "phase_s": phase_s, "scalars": loop.scalars}, f)

    losses = [sc.get(want["loss"], float("nan")) for sc in loop.scalars]  # from step 1 on
    in_window = losses[cell["warmup_steps"]:][:len(loop.step_times)]
    dev = device.describe()
    params_now = _param_checksums(learner)
    checks = {
        "ran_to_its_end": failed_run is None and loop.closed_at is not None,
        "losses_finite": check.losses_finite(in_window) == 0,
        "loss_went_down": check.loss_went_down(
            losses, traffic["pool"], want["min_drop"], want.get("compare", "first_step")),
        "params_changed": loop.params_at_open is not None and loop.params_at_open != params_now,
        "no_compile_in_window": closed and loop.retraces == 0 and (
            tap.value_at("close", "distar_compile_backend_seconds_total")
            == tap.value_at("open", "distar_compile_backend_seconds_total")),
        "platform": rehearse or (dev["platform"] == "tpu" and dev["count"] >= cell["chips"]),
        "no_interpret_fallback": closed and (
            tap.value_at("close", "distar_pallas_interpret_fallbacks_total")
            == tap.value_at("start", "distar_pallas_interpret_fallbacks_total")),
    }
    if reference is not None:
        # the first step ran on the untrained weights: its log is their loss vector
        off = check.off_reference(loop.scalars[0] if loop.scalars else {}, ref.get("first_step"),
                                  **{k: v for k, v in want["reference"].items()
                                     if k in ("keys", "rtol", "rtol_of")})
        checks["first_step_matches_reference"] = not off
        phases["reference_s"] = ref.get("seconds", float("nan"))  # beside the set-up, in its own process
        phases["waited_for_reference_s"] = reference.waited_s
        if off:
            print(f"benchmark: first step against the reference: {off}", flush=True)
    if want.get("replicas_agree"):
        checks["replicas_agree"] = check.replicas_agree(params_now)
        checks["batch_share"] = check.batch_share_ok(_batch_bytes(learner), want["batch_share"])

    values = {"retraces": float(loop.retraces),
              "frames_per_s": frames_per_s, "chips": cell["chips"],
              "flops_per_frame": cell["config"].get("required_flops_per_frame")}
    events = None
    if trace and loop.traced:
        path = trace_reduce.find_xplane(loop.trace_dir)
        events = trace_reduce.load(path) if path else None
        program = spec_tap.program_bytes()
        values["program_bytes"] = float(program["total"])
    print(json.dumps({"memory_stats": device.memory_stats(), "phases_s": {k: round(v, 3) for k, v in phases.items()},
                      "run_loop_to_window_s": round((loop.t_open or t_run) - t_run, 3),
                      "window": win, "checks": checks, "failed_run": failed_run,
                      "loss_first_last": [losses[:4], losses[-4:]]}), flush=True)
    return {
        "attempted": len(loop.step_times) + (1 if failed_run else 0),
        "failed": check.losses_finite(in_window) + (1 if failed_run else 0),
        "checks": checks,
        "end_to_end": {"train_frames_per_s": frames_per_s,
                       "setup_s": (loop.t_open - t0) if loop.t_open else None},
        "device": dev, "tap": tap, "events": events, "values": values,
    }

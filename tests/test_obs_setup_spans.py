"""A learner's set-up under named phases (obs/profiler.py::setup_spans,
SETUP_PHASES), the process's age beside them, and the compile listener's
split by stage, by phase and by program (utils/compile_cache.py)."""
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import SMALL_MODEL  # shared tiny model config
from distar_tpu.obs import SETUP_PHASES, MetricsRegistry, profiler, set_registry, setup_spans
from distar_tpu.utils import compile_cache

_IMPORTED = time.perf_counter()
PHASES = "distar_setup_seconds_total"
AGES = "distar_setup_process_age_seconds"
SECONDS = "distar_compile_seconds_total"
PROGRAMS = "distar_compile_programs_total"

TINY_LM = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
           "num_experts": 8, "num_experts_per_tok": 2, "experts_held": {"offset": 2, "count": 4},
           "vocab_size": 128}
# kind -> (learner, model, unroll, the phases its constructor and first run write,
#          the init program's and the step program's names)
KINDS = {
    "sl": ("distar_tpu.learner.sl_learner:SLLearner", SMALL_MODEL, 2,
           {"learner_base", "dataloader", "fake_batch", "model_init", "state_place", "opt_init",
            "state_ready", "first_step"}, "jit(init_fn)", "jit(sl_train_step)"),
    "rl": ("distar_tpu.learner.rl_learner:RLLearner", SMALL_MODEL, 2,
           {"learner_base", "dataloader", "fake_batch", "model_init", "state_place", "opt_init",
            "state_ready", "first_step"}, "jit(init_fn)", "jit(rl_train_step)"),
    "lm": ("distar_tpu.learner.lm_learner:LMLearner", TINY_LM, 32,
           {"learner_base", "dataloader", "init_shapes", "model_init", "opt_init",
            "state_ready", "first_step"}, "jit(<lambda>)", "jit(lm_train_step)"),
}


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev, during = set_registry(reg), profiler.setup_during
    yield reg
    set_registry(prev)
    profiler.setup_during = during


def _series(reg, family):
    """``labels (a dict's sorted items) -> value`` of one family."""
    return {key: inst.value for fam in reg.collect() if fam["name"] == family
            for key, inst in fam["series"]}


def _by(reg, family, label):
    return {dict(key)[label]: value for key, value in _series(reg, family).items()}


def _seconds(reg, **labels):
    return sum(v for key, v in _series(reg, SECONDS).items()
               if all(dict(key).get(k) == want for k, want in labels.items()))


def _first_run(kind, save_path):
    """One learner at its tiny preset on a registry of its own: constructed,
    run for one iteration, then a program compiled after the first step and
    a restore; what the registry held at each point."""
    import importlib

    from distar_tpu.learner.hooks import LambdaHook

    spec, model, unroll = KINDS[kind][:3]
    module, cls = spec.split(":")
    reg = MetricsRegistry()
    prev, during = set_registry(reg), profiler.setup_during
    try:
        compile_cache.configure()  # where a launcher first touches the backend
        t0 = time.perf_counter()
        learner = getattr(importlib.import_module(module), cls)({
            "common": {"experiment_name": "setup", "save_path": save_path},
            "learner": {"batch_size": 2, "unroll_len": unroll, "save_freq": 10 ** 9, "log_freq": 10 ** 9},
            "model": model,
        })
        stepped = []
        learner.hooks.add(LambdaHook("stepped", "after_iter",
                                     lambda _: stepped.append(time.perf_counter()), priority=99))
        learner.run(max_iterations=1)
        returned = time.perf_counter()
        out = {"to_first_after_iter": stepped[0] - t0, "to_return": returned - t0,
               "phases": _by(reg, PHASES, "phase"), "ages": _by(reg, AGES, "at"),
               "during_after_run": profiler.setup_during}

        @jax.jit
        def compiled_after_the_first_step(x):
            return x * 3 + 1

        compiled_after_the_first_step(jnp.ones(5)).block_until_ready()
        out["programs"] = {tuple(dict(key)[k] for k in ("program", "during", "cache"))
                           for key in _series(reg, PROGRAMS)}
        out["seconds"] = {(dict(key)["stage"], dict(key)["during"]): v
                          for key, v in _series(reg, SECONDS).items()}
        assert learner.resume_latest()  # the checkpoint its run left
        out["phases_after_restore"] = _by(reg, PHASES, "phase")
        if hasattr(learner._dataloader, "close"):
            learner._dataloader.close()
        return out
    finally:
        set_registry(prev)
        profiler.setup_during = during


@pytest.fixture(scope="module")
def first_runs(tmp_path_factory):
    """``kind -> _first_run`` of it, made once a module."""
    made = {}

    def of(kind):
        if kind not in made:
            made[kind] = _first_run(kind, str(tmp_path_factory.mktemp(kind)))
        return made[kind]

    return of


each_kind = pytest.mark.parametrize("kind", list(KINDS))


# ------------------------------------------------------------ the phases
@each_kind
def test_every_second_from_the_constructor_to_the_first_step_has_a_phase(first_runs, kind):
    first_run = first_runs(kind)
    phases, ages = first_run["phases"], first_run["ages"]
    wrote = KINDS[kind][3] | {"backend_init"}
    assert set(phases) == wrote
    assert all(seconds > 0 for seconds in phases.values()), phases
    leaves = sum(phases.values()) - phases["backend_init"]  # before the constructor
    # the leaves lie inside the wall time and leave a tenth of it at most unnamed
    assert leaves <= ages["first_step_done"] - ages["learner_init"] + 1e-3
    assert ages["first_step_done"] - ages["learner_init"] <= first_run["to_return"]
    assert leaves >= 0.9 * first_run["to_first_after_iter"], (phases, first_run)


@each_kind
def test_the_phases_hold_to_the_processs_clock(first_runs, kind):
    first_run = first_runs(kind)
    phases, ages = first_run["phases"], first_run["ages"]
    assert (0 < ages["learner_init"] < ages["learner_ready"] <= ages["run_start"]
            < ages["first_step_done"])
    # no phase is counted twice or outside its span (true on any host); what the phases COVER is that every one this
    # kind of learner runs is there and took time, not a share of a loaded host's seconds (0.9 of a 7.35 s constructor
    # read 6.15 s beside five test workers)
    assert set(phases) == KINDS[kind][3] | {"backend_init"}
    assert all(seconds > 0 for seconds in phases.values()), phases
    inside = sum(v for ph, v in phases.items() if ph not in ("backend_init", "first_step"))
    assert inside <= ages["learner_ready"] - ages["learner_init"] + 1e-3
    assert phases["first_step"] <= ages["first_step_done"] - ages["run_start"] + 1e-3


@each_kind
def test_a_compile_carries_the_phase_it_fell_in_and_its_programs_name(first_runs, kind):
    first_run = first_runs(kind)
    _, _, _, _, init_program, step_program = KINDS[kind]
    named = {(program, during) for program, during, _ in first_run["programs"]}
    assert (init_program, "model_init") in named
    assert (step_program, "first_step") in named
    assert first_run["during_after_run"] == "run"
    assert ("jit(compiled_after_the_first_step)", "run") in named
    assert {cache for _, _, cache in first_run["programs"]} <= {"hit", "miss", "none"}
    seconds = first_run["seconds"]
    for during in ("model_init", "first_step", "run"):
        assert all(seconds[(stage, during)] > 0 for stage in ("trace", "lower", "backend")), seconds
    # what compiled inside the first step took no longer than the step
    assert sum(seconds[(stage, "first_step")] for stage in ("trace", "lower", "backend")) \
        <= first_run["phases"]["first_step"]
    assert all(during in SETUP_PHASES + ("outside", "run") for _, during in seconds)


def test_the_vocabulary_is_what_the_learners_wrote(first_runs):
    """``SETUP_PHASES`` is the union of what the three learners, a restore
    and the launcher's first touch of the backend wrote, and nothing else."""
    wrote = set()
    for kind in KINDS:
        after_restore = set(first_runs(kind)["phases_after_restore"])
        assert {"restore", "state_place"} <= after_restore
        wrote |= after_restore
    assert wrote == set(SETUP_PHASES)


def test_the_vocabulary_is_fixed():
    assert SETUP_PHASES == (
        "backend_init", "learner_base", "dataloader", "fake_batch", "init_shapes",
        "model_init", "opt_init", "state_place", "restore", "state_ready", "first_step")


def test_a_phase_outside_the_vocabulary_is_refused(registry):
    with pytest.raises(ValueError, match="warm_up"):
        setup_spans(registry).span("warm_up")
    assert _series(registry, PHASES) == {}


def test_a_span_names_the_open_phase_and_adds_to_its_counter(registry):
    spans = setup_spans(registry)
    with spans.span("opt_init"):
        assert profiler.setup_during == "opt_init"
        time.sleep(0.002)
    assert profiler.setup_during == "outside"
    with spans.span("opt_init") as again:
        pass
    with spans.span("first_step"):
        assert profiler.setup_during == "first_step"
    assert profiler.setup_during == "run"
    phases = _by(registry, PHASES, "phase")
    assert phases["opt_init"] >= 0.002 + again.seconds and set(phases) == {"opt_init", "first_step"}


def test_a_span_without_a_profiler_session_costs_microseconds(registry):
    spans = setup_spans(registry)
    with spans.span("opt_init"):
        pass
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(2000):
            with spans.span("opt_init"):
                pass
        best = min(best, (time.perf_counter() - t0) / 2000)
    assert best < 20e-6, best


def test_the_processs_age_is_counted_from_its_start_not_from_an_import():
    import os

    age = profiler.process_age()
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    with open("/proc/self/stat") as f:
        started = int(f.read().rsplit(")", 1)[1].split()[19]) / os.sysconf("SC_CLK_TCK")
    assert age == pytest.approx(up - started, abs=0.5)
    assert age > time.perf_counter() - _IMPORTED  # older than this module


# ------------------------------------------------------------ the listener
def test_a_nested_jits_trace_is_counted_once(registry):
    """A ``jit`` that calls a ``jit``: JAX sends the inner trace's duration,
    then the outer's, which contains it; ``stage="trace"`` is the outer's."""
    events = []

    def capture(event, duration_secs, fun_name="", **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            events.append((fun_name, duration_secs))

    @jax.jit
    def inner_program(x):
        for _ in range(200):
            x = x * 1.01 + 1.0
        return x

    @jax.jit
    def outer_program(x):
        return inner_program(x) + inner_program(x[::-1])

    x = jnp.ones(7)
    x.block_until_ready()
    jax.monitoring.register_event_duration_secs_listener(capture)
    try:
        t0 = time.perf_counter()
        outer_program(x).block_until_ready()
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(capture)
    durations = dict(events)
    assert {"inner_program", "outer_program"} <= set(durations)
    traced = _seconds(registry, stage="trace")
    assert durations["outer_program"] <= traced <= wall
    assert sum(d for _, d in events) > traced  # what a sum over every event would say
    assert "distar_compile_trace_seconds_total" not in {fam["name"] for fam in registry.collect()}
    # the stages that stayed as they were still count every program
    assert _series(registry, "distar_compile_backend_seconds_total")[()] == pytest.approx(
        _seconds(registry, stage="backend"))


def test_the_listener_follows_a_registry_a_test_installed(registry):
    @jax.jit
    def first_program(x):
        return x + 1

    first_program(jnp.ones(3)).block_until_ready()
    assert _seconds(registry, stage="trace") > 0
    other = MetricsRegistry()
    set_registry(other)
    try:
        @jax.jit
        def second_program(x):
            return x + 2

        second_program(jnp.ones(3)).block_until_ready()
    finally:
        set_registry(registry)
    assert any(dict(key)["program"] == "jit(second_program)" for key in _series(other, PROGRAMS))
    assert not any(dict(key)["program"] == "jit(second_program)" for key in _series(registry, PROGRAMS))

"""The program's host spans (obs/profiler.py::Spans) in the run loop and the
feeder thread, and the scope vocabulary of the jitted train steps."""
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # benchmark/

from conftest import SMALL_MODEL  # shared tiny model config
from distar_tpu.obs import STEP_SCOPES, MetricsRegistry, feed_spans, loop_spans, set_registry

LOOP = "distar_learner_step_phase_seconds"
FEED = "distar_feeder_phase_seconds"
LEAVES = ("data_wait", "pre_step", "prepare", "dispatch", "fetch", "post_step",
          "host_callback", "tick")


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


def _sum(reg, family, **labels):
    h = reg.histogram(family, **labels)
    return h.sum, h.count


# ------------------------------------------------------------- the helper
def test_nested_spans_child_within_parent(registry):
    spans = loop_spans(registry)
    with spans.span("device_step") as parent:
        with spans.span("dispatch") as child:
            time.sleep(0.002)
        time.sleep(0.001)
    assert 0.002 <= child.seconds <= parent.seconds
    assert _sum(registry, LOOP, phase="dispatch") == (child.seconds, 1)
    assert _sum(registry, LOOP, phase="device_step") == (parent.seconds, 1)


def test_span_observes_when_its_body_raises(registry):
    spans = loop_spans(registry)
    with pytest.raises(KeyError):
        with spans.span("fetch") as s:
            time.sleep(0.001)
            raise KeyError("boom")
    assert s.seconds >= 0.001
    assert _sum(registry, LOOP, phase="fetch") == (s.seconds, 1)


def test_two_threads_keep_their_roles(registry):
    """The run loop and the feeder thread write under their own names and
    into their own families, whichever thread a span is entered on."""
    loop, feed = loop_spans(registry), feed_spans("sllearner", registry)
    names = []

    class Recorder:
        def __init__(self, name):
            names.append((threading.current_thread().name, name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    for s in (loop, feed):
        s._profiler = type("P", (), {"TraceAnnotation": Recorder})

    def feeder():
        for _ in range(50):
            with feed.span("pull"):
                pass

    t = threading.Thread(target=feeder, name="shard-feeder")
    t.start()
    for _ in range(50):
        with loop.span("data_wait"):
            pass
    t.join(timeout=10)
    assert not t.is_alive()
    assert set(names) == {("shard-feeder", "distar:feed/pull"), ("MainThread", "distar:loop/data_wait")}
    assert _sum(registry, FEED, phase="pull", token="sllearner")[1] == 50
    assert _sum(registry, LOOP, phase="data_wait")[1] == 50
    assert _sum(registry, LOOP, phase="pull")[1] == 0


def test_step_is_the_profilers_step_marker(registry):
    spans = loop_spans(registry)
    seen = []
    spans._profiler = type("P", (), {"StepTraceAnnotation": staticmethod(
        lambda name, step_num: seen.append((name, step_num)) or _Null())})
    with spans.step("train", 7):
        pass
    assert seen == [("train", 7)] and _sum(registry, LOOP, phase="iteration")[1] == 1


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# ------------------------------------------------------------ the run loop
@pytest.fixture(scope="module")
def sl_run(tmp_path_factory):
    """Six iterations of a tiny SLLearner through its feeder, on a registry
    of its own; the phases' sums and counts afterwards."""
    from distar_tpu.learner import SLLearner

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        learner = SLLearner({
            "common": {"experiment_name": "spans", "save_path": str(tmp_path_factory.mktemp("spans"))},
            "learner": {"batch_size": 2, "unroll_len": 2, "save_freq": 10 ** 9, "log_freq": 10 ** 9},
            "model": SMALL_MODEL,
        })
        from distar_tpu.learner.hooks import LambdaHook

        logged = []  # before the log_reduce hook (priority 10) empties the buffer
        learner.hooks.add(LambdaHook("peek", "after_iter", lambda lr: logged.append(
            (lr.log_buffer["data_time"], lr.log_buffer["train_time"])), priority=5))
        learner.run(max_iterations=2)  # compile outside the counted iterations
        before = {ph: _sum(reg, LOOP, phase=ph) for ph in LEAVES + ("device_step", "iteration")}
        learner.run(max_iterations=8)
        learner._dataloader.close()
        after = {ph: _sum(reg, LOOP, phase=ph) for ph in before}
        feed = {ph: _sum(reg, FEED, phase=ph, token=learner.name)
                for ph in ("pull", "cap", "put", "put_wait")}
        leaves = reg.histogram("distar_feeder_batch_leaves", token=learner.name)
        placed = reg.histogram("distar_feeder_place_seconds", token=learner.name)
        return {"loop": {ph: (after[ph][0] - before[ph][0], after[ph][1] - before[ph][1])
                         for ph in before},
                "feed": feed, "leaves": leaves, "placed": placed, "logged": logged[2:],
                "names": {fam["name"] for fam in reg.collect()}}
    finally:
        set_registry(prev)


@pytest.mark.parametrize("phase", ["data_wait", "device_step", "host_callback"])
def test_old_phase_labels_get_one_observation_per_iteration(sl_run, phase):
    assert sl_run["loop"][phase][1] == 6


def test_every_phase_is_observed_once_per_iteration(sl_run):
    assert {ph: n for ph, (_, n) in sl_run["loop"].items()} == \
        {ph: 6 for ph in LEAVES + ("device_step", "iteration")}


def test_every_line_of_an_iteration_is_under_a_leaf_span(sl_run):
    """The leaf phases' seconds add up to the iterations' wall time: what
    they leave out (the loop's condition, entering and leaving the spans)
    is under 2%."""
    loop = sl_run["loop"]
    leaf_s = sum(loop[ph][0] for ph in LEAVES)
    assert leaf_s <= loop["iteration"][0]
    assert leaf_s >= 0.98 * loop["iteration"][0], (leaf_s, loop["iteration"][0])
    children = sum(loop[ph][0] for ph in ("prepare", "dispatch", "fetch"))
    assert 0.98 * loop["device_step"][0] <= children <= loop["device_step"][0]


def test_run_loop_keeps_its_series_and_drops_the_duplicates(sl_run):
    assert {"distar_learner_step_seconds", LOOP, FEED, "distar_feeder_batch_leaves",
            "distar_feeder_place_seconds"} <= sl_run["names"]
    assert not {"distar_learner_data_wait_seconds", "distar_stopwatch_seconds"} & sl_run["names"]
    # the log's data_time and train_time are the spans' seconds
    assert len(sl_run["logged"]) == 6
    assert sum(d for d, _ in sl_run["logged"]) == pytest.approx(sl_run["loop"]["data_wait"][0])
    assert sum(t for _, t in sl_run["logged"]) == pytest.approx(sl_run["loop"]["device_step"][0])


def test_feeder_phases_of_the_learners_placement(sl_run):
    """pull and put_wait in the feeder, cap and put inside the learner's
    _place_batch, once per batch each; pull + cap + put is what
    distar_feeder_place_seconds has always measured."""
    feed = sl_run["feed"]
    n = feed["pull"][1]
    assert n >= 8 and feed["cap"][1] == n and feed["put"][1] == n
    assert n - 1 <= feed["put_wait"][1] <= n
    parts = feed["pull"][0] + feed["cap"][0] + feed["put"][0]
    assert parts <= sl_run["placed"].sum * 1.001 and parts >= 0.9 * sl_run["placed"].sum
    assert sl_run["leaves"].count == n and sl_run["leaves"].sum / n > 20


def test_feeder_spans_and_leaf_count_on_a_fake_place_fn(registry):
    from distar_tpu.parallel.feeder import ShardFeeder

    spans = feed_spans("fake", registry)

    def place(batch):
        with spans.span("cap"):
            batch = dict(batch)
            host = batch.pop("host")
        with spans.span("put"):
            out = jax.tree.map(jnp.asarray, batch)
        out["host"] = host
        return out

    batches = [{"a": np.ones(3), "b": {"c": np.zeros(2), "d": np.zeros(1)}, "host": np.ones(1)}
               for _ in range(5)]
    feeder = ShardFeeder(iter(batches), place, depth=1, token="fake")
    got = list(feeder)
    feeder.close()
    assert len(got) == 5 and isinstance(got[0]["a"], jax.Array)
    for phase in ("pull", "cap", "put", "put_wait"):
        total, n = _sum(registry, FEED, phase=phase, token="fake")
        assert n == (6 if phase == "pull" else 5) and total >= 0  # the sixth pull ends the iterator
    leaves = registry.histogram("distar_feeder_batch_leaves", token="fake")
    assert (leaves.count, leaves.sum) == (5, 15)  # a, c, d: the host field is not placed


def test_sample_memory_exports_the_reserved_pool(registry, monkeypatch):
    """On the TPU runtime the step's temporaries are in peak_bytes_reserved."""
    from distar_tpu.obs import PerfMonitor

    class Dev:
        platform, id = "tpu", 0

        def memory_stats(self):
            return {"bytes_in_use": 1, "peak_bytes_in_use": 840, "peak_bytes_reserved": 11990}

    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    PerfMonitor(token="t", registry=registry).sample_memory()
    assert registry.gauge("distar_perf_hbm_reserved_peak_bytes", device="tpu:0").value == 11990
    assert registry.gauge("distar_perf_hbm_peak_bytes", device="tpu:0").value == 840


# ------------------------------------------------------ scope vocabulary
def _element(path):
    """The vocabulary element a trace reader would give the path to."""
    from benchmark.readers.trace_scope import UNNAMED, scope_of

    name = scope_of(path, STEP_SCOPES)
    return None if name == UNNAMED else name


def _compiled_paths(learner, tmp_iters=1):
    """op_name of every instruction of the learner's compiled train step
    that has a path (a bare primitive name is a reducer's body, a
    ``params[...]`` name a parameter)."""
    calls = []
    jitted = learner._train_step

    def tap(*args):
        calls.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x, args))
        return jitted(*args)

    learner._train_step = tap
    learner.run(max_iterations=tmp_iters)
    lowered = jitted.lower(*calls[0])
    text = lowered.compile().as_text()
    return ([n for n in re.findall(r'op_name="([^"]*)"', text) if "/" in n],
            lowered.as_text(debug_info=True))


LEARNER = {"batch_size": 2, "unroll_len": 2, "save_freq": 10 ** 9, "log_freq": 10 ** 9,
           "prefetch_depth": 0, "save_grad": True}


@pytest.mark.parametrize("kind", ["sl", "rl"])
def test_every_part_of_the_step_has_a_scope(kind, tmp_path, registry):
    from distar_tpu.learner import RLLearner, SLLearner

    cls = {"sl": SLLearner, "rl": RLLearner}[kind]
    learner = cls({"common": {"experiment_name": "scopes", "save_path": str(tmp_path)},
                   "learner": LEARNER, "model": dict(SMALL_MODEL, remat=True)})
    paths, before_xla = _compiled_paths(learner)
    found = {_element(p) for p in paths}
    want = set(STEP_SCOPES) - ({"value"} if kind == "sl" else set())
    assert want <= found, want - found
    assert all(re.match(r"jit\(\w+_train_step\)/", p) or _element(p) for p in paths if "/while/" in p)
    # forward, backward and (remat is on) recompute
    assert any("jvp(" in p and "transpose(" not in p for p in paths)
    assert any("transpose(" in p and "rematted_computation" not in p for p in paths)
    assert any("transpose(" in p and "rematted_computation" in p for p in paths)
    # the scopes beneath them, as the program wrote them (XLA merges equal
    # computations, e.g. the three global norms, under one of their names)
    for sub in ("diagnostics/grad_norm", "diagnostics/leaf_norms", "diagnostics/dynamics_tree"):
        assert f"/{sub}/" in before_xla, sub
    if kind == "rl":
        for sub in ("vtrace", "upgo", "td", "kl", "entropy"):
            assert re.search(rf"\(loss\)+/{sub}/", before_xla), sub
            assert any(re.search(rf"\(loss\)+/{sub}/", p) for p in paths), sub
    unnamed = [p for p in paths if _element(p) is None]
    assert len(unnamed) <= 0.02 * len(paths), (len(unnamed), len(paths), unnamed[:5])

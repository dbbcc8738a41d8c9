"""Unified telemetry layer: registry semantics, exporters, the coordinator
/metrics route, and the meters the obs PR touched (EMAMeter debias,
the run loop's phase histograms)."""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from distar_tpu.obs import (
    JsonlExporter,
    MetricsRegistry,
    render_prometheus,
    set_registry,
)


@pytest.fixture
def registry():
    """Fresh process-default registry per test (restored afterwards)."""
    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


# ---------------------------------------------------------------- registry
def test_counter_monotonic(registry):
    c = registry.counter("distar_test_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5  # failed inc leaves the value untouched


def test_gauge_set_inc_dec(registry):
    g = registry.gauge("distar_test_gauge")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == 13


def test_histogram_quantiles_and_bounded_reservoir(registry):
    h = registry.histogram("distar_test_seconds", reservoir=100)
    for v in range(1, 101):
        h.observe(v)
    assert h.count == 100 and h.sum == 5050
    assert h.quantile(0.0) == 1
    assert h.quantile(0.5) == 51  # nearest-rank over [1..100]
    assert h.quantile(1.0) == 100
    # reservoir bounds memory: old samples fall out, count/sum are lifetime
    for v in range(1000, 1100):
        h.observe(v)
    assert h.count == 200
    assert h.quantile(0.0) == 1000  # the [1..100] window aged out


def test_same_name_labels_returns_same_instrument(registry):
    a = registry.counter("distar_x_total", token="t1")
    b = registry.counter("distar_x_total", token="t1")
    c = registry.counter("distar_x_total", token="t2")
    assert a is b and a is not c
    a.inc()
    assert b.value == 1 and c.value == 0


def test_type_conflict_and_bad_names_raise(registry):
    registry.counter("distar_dup")
    with pytest.raises(ValueError):
        registry.gauge("distar_dup")
    with pytest.raises(ValueError):
        registry.counter("0bad name")
    with pytest.raises(ValueError):
        registry.counter("distar_ok", **{"0badlabel": "v"})


def test_counter_thread_safety(registry):
    c = registry.counter("distar_mt_total")

    def spin():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


# --------------------------------------------------------------- exporters
def test_prometheus_rendering_golden(registry):
    """Golden test for the text exposition format."""
    registry.counter("distar_env_steps_total", "env steps completed").inc(7)
    registry.gauge("distar_coordinator_queue_depth", "broker backlog", token="MP0traj").set(3)
    h = registry.histogram("distar_learner_step_seconds", "step time", reservoir=16)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    expected = "\n".join(
        [
            "# HELP distar_coordinator_queue_depth broker backlog",
            "# TYPE distar_coordinator_queue_depth gauge",
            'distar_coordinator_queue_depth{token="MP0traj"} 3',
            "# HELP distar_env_steps_total env steps completed",
            "# TYPE distar_env_steps_total counter",
            "distar_env_steps_total 7",
            "# HELP distar_learner_step_seconds step time",
            "# TYPE distar_learner_step_seconds summary",
            'distar_learner_step_seconds{quantile="0.5"} 3',
            'distar_learner_step_seconds{quantile="0.9"} 4',
            'distar_learner_step_seconds{quantile="0.99"} 4',
            "distar_learner_step_seconds_sum 10",
            "distar_learner_step_seconds_count 4",
            "",
        ]
    )
    assert render_prometheus(registry) == expected


def _parse_prometheus(text):
    """Minimal exposition-format parser: validates line shape, returns
    {series_name_with_labels: float}."""
    series = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            assert len(parts) >= 4 if line.startswith("# HELP") else len(parts) == 4
            continue
        assert not line.startswith("#"), f"unknown comment line {line!r}"
        name_part, _, value_part = line.rpartition(" ")
        assert name_part, f"malformed sample line {line!r}"
        series[name_part] = float(value_part)
    return series


def test_prometheus_label_escaping(registry):
    registry.gauge("distar_esc", label='va"l\\ue').set(1)
    text = render_prometheus(registry)
    assert 'label="va\\"l\\\\ue"' in text
    _parse_prometheus(text)


def test_jsonl_exporter_composes_with_scalar_sink(registry, tmp_path):
    registry.counter("distar_c_total").inc(2)
    h = registry.histogram("distar_h_seconds")
    h.observe(0.5)
    exporter = JsonlExporter(str(tmp_path), registry=registry)
    n = exporter.export(step=42)
    assert n >= 5  # counter + histogram count/sum/p50/p99
    lines = [
        json.loads(line)
        for line in open(os.path.join(str(tmp_path), "scalars.jsonl"))
    ]
    by_name = {rec["name"]: rec for rec in lines}
    assert by_name["distar_c_total"]["value"] == 2
    assert by_name["distar_h_seconds_count"]["value"] == 1
    assert all(rec["step"] == 42 for rec in lines)


# -------------------------------------------------- coordinator /metrics
def test_coordinator_stats_depth_agree(registry):
    """stats() applies the same age filter as depth() (they used to drift:
    stats counted raw lengths)."""
    from distar_tpu.comm import Coordinator

    co = Coordinator(max_age_s=0.2)
    co.register("traj", "1.2.3.4", 1111)
    assert co.stats() == {"traj": 1}
    assert co.depth("traj") == 1
    time.sleep(0.3)
    # the record aged past the serve window: BOTH views call it loss, not backlog
    assert co.depth("traj") == 0
    assert co.stats() == {"traj": 0}
    # raw lengths remain reachable explicitly
    assert co.stats(max_age_s=None) == {"traj": 1}
    assert co.depth("traj", max_age_s=None) == 1


def test_metrics_endpoint_serves_required_series(registry, tmp_path):
    """GET /metrics parses as Prometheus text and carries queue-depth,
    learner step-time and actor env-step-rate series produced by the real
    instrumented code paths."""
    from distar_tpu.actor.env_pool import EnvWorkerPool
    from distar_tpu.comm import Coordinator, CoordinatorServer
    from distar_tpu.envs import MockEnv
    from distar_tpu.learner.base_learner import BaseLearner
    from distar_tpu.obs import PROMETHEUS_CONTENT_TYPE

    # --- actor side: a real env pool stepping a mock env
    pool = EnvWorkerPool([lambda: MockEnv(episode_game_loops=10_000, seed=0)])
    pool.reset(0)
    stepped = 0
    deadline = time.time() + 30
    while stepped < 3 and time.time() < deadline:
        for e, kind, payload in pool.ready(timeout=5.0):
            if kind == "reset":
                obs = payload
                pool.submit(e, {})
            else:
                stepped += 1
                if stepped < 3:
                    pool.submit(e, {})
    pool.close()
    assert stepped >= 3

    # --- learner side: the real run loop on a trivial subclass
    class TinyLearner(BaseLearner):
        def _setup_state(self):
            self._state = {"params": {}}

        def _setup_dataloader(self):
            def gen():
                while True:
                    yield {}

            self._dataloader = gen()

        def _train(self, data):
            return {"total_loss": 0.0}

    learner = TinyLearner(
        {
            "common": {"experiment_name": "obs_test", "save_path": str(tmp_path)},
            "learner": {"save_freq": 10 ** 9, "log_freq": 10 ** 9},
        }
    )
    learner.run(max_iterations=2)

    # --- broker with backlog, serving the scrape
    co = Coordinator()
    co.register("MP0traj", "1.2.3.4", 1111)
    srv = CoordinatorServer(coordinator=co)
    srv.start()
    try:
        with urllib.request.urlopen(
            f"http://{srv.host}:{srv.port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == PROMETHEUS_CONTENT_TYPE
            body = resp.read().decode()
        with urllib.request.urlopen(
            f"http://{srv.host}:{srv.port}/nope", timeout=10
        ) as resp:
            pass
    except urllib.error.HTTPError as e:
        assert e.code == 404  # non-/metrics GETs 404
    finally:
        srv.stop()
    series = _parse_prometheus(body)
    assert series['distar_coordinator_queue_depth{token="MP0traj"}'] == 1
    assert series["distar_learner_step_seconds_count"] == 2
    assert series["distar_env_steps_total"] >= 3
    assert series["distar_actor_env_step_rate"] > 0
    assert series["distar_learner_iterations_total"] == 2
    # step-phase breakdown rides along
    assert series['distar_learner_step_phase_seconds_count{phase="data_wait"}'] == 2
    assert series['distar_learner_step_phase_seconds_count{phase="device_step"}'] == 2
    assert series['distar_learner_step_phase_seconds_count{phase="host_callback"}'] == 2


# ------------------------------------------------------------ EMAMeter fix
def test_ema_meter_debiased_at_startup():
    """The docstring always promised debias; avg now delivers it: the first
    update reads back exactly, later reads are bias-corrected weighted means
    rather than zero-dragged raw EMAs."""
    from distar_tpu.utils.log import EMAMeter

    m = EMAMeter(alpha=0.99)
    assert m.avg == 0.0  # empty meter
    m.update(5.0)
    assert m.avg == pytest.approx(5.0)  # raw EMA would read 0.05 from zero-init
    assert m.val == 5.0
    m.update(7.0)
    # closed form: (alpha*5 + 7) / (alpha + 1) weighted mean
    assert m.avg == pytest.approx((0.99 * 5.0 + 7.0) / 1.99)
    assert m.count == 2


def test_ema_meter_converges_to_plateau():
    from distar_tpu.utils.log import EMAMeter

    m = EMAMeter(alpha=0.9)
    for _ in range(200):
        m.update(3.0)
    assert m.avg == pytest.approx(3.0)


# ------------------------------------------------------------ no-print lint
def test_no_bare_prints_in_library_code():
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "lint_no_print", os.path.join(root, "tools", "lint_no_print.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    offences = mod.find_bare_prints(os.path.join(root, "distar_tpu"))
    assert offences == [], f"bare print() in library code: {offences}"


# ------------------------------------------------------- metric-name lint
def test_metric_names_follow_convention_and_are_documented():
    """Every metric registered in the tree matches distar_<subsystem>_<name>
    and appears in the docs/observability.md metric table (lint_metric_names
    mirrors lint_no_print: importable from tests, runnable standalone)."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "lint_metric_names", os.path.join(root, "tools", "lint_metric_names.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    problems = mod.lint(
        os.path.join(root, "distar_tpu"),
        os.path.join(root, "docs", "observability.md"),
    )
    assert problems == [], "\n".join(problems)


def test_prometheus_nonfinite_rendering(registry):
    """Non-finite values render per the text format (NaN/+Inf/-Inf) —
    repr() would emit 'nan'/'inf', which scrapers reject."""
    registry.gauge("distar_a").set(float("nan"))
    registry.gauge("distar_b").set(float("inf"))
    registry.gauge("distar_c").set(float("-inf"))
    text = render_prometheus(registry)
    assert "distar_a NaN" in text
    assert "distar_b +Inf" in text
    assert "distar_c -Inf" in text
    assert "nan" not in text and "inf" not in text  # no repr() leakage

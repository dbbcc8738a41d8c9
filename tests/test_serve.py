"""Serve subsystem smoke: in-process gateway + mock engine, concurrent
clients over threads. The tier-1 acceptance surface: coalesced batches
(mean occupancy > 1), zero-loss hot swap under load, typed shed responses
from admission control, serve metrics visible in the obs registry.

The mock engine's ``delay_s`` sleep releases the GIL like a device
dispatch, so client threads genuinely pile up behind a flush — batching
happens for the same reason it does on a TPU, not by test rigging.
"""
import threading
import time

import numpy as np
import pytest

from distar_tpu.obs import MetricsRegistry, get_registry, set_registry
from distar_tpu.serve import (
    CapacityError,
    DeadlineExceededError,
    DrainingError,
    InferenceGateway,
    MicroBatcher,
    MockModelEngine,
    ModelRegistry,
    PendingRequest,
    QueueFullError,
    ServeClient,
    ServeError,
    ServeHTTPServer,
    ServeTCPServer,
    SessionTable,
    error_from_wire,
)


@pytest.fixture(autouse=True)
def fresh_registry():
    prev = set_registry(MetricsRegistry())
    yield
    set_registry(prev)


def obs_of(v: float) -> dict:
    return {"x": np.full((2, 3), v, dtype=np.float32)}


def make_gateway(slots=8, delay_s=0.003, max_delay_s=0.01, capacity=64, **kw):
    engine = MockModelEngine(slots, params={"version": "v1", "bias": 0.0}, delay_s=delay_s)
    gw = InferenceGateway(
        engine, max_delay_s=max_delay_s, queue_capacity=capacity, **kw
    ).start()
    gw.load_version("v1", params={"version": "v1", "bias": 0.0}, activate=True)
    return engine, gw


# --------------------------------------------------------------- tier-1 smoke
def test_concurrent_clients_are_batched_and_metrics_visible():
    engine, gw = make_gateway(slots=8, delay_s=0.005, max_delay_s=0.02)
    n_clients, n_req = 8, 12
    errors = []

    def client(c):
        sid = f"client-{c}"
        try:
            for i in range(n_req):
                out = gw.act(sid, obs_of(c), timeout_s=10.0)
                # correctness of the decollation: this slot's obs, this
                # session's step counter
                assert out["action"] == pytest.approx(c * 6.0)
                assert out["step"] == i + 1
                assert out["model_version"] == "v1"
        except Exception as e:  # pragma: no cover - surfaced via errors list
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    gw.drain_and_stop()
    assert not errors
    snap = get_registry().snapshot()
    # every request served through a coalesced flush; occupancy must beat 1
    occ_count = snap["distar_serve_batch_occupancy_count"]
    occ_sum = snap["distar_serve_batch_occupancy_sum"]
    assert occ_sum == n_clients * n_req  # nothing lost, nothing double-served
    assert occ_sum / occ_count > 1.0, "no batching observed"
    assert engine.forward_calls == occ_count
    # acceptance: serve metric families all present in the obs registry
    for fam in (
        "distar_serve_queue_depth",
        "distar_serve_batch_occupancy_count",
        "distar_serve_request_latency_seconds_count",
        "distar_serve_model_generation",
    ):
        assert any(k.startswith(fam) for k in snap), fam
    assert snap["distar_serve_requests_total{outcome=ok}"] == n_clients * n_req


def test_hot_swap_under_load_loses_no_inflight_requests():
    engine, gw = make_gateway(slots=4, delay_s=0.004, max_delay_s=0.01)
    per_client = [[] for _ in range(4)]
    errors = []
    stop = threading.Event()

    def client(c):
        sid = f"swap-client-{c}"
        while not stop.is_set():
            try:
                per_client[c].append(
                    gw.act(sid, obs_of(1.0), timeout_s=10.0)["model_version"]
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)
                return

    threads = [threading.Thread(target=client, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    # load + warm + activate while traffic flows
    gw.load_version("v2", params={"version": "v2", "bias": 1.0}, activate=True)
    time.sleep(0.15)
    stop.set()
    for t in threads:
        t.join()
    gw.drain_and_stop()
    assert not errors, errors[:3]
    results = [v for seq in per_client for v in seq]
    assert set(results) == {"v1", "v2"}  # traffic flowed on both sides of the swap
    for seq in per_client:
        # zero dropped in-flight: each client's stream is a clean v1* v2*
        # boundary — the swap applied atomically between flushes
        assert seq == sorted(seq), seq
    snap = get_registry().snapshot()
    assert snap["distar_serve_swaps_total"] == 2  # v1 boot + v2 swap
    assert snap["distar_serve_swap_duration_seconds_count"] >= 1
    assert snap["distar_serve_requests_total{outcome=ok}"] == len(results)


def test_queue_full_sheds_typed_without_blocking():
    # capacity 2, one slow slot: the third concurrent submit must shed fast
    engine = MockModelEngine(1, delay_s=0.2)
    gw = InferenceGateway(engine, max_delay_s=0.001, queue_capacity=2).start()
    outcomes = []

    def client():
        try:
            gw.act("same-session", obs_of(1.0), timeout_s=5.0)
            outcomes.append("ok")
        except QueueFullError:
            outcomes.append("shed")

    threads = [threading.Thread(target=client) for _ in range(6)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    gw.drain_and_stop()
    assert "shed" in outcomes, outcomes
    assert elapsed < 5.0  # sheds answered immediately, not serialized behind the queue
    snap = get_registry().snapshot()
    assert snap["distar_serve_shed_total{reason=shed_queue_full}"] == outcomes.count("shed")


def test_request_deadline_sheds_typed():
    engine = MockModelEngine(2, delay_s=0.15)
    gw = InferenceGateway(engine, max_delay_s=0.001, queue_capacity=8).start()
    # first request occupies the engine; the second's deadline lapses queued
    t1 = threading.Thread(target=lambda: gw.act("s1", obs_of(1.0), timeout_s=5.0))
    t1.start()
    time.sleep(0.02)  # flush 1 departed (1ms deadline) and is in the forward
    with pytest.raises(DeadlineExceededError):
        gw.act("s2", obs_of(1.0), timeout_s=0.05)
    t1.join()
    gw.drain_and_stop()
    assert get_registry().snapshot()["distar_serve_shed_total{reason=shed_deadline}"] >= 1


# ------------------------------------------------------------------- sessions
def test_sticky_sessions_keep_separate_recurrent_state():
    engine, gw = make_gateway(slots=4, delay_s=0.0, max_delay_s=0.002)
    for i in range(3):
        assert gw.act("a", obs_of(0.0))["step"] == i + 1
    assert gw.act("b", obs_of(0.0))["step"] == 1  # b's slot, not a's
    assert gw.reset_session("a") is True  # episode boundary: carry zeroed
    assert gw.act("a", obs_of(0.0))["step"] == 1
    assert gw.act("b", obs_of(0.0))["step"] == 2  # b untouched by a's reset
    assert gw.end_session("a") is True
    assert gw.reset_session("a") is False  # gone
    gw.drain_and_stop()


def test_session_capacity_shed_and_idle_eviction():
    engine, gw = make_gateway(slots=2, delay_s=0.0, max_delay_s=0.001, idle_ttl_s=0.2)
    assert gw.act("s1", obs_of(1.0))["step"] == 1
    assert gw.act("s2", obs_of(1.0))["step"] == 1
    with pytest.raises(CapacityError):
        gw.act("s3", obs_of(1.0))
    time.sleep(0.25)  # s1/s2 idle past ttl -> evictable
    assert gw.act("s3", obs_of(1.0))["step"] == 1  # fresh slot, zeroed carry
    gw.drain_and_stop()
    assert get_registry().snapshot()["distar_serve_session_evictions_total"] == 1


def test_slot_zeroed_on_recycle_not_leaked():
    engine = MockModelEngine(1, delay_s=0.0)
    gw = InferenceGateway(engine, max_delay_s=0.001, idle_ttl_s=0.05).start()
    for _ in range(3):
        gw.act("first", obs_of(1.0))
    time.sleep(0.1)
    # second session takes the recycled slot: must start from zero carry
    assert gw.act("second", obs_of(1.0))["step"] == 1
    gw.drain_and_stop()


# ----------------------------------------------------------------- shutdown
def test_drain_then_stop_completes_admitted_sheds_new():
    # 3 clients on a 4-lane engine with a long flush deadline: requests sit
    # admitted-but-unflushed until the drain takes them
    engine, gw = make_gateway(slots=4, delay_s=0.0, max_delay_s=0.5)
    results = []
    threads = [
        threading.Thread(
            target=lambda c=c: results.append(gw.act(f"d{c}", obs_of(1.0), timeout_s=5.0))
        )
        for c in range(3)
    ]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    while gw.batcher.depth < 3 and time.perf_counter() - t0 < 2.0:
        time.sleep(0.002)
    assert gw.batcher.depth == 3  # all admitted, none flushed yet
    gw.drain_and_stop(timeout=10.0)
    for t in threads:
        t.join()
    assert len(results) == 3  # everything admitted was served by the drain flush
    with pytest.raises(DrainingError):
        gw.act("late", obs_of(1.0))
    snap = get_registry().snapshot()
    assert snap["distar_serve_flush_total{reason=drain}"] >= 1


# ------------------------------------------------------------------ registry
def test_registry_warmup_runs_off_serving_path_and_unknown_version():
    engine, gw = make_gateway(slots=2, delay_s=0.0)
    assert gw.act("s", obs_of(1.0))["model_version"] == "v1"  # template learned
    calls_before = engine.forward_calls
    gw.load_version("v9", params={"version": "v9", "bias": 0.0})  # no activate
    assert engine.warmup_calls >= 1  # warm-up happened...
    assert engine.forward_calls == calls_before  # ...but not through serving flushes
    assert gw.act("s", obs_of(1.0))["model_version"] == "v1"  # still v1 until swap
    gw.activate_version("v9")
    assert gw.act("s", obs_of(1.0))["model_version"] == "v9"
    from distar_tpu.serve import UnknownVersionError

    with pytest.raises(UnknownVersionError):
        gw.activate_version("never-loaded")
    status = gw.status()
    assert status["registry"]["current"] == "v9"
    assert set(status["registry"]["versions"]) == {"v1", "v9"}
    gw.drain_and_stop()


def test_registry_loads_checkpoint_through_storage_urls(tmp_path):
    """End-to-end version load via utils.checkpoint + mem:// storage."""
    from distar_tpu.utils.checkpoint import save_checkpoint

    state = {"params": {"w": np.ones((3,), np.float32)}, "opt_state": {"m": np.zeros(3)}}
    url = "mem://serve-test/ckpt-1"
    save_checkpoint(url, state)
    reg = ModelRegistry()
    reg.load("ck1", source=url, activate=True)
    gen, version, params = reg.current()
    assert version == "ck1" and gen == 1
    np.testing.assert_allclose(params["w"], np.ones(3))  # opt_state stripped
    assert "opt_state" not in params


# -------------------------------------------------------------------- errors
def test_error_wire_round_trip():
    for err in (QueueFullError("q"), DeadlineExceededError("d"), CapacityError("c"),
                DrainingError("x"), ServeError("e")):
        back = error_from_wire(err.to_wire())
        assert type(back) is type(err)
        assert back.shed == err.shed
    # unknown code degrades to base ServeError
    assert type(error_from_wire({"code": "from-the-future"})) is ServeError


# ------------------------------------------------------------------ frontends
def test_tcp_frontend_round_trip_and_swap():
    engine, gw = make_gateway(slots=4, delay_s=0.0, max_delay_s=0.002)
    srv = ServeTCPServer(gw, host="127.0.0.1").start()
    try:
        with ServeClient(srv.host, srv.port) as c:
            assert c.ping()
            out = c.act("tcp-1", obs_of(2.0))
            assert out["step"] == 1 and out["action"] == pytest.approx(12.0)
            assert isinstance(out["action"], np.ndarray)  # real numpy on the wire
            c.load("v2", params={"version": "v2", "bias": 1.0})
            c.swap("v2")
            assert c.act("tcp-1", obs_of(2.0))["model_version"] == "v2"
            assert c.reset("tcp-1") is True
            assert c.act("tcp-1", obs_of(2.0))["step"] == 1
            assert c.status()["registry"]["current"] == "v2"
            assert c.end("tcp-1") is True
    finally:
        srv.stop()
        gw.drain_and_stop()


def test_tcp_frontend_typed_shed_over_wire():
    engine = MockModelEngine(1, delay_s=0.0)
    gw = InferenceGateway(engine, max_delay_s=0.001, idle_ttl_s=300.0).start()
    srv = ServeTCPServer(gw, host="127.0.0.1").start()
    try:
        with ServeClient(srv.host, srv.port) as c:
            c.act("tcp-a", obs_of(1.0))
            with pytest.raises(CapacityError):  # rehydrated typed shed
                c.act("tcp-b", obs_of(1.0))
    finally:
        srv.stop()
        gw.drain_and_stop()


def test_http_frontend_act_status_metrics():
    import json
    import urllib.request

    engine, gw = make_gateway(slots=4, delay_s=0.0, max_delay_s=0.002)
    srv = ServeHTTPServer(gw, host="127.0.0.1").start()
    try:
        def post(route, body):
            req = urllib.request.Request(
                f"http://{srv.host}:{srv.port}/serve/{route}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"}, method="POST",
            )
            return json.loads(urllib.request.urlopen(req, timeout=10).read())

        out = post("act", {"session_id": "h1", "obs": {"x": [[1.0, 2.0]]}})
        assert out["code"] == 0 and out["info"]["step"] == 1
        assert out["info"]["action"] == pytest.approx(3.0)
        assert post("status", {})["info"]["registry"]["current"] == "v1"
        assert post("bogus", {})["code"] == 404
        with urllib.request.urlopen(
            f"http://{srv.host}:{srv.port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        assert "distar_serve_batch_occupancy" in text
        assert "distar_serve_requests_total" in text
    finally:
        srv.stop()
        gw.drain_and_stop()


# -------------------------------------------------------- component details
def test_batcher_flush_reasons():
    flushed = []
    b = MicroBatcher(lambda reqs, reason: flushed.append((len(reqs), reason)),
                     max_batch=2, max_delay_s=0.02, capacity=8)
    b.start()
    # distinct slots reach max_batch -> "full" without waiting the deadline
    r1, r2 = PendingRequest("a", 0, {}, None), PendingRequest("b", 1, {}, None)
    b.submit(r1)
    b.submit(r2)
    t0 = time.perf_counter()
    while len(flushed) < 1 and time.perf_counter() - t0 < 2.0:
        time.sleep(0.005)
    assert flushed and flushed[0] == (2, "full")
    # single request -> deadline flush
    b.submit(PendingRequest("c", 0, {}, None))
    t0 = time.perf_counter()
    while len(flushed) < 2 and time.perf_counter() - t0 < 2.0:
        time.sleep(0.005)
    assert flushed[1] == (1, "deadline")
    b.drain_and_stop()


def test_batcher_same_slot_requests_serialize_across_flushes():
    flushed = []
    b = MicroBatcher(lambda reqs, reason: flushed.append([r.session_id for r in reqs]),
                     max_batch=4, max_delay_s=0.005, capacity=8)
    # submit BEFORE start: the flush split is then deterministic
    b.submit(PendingRequest("one", 0, {}, None))
    b.submit(PendingRequest("one", 0, {}, None))  # same slot: next flush
    b.submit(PendingRequest("two", 1, {}, None))
    b.start()
    b.drain_and_stop()
    assert flushed == [["one", "two"], ["one"]]


def test_session_table_inflight_blocks_eviction():
    table = SessionTable(1, idle_ttl_s=0.0)  # everything instantly idle-expired
    table.acquire("busy")  # inflight=1, never released
    with pytest.raises(CapacityError):
        table.acquire("other")  # in-flight sessions are not evictable
    table.release("busy")
    assert table.acquire("other") == 0  # now evicted and recycled


# --------------------------------------------------- real-model integration
@pytest.mark.slow
def test_real_model_engine_serves_and_hot_swaps():
    """BatchedInferenceEngine end-to-end: the gateway serves the actual
    jitted ``sample_action`` (conftest SMALL_MODEL shapes) and a hot swap of
    same-shaped params reuses the compiled forward."""
    import jax
    import jax.numpy as jnp

    from conftest import SMALL_MODEL
    from distar_tpu.actor.inference import BatchedInference
    from distar_tpu.lib import features as F
    from distar_tpu.model import Model, default_model_config
    from distar_tpu.serve import BatchedInferenceEngine
    from distar_tpu.utils import deep_merge_dicts

    cfg = deep_merge_dicts(default_model_config(), SMALL_MODEL)
    model = Model(cfg)
    obs = F.fake_step_data(train=False, rng=np.random.default_rng(0))
    batched = jax.tree.map(jnp.asarray, F.batch_tree([obs] * 2))
    H = cfg.encoder.core_lstm.hidden_size
    z = jnp.zeros((2, H))
    hidden = tuple((z, z) for _ in range(cfg.encoder.core_lstm.num_layers))
    params = model.init(
        jax.random.PRNGKey(0),
        batched["spatial_info"], batched["entity_info"], batched["scalar_info"],
        batched["entity_num"], hidden, jax.random.PRNGKey(1),
        method=model.sample_action,
    )
    engine = BatchedInferenceEngine(BatchedInference(model, params, num_slots=2))
    gw = InferenceGateway(engine, max_delay_s=0.01).start()
    gw.load_version("v1", params=params, activate=True)
    out = gw.act("real-a", obs, timeout_s=120.0)  # first flush compiles
    assert out["model_version"] == "v1"
    assert out["action_info"]["action_type"].shape == ()
    # hot swap: perturbed same-shaped params; warmup runs the compiled
    # forward off-path (template known by now), swap serves v2
    p2 = jax.tree.map(lambda x: x * 1.01 if hasattr(x, "dtype") else x, params)
    gw.load_version("v2", params=p2, activate=True)
    out2 = gw.act("real-a", obs, timeout_s=120.0)
    assert out2["model_version"] == "v2"
    assert out2["action_info"]["delay"].shape == ()
    gw.drain_and_stop()


# ------------------------------------------------------------------ soak
@pytest.mark.slow
def test_loadgen_soak_closed_loop_with_swap(tmp_path):
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parents[1]))
    from tools.loadgen import run_loadgen

    artifact = tmp_path / "loadgen.jsonl"
    summary = run_loadgen(
        mode="closed", clients=8, duration_s=3.0, slots=8,
        mock_delay_s=0.002, max_delay_s=0.005, swap_at=0.5,
        artifact=str(artifact),
    )
    assert summary["errors"] == 0
    assert summary["ok"] > 100
    assert summary["mean_batch_occupancy"] > 1.0
    assert summary["latency_p99_s"] > 0
    lines = [l for l in artifact.read_text().splitlines() if l.strip()]
    import json as _json

    parsed = [_json.loads(l) for l in lines]
    assert parsed[-1]["metric"] == "serve_throughput"  # the last line is the summary

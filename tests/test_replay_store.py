"""Replay-store unit coverage: sum-tree sampling, rate-limiter semantics,
eviction policies, spill durability, and the framed-TCP server/client pair
(docs/data_plane.md)."""
import os
import threading
import time

import pytest

from distar_tpu.replay import (
    InsertClient,
    InvalidBatchError,
    RateLimitTimeout,
    RateLimiter,
    ReplayAdminServer,
    ReplayServer,
    ReplayStore,
    ReplayTable,
    SampleClient,
    SpillRing,
    SumTree,
    TableConfig,
    UnknownTableError,
)
from distar_tpu.resilience import RetryPolicy


def _cfg(**kw):
    base = dict(max_size=16, sampler="uniform", samples_per_insert=None,
                min_size_to_sample=1)
    base.update(kw)
    return TableConfig(**base)


# ------------------------------------------------------------------ sum tree
def test_sum_tree_find_respects_mass():
    t = SumTree(8)
    t.set(0, 1.0)
    t.set(3, 3.0)
    assert t.total == pytest.approx(4.0)
    assert t.find(0.5) == 0
    assert t.find(1.5) == 3
    assert t.find(3.9) == 3
    t.set(3, 0.0)
    assert t.find(0.9) == 0


def test_prioritized_sampling_favors_high_priority():
    table = ReplayTable("p", _cfg(sampler="prioritized", max_size=8))
    low = table.insert({"k": "low"}, priority=1.0, timeout_s=1.0)
    high = table.insert({"k": "high"}, priority=50.0, timeout_s=1.0)
    counts = {low: 0, high: 0}
    for s in table.sample(batch_size=200, timeout_s=1.0):
        counts[s.seq] += 1
    assert counts[high] > counts[low] * 5  # ~50x expected, 5x is a safe floor


def test_update_priorities_shifts_distribution():
    table = ReplayTable("up", _cfg(sampler="prioritized", max_size=8))
    a = table.insert("a", priority=1.0, timeout_s=1.0)
    b = table.insert("b", priority=1.0, timeout_s=1.0)
    assert table.update_priorities({a: 100.0, 999: 5.0}) == 1  # unknown ignored
    hits = sum(1 for s in table.sample(batch_size=100, timeout_s=1.0) if s.seq == a)
    assert hits > 80
    assert b is not None


# ---------------------------------------------------------------- fifo table
def test_fifo_is_consume_once_in_order():
    table = ReplayTable("f", _cfg(sampler="fifo", max_size=8))
    for i in range(5):
        table.insert(i, timeout_s=1.0)
    out = table.sample(batch_size=3, timeout_s=1.0)
    assert [s.data for s in out] == [0, 1, 2]
    assert all(s.sample_count == 1 for s in out)
    assert table.size() == 2  # consumed items left the table


def test_size_eviction_is_fifo_and_counted():
    table = ReplayTable("e", _cfg(max_size=4))
    for i in range(6):
        table.insert(i, timeout_s=1.0)
    assert table.size() == 4
    datas = {s.data for s in table.sample(batch_size=50, timeout_s=1.0)}
    assert datas <= {2, 3, 4, 5}  # 0 and 1 were evicted oldest-first


def test_staleness_eviction():
    table = ReplayTable("s", _cfg(max_size=8, max_staleness_s=0.05))
    table.insert("old", timeout_s=1.0)
    time.sleep(0.08)
    table.insert("fresh", timeout_s=1.0)  # insert sweeps the stale item
    assert table.size() == 1
    assert table.sample(timeout_s=1.0)[0].data == "fresh"


def test_sampled_item_reports_staleness_and_reuse():
    table = ReplayTable("m", _cfg(max_size=4))
    table.insert("x", timeout_s=1.0)
    time.sleep(0.02)
    first = table.sample(timeout_s=1.0)[0]
    second = table.sample(timeout_s=1.0)[0]
    assert first.staleness_s >= 0.02
    assert (first.sample_count, second.sample_count) == (1, 2)


# -------------------------------------------------------------- rate limiter
def test_limiter_blocks_sampling_below_min_size():
    table = ReplayTable("rl1", _cfg(min_size_to_sample=3))
    table.insert("a", timeout_s=1.0)
    with pytest.raises(RateLimitTimeout) as e:
        table.sample(timeout_s=0.05)
    assert e.value.side == "sample"


def test_limiter_enforces_samples_per_insert_both_ways():
    lim = RateLimiter(samples_per_insert=2.0, min_size_to_sample=1,
                      error_buffer=2.0, table="t")
    assert lim.can_insert()
    lim.commit_insert()            # inserts=1 (the free min_size insert)
    assert lim.can_insert()        # adj=1 -> 2*1 <= 0+2
    lim.commit_insert()            # inserts=2
    assert not lim.can_insert()    # adj=2 -> 4 > 0+2: inserter too far ahead
    assert lim.can_sample()
    lim.commit_sample(2)           # samples=2
    assert lim.can_insert()        # 4 <= 2+2 again
    # sampler side: samples bounded by spi*adj + eb = 2*1 + 2
    assert lim.can_sample(2)
    assert not lim.can_sample(3)


def test_limiter_disabled_with_none_spi():
    lim = RateLimiter(samples_per_insert=None, min_size_to_sample=2)
    for _ in range(100):
        assert lim.can_insert()
        lim.commit_insert()
    assert lim.can_sample(50)


def test_limiter_unblocks_waiters_on_commit():
    table = ReplayTable("rl2", _cfg(samples_per_insert=1.0, min_size_to_sample=1,
                                    error_buffer=1.0, sampler="fifo"))
    got = []

    def sampler():
        got.append(table.sample(timeout_s=5.0)[0].data)

    t = threading.Thread(target=sampler, daemon=True)
    t.start()
    time.sleep(0.05)  # sampler parks in the limiter
    table.insert("wake", timeout_s=1.0)
    t.join(5.0)
    assert got == ["wake"]
    # block time was recorded on the sample side
    assert table.limiter.state()["block_sample_s"] > 0.0


def test_reuse_ratio_converges_to_spi():
    """The acceptance knob: measured reuse ratio within +/-10% of the
    configured samples-per-insert once min_size is netted out."""
    spi, min_size = 2.0, 4
    table = ReplayTable("ratio", TableConfig(
        max_size=64, sampler="uniform", samples_per_insert=spi,
        min_size_to_sample=min_size, error_buffer=2.0))
    stop = threading.Event()

    def producer():
        i = 0
        while not stop.is_set():
            try:
                table.insert({"i": i}, timeout_s=0.2)
                i += 1
            except RateLimitTimeout:
                continue

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    sampled = 0
    while sampled < 120:
        sampled += len(table.sample(batch_size=4, timeout_s=5.0))
    stop.set()
    t.join(2.0)
    st = table.limiter.state()
    ratio = st["samples"] / max(st["inserts"] - min_size, 1)
    assert abs(ratio - spi) <= 0.1 * spi, st


def test_fifo_rejects_reuse_ratio_above_one():
    with pytest.raises(ValueError, match="consume-once"):
        TableConfig(sampler="fifo", samples_per_insert=2.0)


def test_limiter_max_sample_batch():
    assert RateLimiter(1.0, 1, error_buffer=1.0).max_sample_batch() == 2.0
    assert RateLimiter(2.0, 1, error_buffer=2.0).max_sample_batch() == 4.0
    assert RateLimiter(1.0, 1, error_buffer=4.0).max_sample_batch() == 8.0
    assert RateLimiter(None, 1).max_sample_batch() == float("inf")


def test_inadmissible_batch_raises_config_error_not_timeout():
    """Regression: spi=1 + error_buffer=1 + batch=4 (the old launcher
    defaults) deadlocks — can_sample(4) needs inserts the limiter will never
    admit, so sampler AND inserter block trading timeouts forever. The
    store must answer with a non-retryable config error instead."""
    table = ReplayTable("dead", TableConfig(
        max_size=64, sampler="uniform", samples_per_insert=1.0,
        min_size_to_sample=4, error_buffer=1.0))
    table.insert("a", timeout_s=1.0)
    with pytest.raises(InvalidBatchError, match="error_buffer"):
        table.sample(batch_size=4, timeout_s=0.2)
    # an admissible batch on the same table still behaves normally
    with pytest.raises(RateLimitTimeout):  # below min_size: pacing, retryable
        table.sample(batch_size=1, timeout_s=0.05)


def test_launcher_default_error_buffer_admits_the_learner_batch():
    """rl_train._table_config sizes the default error_buffer to
    max(1, spi) * batch_size so `--type replay` + rl_train's default batch
    can never build a deadlocked table."""
    import argparse

    from distar_tpu.bin.rl_train import _table_config

    args = argparse.Namespace(
        replay_spi=1.0, replay_max_size=1024, replay_sampler="uniform",
        replay_min_size=0, replay_error_buffer=None,
        replay_max_staleness_s=0.0, batch_size=4)
    cfg = _table_config(args)
    assert cfg.error_buffer == 4.0
    lim = RateLimiter(cfg.samples_per_insert, cfg.min_size_to_sample,
                      error_buffer=cfg.error_buffer)
    assert lim.max_sample_batch() >= 4
    # an explicit CLI value still wins
    args.replay_error_buffer = 2.5
    assert _table_config(args).error_buffer == 2.5


# --------------------------------------------------------------------- spill
def test_spill_roundtrip_and_release(tmp_path):
    spill = SpillRing(str(tmp_path), max_items=8)
    store = ReplayStore(table_factory=lambda n: _cfg(), spill=spill)
    for i in range(4):
        store.insert("MP0", {"i": i})
    assert spill.live_count() == 4
    store.sample("MP0", timeout_s=1.0)  # first sample releases one blob
    assert spill.live_count() == 3

    fresh = ReplayStore(table_factory=lambda n: _cfg(),
                        spill=SpillRing(str(tmp_path), max_items=8))
    assert fresh.recover() == 3
    assert fresh.table("MP0").size() == 3


def test_spill_ring_bound_drops_oldest(tmp_path):
    spill = SpillRing(str(tmp_path), max_items=3)
    store = ReplayStore(table_factory=lambda n: _cfg(), spill=spill)
    for i in range(5):
        store.insert("T", i)
    assert spill.live_count() == 3
    fresh = ReplayStore(table_factory=lambda n: _cfg(),
                        spill=SpillRing(str(tmp_path), max_items=3))
    assert fresh.recover() == 3  # only the newest 3 kept their blobs


def test_spill_skips_corrupt_blobs(tmp_path, chaos):
    spill = SpillRing(str(tmp_path), max_items=8)
    store = ReplayStore(table_factory=lambda n: _cfg(), spill=spill)
    for i in range(3):
        store.insert("T", {"i": i})
    blobs = sorted(p for p in os.listdir(tmp_path) if p.endswith(".spill"))
    chaos.bitflip(str(tmp_path / blobs[0]), flips=16)
    fresh = ReplayStore(table_factory=lambda n: _cfg(),
                        spill=SpillRing(str(tmp_path), max_items=8))
    assert fresh.recover() == 2  # the flipped blob failed CRC and was skipped


def test_insert_spills_blob_before_ack_and_releases_on_timeout(tmp_path):
    """Regression: the blob must be on disk BEFORE the item goes live (a
    concurrent release must find it), and a rate-limited insert must not
    leak its reserved blob as a forever-recovered orphan."""
    spill = SpillRing(str(tmp_path), max_items=8)
    cfg = TableConfig(max_size=16, sampler="uniform", samples_per_insert=1.0,
                      min_size_to_sample=1, error_buffer=1.0)
    store = ReplayStore(table_factory=lambda n: cfg, spill=spill)
    store.insert("T", 0)
    store.insert("T", 1)
    # limiter now blocks inserts (2 inserts ahead, 0 samples, buffer 1)
    with pytest.raises(RateLimitTimeout):
        store.insert("T", 2, timeout_s=0.05)
    assert spill.live_count() == 2  # the timed-out blob was released
    fresh = ReplayStore(table_factory=lambda n: cfg,
                        spill=SpillRing(str(tmp_path), max_items=8))
    assert fresh.recover() == 2  # no orphan comes back as a duplicate


def test_spill_bootstrap_lists_resolved_root_for_schemed_backend():
    """Regression: _bootstrap_seq listed the unresolved root, so a scheme'd
    spill (mem://, gs://) restarted its key sequence at 0 and silently
    overwrote live blobs."""
    root = "mem://spill-bootstrap-regression"
    first = SpillRing(root, max_items=8)
    first.append(first.reserve_key("T"), "T", {"i": 1}, 1.0)
    restarted = SpillRing(root, max_items=8)
    key = restarted.reserve_key("T")
    assert int(key.rsplit("-", 1)[-1]) >= 1  # never reuses the live key


def test_spill_key_sequence_survives_restart(tmp_path):
    spill = SpillRing(str(tmp_path), max_items=8)
    store = ReplayStore(table_factory=lambda n: _cfg(), spill=spill)
    store.insert("T", 1)
    spill2 = SpillRing(str(tmp_path), max_items=8)
    k = spill2.reserve_key("T")
    # a restarted ring must never reuse (and overwrite) a live key
    assert int(k.rsplit("-", 1)[-1]) >= 1


# ----------------------------------------------------------- server / client
def test_server_roundtrip_acked_insert_and_sample():
    store = ReplayStore(table_factory=lambda n: _cfg())
    server = ReplayServer(store, port=0).start()
    try:
        with InsertClient(server.host, server.port) as ic, \
                SampleClient(server.host, server.port) as sc:
            assert ic.ping()
            seq = ic.insert("MP0", {"traj": [1, 2]}, priority=3.0)
            assert seq == 0
            items, info = sc.sample("MP0", batch_size=2, timeout_s=5.0)
            assert items == [{"traj": [1, 2]}] * 2  # with replacement
            assert info[0]["seq"] == 0 and info[1]["sample_count"] == 2
            stats = sc.stats()
            assert stats["tables"]["MP0"]["limiter"]["inserts"] == 1
            assert sc.tables() == ["MP0"]
    finally:
        server.stop()


def test_server_typed_errors():
    store = ReplayStore(table_factory=None)  # no auto-create
    server = ReplayServer(store, port=0).start()
    try:
        sc = SampleClient(server.host, server.port,
                          retry_policy=RetryPolicy(max_attempts=1))
        with pytest.raises(UnknownTableError):
            sc.sample("nope", timeout_s=1.0)
        sc.close()
    finally:
        server.stop()


def test_server_rate_limit_timeout_is_retryable_wire_error():
    store = ReplayStore(table_factory=lambda n: _cfg(min_size_to_sample=5))
    server = ReplayServer(store, port=0).start()
    try:
        sc = SampleClient(server.host, server.port,
                          retry_policy=RetryPolicy(max_attempts=2,
                                                   backoff_base_s=0.01,
                                                   jitter=0.0))
        with pytest.raises(RateLimitTimeout) as e:
            sc.sample("MP0", timeout_s=0.05)
        assert e.value.side == "sample"
        sc.close()
    finally:
        server.stop()


def test_server_invalid_batch_is_nonretryable_wire_error():
    """An inadmissible batch must surface immediately as the typed
    invalid_batch error — not burn the client's whole retry/deadline budget
    the way the (retryable) rate_limited answer does."""
    store = ReplayStore(
        table_factory=lambda n: _cfg(samples_per_insert=1.0, error_buffer=1.0))
    server = ReplayServer(store, port=0).start()
    try:
        sc = SampleClient(server.host, server.port)
        t0 = time.monotonic()
        with pytest.raises(InvalidBatchError):
            sc.sample("MP0", batch_size=8, timeout_s=5.0)
        assert time.monotonic() - t0 < 2.0  # no retry loop, no server-side park
        sc.close()
    finally:
        server.stop()


def test_client_rides_through_server_restart(chaos):
    """Kill the store between requests; the client's retry policy dials the
    restarted server on the same port invisibly (the resilience contract)."""
    store = ReplayStore(table_factory=lambda n: _cfg())
    server = ReplayServer(store, port=0).start()
    host, port = server.host, server.port
    ic = InsertClient(host, port)
    assert ic.insert("MP0", {"i": 0}) == 0
    chaos.kill_role(server, name="replay")
    server2 = ReplayServer(ReplayStore(table_factory=lambda n: _cfg()),
                           host=host, port=port).start()
    try:
        assert ic.insert("MP0", {"i": 1}) == 0  # fresh store, fresh seqs
    finally:
        ic.close()
        server2.stop()


def test_admin_surface_serves_stats_and_metrics():
    import json
    import urllib.request

    store = ReplayStore(table_factory=lambda n: _cfg())
    store.insert("MP0", {"x": 1})
    admin = ReplayAdminServer(store, port=0).start()
    try:
        base = f"http://{admin.host}:{admin.port}"
        body = json.load(urllib.request.urlopen(base + "/replay/stats", timeout=5))
        assert body["tables"]["MP0"]["size"] == 1
        text = urllib.request.urlopen(base + "/metrics", timeout=5).read().decode()
        assert "distar_replay_inserts_total" in text
    finally:
        admin.stop()

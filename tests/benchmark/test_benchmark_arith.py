"""The yardstick's arithmetic on hand-made inputs: windows, percentiles, the
registry tap, the generators and the comparisons that decide ``correct``."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import check, window  # noqa: E402
from benchmark.gen import rl_pool, sl_pool  # noqa: E402
from benchmark.registry_tap import RegistryTap  # noqa: E402

DRAW = {"pool": 2, "batch_size": 2, "unroll_len": 3, "entity_num": [1, 511],
        "entity_num_floor": 8, "selected_units_num": [2, 6]}
CORE = {"encoder": {"core_lstm": {"hidden_size": 8, "num_layers": 2}}}


def test_step_window_edges():
    # steps complete every 0.5 s from 10.5 on; the window opens at 10.0 for 2 s
    times = [10.0 + 0.5 * i for i in range(1, 9)]
    w = window.step_window(times, 10.0, 2.0)
    assert w["steps"] == 4 and w["seconds"] == pytest.approx(2.0) and w["per_s"] == pytest.approx(2.0)
    # a deadline that cuts a step costs nothing: the window ends at the last whole step
    w = window.step_window(times, 10.0, 2.2)
    assert w["steps"] == 4 and w["seconds"] == pytest.approx(2.0)
    # the step that completes exactly at the opening belongs to the warm-up
    assert window.step_window([10.0, 10.5], 10.0, 1.0)["steps"] == 1
    assert window.step_window([], 10.0, 1.0)["per_s"] is None


def test_percentile_and_median():
    v = list(range(1, 201))  # 200 samples: nearest rank, ten lie beyond the 95th percentile
    assert window.percentile(v, 95) == 190
    assert window.percentile(v, 50) == 100 and window.median(v) == 100.5
    assert window.percentile([7.0], 95) == 7.0
    assert window.percentile([], 95) is None and window.median([]) is None


@pytest.mark.parametrize("reduce,want", [("median", 2500.0), ("mean", 3000.0), ("p95", 6000.0)])
def test_histogram_reader_reduces_the_windows_observations(reduce, want):
    from benchmark.readers import histogram_window
    from distar_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    tap = RegistryTap(reg)
    h = reg.histogram("wait_seconds", "", phase="a")
    h.observe(99.0)  # warm-up: before the window
    tap.mark("open")
    assert histogram_window.read({"tap": tap}, "wait_seconds") is None  # no window yet
    for x in (1.0, 2.0, 3.0, 6.0):
        h.observe(x)
    tap.mark("close")
    assert histogram_window.read({"tap": tap}, "wait_seconds", {"phase": "a"}, reduce,
                                 scale=1000.0) == pytest.approx(want)
    assert histogram_window.read({"tap": tap}, "wait_seconds", {"phase": "b"}) is None


def test_registry_tap_reads_between_marks():
    from distar_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    tap = RegistryTap(reg)
    h = reg.histogram("phase_seconds", "", reservoir=4, phase="a")
    other = reg.histogram("phase_seconds", "", phase="b")
    c = reg.counter("compiles_total")
    h.observe(1.0)
    c.inc(3)
    tap.mark("open")
    for x in (2.0, 3.0):
        h.observe(x)
    other.observe(9.0)
    c.inc()
    tap.mark("close")
    h.observe(4.0)
    assert tap.value_at("open", "compiles_total") == 3.0 and tap.value_at("close", "compiles_total") == 4.0
    assert tap.value_at("open", "never_made") is None
    assert tap.observed_between("phase_seconds", "open", "close", {"phase": "a"}) == [2.0, 3.0]
    assert sorted(tap.observed_between("phase_seconds", "open", "close")) == [2.0, 3.0, 9.0]
    # the reservoir keeps the last 4: an older window loses what fell out
    for x in (5.0, 6.0, 7.0):
        h.observe(x)
    assert tap.observed_between("phase_seconds", "open", "close", {"phase": "a"}) == []


@pytest.mark.parametrize("gen,kw", [(sl_pool, {}), (rl_pool, {"model_cfg": CORE})])
def test_pools_repeat_from_a_seed_and_differ_across_seeds(gen, kw):
    import jax

    a, b, c = (gen.build(s, DRAW, **kw) for s in (5, 5, 6))
    la, lb, lc = (jax.tree.leaves(p) for p in (a, b, c))
    assert all(np.array_equal(x, y) and x.dtype == y.dtype for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert len(a) == DRAW["pool"]


def test_pools_have_the_programs_schema():
    import jax

    from distar_tpu.learner.data import fake_rl_batch, fake_sl_batch

    sig = lambda t: jax.tree.map(lambda x: (np.asarray(x).shape, np.asarray(x).dtype), t)
    assert sig(sl_pool.build(0, DRAW)[0]) == sig(fake_sl_batch(2, 3))
    assert sig(rl_pool.build(0, DRAW, model_cfg=CORE)[0]) == sig(
        fake_rl_batch(2, 3, hidden_size=8, hidden_layers=2))


def test_selected_units_are_distinct_then_the_end_token():
    batch = sl_pool.build(1, dict(DRAW, batch_size=8, unroll_len=8))[0]
    su, n, en = (batch["action_info"]["selected_units"], batch["selected_units_num"],
                 batch["entity_num"])
    for row, k, e in zip(su, n, en):
        assert row[k - 1] == e and len(set(row[:k - 1])) == k - 1 and (row[:k - 1] < 8).all()
        assert (row[k:] == 0).all()
    assert en.min() >= 8 and 2 <= n.min() and n.max() <= 6


def test_cycle_serves_the_pool_round_robin_as_fresh_dicts():
    pool = [{"i": 0, "new_episodes": 1}, {"i": 1, "new_episodes": 1}]
    it = sl_pool.cycle(pool)
    served = [next(it) for _ in range(5)]
    assert [s["i"] for s in served] == [0, 1, 0, 1, 0]
    served[0].pop("new_episodes")  # what the learner does to its batch
    assert "new_episodes" in pool[0]


def test_loss_and_replica_checks_flip():
    falling = [407.0, 400.0, 395.0, 392.0, 391.0, 390.0, 390.5, 389.5]
    assert check.loss_went_down(falling, pool=4, min_drop=0.02)
    assert not check.loss_went_down([407.0] * 8, pool=4, min_drop=0.02)  # the update was dropped
    assert not check.loss_went_down(falling[:5], pool=4, min_drop=0.02)  # too few steps to say
    assert not check.loss_went_down([float("nan")] + falling[1:], pool=4, min_drop=0.02)
    assert check.losses_finite([1.0, float("inf"), float("nan")]) == 2
    assert check.replicas_agree({"d0": 1.5, "d1": 1.5}) and not check.replicas_agree({"d0": 1.5, "d1": 1.25})
    assert not check.replicas_agree({"d0": 1.5})
    assert check.batch_share_ok({"a": 25, "b": 25, "c": 25, "d": 25}, 0.25)
    assert not check.batch_share_ok({"a": 100, "b": 0, "c": 0, "d": 0}, 0.25)


REFERENCE = {"action_type_loss": 6.0, "delay_loss": 4.86, "total_loss": 406.0, "queued_acc": 0.5}


@pytest.mark.parametrize("first,off", [
    ({"action_type_loss": 6.05, "delay_loss": 4.87, "total_loss": 405.0, "queued_acc": 0.9}, []),
    ({"action_type_loss": 6.05, "delay_loss": 4.87, "total_loss": 392.0}, ["total_loss"]),  # a term dropped
    ({"action_type_loss": 6.05, "total_loss": 405.0}, ["delay_loss"]),                      # a head left out
    ({"action_type_loss": float("nan"), "delay_loss": 4.87, "total_loss": 405.0}, ["action_type_loss"]),
])
def test_off_reference_names_what_parts_from_it(first, off):
    found = check.off_reference(first, REFERENCE, keys=["loss"], rtol=0.02)
    assert [f.split(":")[0] for f in found] == off


def test_off_reference_without_a_reference_is_a_fault():
    assert check.off_reference({"total_loss": 1.0}, None, keys=["loss"], rtol=0.02)
    assert check.off_reference({"total_loss": 1.0}, {"total_loss": 1.0}, keys=["kl/"], rtol=0.02)
    # a component may have a tolerance of its own
    flag = {"end_flag_loss": 5.15}, {"end_flag_loss": 5.0}
    assert check.off_reference(*flag, keys=["loss"], rtol=0.02)
    assert not check.off_reference(*flag, keys=["loss"], rtol=0.02, rtol_of={"end_flag_loss": 0.04})

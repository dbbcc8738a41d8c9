"""Direct unit tests for the learner hook registry (previously exercised
only through full learner runs)."""
import types

import pytest

from distar_tpu.learner.hooks import (
    Hook,
    HookRegistry,
    LambdaHook,
    LoadCkptHook,
    ProfilerHook,
    SaveCkptHook,
)


# ------------------------------------------------------------------- hooks
def _fake_learner(iter_val=0):
    learner = types.SimpleNamespace()
    learner.last_iter = types.SimpleNamespace(val=iter_val)
    learner.calls = []
    return learner


def test_registry_orders_by_priority_and_respects_freq():
    reg = HookRegistry()
    order = []
    reg.add(LambdaHook("b", "after_iter", lambda l: order.append("b"), priority=60))
    reg.add(LambdaHook("a", "after_iter", lambda l: order.append("a"), priority=10))
    reg.add(LambdaHook("c", "after_iter", lambda l: order.append("c"),
                       priority=30, freq=2))
    learner = _fake_learner(iter_val=1)
    reg.call("after_iter", learner)
    assert order == ["a", "b"]  # freq=2 hook skipped on odd iter
    order.clear()
    learner.last_iter.val = 2
    reg.call("after_iter", learner)
    assert order == ["a", "c", "b"]  # priority order, freq hook included


def test_registry_freq_only_gates_iter_positions():
    reg = HookRegistry()
    ran = []
    reg.add(LambdaHook("r", "before_run", lambda l: ran.append(1), freq=1000))
    reg.call("before_run", _fake_learner(iter_val=1))
    assert ran == [1]  # run-positions ignore freq


def test_hook_position_validated():
    with pytest.raises(AssertionError):
        Hook("x", "mid_iter")


def test_save_hook_rank_gated(tmp_path):
    learner = _fake_learner(iter_val=5)
    learner.rank = 1
    saved = []
    learner.save = lambda p: saved.append(p)
    learner.checkpoint_path = lambda: str(tmp_path / "c.ckpt")
    SaveCkptHook()(learner)
    assert saved == []  # only rank 0 writes
    learner.rank = 0
    learner.logger = types.SimpleNamespace(info=lambda *a, **k: None)
    SaveCkptHook()(learner)
    assert saved == [str(tmp_path / "c.ckpt")]


def test_load_hook_ignores_missing_path(tmp_path):
    learner = _fake_learner()
    learner.cfg = types.SimpleNamespace(
        learner={"load_path": str(tmp_path / "nope.ckpt")}
    )
    learner.restore = lambda p: (_ for _ in ()).throw(AssertionError("called"))
    LoadCkptHook()(learner)  # missing file: no restore attempt


# ---------------------------------------------------------------- profiler
class _FakeProfiler:
    """jax.profiler stand-in recording start/stop edges."""

    def __init__(self, fail=False):
        self.events = []
        self.fail = fail

    def start_trace(self, logdir):
        if self.fail:
            raise RuntimeError("no profiler backend")
        self.events.append(("start", logdir))

    def stop_trace(self):
        self.events.append(("stop",))


def _profiled_learner():
    learner = _fake_learner()
    learner.rank = 0
    learner.logger = types.SimpleNamespace(info=lambda *a, **k: None)
    return learner


def test_profiler_hook_freq_gated_capture_window(tmp_path):
    """Every ``freq`` iterations the hook opens a trace and closes it
    ``duration`` iterations later — one bounded capture per gate point."""
    prof = _FakeProfiler()
    hook = ProfilerHook(str(tmp_path), freq=4, duration=2, profiler=prof)
    learner = _profiled_learner()
    for it in range(1, 11):
        learner.last_iter.val = it
        hook(learner)
    # gates at 4 and 8; stops at 6 and 10
    assert prof.events == [
        ("start", str(tmp_path)), ("stop",),
        ("start", str(tmp_path)), ("stop",),
    ]
    assert not hook.session.active


def test_profiler_hook_rank_gated(tmp_path):
    prof = _FakeProfiler()
    hook = ProfilerHook(str(tmp_path), freq=1, duration=1, profiler=prof)
    learner = _profiled_learner()
    learner.rank = 1
    for it in range(1, 5):
        learner.last_iter.val = it
        hook(learner)
    assert prof.events == []  # only rank 0 profiles


def test_profiler_hook_survives_broken_profiler(tmp_path):
    """A missing/broken profiler backend must never take down training."""
    prof = _FakeProfiler(fail=True)
    hook = ProfilerHook(str(tmp_path), freq=2, duration=1, profiler=prof)
    learner = _profiled_learner()
    for it in range(1, 7):
        learner.last_iter.val = it
        hook(learner)  # no raise
    assert not hook.session.active


def test_profiler_sessions_counted_in_registry(tmp_path):
    from distar_tpu.obs import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        prof = _FakeProfiler()
        hook = ProfilerHook(str(tmp_path), freq=3, duration=1, profiler=prof)
        learner = _profiled_learner()
        for it in range(1, 8):
            learner.last_iter.val = it
            hook(learner)
        assert reg.counter("distar_profiler_sessions_total").value == 2  # it=3, it=6
    finally:
        set_registry(prev)


def test_profiler_failures_counted_and_hook_self_disables(tmp_path):
    """start/stop failures are no longer silent warnings: each one counts
    distar_profiler_failures_total{stage=...}, and after 3 consecutive
    start failures (unwritable logdir) the hook retires itself instead of
    re-failing at every gate."""
    from distar_tpu.obs import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        prof = _FakeProfiler(fail=True)
        hook = ProfilerHook(str(tmp_path), freq=1, duration=1, profiler=prof)
        learner = _profiled_learner()
        for it in range(1, 10):
            learner.last_iter.val = it
            hook(learner)
        assert hook.disabled
        # exactly MAX_CONSECUTIVE_FAILURES attempts, then silence
        assert reg.counter(
            "distar_profiler_failures_total", stage="start"
        ).value == ProfilerHook.MAX_CONSECUTIVE_FAILURES
    finally:
        set_registry(prev)


def test_profiler_session_records_last_profile_path(tmp_path):
    """A successful stop resolves the newest capture dir under the logdir
    (the jax.profiler plugins/profile/<stamp>/ layout) — what the admin
    /profile route hands to the analyzer."""
    import os

    from distar_tpu.obs import MetricsRegistry, ProfilerSession

    stamp = tmp_path / "plugins" / "profile" / "2026_01_02"

    class WritingProfiler(_FakeProfiler):
        def stop_trace(self):
            os.makedirs(stamp)
            super().stop_trace()

    sess = ProfilerSession(str(tmp_path), profiler=WritingProfiler(),
                           registry=MetricsRegistry())
    assert sess.start()
    assert sess.stop()
    assert sess.last_profile_path == str(stamp)
    # failure paths count into the session's registry, typed by stage
    failing = ProfilerSession(str(tmp_path), profiler=_FakeProfiler(fail=True),
                              registry=MetricsRegistry())
    assert not failing.start()
    assert failing.failures == 1

"""Harness tests for bench.py's sweep logic (the driver-facing surface).

Three rounds of BENCH_r{N} artifacts died to harness bugs, not model bugs —
so the sweep/retry/emit logic gets direct coverage: the _bench_* measurement
functions are monkeypatched and run_child exercised in-process on the CPU
backend (fast), plus one slow-marked subprocess test that builds the real
model to prove the parent never kills a compiling child (the livelock).
"""
import json
import os
import sys

import pytest

# repo root (bench.py lives outside the package)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


def _fake_point(b, t, fps=100.0, remat=False):
    point = {
        "frames_per_sec": fps,
        "step_time_s": round(b * t / fps, 4),
        "trace_s": 0.1,
        "compile_s": 0.1,
        "batch": b,
        "unroll": t,
    }
    if remat:
        point["remat"] = True
    return point


def _final_json(capsys):
    out = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert out, "run_child printed no JSON line"
    return json.loads(out[-1])


@pytest.fixture()
def sl_only_env(monkeypatch):
    # single-config plan: BENCH_BATCH/UNROLL pins plan = [(sl, 4, 16)]
    monkeypatch.setenv("BENCH_MODE", "sl")
    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_UNROLL", "16")
    monkeypatch.delenv("BENCH_REMAT", raising=False)
    monkeypatch.delenv("BENCH_PLATFORM", raising=False)


def test_oom_retries_with_remat(sl_only_env, monkeypatch, capsys):
    """A RESOURCE_EXHAUSTED SL config must be retried once rematerialized,
    and the sweep must record both the failure and the retried point."""
    calls = []

    def fake_sl(b, t, peak, iters=4, remat=False, cap=None):
        calls.append(remat)
        if not remat:
            raise RuntimeError("RESOURCE_EXHAUSTED: HBM OOM allocating 1.9G")
        return _fake_point(b, t, fps=50.0, remat=True)

    monkeypatch.setattr(bench, "_bench_sl", fake_sl)
    bench.run_child()

    assert calls == [False, True]
    final = _final_json(capsys)
    assert final["value"] == 50.0
    assert final["sl"]["remat"] is True
    # sweep keeps the diagnostic error record AND the successful retry
    assert any("error" in p for p in final["sl_sweep"])
    assert any(p.get("remat") for p in final["sl_sweep"] if "error" not in p)


def test_non_oom_error_is_not_retried(sl_only_env, monkeypatch, capsys):
    calls = []

    def fake_sl(b, t, peak, iters=4, remat=False, cap=None):
        calls.append(remat)
        raise ValueError("shape mismatch")

    monkeypatch.setattr(bench, "_bench_sl", fake_sl)
    # nothing completed -> run_child raises so the parent's retry loop fires
    with pytest.raises(RuntimeError, match="no config completed"):
        bench.run_child()
    assert calls == [False]  # no remat retry for non-OOM failures


def test_env_remat_run_skips_oom_retry(sl_only_env, monkeypatch, capsys):
    """BENCH_REMAT=1 runs already built the remat model: an OOM there must
    NOT rebuild the identical config."""
    monkeypatch.setenv("BENCH_REMAT", "1")
    calls = []

    def fake_sl(b, t, peak, iters=4, remat=False, cap=None):
        calls.append(remat)
        raise RuntimeError("RESOURCE_EXHAUSTED")

    monkeypatch.setattr(bench, "_bench_sl", fake_sl)
    with pytest.raises(RuntimeError, match="no config completed"):
        bench.run_child()
    assert calls == [False]


def test_full_plan_budget_break(monkeypatch, capsys):
    """Once any best exists and the budget is spent, the sweep stops —
    partial results must still produce a valid headline line."""
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    monkeypatch.delenv("BENCH_UNROLL", raising=False)
    monkeypatch.delenv("BENCH_REMAT", raising=False)
    monkeypatch.setenv("BENCH_MODE", "both")
    monkeypatch.setenv("BENCH_TIME_BUDGET", "0")  # expire after first point

    seen = []

    def fake_sl(b, t, peak, iters=4, remat=False, cap=None):
        seen.append((b, t))
        return _fake_point(b, t)

    monkeypatch.setattr(bench, "_bench_sl", fake_sl)
    monkeypatch.setattr(bench, "_bench_rl", fake_sl)
    monkeypatch.setattr(bench, "_bench_sl_real", fake_sl)
    bench.run_child()

    assert seen == [(2, 8)]  # probe landed, then the budget gate fired
    final = _final_json(capsys)
    assert final["value"] == 100.0
    assert final["vs_baseline"] == round(100.0 / bench.SL_BASELINE_FRAMES, 3)


def test_headline_modes(monkeypatch, capsys):
    """rl-only and sl_real-only runs headline their own number, never a
    misleading 0.0 SL metric."""
    monkeypatch.setenv("BENCH_MODE", "rl")
    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_UNROLL", "16")
    monkeypatch.delenv("BENCH_REMAT", raising=False)

    def fake_rl(b, t, peak, iters=4, remat=False, cap=None):
        point = _fake_point(b, t, fps=64.0)
        point["steps_per_sec"] = 1.0
        return point

    monkeypatch.setattr(bench, "_bench_rl", fake_rl)
    bench.run_child()
    final = _final_json(capsys)
    assert "RL learner" in final["metric"]
    assert final["value"] == 64.0
    assert final["rl"]["vs_baseline_frames"] == round(64.0 / bench.RL_BASELINE_FRAMES, 3)


def test_default_plan_routes_entity_caps(monkeypatch, capsys):
    """4-tuple plan entries carry their bucket into the measurement fns;
    the capped baseline regime runs immediately after the probe so the
    strongest number lands earliest in the driver's window."""
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    monkeypatch.delenv("BENCH_UNROLL", raising=False)
    monkeypatch.delenv("BENCH_REMAT", raising=False)
    monkeypatch.setenv("BENCH_MODE", "both")
    monkeypatch.setenv("BENCH_TIME_BUDGET", str(10 ** 9))

    calls = []

    def fake(kind):
        def fn(b, t, peak, iters=4, remat=False, cap=None):
            calls.append((kind, b, t, cap))
            point = _fake_point(b, t)
            if kind == "rl":
                point["steps_per_sec"] = 1.0
            return point

        return fn

    monkeypatch.setattr(bench, "_bench_sl", fake("sl"))
    monkeypatch.setattr(bench, "_bench_rl", fake("rl"))
    monkeypatch.setattr(bench, "_bench_sl_real", fake("sl_real"))
    bench.run_child()

    assert calls[0] == ("sl", 2, 8, None)          # probe first
    assert calls[1] == ("sl", 6, 64, 256)          # capped baseline next
    assert ("rl", 6, 64, 256) in calls             # capped RL regime
    assert ("sl", 32, 64, 256) in calls            # HBM edge bucketed
    assert ("sl_real", 6, 64, None) in calls       # real-data path uncapped
    _final_json(capsys)  # a valid headline line printed


def _run_parent(tmp_path, simulate, attempt_timeout, deadline, timeout=120):
    """Run bench.py's PARENT with a scripted simulated child (no jax, no
    compile — the round-4 version of these tests cold-compiled the real
    model and was flaky under -n 4 oversubscription)."""
    import subprocess
    import sys as _sys

    state = tmp_path / "attempts"
    env = dict(
        os.environ,
        BENCH_SIMULATE=simulate,
        BENCH_SIMULATE_STATE=str(state),
        BENCH_ATTEMPT_TIMEOUT=str(attempt_timeout),
        BENCH_DEADLINE=str(deadline),
    )
    out = subprocess.run(
        [_sys.executable, "-u",
         os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert lines, out.stderr[-500:]
    attempts = int(state.read_text() or 0) if state.exists() else 0
    return json.loads(lines[-1]), attempts


def test_parent_extends_attempt_past_compile(tmp_path):
    """A child past backend-init must not be killed at BENCH_ATTEMPT_TIMEOUT:
    killing mid-compile caches nothing and the retry repeats the same
    compile forever (a livelock). The simulated child holds
    the compile stage for >2x the attempt timeout, then lands its number.
    Under the livelock bug no attempt EVER lands (each child dies
    mid-compile), so the landed value is the whole assertion — exact
    attempt counts are load-dependent (a python start slower than the
    attempt timeout adds a legitimate pre-stage retry under -n 4
    oversubscription) and deliberately not pinned."""
    final, attempts = _run_parent(
        tmp_path,
        # margins are sleeps, not compiles: load-independent
        "stage:backend-init:0,stage:sl-compile b2xt4:20,result:123.0",
        attempt_timeout=8, deadline=300, timeout=360,
    )
    assert final["value"] == 123.0, final
    assert attempts <= 4, f"{attempts} attempts: extend logic not engaging"


def test_parent_kills_stuck_backend_init_and_retries(tmp_path):
    """A child that never gets past backend init IS killed at the attempt
    timeout, and a later attempt's fresh init can land."""
    final, attempts = _run_parent(
        tmp_path,
        # attempt 1: stuck in backend-init far past the attempt timeout;
        # later attempts initialise instantly and land
        "stage:backend-init:90;"
        "stage:backend-init:0,stage:devices-ok cpu:0,result:55.5",
        attempt_timeout=8, deadline=300, timeout=360,
    )
    assert final["value"] == 55.5, final
    assert attempts >= 2, "stuck first attempt was never killed"


def test_env_cap_governs_whole_sweep(monkeypatch, capsys):
    """BENCH_MAX_ENTITIES overrides the plan's own buckets — no entry runs
    at a different bucket and no duplicate configs pay a second compile."""
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    monkeypatch.delenv("BENCH_UNROLL", raising=False)
    monkeypatch.delenv("BENCH_REMAT", raising=False)
    monkeypatch.setenv("BENCH_MODE", "both")
    monkeypatch.setenv("BENCH_TIME_BUDGET", str(10 ** 9))
    monkeypatch.setenv("BENCH_MAX_ENTITIES", "384")

    calls = []

    def fake(kind):
        def fn(b, t, peak, iters=4, remat=False, cap=None):
            calls.append((kind, b, t, cap, remat))
            point = _fake_point(b, t)
            if kind == "rl":
                point["steps_per_sec"] = 1.0
            return point

        return fn

    monkeypatch.setattr(bench, "_bench_sl", fake("sl"))
    monkeypatch.setattr(bench, "_bench_rl", fake("rl"))
    monkeypatch.setattr(bench, "_bench_sl_real", fake("sl_real"))
    bench.run_child()

    assert all(cap is None for _, _, _, cap, _ in calls)  # env governs via fns
    # remat is part of a config's identity: the b16-remat A/B entry is NOT a
    # duplicate of plain b16 (their compiles differ)
    configs = [(k, b, t, remat) for k, b, t, _, remat in calls]
    assert len(configs) == len(set(configs))  # duplicates deduped
    assert ("sl", 6, 64, False) in configs and ("rl", 6, 64, False) in configs
    assert ("sl", 16, 64, True) in configs  # the remat A/B point survives

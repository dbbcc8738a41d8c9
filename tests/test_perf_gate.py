"""tools/perf_gate.py — the tier-1 perf regression gate.

Runs against the COMMITTED baseline artifact (skips when absent): the gate
must pass on the baseline vs itself, fail on an injected 2x step-time
regression, and hard-fail the impossible-timing precondition regardless of
how favourable the comparison looks."""
import copy
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import perf_gate  # noqa: E402

BASELINE = os.path.join(REPO, "artifacts", "perf_baseline_cpu_r07.json")

pytestmark = pytest.mark.skipif(
    not os.path.exists(BASELINE),
    reason="no committed perf baseline artifact",
)


@pytest.fixture(scope="module")
def baseline():
    with open(BASELINE) as f:
        return json.load(f)


def test_gate_passes_on_committed_baseline(baseline):
    assert perf_gate.impossible_timing(baseline) == []
    regressions, _notes = perf_gate.compare(baseline, baseline, tolerance=0.5)
    assert regressions == []


def test_gate_fails_on_injected_2x_regression(baseline, tmp_path):
    candidate = copy.deepcopy(baseline)
    for p in candidate["sl_sweep"]:
        p["step_time_s"] *= 2.0
        p["frames_per_sec"] /= 2.0
    regressions, _ = perf_gate.compare(baseline, candidate, tolerance=0.5)
    assert regressions, "2x slower must breach a 50% tolerance"
    # and through the CLI, end to end (exit code contract: 1 = regression)
    cand_path = tmp_path / "cand.json"
    cand_path.write_text(json.dumps(candidate))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"), "check",
         "--baseline", BASELINE, "--candidate", str(cand_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESSION" in proc.stdout


def test_gate_tolerance_absorbs_noise(baseline):
    candidate = copy.deepcopy(baseline)
    for p in candidate["sl_sweep"]:
        p["step_time_s"] *= 1.3  # 30% drift < 50% tolerance
    regressions, _ = perf_gate.compare(baseline, candidate, tolerance=0.5)
    assert regressions == []


def test_impossible_timing_is_a_hard_precondition(baseline, tmp_path):
    # a candidate claiming a TPU whose own flop count says the step cannot
    # run that fast must fail with exit 2 even though it "improved"
    candidate = copy.deepcopy(baseline)
    candidate["device"] = "TPU v5 lite"
    for p in candidate["sl_sweep"]:
        flops = max(p.get("flops_unoptimized", 0), p.get("flops_optimized", 0))
        assert flops > 0, "baseline must carry flop counts"
        p["step_time_s"] = flops / (200 * 197e12)  # 200x peak: impossible
        p["frames_per_sec"] = 10 ** 9
    offences = perf_gate.impossible_timing(candidate)
    assert offences
    cand_path = tmp_path / "impossible.json"
    cand_path.write_text(json.dumps(candidate))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"), "check",
         "--baseline", BASELINE, "--candidate", str(cand_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "PRECONDITION" in proc.stdout


def test_suspect_flag_is_a_hard_precondition(baseline):
    candidate = copy.deepcopy(baseline)
    candidate["suspect"] = True
    candidate["suspect_reason"] = "CPU-derived scaling numbers"
    assert perf_gate.impossible_timing(candidate)


def test_missing_candidate_points_note_not_fail(baseline):
    candidate = copy.deepcopy(baseline)
    candidate["sl_sweep"] = []
    candidate.pop("value", None)
    regressions, notes = perf_gate.compare(baseline, candidate, tolerance=0.5)
    # nothing comparable IS a failure; a truncated (but nonempty) sweep is not
    assert any("no comparable points" in r for r in regressions) or notes


def test_trajectory_collects_rounds_and_flags_suspects():
    rows = perf_gate.collect_trajectory()
    assert rows, "repo carries BENCH_*/MULTICHIP_* artifacts"
    by_artifact = {r["artifact"]: r for r in rows}
    assert "perf_baseline_cpu_r07.json" in by_artifact
    # the r06 multichip artifact flags itself in-band
    if "multichip_scaling_cpu_r06.json" in by_artifact:
        assert "SUSPECT" in by_artifact["multichip_scaling_cpu_r06.json"]["status"]


def test_trajectory_write_round_trips_markers(tmp_path):
    target = tmp_path / "PERF.md"
    target.write_text("# perf\n\nintro text\n")
    ns = type("A", (), {"write": str(target)})
    perf_gate.cmd_trajectory(ns)
    first = target.read_text()
    assert perf_gate.TRAJ_BEGIN in first and perf_gate.TRAJ_END in first
    assert "intro text" in first
    perf_gate.cmd_trajectory(ns)  # idempotent: replaces between markers
    second = target.read_text()
    assert second.count(perf_gate.TRAJ_BEGIN) == 1
    assert second == first


def test_perf_md_trajectory_block_is_current():
    """PERF.md's committed trajectory table matches what the artifacts
    derive — the block can't silently rot as artifacts accumulate."""
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    assert perf_gate.TRAJ_BEGIN in text
    committed = text.split(perf_gate.TRAJ_BEGIN, 1)[1].split(perf_gate.TRAJ_END, 1)[0]
    fresh = perf_gate.render_trajectory(perf_gate.collect_trajectory())
    assert committed.strip() == fresh.strip()


# ------------------------------------------------------------- curve gate
def _fam(*entries):
    return [{"round": r, "artifact": f"curves_r{r}.json", "values": list(v)}
            for r, v in entries]


def test_curve_gate_passes_on_descending_rounds():
    fams = {"sl_total_loss": _fam(("15", [10.0, 8.0, 6.0]),
                                  ("16", [10.0, 7.0, 5.9]))}
    verdicts, failures = perf_gate.curve_verdicts(fams, tolerance=0.10)
    assert failures == []
    assert verdicts[0]["regressed"] is False
    assert verdicts[0]["candidate_last"] == 5.9


def test_curve_gate_fails_past_tolerance_and_absorbs_within():
    fams = {"rl_total_loss": _fam(("15", [10.0, 5.0]), ("16", [10.0, 5.4]))}
    # 5.4 <= 5.0 * 1.10: inside the band
    _, failures = perf_gate.curve_verdicts(fams, tolerance=0.10)
    assert failures == []
    # 5.4 > 5.0 * 1.05: regression
    _, failures = perf_gate.curve_verdicts(fams, tolerance=0.05)
    assert len(failures) == 1 and "regressed past" in failures[0]


def test_curve_gate_rejects_nondescent_and_nonfinite():
    fams = {
        "flat": _fam(("16", [5.0, 5.0])),
        "nan": _fam(("16", [5.0, float("nan"), 4.0])),
    }
    _, failures = perf_gate.curve_verdicts(fams, tolerance=0.10)
    assert any("does not descend" in f for f in failures)
    assert any("non-finite" in f for f in failures)


def test_curve_gate_single_round_is_baseline_pass():
    verdicts, failures = perf_gate.curve_verdicts(
        {"distill_kl": _fam(("15", [30.0, 26.0]))}, tolerance=0.10)
    assert failures == [] and verdicts[0]["regressed"] is False
    assert "single round" in verdicts[0]["note"]


def test_curve_gate_sign_safe_for_negative_losses():
    # RL total_loss can be negative; the band must widen, not flip
    fams = {"rl": _fam(("15", [1.0, -2.0]), ("16", [1.0, -1.9]))}
    _, failures = perf_gate.curve_verdicts(fams, tolerance=0.10)
    assert failures == []  # -1.9 <= -2.0 + 0.10*2.0
    _, failures = perf_gate.curve_verdicts(fams, tolerance=0.01)
    assert len(failures) == 1


def test_curve_gate_runs_green_on_committed_artifacts():
    """The repo's own committed toy-run curves must satisfy the gate (the
    chain perf_gate curve walks in CI)."""
    fams = perf_gate.collect_curves()
    assert {"sl_total_loss", "rl_total_loss", "distill_kl"} <= set(fams)
    _, failures = perf_gate.curve_verdicts(fams, tolerance=0.10)
    assert failures == []


ARENA_ARTIFACT = os.path.join(REPO, "ARENA_r18.json")


def _arena_doc(anchor_relative, player="main:300", matches=12):
    return {"bench": "arena", "metric": "arena match throughput",
            "value": 0.5, "unit": "matches/s", "host_cores": 1,
            "scaling_valid": False,
            "arena": {"player": player, "matches": matches,
                      "anchor": "mean(attack_nearest,idle)",
                      "anchor_relative": anchor_relative}}


@pytest.mark.skipif(not os.path.exists(ARENA_ARTIFACT),
                    reason="no committed arena skill artifact")
def test_skill_gate_passes_on_committed_artifact():
    entries = perf_gate.collect_skill()
    assert any(e["artifact"] == "ARENA_r18.json" for e in entries)
    assert entries[-1]["player"].startswith("main:")
    verdicts, failures = perf_gate.skill_verdicts(entries, tolerance=50.0)
    assert failures == []
    assert verdicts and verdicts[0]["regressed"] is False
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
         "skill"], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "skill gate: PASS" in proc.stdout


def test_skill_gate_fails_on_injected_regression(tmp_path):
    (tmp_path / "ARENA_r18.json").write_text(json.dumps(_arena_doc(-100.0)))
    (tmp_path / "ARENA_r19.json").write_text(
        json.dumps(_arena_doc(-200.0, player="main:400")))
    entries = perf_gate.collect_skill(repo=str(tmp_path))
    assert [e["round"] for e in entries] == ["18", "19"]
    verdicts, failures = perf_gate.skill_verdicts(entries, tolerance=50.0)
    assert len(failures) == 1 and "regressed past" in failures[0]
    assert verdicts[0]["regressed"] is True
    # a 100-point drop inside a 150-point tolerance is absorbed
    _, failures = perf_gate.skill_verdicts(entries, tolerance=150.0)
    assert failures == []
    # and through the CLI, end to end (exit code contract: 1 = regression)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
         "skill", "--repo", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESSED" in proc.stdout


def test_skill_gate_single_round_is_baseline_pass(tmp_path):
    (tmp_path / "ARENA_r18.json").write_text(json.dumps(_arena_doc(-250.0)))
    verdicts, failures = perf_gate.skill_verdicts(
        perf_gate.collect_skill(repo=str(tmp_path)), tolerance=50.0)
    assert failures == [] and verdicts[0]["note"] == "single round: baseline PASS"


def test_skill_gate_rejects_nonfinite(tmp_path):
    (tmp_path / "ARENA_r18.json").write_text(
        json.dumps(_arena_doc(float("nan"))))
    _, failures = perf_gate.skill_verdicts(
        perf_gate.collect_skill(repo=str(tmp_path)), tolerance=50.0)
    assert any("non-finite" in f for f in failures)


@pytest.mark.skipif(not os.path.exists(ARENA_ARTIFACT),
                    reason="no committed arena skill artifact")
def test_skill_trajectory_rows_present():
    rows = perf_gate.collect_trajectory()
    arena_rows = [r for r in rows if r["artifact"] == "ARENA_r18.json"]
    units = {r["unit"] for r in arena_rows}
    assert "matches/s" in units, "headline throughput row missing"
    assert "elo" in units, "in-band anchor-relative skill row missing"

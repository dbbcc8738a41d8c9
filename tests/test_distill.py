"""Actor-learner distillation tier (docs/serving.md model tiering).

Covers the distillation contracts end to end: the masked per-head KL loss
against hand-computed values (selected-units mask edges included), the
student learner's training signal + ``distar_distill_*`` gauges,
checkpoint ROLE isolation (teacher resume can never pick a student
generation), the ``distill_divergence_runaway`` health rule's trend
detector, the committed DISTILL artifact's honesty flags, and the first
real consumer of canary compare: a student checkpoint rolled through a
canary split -> ``compare()`` verdict -> gated ``promote()`` over a
player-multiplexed (teacher + student behind one address) gateway fleet
with exact per-client version streams and zero in-flight loss.
"""
import itertools
import json
import math
import os
import time

import numpy as np
import pytest

from distar_tpu.losses import DistillLossConfig, compute_distill_loss

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_MODEL = {
    "encoder": {
        "entity": {"layer_num": 1, "hidden_dim": 32, "output_dim": 16, "head_dim": 8},
        "spatial": {"down_channels": [4, 4, 8], "project_dim": 4, "resblock_num": 1, "fc_dim": 16},
        "scatter": {"output_dim": 4},
        "core_lstm": {"hidden_size": 32, "num_layers": 1},
    },
    "policy": {
        "action_type_head": {"res_dim": 16, "res_num": 1, "gate_dim": 32},
        "delay_head": {"decode_dim": 16},
        "queued_head": {"decode_dim": 16},
        "selected_units_head": {"func_dim": 16},
        "target_unit_head": {"func_dim": 16},
        "location_head": {"res_dim": 8, "res_num": 1, "upsample_dims": [4, 4, 1], "map_skip_dim": 8},
    },
    "value": {"res_dim": 8, "res_num": 1},
}


# ----------------------------------------------------------------- the loss
def _loss_inputs(T=1, B=1, S=2, K=3):
    """Minimal schema-complete distill-loss inputs: identical student and
    teacher logits everywhere (KL == 0 baseline) that individual tests
    perturb head by head."""
    shapes = {
        "action_type": (K,), "delay": (K,), "queued": (2,),
        "selected_units": (S, K + 1), "target_unit": (K,),
        "target_location": (K,),
    }
    teacher = {k: np.zeros((T, B) + s, np.float32) for k, s in shapes.items()}
    student = {k: np.zeros((T, B) + s, np.float32) for k, s in shapes.items()}
    masks = {
        "actions_mask": {k: np.ones((T, B), np.float32) for k in shapes},
        "selected_units_mask": np.ones((T, B, S), np.float32),
        "step_mask": np.ones((T, B), np.float32),
    }
    return {"student_logit": student, "teacher_logit": teacher, "mask": masks}


def _kl(p_logits, q_logits):
    """Reference forward KL over the last axis, computed independently."""
    p_logits = np.asarray(p_logits, np.float64)
    q_logits = np.asarray(q_logits, np.float64)
    p = np.exp(p_logits - p_logits.max())
    p /= p.sum()
    q = np.exp(q_logits - q_logits.max())
    q /= q.sum()
    return float((p * (np.log(p) - np.log(q))).sum())


def test_distill_kl_matches_hand_computed_value():
    inputs = _loss_inputs()
    # teacher p = softmax([ln4, ln2, ln1]) = [4/7, 2/7, 1/7]; student uniform
    t = np.log([4.0, 2.0, 1.0]).astype(np.float32)
    inputs["teacher_logit"]["action_type"][0, 0] = t
    expected = (4 / 7) * math.log(12 / 7) + (2 / 7) * math.log(6 / 7) \
        + (1 / 7) * math.log(3 / 7)
    total, info = compute_distill_loss(inputs)
    assert float(info["kl/action_type"]) == pytest.approx(expected, rel=1e-5)
    # every untouched head is exactly zero and action_type's weight is 1.0
    for head in ("delay", "queued", "selected_units", "target_unit",
                 "target_location"):
        assert float(info[f"kl/{head}"]) == pytest.approx(0.0, abs=1e-7)
    assert float(total) == pytest.approx(expected, rel=1e-5)
    assert float(info["divergence"]) == pytest.approx(expected, rel=1e-5)


def test_distill_kl_selected_units_mask_edges_and_zero_active_lane():
    # both lanes diverge; only lane 0 is active -> exactly lane 0's KL
    inputs = _loss_inputs()
    lane_logits = np.array([2.0, 0.0, -1.0, 0.5], np.float32)
    inputs["teacher_logit"]["selected_units"][0, 0, 0] = lane_logits
    inputs["teacher_logit"]["selected_units"][0, 0, 1] = lane_logits
    inputs["mask"]["selected_units_mask"][0, 0] = [1.0, 0.0]
    _, info = compute_distill_loss(inputs)
    assert float(info["kl/selected_units"]) == pytest.approx(
        _kl(lane_logits, np.zeros(4)), rel=1e-5)
    # zero active lanes: the step contributes NOTHING however far the
    # teacher diverges (the pointer decode never ran for this action)
    inputs["mask"]["selected_units_mask"][0, 0] = [0.0, 0.0]
    _, info = compute_distill_loss(inputs)
    assert float(info["kl/selected_units"]) == pytest.approx(0.0, abs=1e-7)


def test_distill_kl_actions_mask_gates_heads_and_step_mask_pads():
    inputs = _loss_inputs()
    inputs["teacher_logit"]["target_unit"][0, 0] = [3.0, 0.0, 0.0]
    inputs["mask"]["actions_mask"]["target_unit"][0, 0] = 0.0
    _, info = compute_distill_loss(inputs)
    # the head diverges but the action type took no target unit
    assert float(info["kl/target_unit"]) == pytest.approx(0.0, abs=1e-7)
    # ALWAYS_ON heads ignore actions_mask but respect step_mask (pad steps)
    inputs = _loss_inputs()
    inputs["teacher_logit"]["action_type"][0, 0] = [3.0, 0.0, 0.0]
    inputs["mask"]["actions_mask"]["action_type"][0, 0] = 0.0
    _, info = compute_distill_loss(inputs)
    assert float(info["kl/action_type"]) > 0.0
    inputs["mask"]["step_mask"][0, 0] = 0.0
    total, info = compute_distill_loss(inputs)
    assert float(total) == pytest.approx(0.0, abs=1e-7)


def test_distill_temperature_softens_both_sides():
    inputs = _loss_inputs()
    inputs["teacher_logit"]["action_type"][0, 0] = [4.0, 0.0, 0.0]
    _, sharp = compute_distill_loss(inputs, DistillLossConfig(temperature=1.0))
    _, soft = compute_distill_loss(inputs, DistillLossConfig(temperature=4.0))
    assert float(soft["kl/action_type"]) == pytest.approx(
        _kl(np.array([1.0, 0.0, 0.0]), np.zeros(3)), rel=1e-5)
    assert float(soft["kl/action_type"]) < float(sharp["kl/action_type"])


# -------------------------------------------------- checkpoint role isolation
def test_checkpoint_manager_role_keys_never_cross(tmp_path):
    from distar_tpu.utils.checkpoint import CheckpointManager, save_checkpoint

    d = str(tmp_path / "checkpoints")
    teacher_path = os.path.join(d, "iteration_5.ckpt")
    student_path = os.path.join(d, "student_iteration_9.ckpt")
    save_checkpoint(teacher_path, {"w": np.ones((2,), np.float32)})
    save_checkpoint(student_path, {"w": np.zeros((3,), np.float32)})

    teacher_mgr = CheckpointManager(d)
    student_mgr = CheckpointManager(d, role="student")
    teacher_mgr.record(teacher_path, step=5)
    student_mgr.record(student_path, step=9)

    # distinct pointer files; each role resolves ONLY its own generations
    assert os.path.exists(os.path.join(d, "latest.json"))
    assert os.path.exists(os.path.join(d, "latest_student.json"))
    assert teacher_mgr.resolve_latest()["path"] == teacher_path
    assert student_mgr.resolve_latest()["path"] == student_path
    assert [g["path"] for g in teacher_mgr.generations()] == [teacher_path]
    assert [g["path"] for g in student_mgr.generations()] == [student_path]

    # even a hand-merged pointer cannot hand the teacher a student
    # generation: the role filter drops foreign entries on read
    merged = {"generations": [
        {"path": student_path, "step": 9, "ts": time.time(), "role": "student"},
        {"path": teacher_path, "step": 5, "ts": time.time()},
    ]}
    with open(os.path.join(d, "latest.json"), "w") as f:
        json.dump(merged, f)
    assert [g["path"] for g in teacher_mgr.generations()] == [teacher_path]
    assert teacher_mgr.resolve_latest()["path"] == teacher_path
    fresh_student = CheckpointManager(d, role="student")
    assert fresh_student.resolve_latest()["path"] == student_path


# ------------------------------------------------------- the student learner
def test_distill_learner_toy_run_decreases_divergence(tmp_path):
    """Tier-1 e2e of the --distill learner role: a toy run through the real
    run loop (hooks, checkpointing, gauges) on a fixed batch must decrease
    the KL divergence monotonically, publish the drift gauges, and leave
    its checkpoint under the STUDENT role key only."""
    from distar_tpu.learner import DistillLearner
    from distar_tpu.learner.data import fake_rl_batch
    from distar_tpu.obs import get_registry
    from distar_tpu.utils.checkpoint import CheckpointManager

    learner = DistillLearner({
        "common": {"experiment_name": "distill_e2e", "save_path": str(tmp_path)},
        "learner": {"batch_size": 2, "unroll_len": 3, "save_freq": 10 ** 9,
                    "log_freq": 1},
        "model": SMOKE_MODEL,
    })
    assert learner.CKPT_ROLE == "student"
    batch = fake_rl_batch(2, 3)
    batch["model_last_iter"] = np.full((2,), 37.0, np.float32)
    learner.set_dataloader(itertools.repeat(batch))
    kls = []
    for _ in range(5):
        kls.append(learner._train(dict(batch))["divergence"])
    assert all(b < a for a, b in zip(kls, kls[1:])), kls

    snap = get_registry().snapshot()
    assert snap["distar_distill_kl"] == pytest.approx(kls[-1], rel=1e-5)
    assert snap["distar_distill_teacher_generation"] == 37.0
    assert "distar_distill_head_kl{head=selected_units}" in snap

    learner.last_iter.update(5)
    learner.save(learner.checkpoint_path(), sync=True)
    assert get_registry().snapshot()["distar_distill_student_generation"] == 5.0
    ckpt_dir = os.path.join(str(tmp_path), "checkpoints")
    assert os.path.exists(os.path.join(ckpt_dir, "latest_student.json"))
    # a teacher manager over the SAME directory sees no resumable
    # generation: student checkpoints are invisible to teacher resume
    assert CheckpointManager(ckpt_dir).resolve_latest() is None
    assert CheckpointManager(ckpt_dir, role="student").resolve_latest()[
        "path"].endswith("student_iteration_5.ckpt")


# -------------------------------------------------------- divergence watchdog
def test_distill_divergence_runaway_rule_fires_on_rising_kl():
    from distar_tpu.obs import HealthEvaluator, TimeSeriesStore, default_rulebook

    rules = default_rulebook(roles=("distill",))
    assert [r.name for r in rules] == ["distill_divergence_runaway"]
    store = TimeSeriesStore()
    ev = HealthEvaluator(store, rules, interval_s=3600.0)
    t0 = time.time()
    # falling KL (healthy convergence): never breaches
    for i in range(6):
        store.record("distar_distill_kl", 5.0 - 0.5 * i, ts=t0 + i,
                     source="distill:MP0:0")
    ev.evaluate_once()
    assert ev.alerts()["rules"]["distill_divergence_runaway"]["state"] == "ok"
    # rising KL (a full window past the falling phase, so the 60s query
    # window holds ONLY the rise): warning immediately, firing after the
    # for_count debounce
    for i in range(6):
        store.record("distar_distill_kl", 2.0 + 0.4 * i, ts=t0 + 100 + i,
                     source="distill:MP0:0")
    ev.evaluate_once()
    assert ev.alerts()["rules"]["distill_divergence_runaway"]["state"] == "warning"
    ev.evaluate_once()
    ev.evaluate_once()
    alerts = ev.alerts()
    assert alerts["rules"]["distill_divergence_runaway"]["state"] == "firing"
    assert alerts["rules"]["distill_divergence_runaway"]["severity"] == "warning"
    # recovery: KL falls again -> clears after clear_count evaluations
    for i in range(6):
        store.record("distar_distill_kl", 4.0 - 0.5 * i, ts=t0 + 200 + i,
                     source="distill:MP0:0")
    ev.evaluate_once()
    ev.evaluate_once()
    assert ev.alerts()["rules"]["distill_divergence_runaway"]["state"] == "ok"


# ----------------------------------------------- canary compare-then-promote
def _obs(i: int = 0) -> dict:
    return {"x": np.full((2, 2), float(i), dtype=np.float32)}


def _tier_gateway(slots, version):
    from distar_tpu.serve import InferenceGateway, MockModelEngine

    params = {"version": version, "bias": 0.0}
    gw = InferenceGateway(MockModelEngine(slots, params=params),
                         max_batch=slots, max_delay_s=0.002)
    gw.load_version(version, params=params, activate=True)
    return gw.start()


class _TierFleet:
    """N player-multiplexed gateways — teacher + student tiers behind ONE
    address each (the wire ``player`` field is the QoS class)."""

    def __init__(self, n, slots=64):
        from distar_tpu.serve import (
            STUDENT_TIER, TEACHER_TIER, GatewayMux, ServeTCPServer,
        )

        self.muxes = [
            GatewayMux({TEACHER_TIER: _tier_gateway(slots, "t1"),
                        STUDENT_TIER: _tier_gateway(slots, "s1")},
                       default_player=TEACHER_TIER)
            for _ in range(n)
        ]
        self.servers = [ServeTCPServer(m, port=0).start() for m in self.muxes]
        self.addrs = [f"{s.host}:{s.port}" for s in self.servers]

    def close(self):
        for s in self.servers:
            s.stop()
        for m in self.muxes:
            m.drain_and_stop(2.0)


def test_student_canary_compare_then_promote_tiered_fleet():
    """Acceptance e2e: a student checkpoint rolls to a live tiered gateway
    fleet through canary split -> compare() -> GATED promote, with zero
    in-flight request loss, exact per-client v(s1)->v(s2) version streams
    on the student tier, and the teacher tier serving untouched throughout
    — both tiers simultaneously behind one address via ``player``."""
    from distar_tpu.serve import STUDENT_TIER, TEACHER_TIER, ServeClient
    from distar_tpu.serve.fleet import FleetClient, FleetRollout, GatewayMap

    fleet = _TierFleet(3)
    student_fc = FleetClient(gateway_map=GatewayMap(fleet.addrs),
                             timeout_s=5.0, player=STUDENT_TIER)
    teacher_fc = FleetClient(gateway_map=GatewayMap(fleet.addrs),
                             timeout_s=5.0, player=TEACHER_TIER)
    ctl = FleetRollout(GatewayMap(fleet.addrs), timeout_s=5.0)
    try:
        canary_addr = fleet.addrs[0]
        verdict = ctl.canary_start(
            "s2", [canary_addr], 40.0,
            params={"version": "s2", "bias": 1.0},
            router=student_fc.router, player=STUDENT_TIER)
        assert verdict["ok"]
        baseline = ctl.compare([canary_addr])

        streams = {f"tier-{i}": [] for i in range(40)}
        teacher_streams = {f"tier-{i}": [] for i in range(40)}
        def traffic(rounds):
            for _ in range(rounds):
                res = student_fc.act_many(
                    [{"session_id": s, "obs": _obs()} for s in streams])
                tres = teacher_fc.act_many(
                    [{"session_id": s, "obs": _obs()} for s in streams])
                for s, r, tr in zip(streams, res, tres):
                    # zero in-flight loss: every answer is a result dict
                    assert isinstance(r, dict), r
                    assert isinstance(tr, dict), tr
                    streams[s].append(r["version"])
                    teacher_streams[s].append(tr["version"])
        traffic(3)
        on_canary = {s for s in streams
                     if student_fc.router.gateway_for(s) == canary_addr}
        assert on_canary  # the deterministic 40% split put someone there
        for s, versions in streams.items():
            assert set(versions) == ({"s2"} if s in on_canary else {"s1"})

        # compare: fps-per-slot measurable against the baseline snapshot,
        # divergence-vs-teacher folded into the verdict
        cmp_bad = ctl.compare([canary_addr], baseline=baseline,
                              divergence=9.9, max_divergence=1.0,
                              min_fps_ratio=0.25)
        assert cmp_bad["canary"]["fps_per_slot"] > 0
        assert cmp_bad["stable"]["fps_per_slot"] > 0
        assert cmp_bad["divergence"] == 9.9
        assert cmp_bad["verdict"]["promote"] is False
        # a failing verdict GATES promote: nothing rolls, the canary split
        # keeps serving (outcome is the typed compare_gated refusal)
        gated = ctl.promote("s2", params={"version": "s2", "bias": 1.0},
                            router=student_fc.router, player=STUDENT_TIER,
                            verdict=cmp_bad)
        assert gated == {"ok": False, "outcome": "compare_gated",
                         "reasons": gated["reasons"]}
        assert any("divergence" in r for r in gated["reasons"])
        host, _, port = fleet.addrs[1].rpartition(":")
        probe = ServeClient(host, int(port), player=STUDENT_TIER)
        assert probe.act("probe-gated", _obs())["version"] == "s1"
        probe.close()

        # healthy verdict -> promote graduates the student fleet-wide
        cmp_ok = ctl.compare([canary_addr], baseline=baseline,
                             divergence=0.2, max_divergence=1.0,
                             min_fps_ratio=0.25)
        assert cmp_ok["verdict"]["promote"] is True, cmp_ok["verdict"]
        assert ctl.promote("s2", params={"version": "s2", "bias": 1.0},
                           router=student_fc.router, player=STUDENT_TIER,
                           verdict=cmp_ok)["ok"]
        assert student_fc.router.canary_config() == ([], 0.0)
        traffic(2)

        for s, versions in streams.items():
            # monotone per-client stream: s1* then s2*, never interleaved —
            # the PR 2 flush-boundary contract held fleet-wide for the
            # student tier
            first_s2 = versions.index("s2") if "s2" in versions else len(versions)
            assert all(v == "s1" for v in versions[:first_s2])
            assert all(v == "s2" for v in versions[first_s2:])
        # the teacher tier never moved: one address served BOTH tiers the
        # whole time, and the student rollout touched only its player
        for versions in teacher_streams.values():
            assert set(versions) == {"t1"}
    finally:
        student_fc.close()
        teacher_fc.close()
        ctl.close()
        fleet.close()


def test_student_swap_nack_rolls_back_to_student_version_not_teachers():
    """Regression: on a tiered (muxed) gateway the rollback target of a
    student rollout must be the STUDENT player's served version, not the
    default (teacher) player's — the top-level registry block belongs to
    the teacher."""
    from distar_tpu.serve import STUDENT_TIER, ServeClient, ServeError
    from distar_tpu.serve.fleet import FleetRollout, GatewayMap

    fleet = _TierFleet(2)

    class _SwapNack:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def swap(self, version, player=None):
            if version == "s2":
                raise ServeError("injected swap NACK")
            return self._inner.swap(version, player=player)

    def factory(addr):
        host, _, port = addr.rpartition(":")
        client = ServeClient(host, int(port), timeout_s=5.0)
        return _SwapNack(client) if addr == fleet.addrs[1] else client

    ctl = FleetRollout(GatewayMap(fleet.addrs), timeout_s=5.0,
                       client_factory=factory)
    try:
        verdict = ctl.rollout("s2", params={"version": "s2", "bias": 1.0},
                              player=STUDENT_TIER)
        assert not verdict["ok"] and verdict["outcome"] == "rolled_back"
        # the swapped prefix (gateway 0) rolled back to the student's s1 —
        # if the teacher's registry had been read, the target would have
        # been t1 (not loaded under the student player -> rollback_failed)
        st = ctl.fleet_status([fleet.addrs[0]])[fleet.addrs[0]]
        assert st["players"][STUDENT_TIER]["registry"]["current"] == "s1"
        assert st["players"]["teacher"]["registry"]["current"] == "t1"
    finally:
        ctl.close()
        fleet.close()


def test_tier_player_maps_traffic_classes():
    from distar_tpu.serve import STUDENT_TIER, TEACHER_TIER, tier_player

    assert tier_player("eval") == TEACHER_TIER
    assert tier_player("ladder") == TEACHER_TIER
    assert tier_player("rollout") == STUDENT_TIER
    assert tier_player("anything-else") == STUDENT_TIER
    assert tier_player("anything-else", default=TEACHER_TIER) == TEACHER_TIER


# --------------------------------------------------------- artifact + digest
def test_distill_artifact_is_current_and_honest():
    """The committed DISTILL_r15.json parses, carries the in-band honesty
    flags, meets the <=0.5 step-cost bar from real (non-smoke) configs, and
    its toy-run KL curve decreases monotonically."""
    path = os.path.join(_REPO, "DISTILL_r15.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["cpu_derived"] is True and doc["flops_derived"] is True
    assert isinstance(doc["host_cores"], int)
    assert doc["scaling_valid"] is False  # 1-core CI box: honest refusal
    assert doc["smoke_model"] is False
    assert doc["value"] <= 0.5 and doc["meets_target"] is True
    d = doc["distill"]
    assert d["student_flops_per_step"] < d["teacher_flops_per_step"]
    curve = d["toy_run"]["kl_curve"]
    assert d["toy_run"]["monotone_decrease"] is True
    assert all(b < a for a, b in zip(curve, curve[1:]))


def test_perf_gate_trajectory_ingests_distill_artifact():
    import sys

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from perf_gate import collect_trajectory
    finally:
        sys.path.pop(0)
    rows = collect_trajectory()
    arts = {r["artifact"] for r in rows}
    assert "DISTILL_r15.json" in arts
    kl_rows = [r for r in rows if "distill toy-run KL" in r["metric"]]
    assert kl_rows and "monotone=True" in kl_rows[0]["metric"]


def test_opsctl_distill_digest_renders(capsys, monkeypatch):
    import sys

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import opsctl
    finally:
        sys.path.pop(0)

    series = {
        "distar_distill_kl": 0.42,
        "distar_distill_student_generation": 128,
        "distar_distill_teacher_generation": 160,
        "distar_distill_head_kl{head=action_type}": 0.11,
        "distar_distill_step_cost_ratio": 0.31,
    }

    def fake_get(addr, path, timeout=5.0):
        import urllib.parse as up

        name = up.parse_qs(up.urlparse(path).query).get("name", [""])[0]
        if name in series:
            return {"stats": {"distill:MP0:0": {"last": series[name],
                                                "last_ts": 100.0}}}
        return None

    def fake_post(addr, path, body, timeout=5.0):
        if body.get("token") == "serve_canary":
            return {"info": [{"ts": 5.0, "meta": {
                "addrs": ["10.0.0.1:1"], "pct": 25.0, "version": "s2"}}]}
        return None

    monkeypatch.setattr(opsctl, "_try_get", fake_get)
    monkeypatch.setattr(opsctl, "_try_post", fake_post)
    opsctl._print_distill_digest("127.0.0.1:1")
    out = capsys.readouterr().out
    assert "distillation:" in out
    assert "student_gen=128 teacher_gen=160 (lag 32)" in out
    assert "divergence=0.42" in out
    assert "action_type=0.11" in out
    assert "step-cost ratio: 0.31x teacher" in out
    assert "canary split: 25.0% -> 10.0.0.1:1 (version s2)" in out

"""Learner integration tests on the 8-device CPU mesh with a shrunken model.

This is the multi-host collective analogue of the reference's FakeLink tests:
the pjit train step runs dp=8 over virtual devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distar_tpu.parallel import GradClipConfig, MeshSpec, build_grad_clip, build_optimizer, make_mesh


from conftest import SMALL_MODEL  # shared tiny model config


def test_mesh_axes():
    mesh = make_mesh(MeshSpec(dp=-1))
    assert mesh.shape["dp"] == 8 and mesh.shape["tp"] == 1
    mesh2 = make_mesh(MeshSpec(dp=4, sp=2))
    assert mesh2.shape["dp"] == 4 and mesh2.shape["sp"] == 2


def test_shrink_dp_respects_fsdp():
    """shrink_dp must leave a mesh whose dp x fsdp divides the batch — the
    batch shards over BOTH axes when fsdp > 1 (mesh.dp_axes)."""
    from distar_tpu.parallel.mesh import shrink_dp

    mesh = make_mesh(MeshSpec(dp=2, fsdp=2), jax.devices()[:4])
    assert shrink_dp(mesh, 8) is mesh  # 8 % (2*2) == 0: no-op
    m6 = shrink_dp(mesh, 6)  # 6 % 4 != 0 -> must shrink
    assert 6 % (m6.shape["dp"] * m6.shape["fsdp"]) == 0
    m3 = shrink_dp(mesh, 3)
    assert 3 % (m3.shape["dp"] * m3.shape["fsdp"]) == 0


def test_grad_clip_modes():
    params = {"w": jnp.ones((3,)), "b": jnp.ones((2,))}
    grads = {"w": jnp.full((3,), 10.0), "b": jnp.full((2,), 10.0)}
    for kind in ("none", "value", "norm", "max_norm", "momentum_norm"):
        tx = build_grad_clip(GradClipConfig(type=kind, threshold=1.0))
        state = tx.init(params)
        out, _ = tx.update(grads, state, params)
        n = float(jax.tree.leaves(jax.tree.map(lambda g: jnp.abs(g).max(), out))[0])
        if kind != "none":
            assert n <= 10.0


def test_grad_clip_norm_is_exact_and_reports_activation():
    """The norm clip the flagship config ships: post-clip global norm is
    exactly min(||g||, threshold), and clip_activation (the dynamics
    tree's clip gauges) reports the removed fraction to match."""
    from distar_tpu.parallel.grad_clip import clip_activation

    params = {"w": jnp.zeros((3,)), "b": jnp.zeros((2,))}
    grads = {"w": jnp.asarray([3.0, 0.0, 0.0]), "b": jnp.asarray([0.0, 4.0])}
    gnorm = 5.0
    for threshold, expect in ((2.0, 2.0), (7.0, gnorm)):
        tx = build_grad_clip(GradClipConfig(type="norm", threshold=threshold))
        out, _ = tx.update(grads, tx.init(params), params)
        clipped_norm = float(jnp.sqrt(sum(
            jnp.sum(g * g) for g in jax.tree.leaves(out))))
        assert clipped_norm == pytest.approx(expect, rel=1e-6)
        # direction preserved: clip rescales, never rotates
        assert float(out["w"][0]) / float(out["b"][1]) == pytest.approx(3.0 / 4.0)
        frac, active = clip_activation(grads, jnp.asarray(gnorm), "norm", threshold)
        assert float(frac) == pytest.approx(max(0.0, 1.0 - threshold / gnorm))
        assert float(active) == (1.0 if gnorm > threshold else 0.0)
    # value mode: per-element census
    frac, active = clip_activation(grads, jnp.asarray(gnorm), "value", 3.5)
    assert float(frac) == pytest.approx(1.0 / 5.0)  # only b[1]=4 exceeds
    assert float(active) == 1.0
    frac, active = clip_activation(grads, jnp.asarray(gnorm), "none", 1.0)
    assert float(frac) == 0.0 and float(active) == 0.0


def test_optimizer_adam_zero_beta1():
    opt = build_optimizer(learning_rate=1e-3, betas=(0.0, 0.99), eps=1e-5,
                          clip=GradClipConfig(type="norm", threshold=1.0))
    params = {"w": jnp.zeros((4,))}
    state = opt.init(params)
    g = {"w": jnp.ones((4,))}
    updates, state = opt.update(g, state, params)
    assert jnp.all(jnp.isfinite(updates["w"]))


@pytest.fixture(scope="module")
def rl_learner(tmp_path_factory):
    from distar_tpu.learner import RLLearner

    tmp = tmp_path_factory.mktemp("rl")
    cfg = {
        "common": {"experiment_name": "t", "save_path": str(tmp)},
        "learner": {
            "batch_size": 8,
            "unroll_len": 2,
            "save_freq": 100000,
            "log_freq": 1,
        },
        "model": SMALL_MODEL,
    }
    return RLLearner(cfg)


@pytest.mark.slow
def test_rl_learner_steps_and_checkpoint(rl_learner, tmp_path):
    learner = rl_learner
    learner.run(max_iterations=2)
    assert learner.last_iter.val == 2
    assert np.isfinite(learner.variable_record.get("total_loss").avg)
    assert learner.variable_record.get("grad_norm").avg > 0
    # checkpoint roundtrip on the same (already-compiled) learner
    p = str(tmp_path / "ck.ckpt")
    learner.save(p)
    w0 = np.asarray(jax.tree.leaves(learner.state["params"])[0]).copy()
    learner.run(max_iterations=4)
    w1 = jax.tree.leaves(learner.state["params"])[0]
    assert not np.allclose(w0, np.asarray(w1))
    learner.restore(p)
    w2 = jax.tree.leaves(learner.state["params"])[0]
    np.testing.assert_allclose(w0, np.asarray(w2))
    assert learner.last_iter.val == 2


@pytest.mark.slow
def test_rl_learner_fsdp_mesh(tmp_path):
    """A mesh with a REAL second axis: params + Adam moments sharded over
    fsdp (ZeRO-style), batch sharded over dp x fsdp. Verifies the train step
    compiles and executes with non-replicated parameter shardings and that
    a checkpoint still round-trips (device_get gathers the shards)."""
    from distar_tpu.learner import RLLearner

    mesh = make_mesh(MeshSpec(dp=2, fsdp=2), jax.devices()[:4])
    cfg = {
        "common": {"experiment_name": "fsdp", "save_path": str(tmp_path)},
        "learner": {"batch_size": 8, "unroll_len": 2, "save_freq": 100000, "log_freq": 1},
        "model": SMALL_MODEL,
    }
    learner = RLLearner(cfg, mesh=mesh)
    # at least one large param leaf must actually shard over fsdp
    specs = [
        x.sharding.spec
        for x in jax.tree.leaves(learner.state["params"])
        if hasattr(x, "sharding")
    ]
    assert any("fsdp" in str(s) for s in specs), specs
    # and the Adam moments follow (1/fsdp-sized opt state per device)
    mom_specs = [x.sharding.spec for x in jax.tree.leaves(learner.state["opt_state"])]
    assert any("fsdp" in str(s) for s in mom_specs), mom_specs
    learner.run(max_iterations=2)
    assert learner.last_iter.val == 2
    assert np.isfinite(learner.variable_record.get("total_loss").avg)
    p = str(tmp_path / "fsdp.ckpt")
    learner.save(p)
    w0 = np.asarray(jax.tree.leaves(learner.state["params"])[0]).copy()
    learner.restore(p)
    np.testing.assert_allclose(w0, np.asarray(jax.tree.leaves(learner.state["params"])[0]))


@pytest.mark.slow
def test_sl_learner_steps(tmp_path):
    from distar_tpu.learner import SLLearner

    cfg = {
        "common": {"experiment_name": "t", "save_path": str(tmp_path)},
        "learner": {"batch_size": 8, "unroll_len": 2, "save_freq": 100000, "log_freq": 1},
        "model": SMALL_MODEL,
    }
    learner = SLLearner(cfg)
    learner.run(max_iterations=2)
    assert learner.last_iter.val == 2
    assert np.isfinite(learner.variable_record.get("total_loss").avg)
    assert np.isfinite(learner.variable_record.get("action_type_acc").avg)


def test_sl_learner_save_grad_logs_leaf_norms(tmp_path):
    """learner.save_grad folds per-parameter grad/param L2 norms into the
    log (role of the reference's save_grad TB dumps,
    rl_learner.py:35-47,118-130)."""
    from distar_tpu.learner import SLLearner

    cfg = {
        "common": {"experiment_name": "sg", "save_path": str(tmp_path)},
        "learner": {"batch_size": 4, "unroll_len": 2, "save_freq": 100000,
                    "log_freq": 1, "save_grad": True},
        "model": SMALL_MODEL,
    }
    learner = SLLearner(cfg)
    learner.run(max_iterations=1)
    names = set(learner.variable_record.vars())
    per_param_grad = [n for n in names if n.startswith("grad_norm/")]
    per_param_w = [n for n in names if n.startswith("param_norm/")]
    assert len(per_param_grad) > 10 and len(per_param_grad) == len(per_param_w)
    for n in per_param_grad[:5] + per_param_w[:5]:
        assert np.isfinite(learner.variable_record.get(n).avg)


@pytest.mark.slow
def test_rl_learner_save_grad_logs_leaf_norms(tmp_path):
    """RL wiring of learner.save_grad (both the init jit and the admin
    config-patch rebuild thread the same kwarg into make_rl_train_step)."""
    from distar_tpu.learner import RLLearner

    cfg = {
        "common": {"experiment_name": "sg_rl", "save_path": str(tmp_path)},
        "learner": {"batch_size": 2, "unroll_len": 2, "save_freq": 100000,
                    "log_freq": 1, "save_grad": True},
        "model": SMALL_MODEL,
    }
    learner = RLLearner(cfg)
    learner.run(max_iterations=1)
    names = set(learner.variable_record.vars())
    grads = [n for n in names if n.startswith("grad_norm/")]
    assert len(grads) > 10
    assert len(grads) == len([n for n in names if n.startswith("param_norm/")])


def test_sl_loss_spike_guard_snapshots(tmp_path):
    """debug_loss_spike: a loss term jumping past factor x its EMA (or going
    non-finite) after warmup dumps the step's exact inputs + a checkpoint
    (reference SL debug mode, sl_learner.py:55-60)."""
    import glob
    import os

    from distar_tpu.comm.serializer import loads
    from distar_tpu.learner import SLLearner

    cfg = {
        "common": {"experiment_name": "spike", "save_path": str(tmp_path)},
        "learner": {"batch_size": 4, "unroll_len": 2, "save_freq": 100000,
                    "log_freq": 10, "debug_loss_spike": True,
                    "debug_spike_factor": 10.0, "debug_spike_warmup": 0},
        "model": SMALL_MODEL,
    }
    learner = SLLearner(cfg)
    learner.run(max_iterations=1)  # primes the EMA from real values

    def spike_files():
        return glob.glob(os.path.join(str(tmp_path), "debug", "*.spike"))

    pre_step = {"batch": {"x": np.zeros(2)}, "hidden_state": learner._hidden,
                "new_episodes": np.zeros(4, bool), "traj_lens": None}

    # drive the guard directly with a synthetic 20x spike
    base = dict(learner._debug_ema)
    spiked_key = next(k for k in base if "loss" in k and base[k] > 0.01)
    log = dict(base)
    log[spiked_key] = base[spiked_key] * 20 + 1.0
    learner.last_iter.update(5)
    learner._loss_spike_guard(log, pre_step)

    dumps = spike_files()
    assert len(dumps) == 1
    snap = loads(open(dumps[0], "rb").read())
    assert snap["key"] == spiked_key
    # the step's exact inputs travel with the snapshot
    assert "batch" in snap and "hidden_state" in snap and "new_episodes" in snap
    assert "note" in snap  # params-offset caveat recorded
    assert os.path.exists(learner.checkpoint_path())
    # the dump folded the spike into the EMA (0.95/0.05)
    assert learner._debug_ema[spiked_key] == pytest.approx(
        base[spiked_key] * 0.95 + log[spiked_key] * 0.05
    )

    # near-zero EMA (masked heads) must NOT trigger on normal growth
    learner._debug_ema[spiked_key] = 1e-6
    learner._loss_spike_guard({spiked_key: 0.5}, pre_step)
    assert len(spike_files()) == 1

    # a finite -> non-finite transition MUST trigger and not poison the EMA
    learner._debug_ema[spiked_key] = 2.0
    learner._loss_spike_guard({spiked_key: float("nan")}, pre_step)
    assert len(spike_files()) == 2
    assert learner._debug_ema[spiked_key] == 2.0

    # non-finite from the FIRST iteration (no EMA ever seeded) also dumps —
    # a run that diverges immediately is the headline event
    learner._debug_ema.pop("fresh_loss", None)
    learner._loss_spike_guard({"fresh_loss": float("inf")}, pre_step)
    assert len(spike_files()) == 3

    # the dump cap bounds disk usage
    learner._debug_dumps = learner._DEBUG_DUMP_CAP
    learner._loss_spike_guard({spiked_key: 1e9}, pre_step)
    assert len(spike_files()) == 3

def test_rl_learner_with_value_feature(tmp_path):
    """Centralized-critic path: use_value_feature routes opponent features
    through the ValueEncoder into every baseline tower."""
    from distar_tpu.learner import RLLearner

    model = dict(SMALL_MODEL)
    model = {**model, "use_value_feature": True}
    cfg = {
        "common": {"experiment_name": "vf", "save_path": str(tmp_path)},
        "learner": {"batch_size": 8, "unroll_len": 2, "save_freq": 100000, "log_freq": 1},
        "model": model,
    }
    learner = RLLearner(cfg)
    learner.run(max_iterations=1)
    assert learner.last_iter.val == 1
    assert np.isfinite(learner.variable_record.get("total_loss").avg)


@pytest.mark.slow
def test_learner_admin_api(rl_learner):
    """Live admin surface: status, value reset, config patch between iters."""
    import urllib.request, json as _json

    learner = rl_learner
    learner.run(max_iterations=max(learner.last_iter.val + 1, 1))
    admin = learner.start_admin()

    def post(route, body=None):
        req = urllib.request.Request(
            f"http://{admin.host}:{admin.port}/learner/{route}",
            data=_json.dumps(body or {}).encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        return _json.loads(urllib.request.urlopen(req, timeout=10).read())

    try:
        status = post("status")
        assert status["code"] == 0 and status["info"]["last_iter"] >= 1
        # queue a value reset + lr patch; both apply on the next iteration
        w_before = np.asarray(
            jax.tree.leaves(learner.state["params"]["params"]["value_winloss"])[0]
        ).copy()
        assert post("reset_value")["code"] == 0
        assert post("update_config", {"config": {"learner": {"learning_rate": 5e-6}}})["code"] == 0
        learner.run(max_iterations=learner.last_iter.val + 1)
        w_after = np.asarray(
            jax.tree.leaves(learner.state["params"]["params"]["value_winloss"])[0]
        )
        assert not np.allclose(w_before, w_after)
        assert float(learner.cfg.learner.learning_rate) == 5e-6
        assert post("bogus")["code"] == 404
    finally:
        admin.stop()


def test_admin_profile_route_e2e(tmp_path):
    """Tier-1 perf-attribution acceptance: POST /profile?steps=2 on a LIVE
    learner captures a real jax.profiler trace at iteration boundaries and
    returns a ranked bucket report whose shares sum to 100%+-1 of measured
    device time (obs/traceview.py through learner/admin.py)."""
    import json as _json
    import threading
    import urllib.request

    from distar_tpu.learner import SLLearner

    cfg = {
        "common": {"experiment_name": "prof", "save_path": str(tmp_path)},
        # same step signature as test_sl_learner_save_grad_logs_leaf_norms,
        # so the persistent compile cache serves the executable
        "learner": {"batch_size": 4, "unroll_len": 2, "save_freq": 100000,
                    "log_freq": 100000, "save_grad": True},
        "model": SMALL_MODEL,
    }
    learner = SLLearner(cfg)
    learner.run(max_iterations=1)  # compile OUTSIDE the capture window
    admin = learner.start_admin()
    runner_err = []

    def runner():
        try:
            # generous ceiling; request_stop ends the loop once profiled
            learner.run(max_iterations=learner.last_iter.val + 10_000)
        except Exception as e:  # pragma: no cover - surfaced via assert
            runner_err.append(e)

    thread = threading.Thread(target=runner, daemon=True)
    thread.start()
    try:
        req = urllib.request.Request(
            f"http://{admin.host}:{admin.port}/learner/profile"
            f"?steps=2&timeout_s=240",
            data=b"{}", method="POST",
            headers={"Content-Type": "application/json"},
        )
        body = _json.loads(urllib.request.urlopen(req, timeout=300).read())
    finally:
        learner.request_stop()
        thread.join(timeout=300)
        admin.stop()
    assert not runner_err, runner_err
    assert not thread.is_alive()
    assert body["code"] == 0, body
    report = body["info"]
    assert report["captured_steps"] == 2
    assert report["total_device_us"] > 0
    buckets = report["buckets"]
    assert buckets, report
    # shares partition measured device time: sum to 100% +- 1
    assert abs(sum(b["share"] for b in buckets) - 1.0) < 0.01
    # ranked most-expensive first
    times = [b["time_us"] for b in buckets]
    assert times == sorted(times, reverse=True)
    # a real train step must show MXU work and a rendered table
    assert any(b["bucket"] == "matmul/MXU" for b in buckets)
    assert "| bucket |" in report["markdown"]
    # the capture wrote a real trace under the experiment dir
    assert str(tmp_path) in report["trace_path"]
    # profile requests after the loop stopped fail typed, not hang
    with pytest.raises(Exception):
        learner.request_profile(steps=1, timeout_s=0.5)


def test_rl_cap_entities_exact_below_cap(tmp_path):
    """cap_entities_rl (learner.max_entities on the RL learner) is
    numerically exact within the cap: same batch trained at the 512 pad and
    sliced to 256 yields the same loss grid. (A real teacher's logits carry
    ~zero mass beyond its masked candidates; the fake teacher's off-label
    tails are e^-40 relative — negligible.)"""
    from distar_tpu.learner import RLLearner
    from distar_tpu.learner.data import fake_rl_batch

    rng = np.random.default_rng(11)
    batch = fake_rl_batch(4, 2, rng=rng, hidden_size=32, hidden_layers=1)
    batch["entity_num"] = np.minimum(batch["entity_num"], 250)
    batch["model_last_iter"] = np.zeros(4)
    # re-pin end tokens to the clamped entity_num (fake labels put the end
    # flag at the ORIGINAL entity_num)
    su = batch["action_info"]["selected_units"]
    sun = batch["selected_units_num"]
    for t in range(su.shape[0]):
        for b in range(su.shape[1]):
            su[t, b, sun[t, b] - 1] = batch["entity_num"][t, b]
    onehot = np.eye(513, dtype=np.float32)[su]
    batch["teacher_logit"]["selected_units"] = (40.0 * onehot - 20.0).astype(np.float32)

    logs = {}
    for name, cap in (("full", None), ("capped", 256)):
        cfg = {
            "common": {"experiment_name": f"rlcap_{name}", "save_path": str(tmp_path)},
            "learner": {"batch_size": 4, "unroll_len": 2, "save_freq": 100000,
                        "log_freq": 10 ** 9, "max_entities": cap},
            "model": SMALL_MODEL,
        }
        learner = RLLearner(cfg)
        logs[name] = learner._train(dict(batch))
    for k in logs["full"]:
        if k.startswith("staleness"):
            continue
        np.testing.assert_allclose(
            logs["full"][k], logs["capped"][k], rtol=2e-4, atol=2e-4,
            err_msg=f"RL loss term {k} diverged under the entity cap",
        )


def test_rl_cap_entities_overflow_semantics():
    """Above-cap RL steps: every out-of-range selected_units lane clamps
    into range (post-end junk lanes would gather OOB in the sliced decode)
    and the su/tu masks zero for overflow steps (a truncated teacher
    distribution would bias the KL)."""
    from distar_tpu.learner.data import cap_entities_rl, fake_rl_batch

    batch = fake_rl_batch(2, 1, rng=np.random.default_rng(5))
    batch["entity_num"][:] = 100
    batch["entity_num"][0, 0] = 300  # step 0, sample 0 overflows cap 256
    su = batch["action_info"]["selected_units"]
    su[0, 0, :] = 280  # junk + labels beyond the cap
    out = cap_entities_rl(batch, 256)
    assert out["entity_num"].max() == 256
    assert out["action_info"]["selected_units"].max() <= 256  # all in range
    am = out["mask"]["actions_mask"]
    assert am["selected_units"][0, 0] == 0.0 and am["target_unit"][0, 0] == 0.0
    assert am["selected_units"][0, 1] == 1.0  # non-overflow sample untouched
    assert out["teacher_logit"]["selected_units"].shape[-1] == 257
    assert out["teacher_logit"]["target_unit"].shape[-1] == 256


@pytest.mark.slow
def test_rl_learner_resume_latest_with_corrupt_fallback(rl_learner, chaos):
    """The real learner's crash-resume path: save() publishes the durable
    latest pointer, resume_latest() restores from it, and a truncated
    newest checkpoint falls back to the previous generation."""
    learner = rl_learner
    learner.run(max_iterations=max(learner.last_iter.val, 2))
    p1 = learner.checkpoint_path()
    learner.save(p1, sync=True)
    iter1 = learner.last_iter.val
    w1 = np.asarray(jax.tree.leaves(learner.state["params"])[0]).copy()
    learner.run(max_iterations=iter1 + 2)
    p2 = learner.checkpoint_path()
    learner.save(p2, sync=True)
    assert learner.checkpoint_manager.resolve_latest()["path"] == p2
    chaos.truncate(p2)  # torn newest checkpoint
    assert learner.resume_latest() == p1  # fell back a generation
    assert learner.last_iter.val == iter1
    np.testing.assert_allclose(
        w1, np.asarray(jax.tree.leaves(learner.state["params"])[0])
    )


@pytest.mark.parametrize("kind", ["sl", "rl"])
def test_step_lowered_from_its_arguments_types_is_the_calls_program(kind, tmp_path):
    """``jit.lower`` of the ``ShapeDtypeStruct``s of a call's arguments (how
    the perf monitor and the benchmark ask for the step's cost and memory
    analysis) gives the call's own program, so the compile cache serves it.
    An argument the call leaves uncommitted breaks that: its struct carries
    the device it happened to sit on, and the step compiles twice."""
    from distar_tpu.learner import RLLearner, SLLearner

    learner = {"sl": SLLearner, "rl": RLLearner}[kind]({
        "common": {"experiment_name": "lower", "save_path": str(tmp_path)},
        "learner": {"batch_size": 2, "unroll_len": 2, "save_freq": 100000, "log_freq": 1},
        "model": SMALL_MODEL,
    })
    jitted, texts = learner._train_step, []

    def tap(*args):
        if not texts:  # before the call: it donates the state
            specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None))
                if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)
            texts.extend(jitted.lower(*a).as_text() for a in (args, specs))
        return jitted(*args)

    learner._train_step = tap
    learner.run(max_iterations=1)
    assert len(texts) == 2 and texts[0] == texts[1]

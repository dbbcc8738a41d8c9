"""The RL step's loss vector without the step: ``rl_forward`` over the
trajectory batch and ``compute_rl_loss`` (V-trace, UPGO, teacher KL,
entropy, the value towers), wired as ``make_rl_train_step``'s loss is, with
no gradient and no update. The program has no forward-only pass for RL, so
the wiring is written out here."""
import dataclasses
from typing import Dict


def first_step(learner, batch: Dict) -> Dict[str, float]:
    import jax

    from distar_tpu.losses import compute_rl_loss

    B, T = learner.cfg.learner.batch_size, learner.cfg.learner.unroll_len
    data = learner._place_batch(batch)
    for host_field in ("model_last_iter", "_on_device"):
        data.pop(host_field)
    loss_cfg = dataclasses.replace(learner.loss_cfg, only_update_value=False)
    flat = lambda tree: jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), tree)

    def loss(params, batch):
        out = learner.model.apply(
            params, flat(batch["spatial_info"]), flat(batch["entity_info"]),
            flat(batch["scalar_info"]), batch["entity_num"].reshape(-1),
            batch["hidden_state"], batch["action_info"], batch["selected_units_num"], B, T,
            value_feature=None, method=learner.model.rl_forward)
        _, info = compute_rl_loss({
            "target_logit": out["target_logit"], "value": out["value"],
            "action_log_prob": batch["behaviour_logp"], "teacher_logit": batch["teacher_logit"],
            "action": batch["action_info"], "reward": batch["reward"], "step": batch["step"],
            "done": batch.get("done"), "mask": batch["mask"],
            "entity_num": batch["entity_num"].reshape(-1, B)[:T],
            "selected_units_num": batch["selected_units_num"],
        }, loss_cfg)
        return info

    info = jax.device_get(jax.jit(loss)(learner.state["params"], data))
    return {k: float(v) for k, v in info.items()}

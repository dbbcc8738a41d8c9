"""``correct`` of the ``nemotron_h`` cell turns false when the run leaves one
term of the equations out. The fault is put into the program in this process
only: the reference is a process of its own and computes the whole model. The
run still trains (its loss falls, its parameters change); what fails is the
first step against the reference, at the cell's own limits."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402

CELL = "nemotron_twotower_train_b2s8k"


def _no_skip(monkeypatch):
    """``y_t = h_t C_t`` without ``D x_t`` (``D`` is drawn as ones)."""
    import jax.numpy as jnp

    from distar_tpu.ops import ssm

    whole = ssm.chunked_scan

    def scan(x, dt, A, B, C, chunk, dtype=jnp.float32):
        y, last = whole(x, dt, A, B, C, chunk, dtype)
        return y - x.astype(jnp.float32), last

    monkeypatch.setattr(ssm, "chunked_scan", scan)


def _no_dt_bias(monkeypatch):
    """``dt = softplus(dt)``: the bias is drawn as zeros."""
    import jax.numpy as jnp

    from distar_tpu.ops import ssm

    monkeypatch.setattr(ssm, "_dt_bias_init", lambda lo, hi, floor: lambda key, shape: jnp.zeros(shape))


def _no_carry(monkeypatch):
    """Every chunk starts from a zero state: the chunks become sequences of their own."""
    from distar_tpu.ops import ssm

    whole = ssm._group_scan

    def scan(x, dt, A, B, C, dtype):
        b, c = x.shape[:2]
        apart = lambda t: t.reshape(b * c, 1, *t.shape[2:])
        y, last = whole(apart(x), apart(dt), A, apart(B), apart(C), dtype)
        return y.reshape(b, c, *y.shape[2:]), last.reshape(b, c, *last.shape[1:])[:, -1]

    monkeypatch.setattr(ssm, "_group_scan", scan)


def _no_shared_expert(monkeypatch):
    import jax.numpy as jnp

    from distar_tpu.ops import moe

    monkeypatch.setattr(moe, "shared_expert", lambda body, u, ws: jnp.zeros_like(u))


def _relu_not_squared(monkeypatch):
    from flax import linen as nn

    from distar_tpu.ops import moe

    relu = lambda product, x, w1, w2: product(nn.relu(product(x, w1)), w2)
    monkeypatch.setitem(moe.EXPERT_BODIES, "relu2", (relu, ("w1", "w2")))


def _no_scale(monkeypatch):
    from distar_tpu.ops import moe

    whole = moe.route
    monkeypatch.setattr(moe, "route", lambda logits, bias, k, scaling=1.0: whole(logits, bias, k, 1.0))


def _no_selection_bias(monkeypatch):
    from distar_tpu.ops import moe

    whole = moe.route
    monkeypatch.setattr(moe, "route", lambda logits, bias, k, scaling=1.0: whole(logits, 0.0, k, scaling))


FAULTS = {"no_skip": (_no_skip, "mixer_rms/layer_0"), "no_dt_bias": (_no_dt_bias, "mixer_rms/layer_0"),
          "no_carry": (_no_carry, "mixer_rms/layer_0"), "no_shared_expert": (_no_shared_expert, "mixer_rms/layer_1"),
          "relu_not_squared": (_relu_not_squared, "mixer_rms/layer_1"), "no_scale": (_no_scale, "mixer_rms/layer_1"),
          "no_selection_bias": (_no_selection_bias, "moe_rows")}


@pytest.mark.parametrize("fault", FAULTS)
def test_correct_turns_false_when_the_run_leaves_a_term_out(capsys, tmp_path, monkeypatch, fault):
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    inject, seen_by = FAULTS[fault]
    inject(monkeypatch)
    assert run.main(["--workload", CELL, "--seed", "3000000037", "--seconds", "2.5", "--trace", "0",
                     "--rehearse"]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.strip().split("\n") if ln.startswith("{")]
    line, notes = json.loads(lines[-1]), json.loads(lines[-2])
    assert line["correct"] is False and not notes["checks"]["first_step_matches_reference"]
    assert notes["checks"]["loss_went_down"] and notes["checks"]["params_changed"]
    assert notes["checks"]["ran_to_its_end"] and line["failed"] == 0
    # by the component that sees this fault
    off = next(ln for ln in out.split("\n") if ln.startswith("benchmark: first step against the reference"))
    assert seen_by in off, off

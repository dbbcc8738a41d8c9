"""The FLOP count against XLA's own, and the peaks table."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, device, flops  # noqa: E402


def _tiny_model(config_name):
    from distar_tpu.model import default_model_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", config_name)
    return cfg, deep_merge_dicts(default_model_config(), cfg["tiny"]["model"])


@pytest.mark.parametrize("config_name", ["distar_sl_flagship", "distar_rl_flagship"])
def test_count_against_cost_analysis_of_a_scan_free_forward(config_name):
    """At unroll 1 every scan makes one trip, so XLA's ``cost_analysis()``
    (which counts a loop's body once) counts the whole forward pass. The
    benchmark's count has matrix multiplications and convolutions only; XLA
    adds the elementwise work, softmaxes, layer norms and reductions, which at
    the tiny preset's widths (16-32) are 15-20% of the total and at the
    flagship's (256-1024) a few percent. So: never above XLA's count, and not
    under 75% of it. A count that lost a layer or double-counted one leaves
    that band."""
    import jax

    cfg, model_cfg = _tiny_model(config_name)
    fn, args = flops.FORWARDS[cfg["flops"]](model_cfg, 2, 1)
    mine = flops.forward_flops(fn, *args)
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert 0.75 * cost["flops"] <= mine <= cost["flops"]


def test_a_scan_counts_its_trips():
    cfg, model_cfg = _tiny_model("distar_sl_flagship")
    one = flops.required_per_frame(cfg["flops"], model_cfg, 2, 1)
    four = flops.required_per_frame(cfg["flops"], model_cfg, 2, 4)
    assert four["forward"] == pytest.approx(one["forward"]) and four["step"] == 3 * four["forward"]


def test_walker_on_a_known_program():
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    img = jax.ShapeDtypeStruct((2, 10, 10, 3), jnp.float32)
    k = jax.ShapeDtypeStruct((3, 3, 3, 5), jnp.float32)

    def fn(x, w, img, k):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w @ w.T), None), x, None, length=7)
        conv = jax.lax.conv_general_dilated(img, k, (1, 1), "SAME",
                                            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y, conv

    dots = 7 * (2 * 4 * 8 * 16 + 2 * 4 * 16 * 8)
    conv = 2 * (2 * 10 * 10 * 5) * (3 * 3 * 3)
    assert flops.forward_flops(fn, x, w, img, k) == dots + conv


def test_recorded_counts_are_what_the_module_gives():
    """The flagship numbers in the configuration files are this module's, per
    frame at b6 x t64; re-made here at a batch of 1 (the count is linear in
    the batch) because tracing the flagship forward takes its seconds."""
    from distar_tpu.model import default_model_config
    from distar_tpu.utils import deep_merge_dicts

    cfg = cells.load("configs", "distar_sl_flagship")
    model_cfg = deep_merge_dicts(default_model_config(), cfg["as_run"]["model"])
    got = flops.required_per_frame(cfg["flops"], model_cfg, 1, 2)
    assert got["step"] == pytest.approx(cfg["required_flops_per_frame"], rel=1e-3)


def test_peaks_know_the_chip_and_refuse_a_stranger():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e == device.peaks("TPU v5e") and v5e["ici_bytes_per_s"] == 200e9
    for stranger in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            device.peaks(stranger)

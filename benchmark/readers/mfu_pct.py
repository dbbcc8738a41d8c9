"""Model FLOP/s utilisation: the FLOPs the forward and backward passes
require per frame (recorded in the configuration's file; made by
``benchmark/flops.py`` from shapes, no recompute) times
the frames per second of this run's window, over chips times the chip's
published bf16 peak (``benchmark/peaks.json``)."""
from benchmark import device


def read(result):
    v = result.get("values", {})
    if not v.get("frames_per_s") or result["device"]["platform"] != "tpu":
        return None
    per_frame = v.get("flops_per_frame")
    if per_frame is None:
        return None
    peak = device.peaks(result["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_frame * v["frames_per_s"] / (v["chips"] * peak)

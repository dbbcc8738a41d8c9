"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the kernel requires (``benchmark/kernels_lm.py``,
from shapes; the larger of FLOPs over the peak FLOP/s and bytes over the peak
bytes/s, ``peaks.json``) over the device time of the scopes the kernel runs
under in ``passes`` (``trace_scope_lm``), in %. The rows come from the
program's own counter (``rows``: a histogram of the registry, its median over
the window); the time is of the traced steps, which follow the window on the
same pool. The kernel's recomputation under remat is not required work:
a metric whose ``passes`` leave ``recompute`` out reads the kernel itself,
one that takes all three reads what the step pays for it."""
from benchmark import device, kernels_lm
from benchmark.readers import histogram_window, trace_scope_lm


def read(result, kernel, scopes, rows, shape, passes=None):
    ms = trace_scope_lm.read(result, scopes=scopes, passes=passes)
    n = histogram_window.read(result, **rows)
    if not ms or n is None or result["device"]["platform"] != "tpu":
        return None
    need = getattr(kernels_lm, kernel)(n, **shape)
    peak = device.peaks(result["device"]["kind"])
    least_s = max(need["flops"] / peak["bf16_flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
